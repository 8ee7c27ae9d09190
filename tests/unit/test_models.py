"""Model zoo tests: forward shapes, train-step convergence, KV-cache decode
equivalence, and sharded (8-device CPU mesh) training — the framework-matrix
role of the reference's sklearn/pytorch/keras parametrization
(reference: tests/integration/ app dirs; SURVEY.md §4.3(c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import (
    BertClassifier,
    BertConfig,
    Llama,
    LlamaConfig,
    LLAMA_PARTITION_RULES,
    Mlp,
    MlpConfig,
    ViT,
    ViTConfig,
    VIT_PARTITION_RULES,
    classification_step,
    create_train_state,
    init_cache,
    lm_step,
    make_evaluator,
    make_predictor,
)
from unionml_tpu.parallel import ShardingConfig


def test_mlp_forward_and_training_converges():
    cfg = MlpConfig(num_classes=2, hidden_dims=(32,))
    module = Mlp(cfg)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    state = create_train_state(module, x[:2], learning_rate=1e-2)
    step = jax.jit(classification_step(module))
    for _ in range(100):
        state, metrics = step(state, (x, y))
    assert float(metrics["accuracy"]) > 0.9
    evaluator = make_evaluator(module)
    assert evaluator(state, x, y) > 0.9
    preds = make_predictor(module)(state, x)
    assert preds.shape == (64,)


def test_vit_tiny_forward_shape():
    cfg = ViTConfig.tiny(image_size=16, num_classes=3)
    module = ViT(cfg)
    x = jnp.zeros((2, 16, 16, 3))
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    logits = module.apply({"params": params}, x)
    assert logits.shape == (2, 3)
    assert logits.dtype == jnp.float32


def test_vit_base16_config_matches_paper():
    cfg = ViTConfig.base16()
    assert (cfg.hidden_dim, cfg.num_layers, cfg.num_heads, cfg.mlp_dim) == (
        768, 12, 12, 3072,
    )


def test_bert_tiny_classifier_forward_with_mask():
    cfg = BertConfig.tiny(vocab_size=100, num_classes=4)
    module = BertClassifier(cfg)
    ids = jnp.ones((2, 10), jnp.int32)
    mask = jnp.array([[1] * 10, [1] * 5 + [0] * 5])
    params = module.init(jax.random.PRNGKey(0), ids, attention_mask=mask)["params"]
    logits = module.apply({"params": params}, ids, attention_mask=mask)
    assert logits.shape == (2, 4)
    # padding must not influence the [CLS] logits: same ids, padded vs not
    short = module.apply({"params": params}, ids[:, :5], attention_mask=mask[:, :5])
    np.testing.assert_allclose(logits[1], short[1], rtol=2e-2, atol=2e-2)


def test_llama_tiny_lm_step_reduces_loss():
    cfg = LlamaConfig.tiny(vocab_size=64)
    module = Llama(cfg)
    rng = np.random.default_rng(0)
    tokens = np.asarray(rng.integers(0, 64, size=(8, 16)), np.int32)
    state = create_train_state(module, jnp.asarray(tokens[:1]), learning_rate=1e-2)
    step = jax.jit(lm_step(module))
    losses = []
    for _ in range(30):
        state, metrics = step(state, jnp.asarray(tokens))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.8


def test_llama_kv_cache_decode_matches_full_forward():
    """Cached token-by-token decode must equal the full-sequence forward."""
    cfg = LlamaConfig.tiny(vocab_size=32)
    module = Llama(cfg)
    tokens = jnp.asarray([[3, 7, 11, 2, 9, 17, 4, 1]], jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens)["params"]
    full = module.apply({"params": params}, tokens)

    cache = init_cache(cfg, batch=1, max_len=16, dtype=jnp.float32)

    @jax.jit
    def decode(params, cache, tok, idx):
        return module.apply(
            {"params": params}, tok, cache=cache, cache_index=idx
        )

    outs = []
    for i in range(tokens.shape[1]):
        logits, cache = decode(params, cache, tokens[:, i : i + 1], jnp.int32(i))
        outs.append(logits[:, 0])
    stepwise = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stepwise), rtol=2e-2, atol=2e-2)


def test_llama_prefill_with_cache_matches_full_forward():
    cfg = LlamaConfig.tiny(vocab_size=32)
    module = Llama(cfg)
    tokens = jnp.asarray([[5, 2, 9, 13]], jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens)["params"]
    full = module.apply({"params": params}, tokens)
    cache = init_cache(cfg, batch=1, max_len=8, dtype=jnp.float32)
    prefill, cache = jax.jit(
        lambda p, c, t: module.apply({"params": p}, t, cache=c, cache_index=jnp.int32(0))
    )(params, cache, tokens)
    np.testing.assert_allclose(np.asarray(full), np.asarray(prefill), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize(
    "sharding",
    [
        ShardingConfig(data=-1),
        ShardingConfig(data=2, fsdp=2, tensor=2, rules=VIT_PARTITION_RULES),
    ],
    ids=["dp8", "dp2_fsdp2_tp2"],
)
def test_vit_sharded_train_step(sharding):
    """ViT train step under DP and 3D (dp×fsdp×tp) meshes on 8 CPU devices."""
    from unionml_tpu.parallel import compile_step

    cfg = ViTConfig.tiny(image_size=16, num_classes=4)
    module = ViT(cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 16, 16, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(16,)), jnp.int32)
    state = create_train_state(module, x[:2], learning_rate=1e-3)
    step, state = compile_step(classification_step(module), state, sharding=sharding)
    for _ in range(3):
        state, metrics = step(state, (x, y))
    assert np.isfinite(float(metrics["loss"]))


def test_llama_tp_sharded_lm_step():
    """Llama LM step with tensor-parallel param rules over tensor=4."""
    from unionml_tpu.parallel import compile_step

    cfg = LlamaConfig.tiny(vocab_size=64)
    module = Llama(cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 64, size=(8, 16)), jnp.int32)
    sharding = ShardingConfig(data=-1, tensor=2, rules=LLAMA_PARTITION_RULES)
    state = create_train_state(module, tokens[:1], learning_rate=1e-3)
    step, state = compile_step(lm_step(module), state, sharding=sharding)
    state, metrics = step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))
    # params actually sharded over the tensor axis
    k = state.params["block_0"]["attn"]["q"]["kernel"]
    assert len(k.sharding.device_set) >= 2


def test_mlm_masking_rule_and_pretraining_step():
    """The BERT masking rule (15% selected; 80/10/10 mask/random/keep,
    specials untouched) produces lm_step-compatible batches, and an MLM
    pretraining step over BertMlm reduces the masked-CE loss."""
    from unionml_tpu.models import BertConfig, BertMlm, make_mlm_batch
    from unionml_tpu.models.train import create_train_state, lm_step

    rng = np.random.default_rng(0)
    vocab, mask_id = 1024, 103
    tokens = rng.integers(4, vocab, size=(64, 32))
    tokens[:, 0] = 0  # special position (e.g. [CLS]=0 here) never masked
    inputs, labels = make_mlm_batch(
        tokens, mask_id=mask_id, vocab_size=vocab, rng=rng, special_ids=(0,)
    )
    selected = labels != -100
    frac = selected.mean()
    assert 0.10 < frac < 0.20, frac
    assert not selected[:, 0].any()                      # specials untouched
    assert (labels[selected] == tokens[selected]).all()  # labels = originals
    masked_frac = (inputs[selected] == mask_id).mean()
    assert 0.65 < masked_frac < 0.92, masked_frac        # ~80% become [MASK]
    kept = inputs[~selected] == tokens[~selected]
    assert kept.all()                                    # unselected unchanged

    cfg = BertConfig.tiny(vocab_size=vocab)
    module = BertMlm(cfg)
    state = create_train_state(
        module, jnp.asarray(inputs[:1]), learning_rate=5e-3, seed=1
    )
    step = jax.jit(lm_step(module), donate_argnums=0)
    batch = (jnp.asarray(inputs), jnp.asarray(labels))
    state, first = step(state, batch)
    for _ in range(15):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < float(first["loss"]), (
        float(first["loss"]), float(metrics["loss"]),
    )


def test_mlm_masking_handles_unsigned_token_dtypes():
    """uint corpora must not wrap ignore_id to an in-range positive."""
    from unionml_tpu.models import make_mlm_batch

    rng = np.random.default_rng(2)
    tokens = rng.integers(4, 1000, size=(8, 16)).astype(np.uint16)
    inputs, labels = make_mlm_batch(
        tokens, mask_id=103, vocab_size=1024, rng=rng
    )
    assert labels.dtype.kind == "i"
    assert (labels == -100).any()
    selected = labels != -100
    assert (labels[selected] == tokens.astype(np.int64)[selected]).all()


def test_mlm_step_masks_padding_and_trains():
    """mlm_step threads the attention mask (pads invisible) and reduces
    masked CE; lm_step composition stays valid for unpadded batches."""
    from unionml_tpu.models import BertConfig, BertMlm, make_mlm_batch, mlm_step
    from unionml_tpu.models.train import create_train_state

    rng = np.random.default_rng(3)
    vocab = 512
    cfg = BertConfig.tiny(vocab_size=vocab)
    module = BertMlm(cfg)
    tokens = rng.integers(4, vocab, size=(32, 24))
    tokens[:, 20:] = 0  # right padding
    inputs, labels = make_mlm_batch(
        tokens, mask_id=103, vocab_size=vocab, rng=rng, special_ids=(0,)
    )
    mask = (tokens != 0).astype(np.int32)
    state = create_train_state(module, jnp.asarray(inputs[:1]), learning_rate=5e-3)
    step = jax.jit(mlm_step(module), donate_argnums=0)
    batch = (jnp.asarray(inputs), jnp.asarray(labels), jnp.asarray(mask))
    state, first = step(state, batch)
    for _ in range(10):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < float(first["loss"])


@pytest.mark.parametrize(
    "lhs,rhs,dims",
    [
        # q / k / v: [B, S, d] x [d, H, D]; o: [B, S, H, D] x [H, D, d]
        ((2, 5, 24), (24, 3, 8), (((2,), (0,)), ((), ()))),
        ((2, 5, 3, 8), (3, 8, 24), (((2, 3), (0, 1)), ((), ()))),
        # what the merge does not cover goes to lax.dot_general as it came
        ((2, 5, 24), (24, 16), (((2,), (0,)), ((), ()))),
        ((2, 24, 5), (24, 3, 8), (((1,), (0,)), ((), ()))),
        ((2, 5, 24), (2, 24, 8), (((2,), (1,)), ((0,), (0,)))),
    ],
    ids=["to-heads", "from-heads", "flat-kernel", "inner-axis", "batched"],
)
def test_merged_dot_general_is_dot_general(lhs, rhs, dims):
    from unionml_tpu.models.layers import merged_dot_general

    a = jax.random.normal(jax.random.PRNGKey(0), lhs)
    b = jax.random.normal(jax.random.PRNGKey(1), rhs)
    want = jax.lax.dot_general(a, b, dims)
    got = merged_dot_general(a, b, dims)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    grads = jax.grad(lambda a, b: jnp.sum(merged_dot_general(a, b, dims) ** 2), argnums=(0, 1))(a, b)
    wants = jax.grad(lambda a, b: jnp.sum(jax.lax.dot_general(a, b, dims) ** 2), argnums=(0, 1))(a, b)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4)
