"""LoRA / QLoRA fine-tuning (models/lora.py).

No reference counterpart (the reference trains whatever the user's
sklearn/torch/keras trainer does — reference: unionml/model.py:425-440);
LoRA is the TPU-native fine-tuning path for the serving flagship (int8
frozen base + adapters = single-chip 8B fine-tune).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# measured sub-minute module: part of the `-m quick` tier (Makefile
# test-quick) so iteration/CI sharding get a <5-min spec-path pass
pytestmark = pytest.mark.quick

from unionml_tpu.models import (
    LLAMA_LORA_PARTITION_RULES,
    LLAMA_QUANT_PATTERNS,
    Llama,
    LlamaConfig,
    create_lora_train_state,
    lm_step,
    make_lm_predictor,
    merge_lora,
    merge_param_trees,
    quantize_params,
    split_lora_params,
)
from unionml_tpu.parallel.sharding import ShardingConfig, compile_step

TOKENS = jnp.zeros((2, 16), jnp.int32)


def _batch(seed=0, batch=2, seq=17, vocab=500):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, vocab, (batch, seq)), jnp.int32)


def _base_params(cfg=None):
    cfg = cfg or LlamaConfig.tiny()
    return Llama(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]


def test_lora_init_is_identity():
    """lora_b starts at zero: step-0 forward == the base model exactly."""
    base_params = _base_params()
    model = Llama(LlamaConfig.tiny(lora_rank=4))
    state = create_lora_train_state(model, TOKENS, base_params=base_params)
    out_lora = model.apply({"params": state.full_params()}, TOKENS)
    out_base = Llama(LlamaConfig.tiny()).apply({"params": base_params}, TOKENS)
    np.testing.assert_array_equal(np.asarray(out_lora), np.asarray(out_base))


def test_lora_step_trains_adapters_only():
    model = Llama(LlamaConfig.tiny(lora_rank=4))
    state = create_lora_train_state(
        model, TOKENS, base_params=_base_params(), learning_rate=1e-2
    )
    # optimizer state is adapter-sized: the frozen base carries no m/v
    adapter_count = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    opt_count = sum(
        x.size for x in jax.tree_util.tree_leaves(state.opt_state)
        if hasattr(x, "size")
    )
    base_count = sum(
        x.size for x in jax.tree_util.tree_leaves(state.frozen_params)
    )
    assert opt_count <= 2 * adapter_count + 2  # adam m+v (+ counters)
    assert adapter_count < base_count / 10

    frozen_before = jax.tree_util.tree_map(np.asarray, state.frozen_params)
    adapters_before = jax.tree_util.tree_map(np.asarray, state.params)
    step = jax.jit(lm_step(model))
    batch = _batch()
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]  # adapters learn
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        frozen_before, state.frozen_params,
    )  # base frozen bit-exact
    changed = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(
            lambda a, b: float(np.abs(a - np.asarray(b)).max()),
            adapters_before, state.params,
        )
    )
    assert max(changed) > 0  # adapters actually moved


def test_lora_merge_matches_unmerged_forward():
    cfg = LlamaConfig.tiny(lora_rank=4)
    model = Llama(cfg)
    state = create_lora_train_state(
        model, TOKENS, base_params=_base_params(), learning_rate=1e-2
    )
    step = jax.jit(lm_step(model))
    for _ in range(3):
        state, _ = step(state, _batch())
    merged = merge_lora(state.full_params(), alpha=cfg.lora_alpha)
    # merged tree is lora-free and loads the rank-0 architecture
    lora_leaves, _ = split_lora_params(merged)
    assert lora_leaves == {}
    out_merged = Llama(LlamaConfig.tiny()).apply({"params": merged}, TOKENS)
    out_lora = model.apply({"params": state.full_params()}, TOKENS)
    # the lora branch computes (x@A)@B in bf16 while the merged kernel
    # folds the delta in fp32 — equal up to bf16 rounding of the logits
    err = float(jnp.max(jnp.abs(out_merged - out_lora)))
    scale = float(jnp.max(jnp.abs(out_lora))) + 1e-9
    assert err / scale < 0.02


def test_qlora_int8_base_trains_and_serves():
    """The QLoRA loop: quantize → adapter train → merge → bucketed serve."""
    qparams = quantize_params(_base_params(), LLAMA_QUANT_PATTERNS)
    cfg = LlamaConfig.tiny(quantized=True, lora_rank=4)
    model = Llama(cfg)
    state = create_lora_train_state(
        model, TOKENS, base_params=qparams, learning_rate=1e-2
    )
    step = jax.jit(lm_step(model))
    losses = []
    for _ in range(5):
        state, metrics = step(state, _batch())
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    # int8 kernels stay bit-frozen (no grads leak into the base)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        qparams, state.frozen_params,
    )

    merged = merge_lora(state.full_params(), alpha=cfg.lora_alpha)
    serve_model = Llama(LlamaConfig.tiny(quantized=True))
    out_merged = serve_model.apply({"params": merged}, TOKENS)
    out_lora = model.apply({"params": state.full_params()}, TOKENS)
    # requantization error: bounded by the int8 grid on top of bf16 noise
    err = float(jnp.max(jnp.abs(out_merged - out_lora)))
    scale = float(jnp.max(jnp.abs(out_lora))) + 1e-9
    assert err / scale < 0.05

    predictor = make_lm_predictor(serve_model, max_new_tokens=4, bucket_lens=(16,))
    outs = predictor(merged, [[5, 6, 7, 8]])
    assert len(outs) == 1 and len(outs[0]) == 4


def test_lora_sharded_step_matches_serial():
    """dp2 x tp2 QLoRA-layout rules: compiled-mesh adapters == serial."""
    import optax

    cfg = LlamaConfig.tiny(lora_rank=4)
    model = Llama(cfg)
    # SGD for the equality check: adam's m/sqrt(v) normalization turns
    # near-zero-gradient elements into +-lr sign coin-flips, amplifying
    # bf16 reduction-order noise into O(lr) param diffs that say nothing
    # about the sharding's correctness
    state = create_lora_train_state(
        model, TOKENS, base_params=_base_params(), optimizer=optax.sgd(0.5)
    )
    step = lm_step(model)
    batch = _batch(batch=4)

    serial_state = state
    serial_step = jax.jit(step)
    for _ in range(3):
        serial_state, serial_metrics = serial_step(serial_state, batch)

    sharding = ShardingConfig(data=-1, tensor=2, rules=LLAMA_LORA_PARTITION_RULES)
    compiled, placed = compile_step(step, state, sharding=sharding)
    sharded_state = placed
    sharded_batch = jax.device_put(batch, sharding.batch_sharding())
    for _ in range(3):
        sharded_state, sharded_metrics = compiled(sharded_state, sharded_batch)

    # bf16 activations (2^-8 ~ 4e-3 relative rounding) + cross-device
    # psum reorder the reductions; that per-step few-e-3 activation
    # drift feeds grads that 3 compounding SGD steps at lr=0.5 amplify
    # to ~1e-2 absolute on O(1) params — so 2e-2 is the bf16 compounding
    # floor with 2x margin (was 5e-3 = barely one bf16 ulp, seen flaking
    # at clean HEAD), while a sharding bug (missing/doubled psum) moves
    # params at O(1). The fp32 SP/EP tests keep the tight bounds.
    np.testing.assert_allclose(
        float(sharded_metrics["loss"]), float(serial_metrics["loss"]),
        rtol=1e-2,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-2
        ),
        serial_state.params, jax.device_get(sharded_state.params),
    )


def test_lora_state_through_serving_surface():
    """A LoRATrainState passed straight to the bucketed predictor resolves
    to its FULL params (frozen base + adapters), matching the merged
    weights — no manual merge needed for the state-or-params contract."""
    cfg = LlamaConfig.tiny(lora_rank=4)
    model = Llama(cfg)
    state = create_lora_train_state(model, TOKENS, base_params=_base_params())
    predictor = make_lm_predictor(model, max_new_tokens=4, bucket_lens=(16,))
    out_state = predictor(state, [[5, 6, 7, 8]])
    out_params = predictor(state.full_params(), [[5, 6, 7, 8]])
    assert out_state == out_params


def test_create_lora_state_validations():
    with pytest.raises(ValueError, match="no lora_a/lora_b"):
        create_lora_train_state(Llama(LlamaConfig.tiny()), TOKENS)

    model = Llama(LlamaConfig.tiny(lora_rank=4))
    good = create_lora_train_state(model, TOKENS, base_params=_base_params())
    with pytest.raises(ValueError, match="already contain lora"):
        create_lora_train_state(model, TOKENS, base_params=good.full_params())
    wrong = _base_params(LlamaConfig.tiny(num_layers=1))
    with pytest.raises(ValueError, match="structure does not match"):
        create_lora_train_state(model, TOKENS, base_params=wrong)


def test_split_merge_roundtrip():
    model = Llama(LlamaConfig.tiny(lora_rank=2))
    full = model.init(jax.random.PRNGKey(1), TOKENS)["params"]
    lora, base = split_lora_params(full)
    assert lora and base
    rebuilt = merge_param_trees(base, lora)
    assert jax.tree_util.tree_structure(rebuilt) == jax.tree_util.tree_structure(full)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        full, rebuilt,
    )


def test_tree_walkers_accept_frozendict():
    """Checkpoint-loaded trees often arrive as flax FrozenDicts — the
    walkers must traverse any Mapping, not just dict (a FrozenDict leaf
    would yield zero adapters / silently drop base keys)."""
    from flax.core import freeze

    from unionml_tpu.models.lora import merge_param_trees, split_lora_params

    tree = freeze({
        "block": {
            "q": {"kernel": np.zeros((4, 4)), "lora_a": np.ones((4, 2)),
                  "lora_b": np.zeros((2, 4))},
            "norm": {"scale": np.ones(4)},
        }
    })
    adapters, base = split_lora_params(tree)
    assert set(adapters["block"]["q"]) == {"lora_a", "lora_b"}
    assert set(base["block"]) == {"q", "norm"}
    merged = merge_param_trees(freeze(base), adapters)
    assert set(merged["block"]["q"]) == {"kernel", "lora_a", "lora_b"}
