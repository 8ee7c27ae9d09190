"""Cross-implementation fidelity: checkpoints written by HF transformers'
*torch* reference models load through models/convert.py and reproduce the
torch logits.

This is the output-sanity proof for the ingestion path: the mapping,
layout transforms, and the rotary/GELU/norm conventions are all exercised end-to-end against an independent
implementation — a transposed kernel, permuted head, or mismatched RoPE
convention shifts logits by O(1), far outside the tolerances here. Real
pretrained checkpoints use the exact same tensor names and layouts; only
scale differs.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from unionml_tpu.models import Llama  # noqa: E402
from unionml_tpu.models.bert import BertEncoder  # noqa: E402
from unionml_tpu.models.convert import (  # noqa: E402
    load_bert_checkpoint,
    load_llama_checkpoint,
)
from unionml_tpu.models.generate import make_generator  # noqa: E402


@pytest.fixture(scope="module")
def hf_llama_checkpoint(tmp_path_factory):
    cfg = transformers.LlamaConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10_000.0,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval().to(torch.float32)
    path = tmp_path_factory.mktemp("hf_llama")
    model.save_pretrained(path, safe_serialization=True)
    return model, str(path)


def test_llama_logits_match_torch_reference(hf_llama_checkpoint):
    hf_model, path = hf_llama_checkpoint
    params, cfg = load_llama_checkpoint(path, dtype=jnp.float32, max_len=256)
    # the loader's returned config IS the model config (fp32 compute for
    # a tight comparison against the fp32 torch reference)
    module = Llama(dataclasses.replace(cfg, dtype="float32"))
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 512, size=(2, 16), dtype=np.int32)
    ours = np.asarray(
        module.apply({"params": params}, jnp.asarray(tokens))
    )
    with torch.no_grad():
        theirs = hf_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-3, rtol=1e-3)
    # the distributions agree, not just roughly: identical argmax per position
    np.testing.assert_array_equal(
        ours.argmax(-1), theirs.argmax(-1)
    )


def test_llama_greedy_generation_matches_torch(hf_llama_checkpoint):
    hf_model, path = hf_llama_checkpoint
    params, cfg = load_llama_checkpoint(path, dtype=jnp.float32, max_len=256)
    module = Llama(dataclasses.replace(cfg, dtype="float32", max_len=64))
    generate = make_generator(module, max_new_tokens=8, max_len=64)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 512, size=(2, 12), dtype=np.int32)
    ours = np.asarray(generate(params, jnp.asarray(prompt)))
    with torch.no_grad():
        theirs = hf_model.generate(
            torch.tensor(prompt, dtype=torch.long),
            max_new_tokens=8, do_sample=False,
        ).numpy()[:, 12:]
    np.testing.assert_array_equal(ours, theirs)


def test_llama3_rope_scaling_matches_torch(tmp_path):
    """Llama-3.1/3.2-style checkpoints carry llama3 rope_scaling — the
    frequency rescale must reproduce transformers' torch implementation
    or long-context logits silently drift."""
    cfg = transformers.LlamaConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10_000.0,
        tie_word_embeddings=True,  # 3.2-style: lm_head tied to embed
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0, "original_max_position_embeddings": 32,
        },
    )
    torch.manual_seed(1)
    hf_model = transformers.LlamaForCausalLM(cfg).eval().to(torch.float32)
    hf_model.save_pretrained(tmp_path, safe_serialization=True)
    params, loaded = load_llama_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert loaded.rope_scaling == (8.0, 1.0, 4.0, 32)
    module = Llama(dataclasses.replace(loaded, dtype="float32"))
    rng = np.random.default_rng(4)
    # longer than original_max_position_embeddings so the rescaled
    # low-frequency band actually participates
    tokens = rng.integers(0, 512, size=(1, 48), dtype=np.int32)
    ours = np.asarray(module.apply({"params": params}, jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-3, rtol=1e-3)
    np.testing.assert_array_equal(ours.argmax(-1), theirs.argmax(-1))


def test_unsupported_rope_scaling_is_loud():
    from unionml_tpu.models.convert import llama_config_from_hf

    with pytest.raises(NotImplementedError, match="rope_scaling"):
        llama_config_from_hf({
            "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 128,
            "rope_scaling": {"rope_type": "yarn", "factor": 4.0},
        })


def test_bert_encoder_matches_torch_reference(tmp_path):
    hf_cfg = transformers.BertConfig(
        vocab_size=1024, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=128, type_vocab_size=2,
        hidden_act="gelu",  # erf GELU — matched by gelu_exact=True
    )
    torch.manual_seed(0)
    hf_model = transformers.BertModel(hf_cfg).eval().to(torch.float32)
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    params, loaded_cfg = load_bert_checkpoint(str(tmp_path), encoder_key="")
    # the loader derives gelu_exact=True from hidden_act="gelu" — the
    # erf form erf-pretrained checkpoints need for faithful inference
    assert loaded_cfg.gelu_exact
    module = BertEncoder(dataclasses.replace(loaded_cfg, dtype="float32"))
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 1024, size=(2, 10), dtype=np.int32)
    mask = np.ones((2, 10), np.int32)
    mask[1, 7:] = 0
    types = np.zeros((2, 10), np.int32)
    encoder_params = params  # encoder_key="" roots the tree at the encoder
    ours = np.asarray(
        module.apply(
            {"params": {k: v for k, v in encoder_params.items() if k != "pooler"}},
            jnp.asarray(tokens),
            attention_mask=jnp.asarray(mask),
            token_type_ids=jnp.asarray(types),
        )
    )
    with torch.no_grad():
        out = hf_model(
            torch.tensor(tokens, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
            token_type_ids=torch.tensor(types, dtype=torch.long),
        )
        theirs = out.last_hidden_state.numpy()
        their_pooled = out.pooler_output.numpy()
    # padded positions attend nothing meaningful in either impl — compare
    # real positions only
    np.testing.assert_allclose(ours[0], theirs[0], atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(ours[1, :7], theirs[1, :7], atol=2e-3, rtol=1e-3)

    # pooler: tanh(cls @ W + b) with the loaded pooler weights
    pk = np.asarray(encoder_params["pooler"]["kernel"])
    pb = np.asarray(encoder_params["pooler"]["bias"])
    our_pooled = np.tanh(ours[:, 0] @ pk + pb)
    np.testing.assert_allclose(our_pooled, their_pooled, atol=2e-3, rtol=1e-3)


def test_mixtral_logits_match_torch_reference(tmp_path):
    """Mixtral block-sparse MoE checkpoints load through the grouped
    expert mapping and reproduce transformers' torch logits — router
    transpose, per-expert w1/w3/w2 stacking, and the renormalized top-k
    routing all verified against the independent implementation."""
    cfg = transformers.MixtralConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10_000.0,
        tie_word_embeddings=False,
    )
    torch.manual_seed(3)
    hf_model = transformers.MixtralForCausalLM(cfg).eval().to(torch.float32)
    hf_model.save_pretrained(tmp_path, safe_serialization=True)
    params, loaded = load_llama_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert loaded.num_experts == 4 and loaded.num_selected == 2
    module = Llama(dataclasses.replace(loaded, dtype="float32"))
    rng = np.random.default_rng(6)
    tokens = rng.integers(0, 512, size=(2, 12), dtype=np.int32)
    ours = np.asarray(module.apply({"params": params}, jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)
    np.testing.assert_array_equal(ours.argmax(-1), theirs.argmax(-1))


def test_vit_logits_match_torch_reference(tmp_path):
    """HF ViT checkpoints (pre-LN, qkv biases, erf GELU, cls+pos
    embeddings, OIHW patch conv) load through the ViT mapping and
    reproduce transformers' torch classification logits."""
    hf_cfg = transformers.ViTConfig(
        image_size=32, patch_size=8, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128, hidden_act="gelu",
        num_labels=10,
    )
    torch.manual_seed(4)
    hf_model = (
        transformers.ViTForImageClassification(hf_cfg).eval().to(torch.float32)
    )
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    from unionml_tpu.models import ViT
    from unionml_tpu.models.convert import load_vit_checkpoint

    params, cfg = load_vit_checkpoint(str(tmp_path))
    assert cfg.qkv_bias and cfg.gelu_exact and cfg.num_classes == 10
    module = ViT(dataclasses.replace(cfg, dtype="float32"))
    rng = np.random.default_rng(7)
    images = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
    ours = np.asarray(module.apply({"params": params}, jnp.asarray(images)))
    with torch.no_grad():
        theirs = hf_model(
            torch.tensor(images.transpose(0, 3, 1, 2))  # NHWC -> NCHW
        ).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)
    np.testing.assert_array_equal(ours.argmax(-1), theirs.argmax(-1))
