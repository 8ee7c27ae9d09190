"""GLM-4.7-Flash (``glm4_moe_lite``): latent attention on a latent pool, the
sigmoid router beside a shared expert, and the engine serving them.

Float32 on the CPU at a tiny size (hidden 64, 4 heads, ``q_lora`` 24,
``kv_lora`` 16, nope 12, rope 8, v 20, one dense layer and two mixture
layers of 8 experts top-2 with one shared expert) on seeded random
weights, against the plain reference
(``unionml_tpu/models/glm_moe_lite_reference.py``: expanded attention
only, a loop over experts, no cache).

Tolerances. Program and reference compute the same float32 numbers in
another order (absorbed against expanded attention, a cache against a
full pass, grouped against looped experts), which moves logits of size ~4
by a few 1e-6: ``LOGIT_TOL`` is 1e-4. Served as deployed (bfloat16
activations and latent rows on int8 weights) the logits lie within
``SERVING_TOL`` = 0.2 of the float32 reference on the same int8 weights
(read over three seeds: 0.05-0.09); the reference on int4 weights lies
1.4-2.9 away, and an absorbed score without its rotary term 1.7-3.0.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu import telemetry
from unionml_tpu.models import generate as generate_mod
from unionml_tpu.models import glm_moe_lite as glm_mod
from unionml_tpu.models import glm_moe_lite_reference as reference
from unionml_tpu.models.glm_moe_lite import (
    GLM_MOE_LITE_QUANT_PATTERNS, GlmMoeLite, GlmMoeLiteConfig,
)
from unionml_tpu.models.layers import KVRows, LatentRows
from unionml_tpu.models.quantization import quantize_params
from unionml_tpu.ops import moe
from unionml_tpu.ops import paged_attention as paged
from unionml_tpu.serving.engine import DecodeEngine
from unionml_tpu.serving.prefix_cache import RadixPrefixCache

LOGIT_TOL = 1e-4
SERVING_TOL = 0.2
VOCAB = 211


def _tiny(**over):
    return GlmMoeLiteConfig.tiny(vocab_size=VOCAB, dtype="float32", cache_dtype="float32", **over)


def _params(module, seed=3):
    """Lecun weights, and a selection bias that is not zero (a deviation
    of 0.2 against sigmoid scores between 0.3 and 0.7)."""
    params = module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.tree_util.tree_map(lambda x: x, params)  # a plain, mutable tree
    for i in range(module.config.first_k_dense_replace, module.config.num_hidden_layers):
        shape = params[f"block_{i}"]["moe"]["e_score_correction_bias"].shape
        params[f"block_{i}"]["moe"]["e_score_correction_bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(100 + i), shape
        )
    return params


@pytest.fixture(scope="module")
def served():
    module = GlmMoeLite(_tiny())
    return module, _params(module)


def _reference_logits(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, jnp.asarray([tokens]), cfg.to_hf()))[0]


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).tolist() for n in lengths]


# ------------------------------------------------------------- (a) the model


def test_model_forward_matches_reference(served):
    module, params = served
    tokens = _prompts(150, seed=1)[0]
    got = np.asarray(module.apply({"params": params}, jnp.asarray([tokens])))[0]
    assert np.abs(got - _reference_logits(params, tokens, module.config)).max() < LOGIT_TOL


def test_int8_weights_are_read_as_the_reference_reads_them(served):
    """``quantize_params`` with the module's patterns gives the tree the
    quantized module takes, and both sides dequantise it alike."""
    _, params = served
    module = GlmMoeLite(_tiny(quantized=True))
    qparams = quantize_params(params, GLM_MOE_LITE_QUANT_PATTERNS)
    want = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree_util.tree_structure(qparams) == jax.tree_util.tree_structure(want)
    tokens = _prompts(90, seed=2)[0]
    got = np.asarray(module.apply({"params": qparams}, jnp.asarray([tokens])))[0]
    assert np.abs(got - _reference_logits(qparams, tokens, module.config)).max() < LOGIT_TOL


def test_config_reads_the_published_keys_and_refuses_what_it_cannot_run():
    hf = dict(GlmMoeLiteConfig().to_hf(), model_type="glm4_moe_lite", n_group=1, topk_group=1,
              norm_topk_prob=True, rope_scaling=None, topk_method="noaux_tc", num_nextn_predict_layers=1)
    cfg = GlmMoeLiteConfig.from_hf(hf, num_hidden_layers=13, quantized=True)
    assert cfg.num_hidden_layers == 13 and cfg.qk_head_dim == 256 and cfg.max_len == 202_752
    assert cfg.to_hf() == dict(GlmMoeLiteConfig().to_hf(), num_hidden_layers=13)
    with pytest.raises(ValueError, match="n_group"):
        GlmMoeLiteConfig.from_hf(dict(hf, n_group=8))
    with pytest.raises(ValueError, match="rope_scaling"):
        GlmMoeLiteConfig.from_hf(dict(hf, rope_scaling={"type": "yarn"}))


# ------------------------------------------- (c) two forms of one attention


@pytest.mark.parametrize("prefill_impl", ["flash", "cached"])
def test_absorbed_and_expanded_attention_agree(served, prefill_impl):
    """The same prompt against an empty cache, once as a whole prompt
    (expanded keys and values, through the flash kernel or plainly) and
    once as a call that reads the cache (absorbed): the same logits, and
    the same latent rows written (the first layer's to the bit)."""
    _, params = served
    module = GlmMoeLite(_tiny(prefill_impl=prefill_impl))
    tokens = jnp.asarray(_prompts(48, seed=4))
    fresh = tuple(l.init(1, 64, jnp.float32) for l in module.cache_layout())

    def run(**kw):
        return module.apply({"params": params}, tokens, cache=fresh, cache_index=jnp.int32(0), **kw)

    absorbed, rows_a = run()
    expanded, rows_e = run(full_prefill=True)
    plain = module.apply({"params": params}, tokens)  # no cache: expanded, plain attention
    assert np.abs(np.asarray(absorbed) - np.asarray(plain)).max() < 1e-5
    assert np.abs(np.asarray(expanded) - np.asarray(plain)).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(rows_a[0][0]), np.asarray(rows_e[0][0]))
    for (a,), (e,) in zip(rows_a, rows_e):
        assert np.abs(np.asarray(a) - np.asarray(e)).max() < 1e-5
        assert np.abs(np.asarray(a)[0, :48, :24]).min() > 0 and not np.asarray(a)[0, :, 24:].any()


# ------------------------------------------------------------ (d) the router


def test_selection_bias_picks_and_does_not_weigh():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0, -2.0, 0.0]])
    none = jnp.zeros((6, 1))
    w0, i0 = moe.sigmoid_top_k_routing(logits, none, 2, scaling=1.8)
    assert sorted(np.asarray(i0)[0].tolist()) == [0, 1]
    # a bias that lifts expert 4 over expert 1 flips the choice ...
    bias = none.at[4, 0].set(3.0)
    w1, i1 = moe.sigmoid_top_k_routing(logits, bias, 2, scaling=1.8)
    assert sorted(np.asarray(i1)[0].tolist()) == [0, 4]
    # ... and the weights are the sigmoids of the chosen, without it
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)[0]))
    want = {0: s[0] / (s[0] + s[4]) * 1.8, 4: s[4] / (s[0] + s[4]) * 1.8}
    for w, i in zip(np.asarray(w1)[0], np.asarray(i1)[0]):
        assert w == pytest.approx(want[int(i)], rel=1e-6)
    assert float(w0.sum()) == pytest.approx(1.8, rel=1e-6) and float(w1.sum()) == pytest.approx(1.8, rel=1e-6)
    assert w1.dtype == jnp.float32


def test_router_is_float32_whatever_the_activations(served):
    """bfloat16 tokens meet a float32 router kernel in float32: the scores
    are those of the rounded tokens, not of a rounded product."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 64)).astype(jnp.bfloat16)
    layer = moe.MoEMlp(num_experts=8, num_selected=2, hidden_dim=16, model_dim=64, router="sigmoid",
                       routed_scaling=1.8, dtype=jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert params["e_score_correction_bias"].shape == (8, 1) and params["router_kernel"].dtype == jnp.float32
    seen = {}
    real = moe.sigmoid_top_k_routing
    try:
        moe.sigmoid_top_k_routing = lambda g, b, k, **kw: seen.update(g=g) or real(g, b, k, **kw)
        layer.apply({"params": params}, x)
    finally:
        moe.sigmoid_top_k_routing = real
    want = np.asarray(x, np.float32).reshape(5, 64) @ np.asarray(params["router_kernel"])
    assert seen["g"].dtype == jnp.float32 and np.abs(np.asarray(seen["g"]) - want).max() < 1e-5


def test_softmax_routing_stays_the_default():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 32))
    layer = moe.MoEMlp(num_experts=4, num_selected=2, hidden_dim=16, model_dim=32, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert layer.router == "softmax" and "e_score_correction_bias" not in params
    with pytest.raises(ValueError, match="unknown router"):
        moe.MoEMlp(num_experts=4, num_selected=2, hidden_dim=16, model_dim=32, router="tanh").init(
            jax.random.PRNGKey(1), x)


# ---------------------------------------------------------- (e) the dispatch


def _int8_experts(experts, d, h, seed):
    rng = np.random.default_rng(seed)
    shapes = ((d, h), (d, h), (h, d))
    ws = [jnp.asarray(rng.integers(-127, 128, (experts, k, n), dtype=np.int8)) for k, n in shapes]
    scales = [jnp.full((experts, n), 1.0 / (74.0 * np.sqrt(k)), jnp.float32) for k, n in shapes]
    return ws, scales


@pytest.mark.parametrize("rows", [1, 7, 32, 256])
@pytest.mark.parametrize("experts,k", [(64, 4), (8, 2)])
@pytest.mark.parametrize("impl", ["ragged_dot", "pallas"])
def test_grouped_dispatch_matches_dense(experts, k, rows, impl):
    """Sigmoid-routed rows through the grouped dispatch (the CPU's
    ``ragged_dot`` and the kernel in interpret mode, at the row tile the
    op picks) against every expert on every token; one expert is never
    routed to."""
    d, h = 128, 128
    if impl == "pallas" and rows == 256 and experts == 64:
        pytest.skip("interpret mode walks 1,000 grid steps a matmul here: minutes")
    ws, scales = _int8_experts(experts, d, h, seed=rows)
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), jnp.float32)
    logits = jax.random.normal(jax.random.PRNGKey(rows + 1), (rows, experts))
    bias = jnp.zeros((experts, 1)).at[3, 0].set(-100.0)   # expert 3 is left empty
    weights, indices = moe.sigmoid_top_k_routing(logits, bias, k, scaling=1.8)
    assert 3 not in np.asarray(indices)
    dense = moe.dense_expert_mlp(x, weights, indices, *ws, scales=scales)
    grouped = moe.grouped_expert_mlp(x, weights, indices, *ws, scales=scales, impl=impl)
    off = np.abs(np.asarray(grouped) - np.asarray(dense)).max()
    assert off < 2e-5 * max(1.0, float(jnp.abs(dense).max()))


def test_the_shared_expert_is_counted_once(served):
    """A mixture block's output is the routed sum plus one pass of the
    shared expert: the reference's, and no more when the routed experts
    are silenced."""
    module, params = served
    cfg = module.config
    block = glm_mod.GlmMoeLiteBlock(cfg, True)
    blk = params["block_1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 9, 64))
    got, _ = block.apply({"params": blk}, x)
    with jax.default_matmul_precision("highest"):
        want = reference.layer(x[0], blk, 1, cfg.to_hf())
    assert np.abs(np.asarray(got)[0] - np.asarray(want)).max() < 1e-5
    silent = dict(blk, moe=dict(blk["moe"], w_down=jnp.zeros_like(blk["moe"]["w_down"])))
    only_shared, _ = block.apply({"params": silent}, x)
    attn_only = dict(silent, shared_expert=jax.tree_util.tree_map(jnp.zeros_like, blk["shared_expert"]))
    base, _ = block.apply({"params": attn_only}, x)
    h = glm_mod.RMSNorm(eps=cfg.rms_norm_eps, dtype=jnp.float32).apply({"params": blk["mlp_norm"]}, base)
    s = blk["shared_expert"]
    one_pass = (jax.nn.silu(h @ s["gate"]["kernel"]) * (h @ s["up"]["kernel"])) @ s["down"]["kernel"]
    assert np.abs(np.asarray(only_shared - base) - np.asarray(one_pass)).max() < 1e-5


def _plan_before_pr36(tokens, experts, k):
    """``dispatch_plan`` on a TPU for int8 experts as PR 34 shipped it."""
    routed = tokens * k
    if tokens <= 128:
        return {"dispatch": "dense", "expert_rows_routed": routed, "expert_rows_computed": tokens * experts,
                "computed_over_routed": round(tokens * experts / routed, 3)}
    chunk = 128 if 4 * routed > experts * 128 else 64
    computed = (routed + experts * (chunk - 1)) // chunk * chunk
    return {"dispatch": "grouped:moe_grouped_matmul", "expert_rows_routed": routed,
            "expert_rows_computed": computed, "computed_over_routed": round(computed / routed, 3)}


def test_dispatch_plan_at_mixtrals_shapes_is_unchanged(monkeypatch):
    monkeypatch.setattr(moe, "_interpret", lambda: False)  # as on a TPU
    for tokens in list(range(1, 300)) + [512, 1024, 2048, 4096, 8192]:
        assert moe.dispatch_plan(tokens, 8, 2, quantized=True) == _plan_before_pr36(tokens, 8, 2), tokens
    # with Mixtral's widths the same plan, and what it reads beside it
    plan = moe.dispatch_plan(32, 8, 2, quantized=True, model_dim=4096, hidden_dim=14336)
    assert plan["dispatch"] == "dense" and plan["expert_bytes_read"] == 8 * 3 * 4096 * 14336
    assert plan["experts_touched"] == pytest.approx(8.0, abs=0.01)


def test_dispatch_plan_follows_experts_top_k_rows_and_widths(monkeypatch):
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    kw = dict(quantized=True, model_dim=2048, hidden_dim=1536)
    expert = 3 * 2048 * 1536
    # a decode chunk's 32 rows touch 87 % of 64 experts: the grouped kernel
    # leaves the other eight unread (measured: 759 us a layer against 815)
    chunk = moe.dispatch_plan(32, 64, 4, **kw)
    assert chunk["dispatch"] == "grouped:moe_grouped_matmul"
    assert chunk["experts_touched"] == pytest.approx(55.89, abs=0.01)
    assert chunk["expert_bytes_read"] == int(64 * (1 - (60 / 64) ** 32) * expert)
    assert chunk["expert_rows_computed"] == 1088  # 128 routed pairs in 16-row tiles
    # 64 rows touch 98 % of them, 128 all: the dense einsums read every
    # expert at the weight read's pace (measured at 128: 968 us against 976)
    for rows in (64, 128):
        dense = moe.dispatch_plan(rows, 64, 4, **kw)
        assert dense["dispatch"] == "dense" and dense["expert_bytes_read"] == 64 * expert
    # few experts: the plan PR 34 measured, whatever the rows
    assert moe.dispatch_plan(2, 8, 2, **kw)["dispatch"] == "dense"
    # the buckets: the row tile follows what even routing deals an expert
    tiles = {t: moe._row_chunk(4 * t, 64) for t in (32, 256, 512, 1024, 2048, 4096)}
    assert tiles == {32: 16, 256: 32, 512: 64, 1024: 128, 2048: 128, 4096: 128}
    big = moe.dispatch_plan(4096, 64, 4, **kw)
    assert big["dispatch"] == "grouped:moe_grouped_matmul" and big["expert_rows_computed"] == 24448
    assert moe.expected_experts_touched(1, 8, 2) == pytest.approx(2.0)


# ------------------------------------------------------ (g) the pool's kernel


@pytest.mark.parametrize("lengths", [
    [0, 1, 8, 37, 72], [16, 32, 48, 64, 72], [0, 0, 0, 0, 0], [5, 0, 72, 0, 9],
], ids=["ragged", "block-boundaries", "all-dead", "dead-between"])
def test_paged_latent_attention_kernel_matches_the_gather(lengths):
    rng = np.random.default_rng(0)
    batch, heads, width, values, block, blocks, table_width = 5, 20, 128, 64, 8, 60, 9
    pool = jnp.asarray(rng.standard_normal((blocks, block, width)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((batch, heads, width)), jnp.float32)
    table = rng.permutation(blocks - 1)[:batch * table_width].reshape(batch, table_width) + 1
    table = jnp.asarray(table, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    kw = dict(value_dim=values, scale=0.11)
    want = np.asarray(paged.paged_latent_attention(q, pool, table, lens, impl="reference", **kw))
    got = np.asarray(paged.paged_latent_attention(q, pool, table, lens, impl="pallas", **kw))
    live = np.asarray(lengths) > 0
    assert got.shape == (batch, heads, values)
    if live.any():
        assert np.abs(got[live] - want[live]).max() < 2e-6
    assert not got[~live].any()  # a dead row gathers nothing and writes zeros


def test_paged_latent_attention_walks_several_groups(monkeypatch):
    """Rows longer than a group: the online softmax carries across them."""
    monkeypatch.setattr(paged, "_LATENT_ROWS_PER_STEP", 16)
    test_paged_latent_attention_kernel_matches_the_gather([0, 1, 8, 37, 72])


def test_latent_attention_is_the_expanded_attention():
    """The absorbed identity on its own: scores and values through the
    latent equal ordinary attention on the expanded keys and values."""
    rng = np.random.default_rng(1)
    b, s, h, rank, rope, nope, vd = 2, 6, 3, 16, 8, 12, 20
    c = rng.standard_normal((b, s, rank)).astype(np.float32)
    kr = rng.standard_normal((b, s, rope)).astype(np.float32)
    w = rng.standard_normal((rank, h, nope + vd)).astype(np.float32) / 4
    qn = rng.standard_normal((b, s, h, nope)).astype(np.float32)
    qr = rng.standard_normal((b, s, h, rope)).astype(np.float32)
    up = np.einsum("bsc,chd->bshd", c, w)
    k = np.concatenate([up[..., :nope], np.broadcast_to(kr[:, :, None], (b, s, h, rope))], -1)
    sc = np.einsum("bqhd,bkhd->bhqk", np.concatenate([qn, qr], -1), k) * 0.2
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), up[..., nope:])
    q_row = np.concatenate([np.einsum("bshd,chd->bshc", qn, w[..., :nope]), qr], -1)
    bias = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0, paged.NEG_INF)[None, None]
    o_lat = paged.latent_attention(jnp.asarray(q_row), jnp.asarray(np.concatenate([c, kr], -1)), bias,
                                   value_dim=rank, scale=0.2)
    got = np.einsum("bshc,chd->bshd", np.asarray(o_lat), w[..., nope:])
    assert np.abs(got - want).max() < 1e-5


# ----------------------------------------------------------- (b) the engine


def _serve(monkeypatch, module, params, prompts, *, slots=2, new_tokens=24, paged=True, together=False,
           buckets=(32, 128), **engine_kw):
    """Serve ``prompts`` through a new engine and return, for each, its
    tokens and the logits the engine sampled them from, and the engine's
    stats at the end."""
    seen = []

    def make_sampler(**_):
        def sample(logits, key):
            jax.debug.callback(lambda rows: seen.append(np.asarray(rows)), logits, ordered=True)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return sample

    monkeypatch.setattr(generate_mod, "make_sampler", make_sampler)
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens, prompt_buckets=buckets, paged=paged,
        kv_block_size=16 if paged else None, chunk_steps=4, pipeline_depth=2,
        registry=telemetry.MetricsRegistry(), **engine_kw,
    )
    out = []
    try:
        for prompt in prompts:
            del seen[:]
            tokens = engine.generate(params, [prompt])[0]
            jax.effects_barrier()
            rows = [seen[0][0]] + [r[0] for r in seen[1:] if r.shape[0] == slots]
            out.append((tokens, np.stack(rows[:len(tokens)])))
        deadline = time.monotonic() + 30
        while paged and engine.stats()["kv_pool"]["blocks_in_use"] and time.monotonic() < deadline:
            time.sleep(0.02)
        stats = engine.stats()
    finally:
        engine.close()
    return out, stats


def _worst_gap(params, cfg, prompt, tokens, logits):
    want = _reference_logits(params, list(prompt) + list(tokens), cfg)
    return np.abs(logits - want[len(prompt) - 1:len(prompt) - 1 + len(tokens)]).max()


@pytest.mark.parametrize(
    "lengths,paged_impl,prefill_impl,kw",
    [((20, 5), "reference", "cached", {}), ((64, 128), "pallas", "flash", {}),
     ((100, 70), "reference", "flash", {"prefill_chunk": 64}),
     ((20, 100), "reference", "cached", {"paged": False})],
    ids=["gather", "kernel-and-flash", "chunked-prefill", "contiguous-cache"],
)
def test_engine_serves_the_references_logits(monkeypatch, served, lengths, paged_impl, prefill_impl, kw):
    """Right-padded in its bucket, prefilled (expanded, through the flash
    kernel or plainly; or in lead chunks that read the rows before them,
    absorbed), committed to the latent pool by block scatter, then 24
    tokens decoded through the pool by the absorbed form: every sampled
    row of logits is the reference's row of its full pass."""
    _, params = served
    module = GlmMoeLite(_tiny(paged_impl=paged_impl, prefill_impl=prefill_impl))
    prompts = _prompts(*lengths)
    results, stats = _serve(monkeypatch, module, params, prompts, **kw)
    for prompt, (tokens, logits) in zip(prompts, results):
        assert len(tokens) == 24
        assert _worst_gap(params, module.config, prompt, tokens, logits) < LOGIT_TOL
    if kw.get("paged", True):
        pool = stats["kv_pool"]
        # three layers of 24 float32 values in 128 lanes
        assert pool["row_layout"] == "latent" and pool["bytes_per_token"] == 3 * 4 * 128
        assert pool["blocks_in_use"] == 0 and pool["freed_blocks"] == pool["allocated_blocks"] > 0
        assert stats["moe"]["decode_chunk"]["router"] == "sigmoid"
        assert stats["moe"]["decode_chunk"]["experts_touched"] == pytest.approx(8 * (1 - 0.75 ** 2), abs=0.01)
        assert {"decode_chunk", "prefill_128"} <= set(stats["moe"]) and len(stats["moe"]) == 3


def _int8_served():
    module = GlmMoeLite(GlmMoeLiteConfig.tiny(vocab_size=VOCAB, quantized=True, paged_impl="pallas"))
    qparams = quantize_params(_params(GlmMoeLite(_tiny())), GLM_MOE_LITE_QUANT_PATTERNS)
    return module, qparams


def test_the_serving_precision_holds_its_tolerance(monkeypatch):
    """bfloat16 activations on int8 weights through the engine, against the
    float32 reference on the same int8 weights: within ``SERVING_TOL``."""
    module, qparams = _int8_served()
    prompts = _prompts(60, 23, seed=7)
    results, _ = _serve(monkeypatch, module, qparams, prompts)
    gaps = [_worst_gap(qparams, module.config, p, *r) for p, r in zip(prompts, results)]
    assert max(gaps) < SERVING_TOL, gaps


def test_int4_weights_fail_the_serving_tolerance():
    _, qparams = _int8_served()

    def to_int4(tree):
        if isinstance(tree, dict) and "kernel_q" in tree:
            w = tree["kernel_q"].astype(jnp.float32) * tree["scale"]
            s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 7.0
            return {"kernel": jnp.clip(jnp.round(w / s), -7, 7) * s}
        return {k: to_int4(v) for k, v in tree.items()} if isinstance(tree, dict) else tree

    tokens = _prompts(80, seed=7)[0]
    cfg = _tiny()
    rounded, sound = (_reference_logits(p, tokens, cfg) for p in (to_int4(qparams), qparams))
    gap = np.abs(rounded - sound).max()
    assert gap > 2 * SERVING_TOL, gap


def test_an_absorbed_score_without_its_rotary_term_is_caught(monkeypatch):
    module, qparams = _int8_served()
    real = glm_mod.paged_latent_attention

    def no_rope(q, pool, table, lengths, **kw):
        rank = module.config.kv_lora_rank
        return real(q.at[..., rank:].set(0), pool, table, lengths, **kw)

    monkeypatch.setattr(glm_mod, "paged_latent_attention", no_rope)
    prompts = _prompts(60, seed=7)
    results, _ = _serve(monkeypatch, module, qparams, prompts)
    assert _worst_gap(qparams, module.config, prompts[0], *results[0]) > 2 * SERVING_TOL


# --------------------------------------------------------- (f) the latent pool


def test_cache_layout_is_a_latent_row_a_layer():
    layout = GlmMoeLite(GlmMoeLiteConfig(num_hidden_layers=13)).cache_layout()
    assert layout == (LatentRows(512, 64, "bfloat16"),) * 13
    row = layout[0]
    assert row.owns_rows and row.kind == "latent" and KVRows(8, 128).kind == "kv"
    assert row.width == 576 and row.row_nbytes() == 1152          # what a position holds
    assert row.stored_width == 640 and row.pool_row_nbytes() == 1280   # and takes, in whole lane tiles
    # against 20 heads' keys and values of 256 each
    assert 20 * (256 + 256) * 2 / row.row_nbytes() == pytest.approx(17.8, abs=0.05)
    (buf,) = row.init(3, 32)
    assert buf.shape == (3, 32, 640) and buf.dtype == jnp.bfloat16
    assert KVRows(8, 128).pool_row_nbytes() == KVRows(8, 128).row_nbytes() == 4096


def test_a_cached_prefix_admission_equals_a_cold_one(monkeypatch, served):
    """The prefix cache takes latent blocks as it takes KV blocks: the
    second admission of a prompt splices them and serves the cold one's
    logits; a prompt that shares 32 tokens prefills only its tail."""
    module, params = served
    shared = _prompts(32, seed=9)[0]
    first, second = shared + _prompts(9, seed=10)[0], shared + _prompts(14, seed=11)[0]
    cache = RadixPrefixCache(block_size=16, registry=telemetry.MetricsRegistry())
    results, stats = _serve(
        monkeypatch, module, params, [first, first, second], prefix_cache=cache, buckets=(64,),
    )
    (cold_t, cold_l), (warm_t, warm_l), (part_t, part_l) = results
    assert warm_t == cold_t and np.abs(warm_l - cold_l).max() < 1e-5
    assert _worst_gap(params, module.config, first, warm_t, warm_l) < LOGIT_TOL
    assert _worst_gap(params, module.config, second, part_t, part_l) < LOGIT_TOL
    pc = stats["prefix_cache"]
    assert pc["hits"] + pc["partial_hits"] >= 2 and pc["prefill_tokens_saved"] >= 64
    assert stats["kv_pool"]["blocks_in_use"] == 0


def test_a_preempted_stream_resumes_from_its_latent_blocks(served):
    """Eviction extracts the victim's latent blocks into the host store
    and the resume splices them back: both streams end as their solo
    runs do."""
    module, params = served

    def engine(**kw):
        registry = telemetry.MetricsRegistry()
        return DecodeEngine(
            module, paged=True, registry=registry, slots=2, max_new_tokens=48, prompt_buckets=(64,),
            chunk_steps=2, pipeline_depth=2, kv_block_size=16,
            prefix_cache=RadixPrefixCache(block_size=16, registry=registry), **kw,
        )

    low_prompt, high_prompt = _prompts(8, 8, seed=12)
    solo = engine()
    try:
        want_low = solo.generate(params, [low_prompt])[0]
        want_high = solo.generate(params, [high_prompt], max_new_tokens=8)[0]
    finally:
        solo.close()
    eng = engine(kv_pool_blocks=5)  # capacity 4: one resident fits
    try:
        low_out, errors = [], []

        def low_client():
            try:
                for chunk in eng.generate_stream(params, low_prompt, priority="low"):
                    low_out.extend(chunk)
            except BaseException as exc:  # pragma: no cover - fails below
                errors.append(exc)

        t = threading.Thread(target=low_client)
        t.start()
        deadline = time.monotonic() + 60
        while not low_out and time.monotonic() < deadline:
            time.sleep(0.002)
        high_out = eng.generate(params, [high_prompt], max_new_tokens=8, priority="high")[0]
        t.join(timeout=120)
        assert not t.is_alive() and not errors
        assert high_out == want_high and low_out == want_low
        assert eng.stats()["scheduler"]["preemptions"] >= 1
    finally:
        eng.close()


def test_latent_blocks_hand_off_between_engines(served):
    """``prefill_export`` on one engine, ``kv_export`` / ``kv_import`` to
    another's host store: the second engine splices the latent blocks
    and serves the first one's tokens."""
    module, params = served

    def engine():
        registry = telemetry.MetricsRegistry()
        return DecodeEngine(
            module, paged=True, registry=registry, slots=2, max_new_tokens=12, prompt_buckets=(64,),
            chunk_steps=2, kv_block_size=16, prefix_cache=RadixPrefixCache(block_size=16, registry=registry),
        )

    prompt = _prompts(40, seed=13)[0]
    donor, taker = engine(), engine()
    try:
        want = donor.generate(params, [prompt])[0]
        handle = donor.prefill_export(params, prompt)
        handle["lease"].release()
        assert handle["tokens"] == want[:1] and handle["cached_tokens"] >= 32
        entries = donor.kv_export(prompt)
        assert entries and taker.kv_import(entries) == len(entries)
        assert taker.generate(params, [prompt])[0] == want
        assert taker.stats()["prefix_cache"]["prefill_tokens_saved"] >= 32
    finally:
        donor.close()
        taker.close()


def test_speculation_over_a_paged_latent_pool_is_refused_as_over_any_paged_pool(served):
    module, _ = served
    with pytest.raises(ValueError, match="speculative engine does not compose with the paged"):
        DecodeEngine(module, draft_module=module, speculate_k=2, paged=True, prompt_buckets=(32,))


def test_the_admit_span_and_the_perf_plane_carry_the_new_counters(served):
    module, params = served
    tracer = telemetry.get_tracer()
    seen = []
    tracer.add_listener(lambda rid, meta, spans: seen.append(spans))
    engine = DecodeEngine(
        module, paged=True, slots=2, max_new_tokens=8, prompt_buckets=(32,), kv_block_size=16, chunk_steps=2,
        registry=telemetry.MetricsRegistry(),
    )
    try:
        engine.generate(params, _prompts(20, seed=14))
        report = engine.perf.report()
    finally:
        engine.close()
    admits = [s for spans in seen for s in spans if s["name"] == "admit"]
    assert admits and all(s["args"]["latent_layers"] == 3 and s["args"]["state_layers"] == 0 for s in admits)
    assert "kv_tokens_resident" in report and report["kv_tokens_resident"] >= 0
