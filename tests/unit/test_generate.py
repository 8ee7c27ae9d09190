"""Autoregressive generation tests: the scan-decode path must match
step-free full-recompute decoding, and left-padded prompts must generate
exactly what their unpadded versions do (pad masking + logical RoPE
positions)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import Llama, LlamaConfig
from unionml_tpu.models.generate import make_generator, make_lm_predictor


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(vocab_size=97)
    module = Llama(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens)["params"]
    return module, params


def _reference_greedy(module, params, prompt, n_new):
    """Decode by re-running the full (growing) sequence each step — no
    cache, no scan. The gold standard the fused path must match."""
    toks = np.asarray(prompt)
    out = []
    for _ in range(n_new):
        logits = module.apply({"params": params}, jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1).astype(jnp.int32))
        out.append(nxt)
        toks = np.concatenate([toks, nxt[:, None]], axis=1)
    return np.stack(out, axis=1)


def test_scan_decode_matches_full_recompute(tiny_llama):
    module, params = tiny_llama
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(1, 97, size=(2, 6)), jnp.int32
    )
    gen = make_generator(module, max_new_tokens=5, max_len=32)
    got = np.asarray(gen(params, prompt))
    want = _reference_greedy(module, params, prompt, 5)
    np.testing.assert_array_equal(got, want)


def test_flash_prefill_matches_cached_prefill(tiny_llama):
    """``prefill_impl="flash"`` (monolithic long-prompt prefill through
    the Pallas kernel — no [B,H,S,max_len] score buffer) must generate
    the cached path's tokens on a ragged LEFT-PADDED batch. Exact here
    (fp32 interpret on CPU); on TPU the kernel's bf16 p@v cast makes it
    tolerance-equivalent, like the training flash path."""
    module, params = tiny_llama
    cfg_f = dataclasses.replace(module.config, prefill_impl="flash")
    fmod = Llama(cfg_f)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(1, 97, size=(3, 24)), jnp.int32)
    mask = jnp.asarray(
        [[True] * 24, [False] * 5 + [True] * 19, [False] * 20 + [True] * 4]
    )
    toks = jnp.where(mask, toks, 0)

    gen_c = make_generator(module, max_new_tokens=6, max_len=64)
    gen_f = make_generator(fmod, max_new_tokens=6, max_len=64)
    out_c = np.asarray(gen_c(params, toks, prompt_mask=mask))
    out_f = np.asarray(gen_f(params, toks, prompt_mask=mask))
    np.testing.assert_array_equal(out_c, out_f)

    # a CHUNKED prefill under the flash config must not take the flash
    # path (the tail no longer covers the whole history) — still exact
    gen_fc = make_generator(fmod, max_new_tokens=6, max_len=64, prefill_chunk=8)
    np.testing.assert_array_equal(
        np.asarray(gen_fc(params, toks, prompt_mask=mask)), out_c
    )

    # composes with the int8 KV cache: flash prefill reads the EXACT
    # fresh k/v (decode still reads the quantized cache), so tokens may
    # differ from the cached path — deterministic and well-formed
    cfg_q = dataclasses.replace(cfg_f, kv_quant=True)
    gen_q = make_generator(Llama(cfg_q), max_new_tokens=6, max_len=64)
    out_q = np.asarray(gen_q(params, toks, prompt_mask=mask))
    np.testing.assert_array_equal(
        out_q, np.asarray(gen_q(params, toks, prompt_mask=mask))
    )
    assert out_q.shape == out_c.shape and (out_q < 97).all()

    # the prefix-cache build is the other monolithic full prefill: its
    # flash-built cache must match the cached-impl build (layer i's
    # attention output feeds layer i+1's k/v, so this checks the whole
    # stack, not just the write path)
    from unionml_tpu.models.generate import make_prefix_cache

    prefix = rng.integers(1, 97, size=12).tolist()
    pc_c = make_prefix_cache(module, params, prefix_tokens=prefix, max_len=64)
    pc_f = make_prefix_cache(fmod, params, prefix_tokens=prefix, max_len=64)
    for lc, lf in zip(pc_c.cache, pc_f.cache):
        for bc, bf in zip(lc, lf):
            # a few bf16 ulps: the two attention algorithms round
            # differently into the bf16 residual stream from layer 1 on
            np.testing.assert_allclose(
                np.asarray(bc, np.float32), np.asarray(bf, np.float32),
                atol=6e-2,
            )


def test_left_padded_prompts_match_unpadded(tiny_llama):
    module, params = tiny_llama
    rng = np.random.default_rng(1)
    p1 = rng.integers(1, 97, size=(1, 4)).astype(np.int32)
    p2 = rng.integers(1, 97, size=(1, 7)).astype(np.int32)

    gen7 = make_generator(module, max_new_tokens=4, max_len=32)
    # unpadded references, one at a time
    ref1 = np.asarray(gen7(params, jnp.asarray(p1)))
    ref2 = np.asarray(gen7(params, jnp.asarray(p2)))

    # batched with left-padding to 7 + mask
    batch = np.zeros((2, 7), np.int32)
    mask = np.zeros((2, 7), bool)
    batch[0, 3:] = p1[0]
    mask[0, 3:] = True
    batch[1, :] = p2[0]
    mask[1, :] = True
    got = np.asarray(
        gen7(params, jnp.asarray(batch), jax.random.PRNGKey(0), jnp.asarray(mask))
    )
    np.testing.assert_array_equal(got[0], ref1[0])
    np.testing.assert_array_equal(got[1], ref2[0])


def test_eos_freezes_sequence(tiny_llama):
    module, params = tiny_llama
    prompt = jnp.asarray([[5, 9, 11]], jnp.int32)
    gen = make_generator(module, max_new_tokens=6, max_len=32)
    plain = np.asarray(gen(params, prompt))[0]
    # use the first generated token as the eos id: everything after must pad
    eos = int(plain[0])
    gen_eos = make_generator(module, max_new_tokens=6, max_len=32, eos_id=eos, pad_id=0)
    got = np.asarray(gen_eos(params, prompt))[0]
    assert got[0] == eos
    assert np.all(got[1:] == 0)


def test_sampling_is_deterministic_per_key_and_varies_across_keys(tiny_llama):
    module, params = tiny_llama
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    gen = make_generator(module, max_new_tokens=8, max_len=32, temperature=1.0, top_k=20)
    a = np.asarray(gen(params, prompt, jax.random.PRNGKey(7)))
    b = np.asarray(gen(params, prompt, jax.random.PRNGKey(7)))
    c = np.asarray(gen(params, prompt, jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_sampling_without_key_rejected_and_mask_without_cache_rejected(tiny_llama):
    module, params = tiny_llama
    gen = make_generator(module, max_new_tokens=2, max_len=16, temperature=0.7)
    with pytest.raises(ValueError, match="PRNG key"):
        gen(params, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="kv_mask requires a KV cache"):
        module.apply(
            {"params": params}, jnp.zeros((1, 4), jnp.int32),
            kv_mask=jnp.ones((1, 4), bool),
        )


def test_lm_predictor_batch_bucketing(tiny_llama):
    module, params = tiny_llama

    class S:
        params = None

    s = S()
    s.params = params
    predictor = make_lm_predictor(
        module, max_new_tokens=2, max_len=64, bucket_lens=(16, 8)  # unsorted on purpose
    )
    # 3 prompts pad to a batch of 4 internally; results per row still exact
    out = predictor(s, [[1, 2], [3, 4, 5], [6]])
    assert len(out) == 3
    gen = make_generator(module, max_new_tokens=2, max_len=64)
    ref = np.asarray(gen(params, jnp.asarray([[0, 0, 0, 0, 0, 0, 1, 2]], jnp.int32),
                         None, jnp.asarray([[False] * 6 + [True] * 2])))
    np.testing.assert_array_equal(np.asarray(out[0]), ref[0])


def test_lm_predictor_sizes_cache_per_bucket(tiny_llama, monkeypatch):
    # decode attention reads the whole cache each step: the predictor must
    # build one generator per bucket with cache = bucket + max_new_tokens,
    # not one full-cfg.max_len cache for everything (measured ~4x p50)
    module, params = tiny_llama
    from unionml_tpu.models import generate as gen_mod

    seen = []
    real = gen_mod.make_generator

    def spy(mod, **kwargs):
        seen.append(kwargs["max_len"])
        return real(mod, **kwargs)

    monkeypatch.setattr(gen_mod, "make_generator", spy)
    predictor = gen_mod.make_lm_predictor(
        module, max_new_tokens=4, bucket_lens=(8, 16, 64)
    )
    assert sorted(seen) == [12, 20, 68]
    # bucketed-cache results still match a full-cache generator
    out = predictor(params, [[1, 2, 3]])
    full = real(module, max_new_tokens=4, max_len=module.config.max_len)
    ref = np.asarray(
        full(params, jnp.asarray([[0] * 5 + [1, 2, 3]], jnp.int32), None,
             jnp.asarray([[False] * 5 + [True] * 3]))
    )
    np.testing.assert_array_equal(np.asarray(out[0]), ref[0])


def test_top_p_sampling_restricts_to_nucleus(tiny_llama):
    module, params = tiny_llama
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    # find the greedy continuation: with a tight nucleus every sampled
    # token must stay inside the few top-probability tokens
    greedy = make_generator(module, max_new_tokens=1, max_len=16)
    logits_top = int(np.asarray(greedy(params, prompt))[0, 0])

    gen = make_generator(
        module, max_new_tokens=1, max_len=16, temperature=1.0, top_p=1e-6
    )
    # top_p so tight only the argmax survives: sampling becomes greedy
    for seed in range(5):
        out = gen(params, prompt, jax.random.PRNGKey(seed))
        assert int(np.asarray(out)[0, 0]) == logits_top

    # permissive nucleus still yields valid tokens and varies across keys
    gen_loose = make_generator(
        module, max_new_tokens=4, max_len=16, temperature=1.0, top_p=0.9
    )
    outs = {
        tuple(np.asarray(gen_loose(params, prompt, jax.random.PRNGKey(s)))[0])
        for s in range(8)
    }
    assert len(outs) > 1  # actually sampling


def test_top_p_validation():
    from unionml_tpu.models import Llama, LlamaConfig

    module = Llama(LlamaConfig.tiny())
    with pytest.raises(ValueError, match="top_p"):
        make_generator(module, max_new_tokens=1, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        make_generator(module, max_new_tokens=1, top_p=1.5)


def test_serving_params_casts_floats_only():
    from unionml_tpu.models import serving_params

    tree = {"w": jnp.ones((2,), jnp.float32), "q": jnp.ones((2,), jnp.int8),
            "s": jnp.ones((2,), jnp.float32)}
    cast = serving_params(tree)
    assert cast["w"].dtype == jnp.bfloat16
    assert cast["s"].dtype == jnp.bfloat16
    assert cast["q"].dtype == jnp.int8


def test_serving_params_preserves_quantization_metadata():
    """Scales and the MoE router stay fp32 through the serving cast.

    The dequant contract applies the fp32 scale BEFORE the single cast
    down; bf16-rounding the scales (or the fp32 router master) would make
    quantize-then-cast disagree with the benchmarked cast-then-quantize
    order (ADVICE round 1: templates/llm_serving applies serving_params
    after quantize_params).
    """
    from unionml_tpu.models import serving_params

    tree = {
        "dense": {"kernel_q": jnp.ones((2, 2), jnp.int8),
                  "scale": jnp.ones((2,), jnp.float32)},
        "moe": {"w_gate_q": jnp.ones((2, 2, 2), jnp.int8),
                "w_gate_scale": jnp.ones((2, 2), jnp.float32),
                "router_kernel": jnp.ones((2, 4), jnp.float32)},
        "attn": {"kernel": jnp.ones((2, 2), jnp.float32)},
    }
    cast = serving_params(tree)
    assert cast["dense"]["scale"].dtype == jnp.float32
    assert cast["moe"]["w_gate_scale"].dtype == jnp.float32
    assert cast["moe"]["router_kernel"].dtype == jnp.float32
    assert cast["attn"]["kernel"].dtype == jnp.bfloat16
    assert cast["dense"]["kernel_q"].dtype == jnp.int8

    # a norm param also named "scale" has no int8 sibling -> it DOES cast
    norm_tree = {"norm": {"scale": jnp.ones((2,), jnp.float32),
                          "bias": jnp.zeros((2,), jnp.float32)}}
    assert serving_params(norm_tree)["norm"]["scale"].dtype == jnp.bfloat16
    # bare-array input (no containing dict) still casts
    assert serving_params(jnp.ones((3,), jnp.float32)).dtype == jnp.bfloat16
    # FrozenDict input is accepted
    import flax.core

    frozen = flax.core.freeze(tree)
    assert serving_params(frozen)["dense"]["scale"].dtype == jnp.float32


def test_generation_rejects_cache_overflow(tiny_llama):
    module, params = tiny_llama
    gen = make_generator(module, max_new_tokens=8, max_len=12)
    ok = gen(params, jnp.zeros((1, 4), jnp.int32))  # 4 + 8 == 12 fits
    assert ok.shape == (1, 8)
    with pytest.raises(ValueError, match="exceeds the KV cache"):
        gen(params, jnp.zeros((1, 5), jnp.int32))   # 5 + 8 > 12


def test_generation_under_tensor_parallel_sharding(tiny_llama):
    """Serving multi-chip path: params TP-sharded over the mesh, the
    jitted generate runs with GSPMD collectives, output identical to the
    unsharded run."""
    from unionml_tpu.models import LLAMA_PARTITION_RULES
    from unionml_tpu.parallel import ShardingConfig, shard_pytree

    module, params = tiny_llama
    prompt = jnp.asarray([[7, 3, 9, 2]], jnp.int32)
    gen = make_generator(module, max_new_tokens=4, max_len=32)
    ref = np.asarray(gen(params, prompt))

    cfg = ShardingConfig(data=-1, tensor=2, rules=LLAMA_PARTITION_RULES)
    sharded_params = shard_pytree(params, cfg)
    spec_leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda x: tuple(x.sharding.spec), sharded_params)
    )
    assert any("tensor" in str(s) for s in spec_leaves)  # actually sharded
    got = np.asarray(gen(sharded_params, prompt))
    np.testing.assert_array_equal(got, ref)


def test_flash_prefill_under_tensor_parallel_sharding(tiny_llama):
    """prefill_impl="flash" composes with TP-sharded serving params:
    GSPMD handles the Pallas prefill call without breaking compilation,
    and tokens match the unsharded flash run."""
    from unionml_tpu.models import LLAMA_PARTITION_RULES
    from unionml_tpu.parallel import ShardingConfig, shard_pytree

    module, params = tiny_llama
    fmod = Llama(dataclasses.replace(module.config, prefill_impl="flash"))
    prompt = jnp.asarray([[7, 3, 9, 2, 11, 5]], jnp.int32)
    gen = make_generator(fmod, max_new_tokens=4, max_len=32)
    ref = np.asarray(gen(params, prompt))

    cfg = ShardingConfig(data=-1, tensor=2, rules=LLAMA_PARTITION_RULES)
    got = np.asarray(gen(shard_pytree(params, cfg), prompt))
    np.testing.assert_array_equal(got, ref)


def test_remat_gradients_match_non_remat(tiny_llama):
    """remat recomputes, never changes math: grads must agree to the
    float32 reassociation floor."""
    module, params = tiny_llama
    cfg = module.config
    rm = Llama(dataclasses.replace(cfg, remat=True))
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(1, 97, size=(2, 12)), jnp.int32
    )

    def loss(m):
        def f(p):
            logits = m.apply({"params": p}, tokens)
            return jnp.mean(logits.astype(jnp.float32) ** 2)
        return f

    g_plain = jax.grad(loss(module))(params)
    g_remat = jax.grad(loss(rm))(params)
    # remat changes the graph XLA fuses, and tiny() runs bf16
    # activations (2^-8 ~ 4e-3 relative rounding): refusing vs reusing
    # an activation rounds it differently, so grad elements drift by
    # ~activation_eps * |grad| — measured up to 1.8e-4 absolute on this
    # geometry, with unbounded RELATIVE drift on near-zero elements
    # (sign flips; the old rtol=1e-5/atol=1e-6 flaked at clean HEAD).
    # atol=1e-3 is ~5x the measured bf16 floor; a real math change
    # (dropped term, wrong residual) moves grads at O(|grad|) and still
    # fails loudly.
    for a, b in zip(
        jax.tree_util.tree_leaves(g_plain), jax.tree_util.tree_leaves(g_remat)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-3)


def test_lm_predictor_ragged_prompts(tiny_llama):
    module, params = tiny_llama

    class S:  # predictor accepts raw params or state-like objects
        pass

    s = S()
    s.params = params
    predictor = make_lm_predictor(
        module, max_new_tokens=3, max_len=64, bucket_lens=(8, 16)
    )
    out = predictor(s, [[1, 2, 3], [4, 5, 6, 7, 8]])
    assert len(out) == 2 and all(len(row) == 3 for row in out)
    # per-row results equal the unpadded single-prompt generation
    gen = make_generator(module, max_new_tokens=3, max_len=64)
    ref = np.asarray(gen(params, jnp.asarray([[4, 5, 6, 7, 8]], jnp.int32)))
    np.testing.assert_array_equal(np.asarray(out[1]), ref[0])


def test_lm_predictor_warmup_compiles_all_shapes(tiny_llama):
    """warmup() pre-compiles every (bucket, power-of-two batch) executable
    so a live server never stalls a request behind a first-hit XLA
    compile."""
    module, params = tiny_llama
    pred = make_lm_predictor(module, max_new_tokens=4, bucket_lens=(8, 16), max_len=32)
    n = pred.warmup(params, max_batch=4)
    assert n == 2 * 3  # buckets {8, 16} x batches {1, 2, 4}
    out = pred(params, [[1, 2, 3]])
    assert len(out) == 1 and len(out[0]) == 4


def test_warmup_rejects_unusable_bucket(tiny_llama):
    """A warmup bucket outside the usable set would silently compile the
    covering bucket instead — callers must get a ValueError, not a false
    belief that the shape was pre-compiled."""
    module, params = tiny_llama
    pred = make_lm_predictor(module, max_new_tokens=4, bucket_lens=(8, 16), max_len=32)
    with pytest.raises(ValueError, match="not in the usable bucket"):
        pred.warmup(params, max_batch=1, buckets=(64,))
    with pytest.raises(ValueError, match="empty bucket tuple"):
        pred.warmup(params, max_batch=1, buckets=())


# -- int8 KV cache -------------------------------------------------------- #


def test_kv_quant_cache_structure_and_memory():
    """kv_quant caches store int8 k/v + per-(pos, head) fp32 scales —
    about half the bytes of the bf16 form (the long-context bound)."""
    from unionml_tpu.models import init_cache

    cfg = LlamaConfig.tiny(vocab_size=97, kv_quant=True)
    cache = init_cache(cfg, batch=2, max_len=64)
    assert len(cache[0]) == 4
    k_q, v_q, k_s, v_s = cache[0]
    assert k_q.dtype == jnp.int8 and k_s.dtype == jnp.float32
    assert k_s.shape == k_q.shape[:-1]
    bf16 = init_cache(LlamaConfig.tiny(vocab_size=97), batch=2, max_len=64)
    bytes_q = sum(x.size * x.dtype.itemsize for layer in cache for x in layer)
    bytes_b = sum(x.size * x.dtype.itemsize for layer in bf16 for x in layer)
    # int8 bytes + one fp32 scale per head_dim values vs bf16: for this
    # tiny head_dim=16 that's (1 + 4/16)/2 = 0.625; at the zoo's
    # head_dim=128 it is (1 + 4/128)/2 ~ 0.516 — about half
    head_dim = cfg.head_dim
    assert bytes_q == pytest.approx((1 + 4 / head_dim) / 2 * bytes_b)


def test_kv_quant_attention_close_to_bf16_cache(tiny_llama):
    """Cached decode logits with the int8 cache stay within the int8
    grid's error of the bf16-cache logits (same params, same prompt)."""
    module, params = tiny_llama
    qcfg = dataclasses.replace(module.config, kv_quant=True)
    qmodule = Llama(qcfg)
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(1, 97, size=(2, 6)), jnp.int32
    )
    from unionml_tpu.models import init_cache

    out = {}
    for mod in (module, qmodule):
        cache = init_cache(mod.config, 2, 32)
        logits, cache = mod.apply(
            {"params": params}, prompt, cache=cache, cache_index=jnp.int32(0)
        )
        # one decode step reading the quantized prefix
        step_logits, _ = mod.apply(
            {"params": params},
            jnp.argmax(logits[:, -1:], -1).astype(jnp.int32),
            cache=cache, cache_index=jnp.int32(6),
        )
        out[mod.config.kv_quant] = np.asarray(step_logits, np.float32)
    err = np.abs(out[True] - out[False]).max()
    scale = np.abs(out[False]).max() + 1e-9
    assert err / scale < 0.03, err / scale


def test_kv_quant_generation_end_to_end(tiny_llama):
    """Full generate() + bucketed predictor run on the quantized cache;
    padding invariance holds exactly WITHIN the quantized path."""
    module, params = tiny_llama
    qmodule = Llama(dataclasses.replace(module.config, kv_quant=True))
    gen = make_generator(qmodule, max_new_tokens=5, max_len=32)
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(1, 97, size=(2, 6)), jnp.int32
    )
    toks = np.asarray(gen(params, prompt))
    assert toks.shape == (2, 5)
    # greedy tokens from the quantized path agree with the exact path on
    # this tiny config (int8 KV error ~0.5% << the argmax margins here)
    exact = np.asarray(make_generator(module, max_new_tokens=5, max_len=32)(params, prompt))
    np.testing.assert_array_equal(toks, exact)

    pred = make_lm_predictor(qmodule, max_new_tokens=3, bucket_lens=(8, 16), max_len=32)
    out = pred(params, [[1, 2, 3], [4, 5, 6, 7, 8]])
    gen_ref = np.asarray(gen := make_generator(qmodule, max_new_tokens=3, max_len=64)(
        params, jnp.asarray([[4, 5, 6, 7, 8]], jnp.int32)
    ))
    np.testing.assert_array_equal(np.asarray(out[1]), gen_ref[0])


def test_chunked_prefill_matches_unchunked(tiny_llama):
    """prefill_chunk is a pure memory knob: same cache rows, same tokens
    — exactly — as one-shot prefill, including left-padded prompts and
    chunk sizes that do not divide the prompt length."""
    module, params = tiny_llama
    rng = np.random.default_rng(4)
    prompts = jnp.asarray(rng.integers(1, 97, size=(2, 12)), jnp.int32)
    want = np.asarray(
        make_generator(module, max_new_tokens=5, max_len=32)(params, prompts)
    )
    for chunk in (4, 5, 12):
        gen = make_generator(
            module, max_new_tokens=5, max_len=32, prefill_chunk=chunk
        )
        np.testing.assert_array_equal(np.asarray(gen(params, prompts)), want)
    # left-padded rows through the chunked path
    mask = jnp.asarray([[False] * 3 + [True] * 9, [True] * 12])
    padded = jnp.where(mask, prompts, 0)
    gen = make_generator(module, max_new_tokens=5, max_len=32, prefill_chunk=4)
    got = np.asarray(gen(params, padded, prompt_mask=mask))
    unchunked = np.asarray(
        make_generator(module, max_new_tokens=5, max_len=32)(
            params, padded, prompt_mask=mask
        )
    )
    np.testing.assert_array_equal(got, unchunked)


# -- shared-prefix (system prompt) serving -------------------------------- #


def test_prefix_cache_matches_concatenated_generation(tiny_llama):
    """Prefix-cached generation == prepending the prefix to every prompt,
    exactly (greedy), including left-padded rows and a chunked prefix
    build."""
    from unionml_tpu.models.generate import make_prefix_cache

    module, params = tiny_llama
    rng = np.random.default_rng(6)
    prefix = rng.integers(1, 97, 10).tolist()
    prompts = rng.integers(1, 97, (2, 6)).astype(np.int32)

    ref_gen = make_generator(module, max_new_tokens=5, max_len=64)
    cat = np.concatenate([np.tile(prefix, (2, 1)), prompts], axis=1)
    ref = np.asarray(ref_gen(params, jnp.asarray(cat, jnp.int32)))

    pc = make_prefix_cache(module, params, prefix, max_len=64)
    gen = make_generator(module, max_new_tokens=5, max_len=64, prefix_len=10)
    got = np.asarray(gen(params, jnp.asarray(prompts), prefix_cache=pc))
    np.testing.assert_array_equal(got, ref)

    # left-padded prompt rows: the reference is the LEFT-padded
    # concatenation (the plain generator's contract — pads first)
    mask = np.ones((2, 6), bool)
    mask[0, :2] = False
    padded = prompts.copy()
    padded[0, :2] = 0
    cat_p = np.zeros((2, 16), np.int32)
    cat_m = np.zeros((2, 16), bool)
    cat_p[0, 2:12], cat_p[0, 12:] = prefix, prompts[0, 2:]
    cat_m[0, 2:] = True
    cat_p[1, :10], cat_p[1, 10:] = prefix, prompts[1]
    cat_m[1, :] = True
    ref_p = np.asarray(
        ref_gen(params, jnp.asarray(cat_p), None, jnp.asarray(cat_m))
    )
    got_p = np.asarray(
        gen(params, jnp.asarray(padded), None, jnp.asarray(mask), prefix_cache=pc)
    )
    np.testing.assert_array_equal(got_p, ref_p)

    # chunked prefix build (non-dividing chunk) fills the same rows
    pc_chunked = make_prefix_cache(module, params, prefix, max_len=64, prefill_chunk=4)
    got_c = np.asarray(gen(params, jnp.asarray(prompts), prefix_cache=pc_chunked))
    np.testing.assert_array_equal(got_c, ref)


def test_prefix_cache_validations(tiny_llama):
    from unionml_tpu.models.generate import make_prefix_cache

    module, params = tiny_llama
    gen = make_generator(module, max_new_tokens=2, max_len=32, prefix_len=4)
    with pytest.raises(ValueError, match="prefix_cache must be passed"):
        gen(params, jnp.zeros((1, 4), jnp.int32))
    plain = make_generator(module, max_new_tokens=2, max_len=32)
    pc = make_prefix_cache(module, params, [1, 2, 3, 4], max_len=32)
    with pytest.raises(ValueError, match="prefix_cache must be passed"):
        plain(params, jnp.zeros((1, 4), jnp.int32), None, None, pc)
    with pytest.raises(ValueError, match="no cache room"):
        make_prefix_cache(module, params, list(range(1, 33)), max_len=32)


def test_lm_predictor_system_prefix(tiny_llama):
    """system_prefix through the bucketed predictor: per-row outputs equal
    prepending the prefix; the prefix cache is built once per params and
    reused across calls/buckets."""
    from unionml_tpu.models import generate as gen_mod

    module, params = tiny_llama
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, 97, 8).tolist()

    calls = []
    real = gen_mod.make_prefix_cache

    def spy(*args, **kwargs):
        calls.append(kwargs.get("max_len"))
        return real(*args, **kwargs)

    gen_mod.make_prefix_cache = spy
    try:
        pred = gen_mod.make_lm_predictor(
            module, max_new_tokens=3, bucket_lens=(8, 16), max_len=64,
            system_prefix=prefix,
        )
        out = pred(params, [[5, 6, 7], [9, 10, 11, 12]])
        out2 = pred(params, [[5, 6, 7]])
    finally:
        gen_mod.make_prefix_cache = real
    assert len(calls) == 1  # memoized per (state, bucket)

    full = make_generator(module, max_new_tokens=3, max_len=64)
    for row, prompt in zip(out, [[5, 6, 7], [9, 10, 11, 12]]):
        ref = np.asarray(
            full(params, jnp.asarray([prefix + prompt], jnp.int32))
        )
        np.testing.assert_array_equal(np.asarray(row), ref[0])
    assert out2[0] == out[0]


def test_lm_predictor_system_prefix_memoizes_for_lora_state(tiny_llama):
    """The prefix memo keys on the STATE object: a LoRATrainState resolves
    to a freshly-merged param tree every call, so an id(params) key would
    re-prefill per request (the bug this test pins)."""
    from unionml_tpu.models import create_lora_train_state
    from unionml_tpu.models import generate as gen_mod

    module, params = tiny_llama
    lora_module = Llama(dataclasses.replace(module.config, lora_rank=2))
    state = create_lora_train_state(
        lora_module, jnp.zeros((1, 8), jnp.int32), base_params=params
    )

    calls = []
    real = gen_mod.make_prefix_cache

    def spy(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    gen_mod.make_prefix_cache = spy
    try:
        pred = gen_mod.make_lm_predictor(
            lora_module, max_new_tokens=2, bucket_lens=(8,), max_len=32,
            system_prefix=[1, 2, 3],
        )
        first = pred(state, [[5, 6]])
        second = pred(state, [[5, 6]])
    finally:
        gen_mod.make_prefix_cache = real
    assert len(calls) == 1, "prefix re-prefilled per request for a LoRA state"
    assert first == second


def test_system_prefix_memo_warns_on_rewrapped_state():
    """Re-wrapping the same weight buffers in a fresh state object
    violates the memo's identity contract — the predictor must say so
    instead of silently re-prefilling the prefix per request. (The
    framework logger is propagate=False with a stream handler bound at
    import time, so attach a recording handler instead of capturing
    streams.)"""
    import logging

    from unionml_tpu._logging import logger as framework_logger

    cfg = LlamaConfig.tiny(vocab_size=53)
    module = Llama(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    predict = make_lm_predictor(
        module, max_new_tokens=4, bucket_lens=(8,), system_prefix=[5, 6, 7]
    )
    messages = []
    handler = logging.Handler()
    handler.emit = lambda record: messages.append(record.getMessage())
    framework_logger.addHandler(handler)
    try:
        predict(params, [[1, 2, 3]])
        assert not any("rebuilt" in m for m in messages)
        predict(dict(params), [[1, 2, 3]])  # same buffers, new wrapper
        assert any("rebuilt" in m for m in messages)
    finally:
        framework_logger.removeHandler(handler)
