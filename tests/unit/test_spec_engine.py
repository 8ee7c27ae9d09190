"""Speculative decoding inside the continuous-batching engine.

The contract: a DecodeEngine built with ``draft_module`` emits tokens
IDENTICAL to plain greedy decoding of the target — for any draft —
while slots advance by variable per-round acceptance. (The
make_speculative_generator acceptance rule, restructured for the
resident slot batch.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import Llama, LlamaConfig
from unionml_tpu.models.generate import make_generator
from unionml_tpu.serving.engine import DecodeEngine


@pytest.fixture(scope="module")
def pair():
    t_cfg = LlamaConfig.tiny(vocab_size=97)
    d_cfg = LlamaConfig.tiny(vocab_size=97, num_layers=1, hidden_dim=32,
                             num_heads=2, num_kv_heads=1, mlp_dim=64)
    target, draft = Llama(t_cfg), Llama(d_cfg)
    tp = target.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    dp = draft.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    return target, draft, {"target": tp, "draft": dp}


def _solo(module, t_params, prompt, n_new, eos_id=None, max_len=128):
    # Oracle discipline: pass max_len=engine.cache_len when comparing
    # against an engine.  A padded-length mismatch reorders the padded
    # attention reductions, and a bf16 near-tie argmax can flip on that
    # alone -- which a parity assert reads as lost token parity.
    gen = make_generator(
        module, max_new_tokens=n_new, max_len=max_len, eos_id=eos_id
    )
    return np.asarray(gen(t_params, jnp.asarray([prompt], jnp.int32)))[0].tolist()


def test_spec_engine_matches_plain_greedy(pair):
    target, draft, params = pair
    engine = DecodeEngine(
        target, draft_module=draft, speculate_k=3, slots=3,
        max_new_tokens=10, prompt_buckets=(8, 16), chunk_steps=2,
    )
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (5, 8, 13)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(target, params["target"], prompt, 10, max_len=engine.cache_len)
        stats = engine.stats()
        assert stats["speculative"]["rounds"] > 0
        assert 0.0 <= stats["speculative"]["acceptance_rate"] <= 1.0
    finally:
        engine.close()


def test_spec_engine_flash_prefill_matches_plain_greedy(pair):
    """prefill_impl="flash" on the TARGET (the spec engine's monolithic
    admissions are full prefills): tokens must still equal plain greedy
    decoding of the flash-config target. The draft keeps the cached
    prefill — the two models honor their own configs independently."""
    import dataclasses

    target, draft, params = pair
    ftarget = Llama(dataclasses.replace(target.config, prefill_impl="flash"))
    engine = DecodeEngine(
        ftarget, draft_module=draft, speculate_k=3, slots=3,
        max_new_tokens=10, prompt_buckets=(8, 16), chunk_steps=2,
    )
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (5, 8, 13)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(ftarget, params["target"], prompt, 10, max_len=engine.cache_len)
    finally:
        engine.close()


def test_spec_engine_self_speculation_full_acceptance(pair):
    """Draft == target: every proposal is accepted (the acceptance-rule
    sanity check — a bookkeeping bug shows up as rate < 1)."""
    target, _, params = pair
    engine = DecodeEngine(
        target, draft_module=target, speculate_k=3, slots=2,
        max_new_tokens=9, prompt_buckets=(8,), chunk_steps=2,
    )
    try:
        both = {"target": params["target"], "draft": params["target"]}
        out = engine.generate(both, [[7, 3, 9, 2]])[0]
        assert out == _solo(target, params["target"], [7, 3, 9, 2], 9, max_len=engine.cache_len)
        assert engine.stats()["speculative"]["acceptance_rate"] == 1.0
    finally:
        engine.close()


def test_spec_engine_mid_decode_join(pair):
    """A request joining while another slot is mid-speculation must not
    perturb either sequence (per-slot fills advance independently)."""
    import threading
    import time

    target, draft, params = pair
    engine = DecodeEngine(
        target, draft_module=draft, speculate_k=2, slots=2,
        max_new_tokens=20, prompt_buckets=(8,), chunk_steps=2,
        pipeline_depth=2,
    )
    try:
        engine.warmup(params)
        rng = np.random.default_rng(4)
        p1 = rng.integers(1, 97, 8).tolist()
        p2 = rng.integers(1, 97, 5).tolist()
        res = {}
        t = threading.Thread(
            target=lambda: res.update(a=engine.generate(params, [p1])[0])
        )
        t.start()
        time.sleep(0.15)
        res["b"] = engine.generate(params, [p2], max_new_tokens=8)[0]
        t.join(timeout=60)
        assert res["a"] == _solo(target, params["target"], p1, 20, max_len=engine.cache_len)
        assert res["b"] == _solo(target, params["target"], p2, 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_spec_engine_eos_and_budget(pair):
    """eos inside a round truncates emission exactly like plain greedy
    (device n_emit truncation + host _req_done walk agree)."""
    target, draft, params = pair
    plain = _solo(target, params["target"], [5, 3, 9, 2], 12)
    eos = plain[3]   # force an eos hit mid-generation
    engine = DecodeEngine(
        target, draft_module=draft, speculate_k=3, slots=2,
        max_new_tokens=12, prompt_buckets=(8,), chunk_steps=2, eos_id=eos,
    )
    try:
        out = engine.generate(params, [[5, 3, 9, 2]])[0]
        # the engine truncates AT eos (the _req_done contract); the solo
        # generator's static shapes pad AFTER it — compare the prefix
        assert out == plain[: plain.index(eos) + 1]
        assert out[-1] == eos and eos not in out[:-1]
    finally:
        engine.close()


def test_spec_engine_chunked_prefill(pair):
    """Speculation composes with chunked admission: both caches fill
    chunk-by-chunk, then rounds run over the spliced slot."""
    target, draft, params = pair
    engine = DecodeEngine(
        target, draft_module=draft, speculate_k=2, slots=2,
        max_new_tokens=8, prompt_buckets=(8, 32), prefill_chunk=8,
        chunk_steps=2,
    )
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (6, 20, 32)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(target, params["target"], prompt, 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_spec_engine_streaming(pair):
    target, draft, params = pair
    engine = DecodeEngine(
        target, draft_module=draft, speculate_k=2, slots=2,
        max_new_tokens=10, prompt_buckets=(8,), chunk_steps=2,
    )
    try:
        chunks = list(engine.generate_stream(params, [7, 3, 9, 2]))
        flat = [t for c in chunks for t in c]
        assert flat == _solo(target, params["target"], [7, 3, 9, 2], 10, max_len=engine.cache_len)
        assert len(chunks[0]) == 1   # prefill token = the TTFT event
    finally:
        engine.close()


def test_spec_engine_validation(pair):
    target, draft, params = pair
    with pytest.raises(ValueError, match="greedy-only"):
        DecodeEngine(target, draft_module=draft, temperature=0.7)
    with pytest.raises(ValueError, match="prefix KV-cache"):
        DecodeEngine(target, draft_module=draft, prefix_cache=True)
    with pytest.raises(ValueError, match="vocabularies differ"):
        DecodeEngine(
            target,
            draft_module=Llama(LlamaConfig.tiny(vocab_size=50)),
        )
    with pytest.raises(ValueError, match="speculate_k"):
        DecodeEngine(target, draft_module=draft, speculate_k=0)
    with pytest.raises(ValueError, match='"target"'):
        eng = DecodeEngine(target, draft_module=draft, prompt_buckets=(8,),
                           max_new_tokens=8, chunk_steps=2, pipeline_depth=1)
        try:
            eng.generate(params["target"], [[1, 2, 3]])
        finally:
            eng.close()
