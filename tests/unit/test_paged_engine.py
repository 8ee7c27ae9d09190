"""Block-paged decode engine tests (docs/performance.md "Paged KV
attention").

The contract: with the reference paged-attention path, a paged engine's
tokens are IDENTICAL to the contiguous engine's (and to each prompt's
solo generator run) across cold/warm/partial prefix-cache hits, chunked
prefill, and kv-quant/int4 composition — the layout changed, the math
did not. On top of parity: pool exhaustion surfaces as a clean typed
reject or a parked admission (never a mid-decode failure), retirement/
abandonment/recovery leak no blocks (``unionml_kv_pool_*`` returns to
baseline), block tables grow across the ``max_new_tokens`` boundary,
and block geometry is unified with the prefix cache.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu import telemetry
from unionml_tpu.models import Llama, LlamaConfig
from unionml_tpu.models.generate import make_generator
from unionml_tpu.serving.engine import DecodeEngine
from unionml_tpu.serving.faults import FaultInjector, Overloaded, xla_oom_error
from unionml_tpu.serving.prefix_cache import RadixPrefixCache


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(vocab_size=97)
    module = Llama(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return module, params


def _solo(module, params, prompt, n_new, max_len=256):
    # Oracle discipline: pass max_len=engine.cache_len when comparing
    # against an engine.  A padded-length mismatch reorders the padded
    # attention reductions, and a bf16 near-tie argmax can flip on that
    # alone -- which a parity assert reads as lost token parity.
    gen = make_generator(module, max_new_tokens=n_new, max_len=max_len)
    return np.asarray(gen(params, jnp.asarray([prompt], jnp.int32)))[0].tolist()


def _paged_engine(module, **kw):
    kw.setdefault("registry", telemetry.MetricsRegistry())
    kw.setdefault("paged", True)
    return DecodeEngine(module, **kw)


def _assert_pool_drained(engine, timeout=30.0):
    """The acceptance gauge: unionml_kv_pool_* back to baseline (the
    harvester's deferred frees may land a beat after the waiter wakes)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = engine.stats()["kv_pool"]
        if st["blocks_in_use"] == 0 and st["blocks_reserved"] == 0:
            return st
        time.sleep(0.02)
    raise AssertionError(f"kv pool leaked blocks: {engine.stats()['kv_pool']}")


def test_paged_engine_matches_solo(tiny_llama):
    module, params = tiny_llama
    engine = _paged_engine(
        module, slots=4, max_new_tokens=8, prompt_buckets=(8, 16),
        chunk_steps=4,
    )
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (5, 8, 11, 16)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prompt, 8, max_len=engine.cache_len)
        st = _assert_pool_drained(engine)
        assert st["allocated_blocks"] > 0
        assert st["allocated_blocks"] == st["freed_blocks"]
    finally:
        engine.close()


def test_paged_matches_contiguous_stream(tiny_llama):
    """The acceptance parity bar: one request stream, contiguous vs
    paged engine, bit-identical tokens on the reference kernel."""
    module, params = tiny_llama
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 97, size=n).tolist() for n in (3, 7, 12, 16, 9)]
    outs = {}
    for paged in (False, True):
        engine = DecodeEngine(
            module, slots=2, max_new_tokens=6, prompt_buckets=(16,),
            chunk_steps=3, paged=paged,
            registry=telemetry.MetricsRegistry(),
        )
        try:
            outs[paged] = engine.generate(params, prompts)
        finally:
            engine.close()
    assert outs[True] == outs[False]


def test_paged_prefix_cache_cold_warm_partial(tiny_llama):
    """Paged pool + radix prefix cache share one block unit: cold
    admission inserts, warm splices every block, partial splices the
    shared prefix and prefills the suffix — all token-identical to the
    cache-off contiguous baseline."""
    module, params = tiny_llama
    rng = np.random.default_rng(2)
    shared = rng.integers(1, 97, 32).tolist()
    p_cold = shared + rng.integers(1, 97, 8).tolist()
    p_part = shared + rng.integers(1, 97, 12).tolist()
    engine = _paged_engine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(48,),
        chunk_steps=3,
        prefix_cache=RadixPrefixCache(
            block_size=16, registry=telemetry.MetricsRegistry()
        ),
    )
    try:
        cold = engine.generate(params, [p_cold])[0]
        warm = engine.generate(params, [p_cold])[0]
        part = engine.generate(params, [p_part])[0]
        assert cold == _solo(module, params, p_cold, 6, max_len=engine.cache_len)
        assert warm == cold
        assert part == _solo(module, params, p_part, 6, max_len=engine.cache_len)
        pc = engine.stats()["prefix_cache"]
        assert pc["hits"] + pc["partial_hits"] >= 2
        assert pc["prefill_tokens_saved"] > 0
        _assert_pool_drained(engine)
    finally:
        engine.close()


def test_paged_chunked_prefill_token_identity(tiny_llama):
    module, params = tiny_llama
    rng = np.random.default_rng(3)
    engine = _paged_engine(
        module, slots=2, max_new_tokens=5, prompt_buckets=(64,),
        prefill_chunk=16, chunk_steps=2,
    )
    try:
        prompt = rng.integers(1, 97, 50).tolist()
        out = engine.generate(params, [prompt])[0]
        assert out == _solo(module, params, prompt, 5, max_len=engine.cache_len)
        _assert_pool_drained(engine)
    finally:
        engine.close()


def test_paged_kv_quant_parity():
    """int8 KV pools (quantized k/v blocks + per-row scale planes ride
    the rank-generic scatter/gather) decode identically to the int8
    contiguous cache."""
    cfg = LlamaConfig.tiny(vocab_size=97, kv_quant=True)
    module = Llama(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 97, size=n).tolist() for n in (7, 12, 16)]
    outs = {}
    for paged in (False, True):
        engine = DecodeEngine(
            module, slots=2, max_new_tokens=6, prompt_buckets=(16,),
            chunk_steps=3, paged=paged,
            registry=telemetry.MetricsRegistry(),
        )
        try:
            outs[paged] = engine.generate(params, prompts)
        finally:
            engine.close()
    assert outs[True] == outs[False]


def test_paged_int4_weights_with_kv_quant():
    """The full serving quantization stack — int4 weights + int8 KV —
    composed with the paged pool: parity against the contiguous engine
    under the same quantized tree."""
    from unionml_tpu.models.quantization import (
        LLAMA_QUANT_PATTERNS,
        quantize_params,
    )

    base = LlamaConfig(
        vocab_size=97, hidden_dim=64, num_layers=2, num_heads=4,
        num_kv_heads=2, mlp_dim=128, max_len=256, rope_theta=10_000.0,
    )
    fp_params = Llama(base).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    q4 = quantize_params(fp_params, LLAMA_QUANT_PATTERNS, bits=4)
    cfg = LlamaConfig(**{
        **base.__dict__, "quantized": True, "weight_bits": 4,
        "kv_quant": True,
    })
    module = Llama(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 97, size=n).tolist() for n in (6, 11)]
    outs = {}
    for paged in (False, True):
        engine = DecodeEngine(
            module, slots=2, max_new_tokens=5, prompt_buckets=(16,),
            chunk_steps=2, paged=paged,
            registry=telemetry.MetricsRegistry(),
        )
        try:
            outs[paged] = engine.generate(q4, prompts)
        finally:
            engine.close()
    assert outs[True] == outs[False]


def test_block_geometry_unified(tiny_llama):
    """Satellite: bucket rounding no longer depends on whether a prefix
    cache is attached — a paged engine with and without one lands on
    the same bucket set, and a block-size mismatch raises."""
    module, _ = tiny_llama
    plain = _paged_engine(
        module, slots=1, max_new_tokens=4, prompt_buckets=(10, 40),
        prefill_chunk=8, kv_block_size=16,
    )
    with_cache = _paged_engine(
        module, slots=1, max_new_tokens=4, prompt_buckets=(10, 40),
        prefill_chunk=8,
        prefix_cache=RadixPrefixCache(
            block_size=16, registry=telemetry.MetricsRegistry()
        ),
    )
    try:
        assert plain.buckets == with_cache.buckets
        assert plain.cache_len == with_cache.cache_len
        assert plain._kv_block_size == with_cache._kv_block_size == 16
    finally:
        plain.close()
        with_cache.close()
    with pytest.raises(ValueError, match="block"):
        DecodeEngine(
            module, slots=1, max_new_tokens=4, prompt_buckets=(16,),
            paged=True, kv_block_size=8,
            prefix_cache=RadixPrefixCache(
                block_size=16, registry=telemetry.MetricsRegistry()
            ),
            registry=telemetry.MetricsRegistry(),
        )


def test_oversize_request_rejected_at_submit(tiny_llama):
    """A request whose worst case exceeds the whole pool can never be
    admitted: clean Overloaded at submit, nothing queued, no device
    work burned."""
    module, params = tiny_llama
    engine = _paged_engine(
        module, slots=2, max_new_tokens=8, prompt_buckets=(16,),
        chunk_steps=4, kv_pool_blocks=2,  # capacity 1 block
    )
    try:
        with pytest.raises(Overloaded, match="never fit"):
            engine.generate(params, [list(range(1, 16))])
        st = engine.stats()
        assert st["robustness"]["rejected"]["pool_full"] == 1
        assert st["kv_pool"]["blocks_in_use"] == 0
    finally:
        engine.close()


def test_transient_exhaustion_parks_not_fails(tiny_llama):
    """A pool that only fits ONE resident request (capacity 2 blocks,
    2 blocks per request) serves a 6-deep stream by parking admissions
    until retirements free blocks — every request completes with
    solo-identical tokens and the pressure is visible in the flight
    recorder + alloc-failure counter. (One-resident sizing makes the
    park deterministic: any queued request overlaps the resident.)"""
    module, params = tiny_llama
    flight = telemetry.FlightRecorder()
    engine = _paged_engine(
        module, slots=4, max_new_tokens=8, prompt_buckets=(16,),
        chunk_steps=4, kv_pool_blocks=3, flight=flight,
    )
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 97, size=9).tolist() for _ in range(6)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prompt, 8, max_len=engine.cache_len)
        st = engine.stats()["kv_pool"]
        assert st["alloc_failures"] > 0
        pressure = [
            e for e in flight.dump() if e["kind"] == "pool_pressure"
        ]
        assert pressure and pressure[0]["reason"] == "alloc_fail"
        # every event carries the preempt-candidate field; it names the
        # oldest resident when one exists (None only in the narrow race
        # where the last resident retired with its blocks still
        # fence-deferred)
        assert all("preempt_candidate" in e for e in pressure)
        named = [e for e in pressure if e["preempt_candidate"]]
        for e in named:
            assert isinstance(e["preempt_candidate"], str)
        _assert_pool_drained(engine)
    finally:
        engine.close()


def test_pool_full_backlog_sheds_through_queue_bound(tiny_llama):
    """Under pool pressure the backlog behind a parked admission hits
    max_queue_depth and sheds with Overloaded (429) — the accepted
    requests still complete; flight analysis can tell pool-full
    (pool_pressure events) from queue-full (reject reason)."""
    module, params = tiny_llama
    engine = _paged_engine(
        module, slots=4, max_new_tokens=8, prompt_buckets=(16,),
        chunk_steps=4, kv_pool_blocks=4, max_queue_depth=2,
    )
    try:
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 97, size=9).tolist() for _ in range(12)]
        shed, done = [], []
        lock = threading.Lock()

        def client(p):
            try:
                out = engine.generate(params, [p])[0]
                with lock:
                    done.append((p, out))
            except Overloaded:
                with lock:
                    shed.append(p)

        threads = [
            threading.Thread(target=client, args=(p,)) for p in prompts
        ]
        for t in threads:
            t.start()
            time.sleep(0.002)
        for t in threads:
            t.join(timeout=120)
        assert shed, "expected queue-full shedding under pool pressure"
        assert done, "expected accepted requests to complete"
        for p, out in done:
            assert out == _solo(module, params, p, 8, max_len=engine.cache_len)
        _assert_pool_drained(engine)
    finally:
        engine.close()


def test_table_growth_across_max_new_boundary(tiny_llama):
    """Decode crosses several block boundaries (small blocks, long
    generation): the table grows one block at a time from the
    admission-time reservation and the tokens stay solo-identical."""
    module, params = tiny_llama
    engine = _paged_engine(
        module, slots=2, max_new_tokens=24, prompt_buckets=(8,),
        chunk_steps=2, kv_block_size=8,
    )
    try:
        rng = np.random.default_rng(8)
        prompt = rng.integers(1, 97, size=6).tolist()
        out = engine.generate(params, [prompt])[0]
        assert out == _solo(module, params, prompt, 24, max_len=engine.cache_len)
        st = _assert_pool_drained(engine)
        # 6-token prompt + 24 new = 30 rows -> at least 4 blocks of 8
        assert st["allocated_blocks"] >= 4
    finally:
        engine.close()


def test_table_growth_by_blocks_of_positions():
    """A module that generates by blocks moves a slot on a block of four
    positions a commit and writes the open block's rows past its fill: the
    table is grown ahead of both from a reservation of prompt + asked + a
    block, across pool blocks of 8, and nothing leaks. A stream abandoned
    mid-way frees its blocks too."""
    from unionml_tpu.models.sdar_moe import SdarMoe, SdarMoeConfig

    module = SdarMoe(SdarMoeConfig.tiny(vocab_size=97, dtype="float32", cache_dtype="float32"))
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = _paged_engine(
        module, slots=2, max_new_tokens=30, prompt_buckets=(8,), chunk_steps=3, kv_block_size=8,
    )
    try:
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (6, 7)]
        out = engine.generate(params, prompts)
        assert [len(t) for t in out] == [30, 30]
        st = _assert_pool_drained(engine)
        # 6 + 30 = 36 positions, the last block whole: 5 blocks of 8 a request
        assert st["allocated_blocks"] >= 10
        assert engine.generate(params, [prompts[0]])[0] == out[0]
        gen = engine.generate_stream(params, prompts[1])
        next(gen)
        gen.close()
        _assert_pool_drained(engine)
    finally:
        engine.close()


def test_no_leaked_blocks_after_abandoned_stream(tiny_llama):
    module, params = tiny_llama
    engine = _paged_engine(
        module, slots=2, max_new_tokens=32, prompt_buckets=(16,),
        chunk_steps=2,
    )
    try:
        rng = np.random.default_rng(9)
        gen = engine.generate_stream(params, rng.integers(1, 97, 8).tolist())
        next(gen)
        gen.close()  # client disconnect mid-decode
        _assert_pool_drained(engine)
        # the engine still serves correctly afterwards
        prompt = rng.integers(1, 97, size=10).tolist()
        assert engine.generate(params, [prompt])[0] == _solo(
            module, params, prompt, 32, max_len=engine.cache_len
        )
        _assert_pool_drained(engine)
    finally:
        engine.close()


@pytest.mark.chaos
def test_no_leaked_blocks_after_recovery(tiny_llama):
    """PR 3's chaos harness against the paged pool: an injected OOM
    fails the poisoned batch, the pool resets with the rebuilt state,
    survivors and follow-ups decode correctly, occupancy returns to
    baseline."""
    module, params = tiny_llama
    fi = FaultInjector()
    engine = _paged_engine(
        module, slots=2, max_new_tokens=8, prompt_buckets=(16,),
        chunk_steps=4, fault_injector=fi,
    )
    try:
        engine.warmup(params)
        rng = np.random.default_rng(10)
        fi.arm("engine.dispatch", exc=xla_oom_error())
        results = []
        lock = threading.Lock()

        def run(p):
            try:
                out = engine.generate(params, [p])[0]
                with lock:
                    results.append((p, out))
            except Exception:
                pass  # the poisoned batch

        threads = [
            threading.Thread(
                target=run, args=(rng.integers(1, 97, 9).tolist(),)
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert engine.stats()["robustness"]["recoveries"] >= 1
        for p, out in results:
            assert out == _solo(module, params, p, 8, max_len=engine.cache_len)
        prompt = rng.integers(1, 97, size=10).tolist()
        assert engine.generate(params, [prompt])[0] == _solo(
            module, params, prompt, 8, max_len=engine.cache_len
        )
        st = _assert_pool_drained(engine)
        # the registry exposition carries the pool series at zero
        text = engine._registry.exposition()
        assert "unionml_kv_pool_blocks_in_use" in text
        assert st["blocks_in_use"] == 0
    finally:
        engine.close()


def test_lease_pinned_prefix_blocks_survive_pool_pressure(tiny_llama):
    """While a paged admission's lease pins host prefix blocks, budget
    pressure evicts around them — the leased path's rows stay live and
    the warm run stays token-identical."""
    module, params = tiny_llama
    cache = RadixPrefixCache(
        block_size=16, max_bytes=64 << 10,
        registry=telemetry.MetricsRegistry(),
    )
    engine = _paged_engine(
        module, slots=2, max_new_tokens=5, prompt_buckets=(48,),
        chunk_steps=3, prefix_cache=cache,
    )
    try:
        rng = np.random.default_rng(11)
        shared = rng.integers(1, 97, 32).tolist()
        prompt = shared + rng.integers(1, 97, 8).tolist()
        cold = engine.generate(params, [prompt])[0]
        # hold a lease (an in-flight admission's pin), then pressure the
        # budget with distinct prompts until evictions happen
        lease = cache.match(prompt)
        assert lease.n_blocks >= 2
        for _ in range(12):
            engine.generate(
                params, [rng.integers(1, 97, 40).tolist()]
            )
        assert cache.stats()["evictions"] > 0
        for node_rows in lease.rows:
            assert node_rows is not None  # never reclaimed under lease
        lease.release()
        warm = engine.generate(params, [prompt])[0]
        assert warm == cold
        _assert_pool_drained(engine)
    finally:
        engine.close()


def test_paged_refuses_speculative(tiny_llama):
    module, _ = tiny_llama
    draft = Llama(LlamaConfig.tiny(vocab_size=97))
    with pytest.raises(ValueError, match="paged"):
        DecodeEngine(
            module, slots=1, max_new_tokens=4, prompt_buckets=(16,),
            draft_module=draft, paged=True,
            registry=telemetry.MetricsRegistry(),
        )


def test_paged_eos_retires_and_frees(tiny_llama):
    """eos retirement mid-chunk: the slot's blocks free behind the
    dispatch fence and the pool drains."""
    module, params = tiny_llama
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, 97, size=7).tolist() for _ in range(3)]
    outs = {}
    for paged in (False, True):
        engine = DecodeEngine(
            module, slots=2, max_new_tokens=16, prompt_buckets=(8,),
            chunk_steps=4, eos_id=11, paged=paged,
            registry=telemetry.MetricsRegistry(),
        )
        try:
            outs[paged] = engine.generate(params, prompts)
            if paged:
                _assert_pool_drained(engine)
        finally:
            engine.close()
    assert outs[True] == outs[False]


def test_paged_dead_slots_reach_the_kernel_with_length_zero(tiny_llama, monkeypatch):
    """The engine says which rows of a decode step are live, and the
    others reach the paged kernel with length 0 whatever ``fill`` their
    last occupant left: the kernel gathers nothing for them. Live rows
    decode as before, and a reused slot decodes as a fresh one."""
    from unionml_tpu.ops import paged_attention as pa

    module, params = tiny_llama
    seen = []  # (table row is the trash block's, length) per row of a kernel call
    real = pa.paged_attention

    def spy(q, k, v, block_table, lengths, **kw):
        jax.debug.callback(
            lambda t, n: seen.append((~np.asarray(t).any(axis=1), np.asarray(n))),
            block_table, lengths,
        )
        return real(q, k, v, block_table, lengths, **kw)

    monkeypatch.setattr(pa, "paged_attention", spy)
    engine = _paged_engine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(16,), chunk_steps=3,
    )
    try:
        rng = np.random.default_rng(13)
        first = [rng.integers(1, 97, size=n).tolist() for n in (9, 14)]
        outs = engine.generate(params, first)
        for prompt, out in zip(first, outs):
            assert out == _solo(module, params, prompt, 6, max_len=engine.cache_len)
        _assert_pool_drained(engine)
        jax.effects_barrier()
        # both slots are retired, and their fills are their occupants' still
        stale = np.asarray(engine._state["fill"])
        assert (stale >= 9).all()
        seen.clear()
        # one request: it takes slot 0 (the lowest free), slot 1 stays dead
        again = rng.integers(1, 97, size=5).tolist()
        assert engine.generate(params, [again])[0] == _solo(
            module, params, again, 6, max_len=engine.cache_len
        )
        jax.effects_barrier()
        assert np.asarray(engine._state["fill"])[1] == stale[1]
    finally:
        engine.close()
    assert seen
    for trash, lengths in seen:
        assert trash[1] and lengths[1] == 0
        # a row is handed its table and its length together, or neither
        assert ((lengths == 0) == trash).all()
    assert any(lengths[0] > 0 for _, lengths in seen)


def test_paged_stats_say_what_the_kernel_walks(tiny_llama):
    """``stats()["kv_pool"]`` carries the table's width and the pool
    blocks a group of the decode kernel takes at this pool's shapes, so a
    deployment can reckon how many groups its longest row walks."""
    module, _ = tiny_llama
    engine = _paged_engine(
        module, slots=2, max_new_tokens=40, prompt_buckets=(16,), chunk_steps=4,
        kv_block_size=8,
    )
    try:
        st = engine.stats()["kv_pool"]
        assert st["table_width"] == engine.cache_len // 8
        # 512 KV rows a group, never more than the table is wide
        assert st["kernel_blocks_per_group"] == min(64, st["table_width"])
        # two pools: one score tile of the 4 query heads against every
        # (position, kv head) row of a group
        assert st["score_tile"] == [4, st["kernel_blocks_per_group"] * 8 * 2]
    finally:
        engine.close()
