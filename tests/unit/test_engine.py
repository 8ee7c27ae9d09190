"""Continuous-batching decode engine tests.

The contract under test: a request decoded in a shared slot batch —
including one that JOINS mid-flight while other slots are deep into
their decode — produces exactly the tokens its solo
``make_generator`` run would (greedy). Plus retirement (eos / budget),
slot reuse under overload, and the stats surface.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import Llama, LlamaConfig
from unionml_tpu.models.generate import make_generator
from unionml_tpu.serving.engine import DecodeEngine


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(vocab_size=97)
    module = Llama(cfg)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _solo(module, params, prompt, n_new, max_len=128):
    # Oracle discipline: pass max_len=engine.cache_len when comparing
    # against an engine.  A padded-length mismatch reorders the padded
    # attention reductions, and a bf16 near-tie argmax can flip on that
    # alone -- which a parity assert reads as lost token parity.
    gen = make_generator(module, max_new_tokens=n_new, max_len=max_len)
    return np.asarray(gen(params, jnp.asarray([prompt], jnp.int32)))[0].tolist()


def test_engine_matches_solo_generation(tiny_llama):
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=4, max_new_tokens=8, prompt_buckets=(8, 16), chunk_steps=4
    )
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (5, 8, 11, 16)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prompt, 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_engine_flash_prefill_matches_solo(tiny_llama):
    """``prefill_impl="flash"``: the engine's no-prefix monolithic
    admissions run through the flash kernel (right-padded buckets —
    causal masking alone hides the trailing garbage) and must still
    produce each prompt's solo-generator tokens."""
    module, params = tiny_llama
    import dataclasses

    fmod = Llama(dataclasses.replace(module.config, prefill_impl="flash"))
    engine = DecodeEngine(
        fmod, slots=4, max_new_tokens=8, prompt_buckets=(8, 16), chunk_steps=4
    )
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (5, 8, 11, 16)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(fmod, params, prompt, 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_mid_decode_join_is_token_identical(tiny_llama):
    """A request submitted while another is mid-decode joins at a chunk
    boundary and must not perturb either sequence."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=24, prompt_buckets=(8,), chunk_steps=2
    )
    try:
        engine.warmup(params)  # keep compile time out of the join timing
        rng = np.random.default_rng(1)
        p1 = rng.integers(1, 97, size=8).tolist()
        p2 = rng.integers(1, 97, size=6).tolist()
        results = {}

        def run(name, prompt, delay):
            time.sleep(delay)
            results[name] = engine.generate(params, [prompt])[0]

        t1 = threading.Thread(target=run, args=("a", p1, 0.0))
        t2 = threading.Thread(target=run, args=("b", p2, 0.05))
        t1.start(), t2.start()
        t1.join(), t2.join()
        assert results["a"] == _solo(module, params, p1, 24, max_len=engine.cache_len)
        assert results["b"] == _solo(module, params, p2, 24, max_len=engine.cache_len)
    finally:
        engine.close()


def test_more_requests_than_slots_queue_and_reuse(tiny_llama):
    """Overload: requests beyond the slot count wait, then reuse retired
    slots; every result still matches its solo run."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(8,), chunk_steps=3
    )
    try:
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, 97, size=7).tolist() for _ in range(6)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prompt, 6, max_len=engine.cache_len)
        stats = engine.stats()
        assert stats["completed_requests"] == 6
        assert stats["decode_steps"] > 0
        assert 0 < stats["slot_occupancy"] <= 1
        assert stats["queue_wait_ms"]["p50"] >= 0
        assert stats["prefill_ms"]["p50"] > 0
    finally:
        engine.close()


def test_eos_retires_slot_early(tiny_llama):
    """Force an eos hit: the engine must stop at (and include) eos, like
    make_generator, and the freed slot is immediately reusable."""
    module, params = tiny_llama
    prompt = list(range(1, 9))
    # find what greedy emits first so we can use it as the "eos"
    first = _solo(module, params, prompt, 1)[0]
    engine = DecodeEngine(
        module, slots=1, max_new_tokens=8, prompt_buckets=(8,), chunk_steps=4,
        eos_id=first,
    )
    try:
        out = engine.generate(params, [prompt])[0]
        assert out == [first]  # eos on the very first token
        # slot freed: a second request still runs
        other = [9, 10, 11, 12]
        out2 = engine.generate(params, [other])[0]
        solo = _solo(module, params, other, 8, max_len=engine.cache_len)
        stop = solo.index(first) + 1 if first in solo else 8
        assert out2 == solo[:stop]
    finally:
        engine.close()


def test_per_request_token_budget(tiny_llama):
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=16, prompt_buckets=(8,), chunk_steps=8
    )
    try:
        prompt = list(range(1, 7))
        out = engine.generate(params, [prompt], max_new_tokens=3)[0]
        assert out == _solo(module, params, prompt, 3, max_len=engine.cache_len)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.generate(params, [prompt], max_new_tokens=99)
    finally:
        engine.close()


def test_engine_rejects_bad_config(tiny_llama):
    module, _ = tiny_llama
    with pytest.raises(ValueError, match="max_len"):
        DecodeEngine(module, max_new_tokens=300, prompt_buckets=(64,))
    with pytest.raises(ValueError, match="bucket"):
        DecodeEngine(module, prompt_buckets=())
    with pytest.raises(ValueError, match="slot"):
        DecodeEngine(module, slots=0)


def test_temperature_sampling_varies_and_respects_budget(tiny_llama):
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=8, prompt_buckets=(8,), chunk_steps=4,
        temperature=0.8, seed=3,
    )
    try:
        prompt = list(range(1, 9))
        outs = engine.generate(params, [prompt, prompt])
        assert all(len(o) == 8 for o in outs)
        vocab_ok = all(0 <= t < 97 for o in outs for t in o)
        assert vocab_ok
    finally:
        engine.close()


def test_bind_refuses_hot_swap_while_busy(tiny_llama):
    """Swapping weights mid-flight would mix trees within one decode —
    the engine must refuse until drained (and allow the swap when idle)."""
    module, params = tiny_llama
    import jax

    other = jax.tree_util.tree_map(lambda x: x + 0, params)  # distinct object
    engine = DecodeEngine(
        module, slots=1, max_new_tokens=16, prompt_buckets=(8,), chunk_steps=2
    )
    try:
        engine.warmup(params)
        done = threading.Event()

        def run():
            engine.generate(params, [list(range(1, 9))])
            done.set()

        t = threading.Thread(target=run)
        t.start()
        raised = False
        while not done.is_set():
            try:
                engine.bind(other)
            except RuntimeError:
                raised = True
                break
            time.sleep(0.001)
        t.join()
        assert raised or done.is_set()  # busy window may be tiny on CPU
        engine.bind(other)  # idle: swap allowed
        out = engine.generate(other, [list(range(1, 9))])
        assert len(out[0]) == 16
    finally:
        engine.close()


def test_stats_archive_is_lightweight(tiny_llama):
    """The stats source holds bounded float windows in the telemetry
    registry, not request payloads."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=4, prompt_buckets=(8,), chunk_steps=2
    )
    try:
        engine.generate(params, [[1, 2, 3], [4, 5, 6]])
        for h in (engine._h_queue, engine._h_prefill, engine._h_decode,
                  engine._h_ttft):
            # floats only (no prompt/token payloads), hard-capped window
            assert all(isinstance(v, float) for v in h._window)
            assert len(h._window) <= h.WINDOW_CAP
        s = engine.stats()
        assert s["completed_requests"] == 2
        assert s["queue_wait_ms"]["p95"] >= s["queue_wait_ms"]["p50"] >= 0
    finally:
        engine.close()


def test_engine_with_moe_llama():
    """Continuous batching over a MoE decoder: per-slot decode routes
    tokens through the experts; outputs match solo generation."""
    cfg = LlamaConfig.tiny(vocab_size=97, num_experts=4, num_selected=2)
    module = Llama(cfg)
    params = module.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(8,), chunk_steps=3
    )
    try:
        prompts = [[1, 2, 3, 4], [5, 6, 7, 8, 9, 10]]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prompt, 6, max_len=engine.cache_len)
    finally:
        engine.close()


def test_stats_say_what_each_programs_expert_layers_do(tiny_llama, monkeypatch):
    """`stats()["moe"]`: for each compiled program the dispatch its expert
    layers take and the rows they compute over the rows the router sent
    (`ops.moe.dispatch_plan`, a count from shapes); absent for a dense model."""
    from unionml_tpu.models import LLAMA_QUANT_PATTERNS, quantize_params

    module, _ = tiny_llama
    dense = DecodeEngine(module, slots=2, max_new_tokens=4, prompt_buckets=(8,), chunk_steps=2)
    try:
        assert "moe" not in dense.stats()
    finally:
        dense.close()

    kw = dict(vocab_size=97, num_experts=4, num_selected=2)
    params = Llama(LlamaConfig.tiny(**kw)).init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    for quantized, dispatch, ratio in ((False, "dense", 2.0), (True, "grouped:ragged_dot", 1.0)):
        module = Llama(LlamaConfig.tiny(**kw, quantized=quantized))
        engine = DecodeEngine(module, slots=2, max_new_tokens=4, prompt_buckets=(8, 16, 192), chunk_steps=2)
        try:
            moe = engine.stats()["moe"]
            assert set(moe) == {"decode_chunk", "prefill_8", "prefill_16", "prefill_192"}
            touched = round(4 * (1 - 0.5 ** 16), 2)  # 16 rows each draw 2 of 4 experts
            read = 4 if dispatch == "dense" else 4 * (1 - 0.5 ** 16)  # experts whose weights are read
            bytes_read = int(read * 3 * 64 * 128 * (1 if quantized else 2))
            assert moe["prefill_16"] == {
                "dispatch": dispatch, "expert_rows_routed": 32,
                "expert_rows_computed": int(32 * ratio), "computed_over_routed": ratio,
                "experts_touched": touched, "expert_bytes_read": bytes_read,
            }
            assert moe["decode_chunk"]["expert_rows_routed"] == 2 * 2  # slots x top-k
            if quantized:
                # on a TPU the kernel serves programs of more than 128 rows,
                # and the stats say the grid it runs, from the same shapes
                from unionml_tpu.ops import moe as moe_ops

                with monkeypatch.context() as on_chip:
                    on_chip.setattr(moe_ops, "_interpret", lambda: False)
                    wide = engine.stats()["moe"]["prefill_192"]
                assert wide["dispatch"] == "grouped:moe_grouped_matmul" and wide["row_tile"] == 128
                assert wide["expert_rows_computed"] == 6 * 128  # 384 pairs and four experts' last tiles
                for product, tk, tn in (("gate_up", 64, 128), ("down", 128, 64)):
                    assert wide[product] == {
                        "tk": tk, "tn": tn, "row_block_tiles": 6, "row_blocks": 1, "grid_steps": 6,
                    }
                assert "row_tile" not in moe["prefill_192"]  # here ragged_dot serves
                # and the grouped programs serve
                qparams = quantize_params(params, LLAMA_QUANT_PATTERNS)
                assert [len(o) for o in engine.generate(qparams, [[1, 2, 3], [4, 5, 6, 7, 8, 9]])] == [4, 4]
        finally:
            engine.close()


def test_engine_under_tensor_parallel_sharding(tiny_llama):
    """Continuous batching with TP-sharded weights: GSPMD propagates the
    `tensor`-axis sharding through prefill and decode chunks, and slot
    outputs stay token-identical to the solo run **under the same
    sharding**. (Comparing against the UNSHARDED solo run is wrong:
    sharded matmuls reduce partial sums in a different order, and on a
    randomly-initialized tiny model the resulting ulp-level logit
    differences flip near-tie argmaxes — the sharded solo generator
    diverges from the unsharded one identically, so that comparison
    tested numerics, not the engine.)

    pipeline_depth=1 on the CPU mesh: deeper async pipelines of
    multi-device programs starve XLA's rendezvous on few-core hosts
    (same reason compile_step syncs per step there)."""
    from unionml_tpu.models import LLAMA_PARTITION_RULES
    from unionml_tpu.parallel import ShardingConfig, shard_pytree

    module, params = tiny_llama
    sharding = ShardingConfig(data=-1, tensor=2, rules=LLAMA_PARTITION_RULES)
    tp_params = shard_pytree(params, sharding)
    # guard against a silent replication fallback: the test must exercise
    # REAL tensor sharding or it proves nothing
    specs = [
        str(tuple(leaf.sharding.spec))
        for leaf in jax.tree_util.tree_leaves(tp_params)
    ]
    assert any("tensor" in s for s in specs), specs
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(8,),
        chunk_steps=3, pipeline_depth=1,
    )
    try:
        prompts = [[1, 2, 3, 4, 5], [6, 7, 8]]
        outs = engine.generate(tp_params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, tp_params, prompt, 6, max_len=engine.cache_len)
    finally:
        engine.close()


def test_engine_with_kv_quant_cache(tiny_llama):
    """The engine on the int8 KV cache (kv_quant=True): joins splice int8
    rows + scale planes, and every request still matches ITS solo run on
    the same quantized-cache path."""
    import dataclasses

    module, params = tiny_llama
    qmodule = Llama(dataclasses.replace(module.config, kv_quant=True))
    engine = DecodeEngine(
        qmodule, slots=4, max_new_tokens=8, prompt_buckets=(8, 16), chunk_steps=4
    )
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (5, 8, 11, 16)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(qmodule, params, prompt, 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_engine_system_prefix_matches_prefixed_solo(tiny_llama):
    """Engine with system_prefix: every request's tokens equal the solo
    generation of (prefix + prompt) — the prefix KV is seeded once and
    shared by all slots."""
    module, params = tiny_llama
    rng = np.random.default_rng(9)
    prefix = rng.integers(1, 97, 7).tolist()
    engine = DecodeEngine(
        module, slots=3, max_new_tokens=6, prompt_buckets=(8, 16),
        chunk_steps=3, system_prefix=prefix,
    )
    try:
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (5, 8, 12)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prefix + prompt, 6, max_len=engine.cache_len)
        # second round reuses the seeded prefix rows (slot reuse path)
        outs2 = engine.generate(params, prompts[:2])
        for prompt, out in zip(prompts[:2], outs2):
            assert out == _solo(module, params, prefix + prompt, 6, max_len=engine.cache_len)
    finally:
        engine.close()


def test_generate_stream_token_identity_and_chunking(tiny_llama):
    """Streamed chunks concatenate to exactly the blocking generate()
    output; the first chunk is the single prefill token (the TTFT event)
    and later chunks respect the chunk_steps granularity."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=12, prompt_buckets=(8,), chunk_steps=4
    )
    try:
        prompt = list(range(1, 8))
        want = engine.generate(params, [prompt])[0]
        chunks = list(engine.generate_stream(params, prompt))
        assert [t for c in chunks for t in c] == want
        assert len(chunks[0]) == 1  # prefill token arrives alone
        assert all(len(c) <= engine.chunk_steps for c in chunks[1:])
        assert len(chunks) >= 3  # actually incremental, not one blob
    finally:
        engine.close()


def test_generate_stream_concurrent_with_blocking_calls(tiny_llama):
    """A stream interleaved with blocking generate() calls on other
    threads keeps token identity for everyone (chunk-boundary joins)."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=4, max_new_tokens=8, prompt_buckets=(8,), chunk_steps=2
    )
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 97, size=6).tolist() for _ in range(3)]
        results = {}

        def blocking(i):
            results[i] = engine.generate(params, [prompts[i]])[0]

        threads = [
            threading.Thread(target=blocking, args=(i,)) for i in (1, 2)
        ]
        for t in threads:
            t.start()
        streamed = [t for c in engine.generate_stream(params, prompts[0]) for t in c]
        for t in threads:
            t.join()
        assert streamed == _solo(module, params, prompts[0], 8, max_len=engine.cache_len)
        for i in (1, 2):
            assert results[i] == _solo(module, params, prompts[i], 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_generate_stream_validation_and_eos(tiny_llama):
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=8, prompt_buckets=(8,), chunk_steps=4,
        eos_id=3,
    )
    try:
        with pytest.raises(ValueError, match="max_new_tokens"):
            list(engine.generate_stream(params, [1, 2], max_new_tokens=99))
        with pytest.raises(ValueError, match="empty"):
            list(engine.generate_stream(params, []))
        prompt = list(range(1, 8))
        want = engine.generate(params, [prompt])[0]
        got = [t for c in engine.generate_stream(params, prompt) for t in c]
        assert got == want  # eos truncation identical across surfaces
    finally:
        engine.close()


def test_stats_include_ttft(tiny_llama):
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=4, prompt_buckets=(8,), chunk_steps=2
    )
    try:
        engine.generate(params, [[1, 2, 3]])
        stats = engine.stats()
        assert "ttft_ms" in stats
        # TTFT covers queue+prefill only — it must not exceed the full
        # request latency (prefill + decode)
        assert stats["ttft_ms"]["p50"] <= (
            stats["queue_wait_ms"]["p50"] + stats["prefill_ms"]["p50"]
            + stats["decode_ms"]["p50"] + 1e-6
        )
    finally:
        engine.close()


def test_stream_consumer_disconnect_frees_slot(tiny_llama):
    """Closing a stream early (the SSE client-disconnect lifecycle) must
    abandon the request so its slot stops decoding dead work."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=1, max_new_tokens=64, prompt_buckets=(8,), chunk_steps=2
    )
    try:
        stream = engine.generate_stream(params, [1, 2, 3])
        next(stream)       # first (prefill) chunk arrives
        stream.close()     # GeneratorExit → abandoned
        deadline = time.perf_counter() + 10.0
        while time.perf_counter() < deadline:
            with engine._lock:
                if engine._occupant[0] is None:
                    break
            time.sleep(0.02)
        else:
            raise AssertionError("abandoned stream's slot was never freed")
        # the lone slot is reusable for a live request
        out = engine.generate(params, [[4, 5, 6]], max_new_tokens=4)
        assert len(out[0]) == 4
    finally:
        engine.close()


def test_chunked_prefill_token_identity(tiny_llama):
    """Buckets above prefill_chunk admit via lead-chunk programs + a
    final splice; every request (short prompt in a long bucket, exact
    multiples, ragged tails) matches its solo generation."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=4, max_new_tokens=8, prompt_buckets=(8, 64),
        prefill_chunk=16, chunk_steps=4,
    )
    try:
        rng = np.random.default_rng(11)
        # 5/8 → monolithic bucket 8; 9 → 1 (final-only) chunk in bucket
        # 64; 16/33 → ragged; 64 → full 4-chunk cover
        prompts = [
            rng.integers(1, 97, size=n).tolist() for n in (5, 8, 9, 16, 33, 64)
        ]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prompt, 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_chunked_prefill_with_system_prefix(tiny_llama):
    """Chunked admission composes with the shared system prefix: the
    fresh cache seeds the prefix rows before the lead chunks run."""
    module, params = tiny_llama
    rng = np.random.default_rng(13)
    prefix = rng.integers(1, 97, 7).tolist()
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(32,),
        prefill_chunk=8, chunk_steps=3, system_prefix=prefix,
    )
    try:
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (9, 20, 32)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(module, params, prefix + prompt, 6, max_len=engine.cache_len)
    finally:
        engine.close()


def test_chunked_prefill_with_kv_quant(tiny_llama):
    """Long-bucket chunked admission over the int8 KV cache: lead chunks
    carry the quantized (k_q, v_q, scales) layout through the fresh
    cache and the final splice."""
    import dataclasses

    module, params = tiny_llama
    qmodule = Llama(dataclasses.replace(module.config, kv_quant=True))
    engine = DecodeEngine(
        qmodule, slots=2, max_new_tokens=8, prompt_buckets=(48,),
        prefill_chunk=16, chunk_steps=4,
    )
    try:
        rng = np.random.default_rng(17)
        prompts = [rng.integers(1, 97, size=n).tolist() for n in (10, 48)]
        outs = engine.generate(params, prompts)
        for prompt, out in zip(prompts, outs):
            assert out == _solo(qmodule, params, prompt, 8, max_len=engine.cache_len)
    finally:
        engine.close()


def test_decode_interleaves_with_chunked_admission(tiny_llama):
    """While a long prompt admits chunk-by-chunk, resident slots keep
    decoding: at least one decode chunk is dispatched strictly between
    the first and last prefill-chunk dispatches of the admission."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=180, prompt_buckets=(8, 64),
        prefill_chunk=8, chunk_steps=2, pipeline_depth=2,
    )
    try:
        engine.warmup(params)
        events = []
        lock = threading.Lock()
        real_step, real_decode = engine._prefill_step, engine._decode_chunk

        def rec_decode(*a, **k):
            with lock:
                events.append("decode")
            return real_decode(*a, **k)

        def slow_step(*a, **k):
            # stretch each lead-chunk dispatch across several dispatcher
            # passes so the admission window deterministically overlaps
            # live decode dispatch regardless of host load (the raw
            # timing race flaked under full-suite CPU contention)
            time.sleep(0.01)
            with lock:
                events.append("prefill_step")
            return real_step(*a, **k)

        engine._prefill_step = slow_step
        engine._decode_chunk = rec_decode

        # occupy a slot with a LONG decode, then admit a 64-token prompt
        # (8 lead chunks): its admission must not stall the decode. Up to
        # two retries tolerate pathological scheduler stalls.
        rng = np.random.default_rng(19)
        interleaved = False
        for _attempt in range(3):
            events.clear()
            # pre-draw both prompts: np.random.Generator is not
            # thread-safe, and drawing from the bg thread would race the
            # main thread's draw under CPU contention
            bg_prompt = rng.integers(1, 97, 8).tolist()
            main_prompt = rng.integers(1, 97, 64).tolist()
            bg = threading.Thread(
                target=lambda: engine.generate(params, [bg_prompt])
            )
            bg.start()
            time.sleep(0.05)  # let the background request admit + decode
            out = engine.generate(params, [main_prompt], max_new_tokens=4)
            bg.join(timeout=60)
            # a hung background generate must fail LOUDLY here — retrying
            # over a still-occupied slot would corrupt events/slot state
            # and could even pass spuriously
            assert not bg.is_alive(), "background generate hung"
            assert len(out[0]) == 4
            snapshot = list(events)
            if "prefill_step" in snapshot:
                first = snapshot.index("prefill_step")
                last = (
                    len(snapshot) - 1 - snapshot[::-1].index("prefill_step")
                )
                if "decode" in snapshot[first:last]:
                    interleaved = True
                    break
                # decode events AFTER the admission window mean the bg
                # request was live through it yet never interleaved —
                # the head-of-line-blocking regression this test exists
                # to catch. Fail now: retrying could mask an engine that
                # only intermittently stalls decode behind admission.
                assert "decode" not in snapshot[first:], snapshot
                # otherwise the bg request finished before admission
                # began (OS scheduler stall): uninformative — retry
        assert interleaved, snapshot
    finally:
        engine.close()
