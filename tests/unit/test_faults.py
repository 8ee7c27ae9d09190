"""Chaos-harness tier-1 tests (docs/robustness.md): every serving
failure mode reproduced deterministically on CPU via
:class:`~unionml_tpu.serving.faults.FaultInjector` — device-program
crash mid-stream with supervised recovery, overload shedding at both
the engine and HTTP layers, deadline expiry at dequeue, circuit
breaker, graceful drain, and the abandoned-request / prefix-cache-lease
races recovery must not leak through."""

import threading
import time

import httpx
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import Llama, LlamaConfig
from unionml_tpu.models.generate import make_generator
from unionml_tpu.serving.batcher import MicroBatcher
from unionml_tpu.serving.engine import DecodeEngine
from unionml_tpu.serving.faults import (
    DeadlineExceeded,
    EngineUnavailable,
    FaultInjector,
    Overloaded,
    deadline_scope,
    xla_oom_error,
)
from unionml_tpu.serving.prefix_cache import RadixPrefixCache

pytestmark = pytest.mark.chaos


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


def _wait_for(cond, timeout=30.0, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.005)
    pytest.fail(f"timed out waiting for {what}")


def _resident(engine):
    with engine._lock:
        return sum(r is not None for r in engine._occupant)


# ---------------------------------------------------------------- injector


def test_injector_deterministic_nth_count():
    fi = FaultInjector()
    boom = RuntimeError("boom")
    # three unarmed fires count but do nothing
    for _ in range(3):
        fi.fire("engine.dispatch")
    assert fi.fired("engine.dispatch") == 3 and fi.injected("engine.dispatch") == 0
    # nth counts from ARMING time, not process start: nth=2 skips one
    # more firing, then injects twice (count=2), then self-disarms
    fi.arm("engine.dispatch", nth=2, count=2, exc=boom)
    fi.fire("engine.dispatch")                      # nth=1: clean
    for _ in range(2):
        with pytest.raises(RuntimeError, match="boom"):
            fi.fire("engine.dispatch")
    fi.fire("engine.dispatch")                      # plan exhausted: clean
    assert fi.injected("engine.dispatch") == 2
    # a second identical arming replays identically (determinism)
    fi.arm("engine.dispatch", nth=2, count=2, exc=boom)
    fi.fire("engine.dispatch")
    with pytest.raises(RuntimeError):
        fi.fire("engine.dispatch")
    fi.disarm()
    fi.fire("engine.dispatch")


def test_injector_validation_and_delay():
    fi = FaultInjector()
    with pytest.raises(ValueError, match="unknown injection point"):
        fi.arm("engine.typo", exc=RuntimeError())
    with pytest.raises(ValueError, match="exc and/or"):
        fi.arm("engine.dispatch")
    fi.arm("engine.harvest", delay_s=0.05)
    t0 = time.perf_counter()
    fi.fire("engine.harvest")                       # stall, no raise
    assert time.perf_counter() - t0 >= 0.05
    assert "RESOURCE_EXHAUSTED" in str(xla_oom_error())


def test_deadline_scope_nesting():
    from unionml_tpu.serving.faults import current_deadline_ms

    assert current_deadline_ms() is None
    with deadline_scope(100.0):
        assert current_deadline_ms() == 100.0
        with deadline_scope(5.0):
            assert current_deadline_ms() == 5.0
        assert current_deadline_ms() == 100.0
    assert current_deadline_ms() is None
    with pytest.raises(ValueError):
        with deadline_scope(0.0):
            pass


# ------------------------------------------------------------------ engine


def test_engine_recovers_from_midstream_device_fault(tiny_llama):
    """THE acceptance scenario: an OOM-shaped device-program fault
    injected mid-stream fails ONLY the poisoned batch (the two resident
    requests — one of them a live SSE-style stream), the queued
    requests admit after the rebuild and complete token-identical to
    their solo runs, and ``unionml_engine_recoveries_total``
    increments."""
    module, params = tiny_llama
    fi = FaultInjector()
    n_new = 48
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=n_new, prompt_buckets=(8,),
        chunk_steps=2, fault_injector=fi,
    )
    try:
        results = {}

        def run(name, prompt):
            try:
                results[name] = engine.generate(params, [prompt])[0]
            except BaseException as exc:
                results[name] = exc

        chunks, stream_err = [], [None]

        def run_stream(prompt):
            try:
                for ch in engine.generate_stream(params, prompt):
                    chunks.append(ch)
            except BaseException as exc:
                stream_err[0] = exc

        pa, pb, pc, pd = [1, 2, 3], [4, 5, 6], [2, 3, 4], [5, 6, 7]
        threads = [
            threading.Thread(target=run_stream, args=(pa,)),
            threading.Thread(target=run, args=("b", pb)),
        ]
        for t in threads:
            t.start()
        _wait_for(lambda: _resident(engine) == 2, what="both requests resident")
        _wait_for(lambda: len(chunks) > 0, what="stream mid-flight")
        # the NEXT decode-chunk dispatch hits an OOM-shaped XLA error
        fi.arm("engine.dispatch", exc=xla_oom_error())
        threads += [
            threading.Thread(target=run, args=("c", pc)),
            threading.Thread(target=run, args=("d", pd)),
        ]
        for t in threads[2:]:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        # poisoned batch: the stream and the resident generate both fail
        # with the injected error
        assert isinstance(stream_err[0], RuntimeError), stream_err[0]
        assert "RESOURCE_EXHAUSTED" in str(stream_err[0])
        assert isinstance(results["b"], RuntimeError), results["b"]
        # queued survivors re-admitted onto the rebuilt state and match
        # their solo generations exactly
        assert results["c"] == _solo(module, params, pc, n_new, max_len=engine.cache_len)
        assert results["d"] == _solo(module, params, pd, n_new, max_len=engine.cache_len)
        assert int(engine._m_recoveries.value) == 1
        assert engine.stats()["robustness"]["recoveries"] == 1
        # the engine keeps serving afterwards (breaker never opened:
        # one recovery < breaker_threshold)
        assert engine.health()["status"] == "ok"
        assert engine.generate(params, [pa])[0] == _solo(
            module, params, pa, n_new, max_len=engine.cache_len
        )
    finally:
        engine.close()


def test_engine_queue_full_sheds_with_typed_overload(tiny_llama):
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=1, max_new_tokens=48, prompt_buckets=(8,),
        chunk_steps=2, max_queue_depth=1,
    )
    try:
        results = {}

        def run(name, prompt):
            results[name] = engine.generate(params, [prompt])[0]

        t1 = threading.Thread(target=run, args=("a", [1, 2, 3]))
        t1.start()
        _wait_for(lambda: _resident(engine) == 1, what="slot occupied")
        t2 = threading.Thread(target=run, args=("b", [4, 5, 6]))
        t2.start()
        _wait_for(lambda: engine._room.qsize() == 1, what="one queued")
        with pytest.raises(Overloaded, match="queue is full"):
            engine.generate(params, [[7, 8, 9]])
        assert engine.stats()["robustness"]["rejected"]["queue_full"] == 1
        # a multi-prompt call is all-or-nothing: nothing was enqueued
        assert engine._room.qsize() == 1
        t1.join(timeout=120)
        t2.join(timeout=120)
        # the admitted requests were untouched by the shed
        assert results["a"] == _solo(module, params, [1, 2, 3], 48, max_len=engine.cache_len)
        assert results["b"] == _solo(module, params, [4, 5, 6], 48, max_len=engine.cache_len)
    finally:
        engine.close()


def test_engine_deadline_shed_at_dequeue(tiny_llama):
    """A queued request whose deadline expires is shed when the
    dispatcher dequeues it — before it consumes any prefill — via the
    ambient deadline_scope (the X-Deadline-Ms propagation path)."""
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=1, max_new_tokens=64, prompt_buckets=(8,),
        chunk_steps=2,
    )
    try:
        done = {}

        def run_a():
            done["a"] = engine.generate(params, [[1, 2, 3]])[0]
        t1 = threading.Thread(target=run_a)
        t1.start()
        _wait_for(lambda: _resident(engine) == 1, what="slot occupied")
        err = [None]

        def run_b():
            try:
                with deadline_scope(1.0):  # expires long before the
                    engine.generate(params, [[4, 5, 6]])  # slot frees
            except BaseException as exc:
                err[0] = exc
        t2 = threading.Thread(target=run_b)
        t2.start()
        t1.join(timeout=120)
        t2.join(timeout=120)
        assert isinstance(err[0], DeadlineExceeded), err[0]
        assert int(engine._m_deadline_shed.value) == 1
        # the shed is not an engine error, and the running request
        # finished untouched
        assert int(engine._m_errors.value) == 0
        assert done["a"] == _solo(module, params, [1, 2, 3], 64, max_len=engine.cache_len)
    finally:
        engine.close()


def test_engine_breaker_opens_after_consecutive_recoveries(tiny_llama):
    module, params = tiny_llama
    fi = FaultInjector()
    engine = DecodeEngine(
        module, slots=1, max_new_tokens=4, prompt_buckets=(8,),
        chunk_steps=2, fault_injector=fi,
        breaker_threshold=2, breaker_window_s=30.0, breaker_cooldown_s=0.5,
    )
    try:
        for i in range(2):
            fi.arm("engine.dispatch", exc=xla_oom_error())
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                engine.generate(params, [[1, 2, 3]])
            # the waiter wakes from inside _recover's lock block; the
            # counters land before the lock releases, moments later
            _wait_for(
                lambda: int(engine._m_recoveries.value) == i + 1,
                what=f"recovery {i + 1} recorded",
            )
        # threshold hit: submissions now fail FAST with a typed error
        _wait_for(lambda: engine.breaker_open, what="breaker open")
        assert engine.health() == {
            "status": "degraded", "queue_depth": 0, "breaker_open": True,
        }
        with pytest.raises(EngineUnavailable) as exc_info:
            engine.generate(params, [[1, 2, 3]])
        assert exc_info.value.reason == "breaker_open"
        assert exc_info.value.retry_after_s > 0
        assert engine.stats()["robustness"]["rejected"]["breaker_open"] == 1
        # cooldown elapses -> half-open -> a healthy request closes it
        time.sleep(0.6)
        assert not engine.breaker_open
        out = engine.generate(params, [[1, 2, 3]])[0]
        assert out == _solo(module, params, [1, 2, 3], 4, max_len=engine.cache_len)
        assert engine.health()["status"] == "ok"
    finally:
        engine.close()


def test_engine_drain_finishes_inflight_then_rejects(tiny_llama):
    module, params = tiny_llama
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=48, prompt_buckets=(8,),
        chunk_steps=2,
    )
    try:
        done = {}

        def run_a():
            done["a"] = engine.generate(params, [[1, 2, 3]])[0]
        t1 = threading.Thread(target=run_a)
        t1.start()
        _wait_for(lambda: _resident(engine) == 1, what="slot occupied")
        assert engine.drain(timeout=120) is True
        # the in-flight request FINISHED (drain never kills work) ...
        t1.join(timeout=10)
        assert done["a"] == _solo(module, params, [1, 2, 3], 48, max_len=engine.cache_len)
        # ... and admissions are now rejected with the draining reason
        assert engine.health()["status"] == "draining"
        with pytest.raises(EngineUnavailable) as exc_info:
            engine.generate(params, [[4, 5]])
        assert exc_info.value.reason == "draining"
        assert engine.stats()["robustness"]["draining"] is True
        # drain duration landed in its histogram
        assert engine._h_drain.summary()["n"] == 1
        engine.resume()
        assert engine.health()["status"] == "ok"
        assert engine.generate(params, [[4, 5]])[0] == _solo(
            module, params, [4, 5], 48, max_len=engine.cache_len
        )
    finally:
        engine.close()


def test_engine_tolerates_slow_harvest(tiny_llama):
    """A stalled readback (slow-harvest injection) delays but never
    corrupts: tokens stay identical to the solo run."""
    module, params = tiny_llama
    fi = FaultInjector()
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=8, prompt_buckets=(8,),
        chunk_steps=2, fault_injector=fi,
    )
    try:
        fi.arm("engine.harvest", delay_s=0.05, count=3)
        out = engine.generate(params, [[1, 2, 3, 4]])[0]
        assert out == _solo(module, params, [1, 2, 3, 4], 8, max_len=engine.cache_len)
        assert fi.injected("engine.harvest") == 3
    finally:
        engine.close()


def test_recovery_and_abandon_release_prefix_cache_leases(tiny_llama):
    """Satellite: the abandoned-request races. A poisoned batch whose
    requests hold prefix-cache leases (one of them a concurrently
    abandoned stream) must release every lease at recovery — a leaked
    refcount would pin blocks against eviction forever."""
    module, params = tiny_llama
    fi = FaultInjector()
    cache = RadixPrefixCache(block_size=4)
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=32, prompt_buckets=(16,),
        chunk_steps=2, fault_injector=fi, prefix_cache=cache,
    )

    def live_refcounts():
        with cache._lock:
            total, stack = 0, list(cache._root.children.values())
            while stack:
                n = stack.pop()
                total += n.refcount
                stack.extend(n.children.values())
            return total

    try:
        shared = list(range(1, 13))  # 3 full blocks -> cacheable prefix
        # seed the cache, then verify steady state holds no refcounts
        engine.generate(params, [shared + [20]])
        _wait_for(lambda: live_refcounts() == 0, what="seed leases released")
        assert cache.entries > 0
        # two cache-hitting requests resident: a stream (abandoned
        # mid-recovery) and a generate (failed by the poisoned batch)
        stream = engine.generate_stream(params, shared + [21])
        next(iter(stream))          # consume TTFT: admission completed
        res = {}

        def run_b():
            try:
                res["b"] = engine.generate(params, [shared + [22]])[0]
            except BaseException as exc:
                res["b"] = exc
        t = threading.Thread(target=run_b)
        t.start()
        _wait_for(lambda: _resident(engine) == 2, what="both resident")
        fi.arm("engine.dispatch", exc=xla_oom_error())
        stream.close()              # abandon the stream during the fault
        t.join(timeout=120)
        assert not t.is_alive()
        _wait_for(
            lambda: int(engine._m_recoveries.value) == 1,
            what="recovery",
        )
        # no leaked leases anywhere — poisoned batch, abandoned stream,
        # and in-flight insert entries all released theirs
        _wait_for(lambda: live_refcounts() == 0, what="all leases released")
        # and the cache still SERVES: a fresh shared-prefix request
        # completes and matches its solo run (cache parity contract)
        out = engine.generate(params, [shared + [23]])[0]
        assert out == _solo(module, params, shared + [23], 32, max_len=engine.cache_len)
        _wait_for(lambda: live_refcounts() == 0, what="post-check release")
    finally:
        engine.close()


# ----------------------------------------------------------------- batcher


def test_batcher_queue_full_sheds(tiny_llama):
    picked_up = threading.Event()
    release = threading.Event()

    def predict(feats):
        picked_up.set()
        release.wait(30)
        return feats.sum(axis=1)

    batcher = MicroBatcher(
        predict, max_batch_size=2, max_wait_ms=1.0, max_queue_depth=1,
    )
    results = {}
    try:
        t1 = threading.Thread(
            target=lambda: results.update(a=batcher.submit(np.ones((1, 2))))
        )
        t1.start()
        assert picked_up.wait(30)   # worker is blocked inside the batch
        t2 = threading.Thread(
            target=lambda: results.update(b=batcher.submit(np.ones((1, 2))))
        )
        t2.start()
        _wait_for(lambda: batcher._queue.qsize() == 1, what="one queued")
        with pytest.raises(Overloaded, match="queue is full"):
            batcher.submit(np.ones((1, 2)))
        assert batcher.stats()["robustness"]["rejected"]["queue_full"] == 1
        release.set()
        t1.join(timeout=30)
        t2.join(timeout=30)
        np.testing.assert_allclose(results["a"], [2.0])
        np.testing.assert_allclose(results["b"], [2.0])
    finally:
        release.set()
        batcher.close()


def test_batcher_deadline_shed_and_drain():
    picked_up = threading.Event()
    release = threading.Event()

    def predict(feats):
        picked_up.set()
        release.wait(30)
        return feats.sum(axis=1)

    batcher = MicroBatcher(predict, max_batch_size=2, max_wait_ms=1.0)
    err = [None]
    try:
        t1 = threading.Thread(target=lambda: batcher.submit(np.ones((1, 2))))
        t1.start()
        assert picked_up.wait(30)

        def run_b():
            try:
                batcher.submit(np.ones((1, 2)), deadline_ms=20.0)
            except BaseException as exc:
                err[0] = exc
        t2 = threading.Thread(target=run_b)
        t2.start()
        time.sleep(0.05)            # the queued entry's deadline expires
        release.set()               # worker drains -> sheds it typed
        t1.join(timeout=30)
        t2.join(timeout=30)
        assert isinstance(err[0], DeadlineExceeded), err[0]
        assert int(batcher._m_deadline_shed.value) == 1
        # drain: admissions rejected, health flips, resume reopens
        assert batcher.drain(timeout=30) is True
        assert batcher.health()["status"] == "draining"
        with pytest.raises(EngineUnavailable):
            batcher.submit(np.ones((1, 2)))
        batcher.resume()
        assert batcher.health()["status"] == "ok"
    finally:
        release.set()
        batcher.close()


def test_batcher_predict_injection_surfaces_to_waiters():
    fi = FaultInjector()
    batcher = MicroBatcher(
        lambda feats: feats.sum(axis=1), max_batch_size=4, max_wait_ms=1.0,
        fault_injector=fi,
    )
    try:
        fi.arm("batcher.predict", exc=xla_oom_error())
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            batcher.submit(np.ones((1, 2)))
        # the injected batch failed; the next one is healthy
        np.testing.assert_allclose(batcher.submit(np.ones((1, 2))), [2.0])
    finally:
        batcher.close()


# ----------------------------------------------------- HTTP acceptance


def _engine_serving_app(**engine_kwargs):
    from unionml_tpu import Dataset, Model
    from unionml_tpu.model import ModelArtifact
    from unionml_tpu.serving.http import ServingApp

    cfg = LlamaConfig.tiny(vocab_size=61)
    module = Llama(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = DecodeEngine(
        module, prompt_buckets=(8,), chunk_steps=2, **engine_kwargs
    )
    dataset = Dataset(name="faults_data", targets=[])

    @dataset.reader
    def reader() -> list:
        return []

    lm = Model(name="faults_lm", init=lambda: params, dataset=dataset)

    @lm.trainer
    def trainer(p: dict, features: list) -> dict:
        return p

    @lm.predictor
    def predictor(p: dict, prompts: list) -> list:
        return engine.generate(p, prompts)

    lm.artifact = ModelArtifact(params, {}, {})
    app = ServingApp(
        lm, stats=engine.stats, health=engine.health, drain=engine.drain,
    )
    return app, engine


def test_http_overload_answers_429_with_retry_after():
    """THE transport acceptance scenario: drive the engine queue past
    ``max_queue_depth`` and observe 429 + ``Retry-After`` at the HTTP
    layer, then 503 (+ ``Retry-After``) once the app drains."""
    # request "a" must hold the only slot until the 429 has been seen: on
    # the tiny model its 48 tokens take tens of milliseconds once the
    # programs are compiled, less than the /health GET and the third POST
    # below take on a loaded host — "a" then retired, "b" left the queue
    # and the third POST got 200. Every decode-chunk dispatch stalls
    # (24 chunks x 0.25 s hold the slot for 6 s) until the 429 is in hand.
    fi = FaultInjector()
    fi.arm("engine.dispatch", count=10 ** 6, delay_s=0.25)
    app, engine = _engine_serving_app(
        slots=1, max_new_tokens=48, max_queue_depth=1, fault_injector=fi,
    )
    host, port = app.serve(port=0, blocking=False)
    url = f"http://{host}:{port}"
    results = {}

    def post(name, prompt):
        results[name] = httpx.post(
            f"{url}/predict", json={"features": [prompt]}, timeout=120
        )

    try:
        t1 = threading.Thread(target=post, args=("a", [1, 2, 3]))
        t1.start()
        _wait_for(lambda: _resident(engine) == 1, what="slot occupied")
        t2 = threading.Thread(target=post, args=("b", [4, 5, 6]))
        t2.start()
        _wait_for(lambda: engine._room.qsize() == 1, what="one queued")
        # /health reports the backlog the balancer would act on
        assert httpx.get(f"{url}/health").json()["queue_depth"] == 1
        r = httpx.post(
            f"{url}/predict", json={"features": [[7, 8, 9]]}, timeout=30
        )
        assert r.status_code == 429
        assert "queue is full" in r.json()["error"]
        assert int(r.headers["retry-after"]) >= 1
        fi.disarm()  # the slot may go now: "a" and "b" finish at full speed
        t1.join(timeout=120)
        t2.join(timeout=120)
        assert results["a"].status_code == 200
        assert results["b"].status_code == 200
        # graceful drain: already-admitted work finished above; now the
        # app sheds with 503 + Retry-After and /health serves 503
        assert app.drain(timeout=120) is True
        r = httpx.post(
            f"{url}/predict", json={"features": [[1, 2]]}, timeout=30
        )
        assert r.status_code == 503 and r.json()["reason"] == "draining"
        assert int(r.headers["retry-after"]) >= 1
        h = httpx.get(f"{url}/health")
        assert h.status_code == 503 and h.json()["status"] == "draining"
    finally:
        app.shutdown()
        engine.close()


def test_http_deadline_header_maps_to_504():
    """X-Deadline-Ms propagates through the transport into the engine
    and an expired queued request surfaces as 504."""
    app, engine = _engine_serving_app(slots=1, max_new_tokens=64)
    host, port = app.serve(port=0, blocking=False)
    url = f"http://{host}:{port}"
    results = {}

    def post_a():
        results["a"] = httpx.post(
            f"{url}/predict", json={"features": [[1, 2, 3]]}, timeout=120
        )

    try:
        t1 = threading.Thread(target=post_a)
        t1.start()
        _wait_for(lambda: _resident(engine) == 1, what="slot occupied")
        r = httpx.post(
            f"{url}/predict", json={"features": [[4, 5, 6]]},
            headers={"X-Deadline-Ms": "1"}, timeout=120,
        )
        assert r.status_code == 504
        assert "deadline expired" in r.json()["error"]
        # malformed header is a 422, not a silent no-deadline
        r = httpx.post(
            f"{url}/predict", json={"features": [[4, 5, 6]]},
            headers={"X-Deadline-Ms": "soon"}, timeout=30,
        )
        assert r.status_code == 422
        t1.join(timeout=120)
        assert results["a"].status_code == 200
        assert int(engine._m_deadline_shed.value) == 1
    finally:
        app.shutdown()
        engine.close()
