"""Host spans on the profiler's clock (docs/observability.md).

The contract under test: ``TraceRecorder.span()`` writes one span to both
clocks (the request's timeline in ``perf_counter`` seconds and a
``jax.profiler.TraceAnnotation`` in an open session's nanoseconds) so that
their starts join the clocks; the engine and the train loop open the spans
whose names the trace readers match; ``ServingPerfPlane``'s window sums say
what paced the dispatcher; and the compiled programs keep the module names
the device-trace readers match.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu import telemetry
from unionml_tpu.models import Llama, LlamaConfig
from unionml_tpu.serving.engine import DecodeEngine
from unionml_tpu.serving.perf import (
    DISPATCHER_PHASES,
    POLL_REASONS,
    ServingPerfPlane,
)

#: the span names that are the interface (docs/observability.md "Host
#: spans on the profiler's clock"); chipbench/hostspans.py reads them
ENGINE_SPANS = {
    "engine.pass", "engine.admit", "engine.admit.enqueue",
    "engine.dispatch_chunk", "engine.dispatch_chunk.enqueue", "engine.poll",
    "engine.harvest_wait", "engine.harvest_process",
}
TRAIN_SPANS = {"train.feed_wait", "train.step"}


class _Session:
    """A CPU profiler session (Python tracer off) whose annotation events
    are read back with ``jax.profiler.ProfileData`` alone."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def events(self):
        """[(name, start_s, end_s, stats, line index)] of the host plane."""
        from jax.profiler import ProfileData

        (path,) = glob.glob(
            os.path.join(self.log_dir, "**", "*.xplane.pb"), recursive=True
        )
        out = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(("engine.", "train.", "test.")):
                        out.append((
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            dict(ev.stats), i,
                        ))
        return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(vocab_size=61)
    module = Llama(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return module, params


# ------------------------------------------------------------ the seam


def test_span_lands_on_both_clocks_and_joins_them(tmp_path):
    tracer = telemetry.TraceRecorder()
    rids = [tracer.new_request("generate") for _ in range(7)]
    with _Session(tmp_path) as session:
        for i, rid in enumerate(rids):
            with tracer.span(
                rid, "admit", annotation="test.admit", bucket=16 * (i + 1)
            ) as sp:
                time.sleep(0.002)
                sp.note(cached_tokens=i)
            assert sp.end_s - sp.start_s >= 0.002
        with tracer.span(None, "test.poll", reason="no_work"):
            pass
        with tracer.span(None, "test.step", step=7):
            pass
    events = session.events()
    admits = [e for e in events if e[0] == "test.admit"]
    # each annotation is in the xplane under the profiler's name, with its
    # rid, its args, and what note() added inside the body
    assert [e[3]["rid"] for e in admits] == rids
    assert [e[3]["bucket"] for e in admits] == [16 * (i + 1) for i in range(7)]
    assert [e[3]["cached_tokens"] for e in admits] == list(range(7))
    # the twin in the request's timeline keeps the timeline's own name
    recorded = {rid: spans for rid, spans in tracer._live.items()}
    diffs = []
    for (_, start_s, end_s, _, _), rid in zip(admits, rids):
        (span,) = recorded[rid]
        assert span["name"] == "admit"
        assert span["args"] == {
            "bucket": span["args"]["bucket"],
            "cached_tokens": span["args"]["cached_tokens"],
        }
        assert end_s - start_s == pytest.approx(
            span["end_s"] - span["start_s"], abs=1e-3
        )
        diffs.append(span["start_s"] - start_s)
    # the (perf_counter, trace) pairs agree on one offset: spread < 1 ms
    # (the widest pair each way is left out: on a loaded host a thread can
    # lose the CPU between the two clock reads)
    inner = sorted(diffs)[1:-1]
    assert inner[-1] - inner[0] < 1e-3
    # a span that is no request's goes to the profiler only
    (poll,) = [e for e in events if e[0] == "test.poll"]
    assert poll[3] == {"reason": "no_work"}
    assert set(recorded) == set(rids)
    # step= makes it a StepTraceAnnotation carrying the step number
    (step,) = [e for e in events if e[0] == "test.step"]
    assert step[3]["step_num"] == 7


def test_span_without_a_session_keeps_only_the_timeline():
    tracer = telemetry.TraceRecorder()
    rid = tracer.new_request("generate")
    with tracer.span(rid, "admit", annotation="engine.admit", bucket=8) as sp:
        sp.note(cached_tokens=0)
    with tracer.span(None, "engine.poll", reason="no_credit") as poll:
        pass
    assert poll.end_s >= poll.start_s > 0.0
    (span,) = tracer._live[rid]
    assert span["name"] == "admit"
    assert span["args"] == {"bucket": 8, "cached_tokens": 0}
    assert list(tracer._live) == [rid]  # nothing kept for the rid-less span


# ----------------------------------------- the perf plane's window sums


def test_starved_slot_steps_and_admissions_are_plain_sums():
    plane = ServingPerfPlane(
        registry=telemetry.MetricsRegistry(), engine="e0", slots=4,
        chunk_steps=2, clock=lambda: 0.0,
    )
    plane.note_pass(1, waiting=3, admitted=1, prefill_tokens=40)  # 3 empty, starved
    plane.note_pass(2, waiting=0, admitted=1, prefill_tokens=24)  # 2 empty, nobody waits
    plane.note_pass(4, waiting=5)                                 # full: nothing to fill
    report = plane.report()
    assert report["starved_slot_steps"] == 3 * 2
    assert report["window_dispatched_slot_steps"] == 3 * 8
    assert report["window_occupied_slot_steps"] == (1 + 2 + 4) * 2
    assert report["admissions"] == 2 and report["prefill_tokens"] == 64
    # an admission that found a slot and no pool blocks, counted when it parks
    assert report["admissions_parked_on_pool"] == 0 and report["state_bytes_resident"] == 0
    plane.note_parked()
    assert plane.report()["admissions_parked_on_pool"] == 1
    plane.reset()
    report = plane.report()
    assert report["starved_slot_steps"] == 0 and report["admissions"] == 0
    assert report["admissions_parked_on_pool"] == 0
    assert report["polls"] == {reason: 0 for reason in POLL_REASONS}
    assert report["dispatcher_s"] == {phase: 0.0 for phase in DISPATCHER_PHASES}


def test_no_credit_polls_are_not_idle_passes():
    plane = ServingPerfPlane(
        registry=telemetry.MetricsRegistry(), engine="e1", slots=2,
        chunk_steps=4, clock=lambda: 0.0,
    )
    plane.note_pass(2)
    for _ in range(50):  # the pipeline is full and the chip busy
        plane.note_dispatcher(poll_s=0.002, poll="no_credit")
    plane.note_dispatcher(poll_s=0.002, poll="no_work")
    plane.note_idle()  # the engine really is empty
    report = plane.report()
    assert report["polls"] == {"no_work": 1, "no_credit": 50}
    assert report["passes"]["idle"] == 1 and report["total_passes"] == 2
    # polls lose no slot-steps: one full pass and one true idle
    assert report["goodput_ratio"] == pytest.approx(0.5)
    assert report["occupancy_ratio"] == pytest.approx(1.0)
    assert report["dispatcher_s"]["poll"] == pytest.approx(51 * 0.002)


def test_window_sums_outlive_a_wrapped_ring():
    plane = ServingPerfPlane(
        registry=telemetry.MetricsRegistry(), engine="e2", slots=2,
        chunk_steps=1, ring=16, clock=lambda: 0.0,
    )
    for i in range(100):
        plane.note_pass(1, waiting=1, admitted=1, prefill_tokens=10)
        plane.note_dispatcher(
            admit_s=0.001, dispatch_s=0.003, enqueue_s=0.002, other_s=0.0005
        )
    report = plane.report()
    # the ring wrapped, and says so; its ratios cover the newest 16 passes
    assert report["ring_passes"] == 16 and report["total_passes"] == 100
    assert report["occupied_slot_steps"] == 16
    # the sums still cover all 100
    assert report["window_dispatched_slot_steps"] == 200
    assert report["window_occupied_slot_steps"] == 100
    assert report["starved_slot_steps"] == 100
    assert report["admissions"] == 100 and report["prefill_tokens"] == 1000
    assert report["dispatcher_s"]["admit"] == pytest.approx(0.1)
    assert report["dispatcher_s"]["dispatch"] == pytest.approx(0.3)
    assert report["dispatcher_s"]["enqueue"] == pytest.approx(0.2)
    assert report["dispatcher_s"]["other"] == pytest.approx(0.05)


# ------------------------------------------------------------ the engine


def _generate_together(engine, params, prompts):
    threads = [
        threading.Thread(target=engine.generate, args=(params, [p]))
        for p in prompts
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _settle(engine):
    """Wait until the dispatcher has flushed the iterations of earlier
    work (two polls of an empty engine), then open a clean window."""
    engine.perf.reset()
    deadline = time.monotonic() + 10.0
    while engine.perf.report()["polls"]["no_work"] < 2:
        assert time.monotonic() < deadline, "dispatcher never polled"
        time.sleep(0.005)
    engine.perf.reset()


def test_engine_counts_starved_slots_admissions_and_its_own_time(tiny_llama):
    module, params = tiny_llama
    from unionml_tpu.serving.faults import FaultInjector

    fi = FaultInjector()
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=12, prompt_buckets=(16,),
        chunk_steps=2, pipeline_depth=2, fault_injector=fi,
        registry=telemetry.MetricsRegistry(), tracer=telemetry.TraceRecorder(),
    )
    try:
        engine.warmup(params)
        _settle(engine)
        t0 = time.perf_counter()
        # three requests into two slots, one admission a pass: the first
        # chunk runs with a slot empty while two requests wait. The
        # dispatcher's next dequeue stalls until all three are queued, so
        # that first pass is the same on a loaded host
        fi.arm("engine.dequeue", delay_s=0.1)
        deadline = time.monotonic() + 10.0
        while fi.injected("engine.dequeue") == 0:
            assert time.monotonic() < deadline, "dispatcher never dequeued"
            time.sleep(0.001)
        _generate_together(engine, params, [[1, 2, 3], [4, 5, 6, 7], [8, 9]])
        # the engine is empty again: true idles enter the ring, polls do not
        time.sleep(0.2)
        report = engine.perf.report()
        wall = time.perf_counter() - t0
    finally:
        engine.close()
    assert report["admissions"] == 3
    assert report["prefill_tokens"] == 3 + 4 + 2
    assert report["starved_slot_steps"] > 0
    assert (
        report["starved_slot_steps"]
        <= report["window_dispatched_slot_steps"]
        - report["window_occupied_slot_steps"]
    )
    phases = report["dispatcher_s"]
    assert set(phases) == set(DISPATCHER_PHASES)
    # admit + dispatch + poll + other are the whole of the thread's time
    total = sum(phases[k] for k in ("admit", "dispatch", "poll", "other"))
    assert total == pytest.approx(wall, rel=0.05)
    assert 0.0 < phases["enqueue"] <= phases["admit"] + phases["dispatch"]
    assert phases["admit"] > 0.0 and phases["dispatch"] > 0.0
    assert report["polls"]["no_work"] > 0
    # every idle in the ring is a poll of the empty engine, not the reverse
    assert 0 < report["passes"]["idle"] <= report["polls"]["no_work"]


def test_engine_and_train_loop_open_the_documented_spans(tiny_llama, tmp_path):
    from unionml_tpu.execution import run_step_trainer
    from unionml_tpu.models.train import TrainState, adamw, lm_step

    module, params = tiny_llama
    tracer = telemetry.TraceRecorder()
    done = []
    tracer.add_listener(lambda rid, meta, spans: done.append((rid, spans)))
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(16,),
        chunk_steps=2, pipeline_depth=2,
        registry=telemetry.MetricsRegistry(), tracer=tracer,
    )
    state = TrainState.create(  # a copy: the train loop donates its state
        apply_fn=module.apply, params=jax.tree_util.tree_map(jnp.copy, params),
        tx=adamw(1e-3),
    )
    batches = [np.full((2, 8), i + 1, np.int32) for i in range(3)]
    try:
        engine.warmup(params)
        done.clear()
        with _Session(tmp_path) as session:
            _generate_together(engine, params, [[1, 2, 3], [4, 5, 6, 7], [8, 9]])
            time.sleep(0.01)  # an empty engine polls
            run_step_trainer(
                step_fn=lm_step(module), state=state, features=iter(batches),
                batch_size=2,
            )
    finally:
        engine.close()
    events = session.events()
    names = {e[0] for e in events}
    assert ENGINE_SPANS <= names
    assert TRAIN_SPANS <= names
    by_name = {n: [e for e in events if e[0] == n] for n in names}
    # the dispatcher's spans share one thread line, the harvester's another
    dispatcher = {e[4] for n in ENGINE_SPANS - {
        "engine.harvest_wait", "engine.harvest_process"} for e in by_name[n]}
    harvester = {e[4] for n in ("engine.harvest_wait", "engine.harvest_process")
                 for e in by_name[n]}
    assert len(dispatcher) == 1 and len(harvester) == 1
    assert dispatcher != harvester
    # metadata: what each span says about itself
    assert {"waiting", "free_slots", "live_slots"} <= set(by_name["engine.pass"][0][3])
    assert {e[3]["reason"] for e in by_name["engine.poll"]} <= set(POLL_REASONS)
    assert {e[3]["kind"] for e in by_name["engine.harvest_wait"]} == {"prefill", "chunk"}
    seqs = [e[3]["seq"] for e in by_name["engine.dispatch_chunk"]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    enqueues = by_name["engine.admit.enqueue"]
    assert [e[3]["program"] for e in enqueues] == ["prefill"] * 3
    # engine.admit is the request's admit span: same rid, and the join of
    # the two clocks over these pairs holds to well under a millisecond
    rids = {rid for rid, _ in done}
    assert {e[3]["rid"] for e in by_name["engine.admit"]} == rids
    diffs = []
    for rid, spans in done:
        recorded = {s["name"]: s for s in spans}
        assert {"queue", "admit", "admit.enqueue", "prefill", "harvest"} <= set(recorded)
        (ann,) = [e for e in by_name["engine.admit"] if e[3]["rid"] == rid]
        assert ann[3]["prompt_tokens"] == recorded["admit"]["args"]["prompt_tokens"]
        assert ann[3]["state_layers"] == 0  # every layer of a Llama caches keys and values
        diffs.append(recorded["admit"]["start_s"] - ann[1])
        (enq,) = [e for e in enqueues if e[3]["rid"] == rid]
        diffs.append(recorded["admit.enqueue"]["start_s"] - enq[1])
        # the enqueue lies inside the admission
        assert ann[1] <= enq[1] and enq[2] <= ann[2]
    # six pairs; one may have had its thread descheduled between the two
    # clock reads on a loaded host, so the widest each way is left out
    inner = sorted(diffs)[1:-1]
    assert inner[-1] - inner[0] < 1e-3
    # the train loop: one feed wait per step (and one that finds the end)
    steps = by_name["train.step"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    assert len(by_name["train.feed_wait"]) == 4


def test_a_parked_admission_keeps_one_admit_span_and_polls_no_work(tiny_llama):
    """An admission parked on the KV pool is retried every pass: only the
    try that got through is the request's ``admit`` span, and the polls
    between the tries are ``no_work`` polls, not idle passes."""
    module, params = tiny_llama
    tracer = telemetry.TraceRecorder()
    done = []
    tracer.add_listener(lambda rid, meta, spans: done.append(spans))
    engine = DecodeEngine(
        module, slots=4, max_new_tokens=8, prompt_buckets=(16,),
        chunk_steps=4, paged=True, kv_pool_blocks=3,  # one resident fits
        registry=telemetry.MetricsRegistry(), tracer=tracer,
    )
    try:
        engine.warmup(params)
        done.clear()
        _settle(engine)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 61, size=9).tolist() for _ in range(3)]
        engine.generate(params, prompts)
        report = engine.perf.report()
        assert engine.stats()["kv_pool"]["alloc_failures"] > 0  # it did park
    finally:
        engine.close()
    assert len(done) == 3
    for spans in done:
        assert [s["name"] for s in spans].count("admit") == 1
    assert report["admissions"] == 3
    assert report["polls"]["no_work"] > 0
    # only a poll of the empty engine (after the last request retired) is idle
    assert report["passes"]["idle"] <= report["polls"]["no_work"]


# --------------------------------- module names the trace readers match


def test_compiled_programs_keep_the_names_the_trace_readers_match(tiny_llama):
    """``chipbench`` finds device runs by module name: ``jit_prefill``,
    ``jit_decode_chunk`` (``chipbench/run.py``, ``layer_metrics/``) and the
    train step's ``jit_step``. A rename would silently empty those
    metrics, so the names are held here."""
    from unionml_tpu.execution import _jitted
    from unionml_tpu.models.train import TrainState, adamw, lm_step

    module, params = tiny_llama

    def module_name(lowered):
        text = lowered.as_text()
        return text[text.index("module @") + 8:].split()[0]

    for paged in (False, True):
        kwargs = dict(paged=True, kv_pool_bytes=1 << 20, kv_block_size=8) if paged else {}
        engine = DecodeEngine(
            module, slots=2, max_new_tokens=4, prompt_buckets=(16,),
            chunk_steps=2, registry=telemetry.MetricsRegistry(),
            tracer=telemetry.TraceRecorder(), **kwargs,
        )
        try:
            state = jax.eval_shape(engine._init_state)
            key = jax.random.PRNGKey(0)
            tokens = jnp.zeros((16,), jnp.int32)
            mask = jnp.ones((2,), bool)
            keys = jnp.stack([key, key])
            prefill = getattr(engine._prefill, "__wrapped__", engine._prefill)
            chunk = getattr(engine._decode_chunk, "__wrapped__", engine._decode_chunk)
            # place: the pool's block ids (bucket / block) and table
            ids = jnp.zeros((16 // 8,), jnp.int32) if paged else None
            table = jnp.asarray(engine._table) if paged else None
            lowered_prefill = prefill.lower(
                params, state, jnp.int32(0), ids, tokens, jnp.int32(3), key)
            lowered_chunk = chunk.lower(params, state, mask, table, keys)
        finally:
            engine.close()
        assert module_name(lowered_prefill) == "jit_prefill"
        assert module_name(lowered_chunk) == "jit_decode_chunk"

    state = TrainState.create(apply_fn=module.apply, params=params, tx=adamw(1e-3))
    step = _jitted(lm_step(module), True)
    assert module_name(step.lower(state, jnp.ones((2, 8), jnp.int32))) == "jit_step"
