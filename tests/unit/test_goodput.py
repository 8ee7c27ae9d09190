"""Training goodput accounting (unionml_tpu.goodput).

Covers the docs/observability.md "Training goodput" contract: bucket
math on a synthetic clock (attribution sums to wall time, compile
debits), the regression detector's hysteresis, straggler/skew math,
trainer + elastic-trainer integration (the preemption badput bucket),
the checkpoint save/restore instrumentation, and the SLO-watchdog
coupling through ``unionml_train_goodput_ratio``.
"""

import io

import numpy as np
import pytest

from unionml_tpu.goodput import (
    BADPUT_CAUSES,
    GoodputTracker,
    StepSkewMonitor,
    StepTimeRegressionDetector,
)
from unionml_tpu.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    TraceRecorder,
)


class FakeClock:
    """Deterministic monotonic clock for bucket-math tests."""

    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


def make_tracker(clock=None, **kwargs):
    reg = kwargs.pop("registry", None) or MetricsRegistry()
    tracker = GoodputTracker(
        registry=reg,
        tracer=kwargs.pop("tracer", None) or TraceRecorder(registry=reg),
        flight=kwargs.pop("flight", None) or FlightRecorder(),
        clock=clock if clock is not None else FakeClock(),
        **kwargs,
    )
    return tracker, reg


# ---------------------------------------------------------- bucket math


def test_bucket_set_is_closed():
    # report() keys mirror the documented bucket set exactly — a bucket
    # outside it could silently leak out of the attribution sum
    tracker, _ = make_tracker()
    rep = tracker.report()
    assert set(rep["badput_s"]) == set(BADPUT_CAUSES)
    assert set(rep["buckets_s"]) == {"compute", *BADPUT_CAUSES}


def test_phase_buckets_on_synthetic_clock():
    clock = FakeClock()
    tracker, reg = make_tracker(clock)
    tracker.start()
    with tracker.phase("data_wait"):
        clock.advance(2.0)
    with tracker.phase("host_to_device"):
        clock.advance(0.5)
    with tracker.phase("compute"):
        clock.advance(7.0)
    clock.advance(0.5)  # unattributed loop bookkeeping
    tracker.finish()
    rep = tracker.report()
    assert rep["wall_s"] == pytest.approx(10.0)
    assert rep["badput_s"]["data_wait"] == pytest.approx(2.0)
    assert rep["badput_s"]["host_to_device"] == pytest.approx(0.5)
    assert rep["goodput_s"] == pytest.approx(7.0)
    assert rep["goodput_ratio"] == pytest.approx(0.7)
    assert rep["unattributed_s"] == pytest.approx(0.5)
    # attribution identity: buckets + unattributed == wall, exactly
    total = sum(rep["buckets_s"].values()) + rep["unattributed_s"]
    assert total == pytest.approx(rep["wall_s"])
    assert rep["attributed_fraction"] == pytest.approx(0.95)


def test_badput_series_published():
    clock = FakeClock()
    tracker, reg = make_tracker(clock)
    tracker.start()
    with tracker.phase("checkpoint"):
        clock.advance(1.5)
    with tracker.phase("compute"):
        clock.advance(1.5)
    tracker.step_complete(3.0)
    snap = reg.snapshot()
    assert snap["unionml_train_badput_seconds_total"]["cause=checkpoint"] == (
        pytest.approx(1.5)
    )
    assert snap["unionml_train_goodput_seconds_total"][""] == pytest.approx(1.5)
    assert snap["unionml_train_goodput_ratio"][""] == pytest.approx(0.5)
    hist = snap["unionml_train_phase_ms"]["phase=checkpoint"]
    assert hist["count"] == 1


def test_unknown_phase_rejected():
    tracker, _ = make_tracker()
    with pytest.raises(ValueError, match="unknown phase"):
        tracker.phase("coffee_break")


def test_compile_debit_reclassifies_compute():
    clock = FakeClock()
    tracker, _ = make_tracker(clock)
    tracker.start()
    with tracker.phase("compute"):
        # ProgramTracker fires on_compile mid-call: 3 of these 5 seconds
        # were XLA compiling, not useful work
        clock.advance(5.0)
        tracker.note_compile_ms("trainer.step", 3000.0)
    rep = tracker.report()
    assert rep["goodput_s"] == pytest.approx(2.0)
    assert rep["badput_s"]["compile"] == pytest.approx(3.0)


def test_compile_debit_capped_at_phase_and_carried():
    clock = FakeClock()
    tracker, _ = make_tracker(clock)
    tracker.start()
    tracker.note_compile_ms("trainer.step", 4000.0)
    with tracker.phase("compute"):
        clock.advance(1.0)
    rep = tracker.report()
    # the debit can never exceed the phase it lands in; the remainder
    # waits for the next compute phase
    assert rep["goodput_s"] == pytest.approx(0.0)
    assert rep["badput_s"]["compile"] == pytest.approx(1.0)
    with tracker.phase("compute"):
        clock.advance(5.0)
    rep = tracker.report()
    assert rep["badput_s"]["compile"] == pytest.approx(4.0)
    assert rep["goodput_s"] == pytest.approx(2.0)


def test_resume_after_finish_excludes_gap():
    clock = FakeClock()
    tracker, _ = make_tracker(clock)
    tracker.start()
    with tracker.phase("compute"):
        clock.advance(4.0)
    tracker.finish()
    clock.advance(1000.0)  # the paused gap must not count as wall time
    tracker.start()
    with tracker.phase("compute"):
        clock.advance(6.0)
    tracker.finish()
    rep = tracker.report()
    assert rep["wall_s"] == pytest.approx(10.0)
    assert rep["goodput_ratio"] == pytest.approx(1.0)


def test_phase_spans_recorded_on_trainer_timeline():
    clock = FakeClock()
    reg = MetricsRegistry()
    tracer = TraceRecorder(registry=reg)
    tracker, _ = make_tracker(clock, registry=reg, tracer=tracer)
    tracker.start()
    with tracker.phase("data_wait"):
        clock.advance(1.0)
    with tracker.phase("compute"):
        clock.advance(2.0)
    tracker.finish()
    lines = tracer.export_jsonl().strip().splitlines()
    names = [line for line in lines if '"kind": "trainer"' in line]
    assert len(names) == 2
    assert any('"name": "data_wait"' in line for line in names)
    assert any('"name": "compute"' in line for line in names)


def test_timeline_rotates_onto_fresh_requests():
    # long runs record 3-4 spans per step: without rotation a 100k-step
    # run would hit TraceRecorder's per-request span cap ~1k steps in
    # and silently truncate the exported timeline
    clock = FakeClock()
    reg = MetricsRegistry()
    tracer = TraceRecorder(registry=reg)
    tracker, _ = make_tracker(
        clock, registry=reg, tracer=tracer, timeline_rotate_steps=2,
    )
    tracker.start()
    for _ in range(5):
        with tracker.phase("compute"):
            clock.advance(1.0)
        tracker.step_complete(1.0)
    tracker.finish()
    requests = [
        (rid, meta, spans)
        for rid, meta, spans in tracer._all_requests()
        if meta.get("kind") == "trainer"
    ]
    # 5 steps at rotate-every-2 → rotations after steps 2 and 4: three
    # chained requests, every span retained across them
    assert len(requests) == 3
    assert sum(len(spans) for _, _, spans in requests) == 5
    assert all(meta.get("end_s") is not None for _, meta, _ in requests)
    # attribution is unaffected by rotation
    assert tracker.report()["goodput_s"] == pytest.approx(5.0)


# ------------------------------------------------- regression detection


def test_regression_detector_hysteresis():
    det = StepTimeRegressionDetector(
        window=20, threshold=1.5, clear_threshold=1.2, consecutive=3,
        min_steps=5,
    )
    for _ in range(10):  # warmup: baseline settles at 1.0
        verdict = det.update(1.0)
        assert not verdict["anomaly"]
    assert det.baseline() == pytest.approx(1.0)

    # two anomalous steps do NOT trip the regressed state ...
    for _ in range(2):
        verdict = det.update(2.0)
        assert verdict["anomaly"] and not verdict["regressed"]
    # ... the third consecutive one does
    verdict = det.update(2.0)
    assert verdict["regressed"] and verdict["entered"]

    # inside the hysteresis band (1.2x < r < 1.5x): not anomalous, but
    # not clean enough to clear either
    for _ in range(5):
        verdict = det.update(1.3)
        assert not verdict["anomaly"] and verdict["regressed"]

    # three consecutive clean steps clear it
    det.update(1.0)
    det.update(1.0)
    verdict = det.update(1.0)
    assert verdict["cleared"] and not verdict["regressed"]
    # anomalous samples never polluted the baseline
    assert det.baseline() == pytest.approx(1.0)


def test_regression_detector_anomaly_resets_clear_streak():
    det = StepTimeRegressionDetector(
        window=20, threshold=1.5, clear_threshold=1.2, consecutive=2,
        min_steps=2,
    )
    for _ in range(5):
        det.update(1.0)
    det.update(3.0)
    det.update(3.0)
    assert det.regressed
    det.update(1.0)          # one clean step ...
    verdict = det.update(3.0)  # ... interrupted: still regressed
    assert verdict["regressed"]


def test_regression_detector_validation():
    with pytest.raises(ValueError, match="hysteresis"):
        StepTimeRegressionDetector(threshold=1.2, clear_threshold=1.2)
    with pytest.raises(ValueError):
        StepTimeRegressionDetector(window=1)


def test_step_complete_publishes_and_records_flight_events():
    flight = FlightRecorder()
    tracker, reg = make_tracker(
        flight=flight,
        detector=StepTimeRegressionDetector(
            window=10, threshold=1.5, clear_threshold=1.2, consecutive=2,
            min_steps=2,
        ),
    )
    tracker.start()
    for _ in range(5):
        tracker.step_complete(0.1)
    for _ in range(2):
        tracker.step_complete(0.5)  # 5x baseline: anomalous, then regressed
    snap = reg.snapshot()
    assert snap["unionml_train_step_anomalies_total"][""] == 2.0
    assert snap["unionml_train_step_time_ratio"][""] == pytest.approx(5.0)
    kinds = [e["kind"] for e in flight.dump()]
    assert kinds.count("step_time_anomaly") == 2
    transitions = flight.dump(kind="step_time_regression")
    assert [e["state"] for e in transitions] == ["entered"]


def test_step_complete_detect_false_keeps_sample_out_of_detector():
    # the async-dispatch trainer's window-boundary steps drain a whole
    # window of device work into one sample — fed to the detector they
    # would read as anomalies against the dispatch-scale baseline
    flight = FlightRecorder()
    tracker, reg = make_tracker(
        flight=flight,
        detector=StepTimeRegressionDetector(
            window=10, threshold=1.5, clear_threshold=1.2, consecutive=2,
            min_steps=2,
        ),
    )
    tracker.start()
    for _ in range(5):
        tracker.step_complete(0.001)  # dispatch-scale baseline
    verdict = tracker.step_complete(1.0, detect=False)  # window boundary
    assert not verdict["anomaly"] and not verdict["regressed"]
    snap = reg.snapshot()
    assert snap["unionml_train_step_anomalies_total"][""] == 0.0
    # the excluded sample neither moved the ratio gauge nor the baseline
    assert snap["unionml_train_step_time_ratio"][""] == pytest.approx(1.0)
    assert tracker.detector.baseline() == pytest.approx(0.001)
    assert not flight.dump(kind="step_time_anomaly")
    # the step itself still counts
    assert tracker.report()["steps"] == 6


# ------------------------------------------------------- straggler skew


def test_skew_monitor_names_stragglers():
    monitor = StepSkewMonitor(straggler_factor=1.5, min_skew_ms=50.0)
    sample = monitor.observe(7, [1.0, 1.01, 2.0, 0.99])
    assert sample["stragglers"] == [2]
    assert sample["skew_ms"] == pytest.approx(1000.0, rel=0.02)
    assert sample["median_ms"] == pytest.approx(1000.0, rel=0.02)


def test_skew_monitor_two_host_slice_sees_the_straggler():
    # even host counts take the LOWER middle as the median: with the
    # upper middle a 2-process slice has median == slowest, so skew is
    # always 0 and no straggler can ever trip
    monitor = StepSkewMonitor(straggler_factor=1.5, min_skew_ms=50.0)
    sample = monitor.observe(3, [1.0, 3.0])
    assert sample["median_ms"] == pytest.approx(1000.0)
    assert sample["skew_ms"] == pytest.approx(2000.0)
    assert sample["stragglers"] == [1]


def test_skew_monitor_absolute_floor_filters_jitter():
    # 2x the median but only 10 ms absolute: phantom straggler filtered
    monitor = StepSkewMonitor(straggler_factor=1.5, min_skew_ms=50.0)
    sample = monitor.observe(0, [0.010, 0.011, 0.020])
    assert sample["stragglers"] == []


def test_record_step_skew_publishes_gauges_and_flight():
    flight = FlightRecorder()
    tracker, reg = make_tracker(flight=flight)
    tracker.start()
    sample = tracker.record_step_skew(50, [1.0, 1.0, 3.0, 1.0])
    assert sample["stragglers"] == [2]
    snap = reg.snapshot()
    assert snap["unionml_train_step_skew_ms"][""] == pytest.approx(
        2000.0, rel=0.02
    )
    assert snap["unionml_train_host_step_ms"]["process=2"] == pytest.approx(
        3000.0
    )
    assert snap["unionml_train_stragglers_total"][""] == 1.0
    events = flight.dump(kind="straggler")
    assert len(events) == 1
    assert events[0]["process"] == 2 and events[0]["step"] == 50


# -------------------------------------------------- trainer integration


def _blob_problem():
    import jax.numpy as jnp

    def step(state, batch):
        x, y = batch
        w = state["w"] - 0.01 * x.T @ (x @ state["w"] - y)
        return {"w": w}, {"loss": jnp.mean((x @ state["w"] - y) ** 2)}

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = rng.normal(size=(64,)).astype(np.float32)
    state = {"w": jnp.zeros(4)}
    return step, state, x, y


def test_run_step_trainer_goodput_integration():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _blob_problem()
    reg = MetricsRegistry()
    tracer = TraceRecorder(registry=reg)
    tracker = GoodputTracker(
        registry=reg, tracer=tracer, flight=FlightRecorder()
    )
    run_step_trainer(
        step_fn=step, state=state, features=x, targets=y, num_epochs=2,
        batch_size=16, donate_state=False, registry=reg, goodput=tracker,
    )
    rep = tracker.report()
    assert rep["steps"] == 8
    assert rep["goodput_s"] > 0
    # the loop's wall time is essentially fully classified
    assert rep["attributed_fraction"] >= 0.9
    # the compile of the jitted step was detected and attributed
    assert rep["badput_s"]["compile"] > 0
    snap = reg.snapshot()
    assert 0.0 < snap["unionml_train_goodput_ratio"][""] <= 1.0
    # per-phase spans export like any request timeline
    jsonl = tracer.export_jsonl()
    assert '"kind": "trainer"' in jsonl and '"name": "compute"' in jsonl


def test_run_step_trainer_goodput_true_uses_shared_registry():
    from unionml_tpu import telemetry
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _blob_problem()
    reg = MetricsRegistry()
    before = telemetry.get_tracer()._all_requests()
    run_step_trainer(
        step_fn=step, state=state, features=x, targets=y,
        batch_size=16, donate_state=False, registry=reg, goodput=True,
    )
    snap = reg.snapshot()
    # goodput=True builds a tracker over the trainer's registry
    assert snap["unionml_train_goodput_seconds_total"][""] > 0
    # ... and its timeline landed on the process-global tracer
    after = telemetry.get_tracer()._all_requests()
    assert len(after) == len(before) + 1


def test_trainer_finishes_tracker_on_raising_stream():
    from unionml_tpu.execution import run_step_trainer

    step, state, _, _ = _blob_problem()
    reg = MetricsRegistry()
    tracer = TraceRecorder(registry=reg)
    tracker = GoodputTracker(
        registry=reg, tracer=tracer, flight=FlightRecorder()
    )

    def broken_stream():
        rng = np.random.default_rng(0)
        for _ in range(2):
            x = rng.normal(size=(16, 4)).astype(np.float32)
            yield x, rng.normal(size=(16,)).astype(np.float32)
        raise RuntimeError("loader died")

    with pytest.raises(RuntimeError, match="loader died"):
        run_step_trainer(
            step_fn=step, state=state, features=broken_stream(),
            donate_state=False, registry=reg, goodput=tracker,
        )
    # the timeline was finished (exported, not stuck live) and the wall
    # span froze — a retry with the same tracker excludes the gap
    assert not tracer._live
    assert tracker._t_stop is not None


def test_measure_device_time_samples_every_step():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _blob_problem()
    reg = MetricsRegistry()
    run_step_trainer(
        step_fn=step, state=state, features=x, targets=y,
        batch_size=16, donate_state=False, registry=reg,
        measure_device_time=True,
    )
    hist = reg.snapshot()["unionml_trainer_step_ms"][""]
    assert hist["count"] == 4  # one synced sample per step


def test_prefetch_phases_preserve_stream():
    from unionml_tpu.data.pipeline import prefetch_to_device

    clock = FakeClock()
    tracker, _ = make_tracker(clock)
    tracker.start()
    batches = [np.full((2, 2), float(i)) for i in range(5)]

    def slow_source():
        for b in batches:
            clock.advance(0.25)  # host starvation per batch
            yield b

    out = list(
        prefetch_to_device(slow_source(), goodput=tracker)
    )
    assert len(out) == 5
    for got, want in zip(out, batches):
        np.testing.assert_array_equal(np.asarray(got), want)
    rep = tracker.report()
    assert rep["badput_s"]["data_wait"] == pytest.approx(1.25)


# ------------------------------------------------ checkpoint instrumentation


def test_pytree_io_publishes_checkpoint_metrics():
    from unionml_tpu import telemetry
    from unionml_tpu.checkpoint import load_pytree, save_pytree

    reg = telemetry.get_registry()
    before = reg.snapshot().get("unionml_checkpoint_save_bytes_total", {})
    before_bytes = before.get("kind=pytree", 0.0)
    tree = {"w": np.arange(16, dtype=np.float32)}
    buf = io.BytesIO()
    save_pytree(tree, {"lr": 0.1}, buf)
    buf.seek(0)
    out = load_pytree(buf, lambda hp: {"w": np.zeros(16, np.float32)})
    np.testing.assert_array_equal(out["w"], tree["w"])
    snap = reg.snapshot()
    assert snap["unionml_checkpoint_save_bytes_total"]["kind=pytree"] > (
        before_bytes
    )
    assert snap["unionml_checkpoint_save_ms"]["kind=pytree"]["count"] >= 1
    assert snap["unionml_checkpoint_restore_ms"]["kind=pytree"]["count"] >= 1
    assert snap["unionml_checkpoint_restore_bytes_total"]["kind=pytree"] > 0


def test_checkpoint_manager_publishes_metrics(tmp_path):
    import jax.numpy as jnp

    from unionml_tpu.checkpoint.sharded import CheckpointManager

    reg = MetricsRegistry()
    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    with CheckpointManager(tmp_path, registry=reg) as manager:
        manager.save(1, state)
        manager.wait()
        restored = manager.restore(state)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8))
    snap = reg.snapshot()
    assert snap["unionml_checkpoint_save_ms"]["kind=sharded"]["count"] == 1
    assert snap["unionml_checkpoint_save_bytes_total"]["kind=sharded"] == 32.0
    assert snap["unionml_checkpoint_restore_ms"]["kind=sharded"]["count"] == 1
    assert snap["unionml_checkpoint_restore_bytes_total"]["kind=sharded"] == (
        32.0
    )


# --------------------------------------------- elastic trainer preemption


def test_elastic_preemption_replay_lands_in_preemption_bucket(tmp_path):
    import jax.numpy as jnp

    from unionml_tpu.elastic import Preemption, run_elastic_trainer

    def step(state, batch):
        x, y = batch
        w = state["w"] - 0.01 * x.T @ (x @ state["w"] - y)
        return {"w": w}, {}

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(10):
        x = rng.normal(size=(8, 4)).astype(np.float32)
        batches.append((x, rng.normal(size=(8,)).astype(np.float32)))

    def replayable():
        yield from batches

    def bomb(global_step):
        if global_step == 5:
            raise Preemption("simulated")

    with pytest.raises(Preemption):
        run_elastic_trainer(
            step_fn=step, state={"w": jnp.zeros(4)}, stream=replayable,
            checkpoint_dir=str(tmp_path), checkpoint_every=3,
            fault_hook=bomb, goodput=True,
        )

    reg = MetricsRegistry()
    tracker = GoodputTracker(
        registry=reg, tracer=TraceRecorder(registry=reg),
        flight=FlightRecorder(),
    )
    _, steps = run_elastic_trainer(
        step_fn=step, state={"w": jnp.zeros(4)}, stream=replayable,
        checkpoint_dir=str(tmp_path), checkpoint_every=3, goodput=tracker,
    )
    assert steps == 10
    rep = tracker.report()
    # restore + replaying the 3 consumed batches is preemption badput
    assert rep["badput_s"]["preemption"] > 0
    # the periodic saves are checkpoint badput
    assert rep["badput_s"]["checkpoint"] > 0
    assert rep["goodput_s"] > 0
    assert rep["attributed_fraction"] >= 0.9


def test_elastic_array_path_goodput(tmp_path):
    import jax.numpy as jnp

    from unionml_tpu.elastic import run_elastic_trainer

    def step(state, batch):
        x, y = batch
        w = state["w"] - 0.01 * x.T @ (x @ state["w"] - y)
        return {"w": w}, {}

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = rng.normal(size=(64,)).astype(np.float32)
    reg = MetricsRegistry()
    tracker = GoodputTracker(
        registry=reg, tracer=TraceRecorder(registry=reg),
        flight=FlightRecorder(),
    )
    _, steps = run_elastic_trainer(
        step_fn=step, state={"w": jnp.zeros(4)}, arrays=[x, y],
        checkpoint_dir=str(tmp_path), batch_size=16, checkpoint_every=2,
        goodput=tracker,
    )
    assert steps == 4
    rep = tracker.report()
    assert rep["badput_s"]["checkpoint"] > 0
    assert rep["badput_s"]["preemption"] == 0.0
    assert rep["goodput_s"] > 0
    # the checkpoint I/O series the badput bucket is attributed from
    # land in the SAME registry as the goodput series, not the global
    # one — the manager is constructed with the tracker's registry.
    # The elastic trainer defaults to the async writer single-process:
    # save_ms{kind=async} is the caller stall, commit_ms the background
    # leg (docs/observability.md "Checkpoint I/O")
    snap = reg.snapshot()
    assert snap["unionml_checkpoint_save_ms"]["kind=async"]["count"] >= 2
    assert snap["unionml_checkpoint_commit_ms"]["kind=async"]["count"] >= 2


# -------------------------------------------------------- SLO coupling


def test_goodput_collapse_breaches_gauge_objective():
    from unionml_tpu.slo import GaugeObjective, SloWatchdog

    clock = FakeClock()
    tracker, reg = make_tracker(clock)
    watchdog = SloWatchdog(
        [GaugeObjective(
            "train_goodput", "unionml_train_goodput_ratio", min_value=0.5,
        )],
        registry=reg, fast_window_s=10.0, slow_window_s=10.0,
    )
    tracker.start()
    with tracker.phase("compute"):
        clock.advance(9.0)
    with tracker.phase("data_wait"):
        clock.advance(1.0)
    tracker.step_complete(1.0)  # publishes ratio = 0.9
    report = watchdog.evaluate(now=1000.0)
    assert not report["breached"]

    with tracker.phase("data_wait"):
        clock.advance(90.0)  # input starvation: goodput collapses to 0.09
    tracker.step_complete(90.0)
    # one fast window later the healthy sample has aged out
    report = watchdog.evaluate(now=1015.0)
    assert report["breached"] == ["train_goodput"]
    snap = reg.snapshot()
    assert snap["unionml_slo_breached"]["objective=train_goodput"] == 1.0
