"""Unified telemetry layer: registry thread-safety, histogram bucket
math, Prometheus exposition, trace-span export, and the tier-1-safe
``/metrics`` smoke over a ServingApp with a stub predictor (no TPU,
``JAX_PLATFORMS=cpu`` — the CI scrape check)."""

import json
import re
import threading
import time

import numpy as np
import pytest

from unionml_tpu import telemetry
from unionml_tpu.telemetry import MetricsRegistry, TraceRecorder

# measured sub-minute module: part of the `-m quick` tier
pytestmark = pytest.mark.quick


# ----------------------------------------------------------------- registry


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", ("route",))
    c.labels(route="/predict").inc()
    c.labels("/predict").inc(2)
    assert c.labels(route="/predict").value == 3
    with pytest.raises(ValueError):
        c.labels(route="/x").inc(-1)  # counters only go up

    g = reg.gauge("depth", "queue depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3
    g.set_function(lambda: 99)
    assert g.value == 99

    # same name + schema returns the same family; a changed schema raises
    assert reg.counter("req_total", "requests", ("route",)) is c
    with pytest.raises(ValueError):
        reg.counter("req_total", "requests", ("other",))
    with pytest.raises(ValueError):
        reg.gauge("req_total", "now a gauge", ("route",))


def test_registry_thread_safety_under_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("n_total", "count")
    h = reg.histogram("v_ms", "values")
    n_threads, per_thread = 8, 2000

    def work():
        for i in range(per_thread):
            c.inc()
            h.observe(float(i % 50))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # exact totals: no lost updates
    assert c.value == n_threads * per_thread
    assert h.count == n_threads * per_thread
    assert h.buckets()[-1][1] == n_threads * per_thread  # +Inf cumulative


def test_histogram_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 99.0, 1000.0):
        h.observe(v)
    cum = dict(h.buckets())
    # le is inclusive: the observation at exactly 1.0 lands in le="1"
    assert cum[1.0] == 2
    assert cum[10.0] == 3
    assert cum[100.0] == 4
    assert cum[float("inf")] == 5
    assert h.count == 5 and h.sum == pytest.approx(1105.5)
    s = h.summary()
    assert s["n"] == 5 and s["p50"] == 5.0
    assert s["p99"] >= s["p95"] >= s["p50"]
    h.reset()
    assert h.count == 0 and h.summary() == {}


def test_default_ms_buckets_are_log_spaced_and_sorted():
    b = telemetry.DEFAULT_MS_BUCKETS
    assert list(b) == sorted(b)
    # log-spaced: each decade is covered by a bounded ratio step
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert max(ratios) <= 5.0 and min(ratios) >= 1.5


def test_histogram_window_is_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("w_ms", "window").labels()
    for i in range(h.WINDOW_CAP + 100):
        h.observe(float(i))
    assert len(h._window) <= h.WINDOW_CAP
    assert h.count == h.WINDOW_CAP + 100  # buckets never forget


# ------------------------------------------------------------- exposition


def parse_prometheus_text(text: str) -> dict:
    """Minimal exposition parser: {family: {"type": ..., "samples":
    [(name, labels_dict, value)]}}. Raises on malformed lines — the
    validation the CI smoke check leans on."""
    families: dict = {}
    current = None
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? ([^ ]+)$"
    )
    label_re = re.compile(r'^([a-zA-Z_][a-zA-Z0-9_]*)="(.*)"$')
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            current = line.split(" ", 3)[2]
            families.setdefault(current, {"type": None, "samples": []})
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name == current, f"TYPE line out of order: {line!r}"
            families[name]["type"] = kind
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        m = sample_re.match(line)
        assert m, f"malformed sample line {line!r}"
        name, _, labelstr, value = m.groups()
        labels = {}
        if labelstr:
            for pair in re.split(r",(?=[a-zA-Z_])", labelstr):
                lm = label_re.match(pair)
                assert lm, f"malformed label {pair!r} in {line!r}"
                labels[lm.group(1)] = re.sub(
                    r"\\(.)",
                    lambda e: {"n": "\n"}.get(e.group(1), e.group(1)),
                    lm.group(2),
                )
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        family = families.get(base) or families.get(name)
        assert family is not None, f"sample {name!r} without HELP/TYPE"
        float(value.replace("+Inf", "inf"))  # value must parse
        family["samples"].append((name, labels, value))
    return families


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("a_total", "with \"quotes\" and\nnewline", ("k",)).labels(
        k='va"l\\ue'
    ).inc(3)
    reg.gauge("b_gauge", "plain").set(2.5)
    reg.histogram("c_ms", "hist", buckets=(1.0, 10.0)).observe(4.0)
    text = reg.exposition()
    fams = parse_prometheus_text(text)
    assert fams["a_total"]["type"] == "counter"
    assert fams["b_gauge"]["type"] == "gauge"
    assert fams["c_ms"]["type"] == "histogram"
    # histogram renders cumulative buckets + sum + count, +Inf last
    names = [s[0] for s in fams["c_ms"]["samples"]]
    assert names.count("c_ms_bucket") == 3  # 1, 10, +Inf
    assert "c_ms_sum" in names and "c_ms_count" in names
    inf_rows = [
        s for s in fams["c_ms"]["samples"]
        if s[0] == "c_ms_bucket" and s[1]["le"] == "+Inf"
    ]
    assert inf_rows and inf_rows[0][2] == "1"
    # label escaping round-trips
    (name, labels, value), = fams["a_total"]["samples"]
    assert labels["k"] == 'va"l\\ue' and value == "3"


def test_instance_labels_are_unique():
    a, b = telemetry.instance_label("x"), telemetry.instance_label("x")
    assert a != b and a.startswith("x-")


# ------------------------------------------------------------ trace spans


def test_trace_span_export_round_trip():
    tr = TraceRecorder()
    rid = tr.new_request("generate")
    tr.record_span(rid, "queue", 1.000, 1.010)
    tr.record_span(rid, "prefill", 1.010, 1.050, tokens=1)
    with tr.span(rid, "decode-chunk[0]", tokens=8):
        pass
    tr.finish_request(rid)

    chrome = tr.export_chrome()
    # must be valid JSON that Perfetto/chrome://tracing accepts
    parsed = json.loads(json.dumps(chrome))
    assert parsed["displayTimeUnit"] == "ms"
    events = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in events][:2] == ["queue", "prefill"]
    for e in events:
        assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
        assert e["args"]["request_id"] == rid
        assert {"pid", "tid", "cat"} <= set(e)
    queue_ev = events[0]
    assert queue_ev["ts"] == pytest.approx(1.000 * 1e6)
    assert queue_ev["dur"] == pytest.approx(0.010 * 1e6, rel=1e-6)

    lines = tr.export_jsonl().strip().splitlines()
    records = [json.loads(line) for line in lines]
    assert len(records) == 3
    assert records[1]["name"] == "prefill" and records[1]["tokens"] == 1
    assert all(r["request_id"] == rid for r in records)

    # late span for a finished request is ignored, not an error
    tr.record_span(rid, "ghost", 2.0, 3.0)
    assert len(json.loads(json.dumps(tr.export_chrome()))["traceEvents"]) == 4


def test_trace_recorder_bounds_completed_ring():
    tr = TraceRecorder(max_requests=3)
    for i in range(6):
        rid = tr.new_request("r")
        tr.record_span(rid, "s", 0.0, 1.0)
        tr.finish_request(rid)
    assert len(tr._done) == 3


def test_engine_request_spans_reach_tracer(tiny_llama_engine):
    """A served request's spans follow queue → admit (around its
    admit.enqueue) → prefill → decode-chunk[i] → harvest, and the Chrome
    export is structurally Perfetto-valid."""
    engine, params, tracer = tiny_llama_engine
    engine.generate(params, [[1, 2, 3]])
    chrome = json.loads(json.dumps(tracer.export_chrome()))
    names = [e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"]
    # the export orders by start: admit opens inside the queue wait's
    # last microseconds, prefill starts where queue ends
    assert names[0] == "queue"
    assert set(names[1:4]) == {"admit", "prefill", "admit.enqueue"}
    assert any(n.startswith("decode-chunk[") for n in names)
    assert names[-1] == "harvest"


@pytest.fixture
def tiny_llama_engine():
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import Llama, LlamaConfig
    from unionml_tpu.serving.engine import DecodeEngine

    cfg = LlamaConfig.tiny(vocab_size=61)
    module = Llama(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    tracer = TraceRecorder()
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=6, prompt_buckets=(8,),
        chunk_steps=2, registry=MetricsRegistry(), tracer=tracer,
    )
    try:
        yield engine, params, tracer
    finally:
        engine.close()


# -------------------------------------------------- layer integration


def test_engine_metrics_in_registry(tiny_llama_engine):
    """The engine's stats() is a thin view over its registry series."""
    engine, params, _ = tiny_llama_engine
    engine.generate(params, [[1, 2, 3], [4, 5, 6]])
    text = engine._registry.exposition()
    fams = parse_prometheus_text(text)
    for name in (
        "unionml_engine_requests_total",
        "unionml_engine_decode_steps_total",
        "unionml_engine_slots_in_use",
        "unionml_engine_queue_wait_ms",
        "unionml_engine_prefill_ms",
        "unionml_engine_chunk_dispatch_ms",
        "unionml_engine_chunk_harvest_ms",
    ):
        assert name in fams, name
    sample = fams["unionml_engine_requests_total"]["samples"][0]
    assert sample[1]["engine"].startswith("engine-") and sample[2] == "2"
    assert engine.stats()["completed_requests"] == 2
    engine.reset_stats()
    assert engine.stats()["completed_requests"] == 0


def test_batcher_metrics_in_registry():
    from unionml_tpu.serving.batcher import MicroBatcher

    reg = MetricsRegistry()
    batcher = MicroBatcher(
        lambda f: f.sum(axis=1), max_batch_size=8, max_wait_ms=5.0,
        registry=reg,
    )
    try:
        batcher.submit(np.ones((2, 3)))
        fams = parse_prometheus_text(reg.exposition())
        for name in (
            "unionml_batcher_requests_total",
            "unionml_batcher_batches_total",
            "unionml_batcher_batch_rows",
            "unionml_batcher_queue_wait_ms",
            "unionml_batcher_device_ms",
            "unionml_batcher_abandoned_total",
        ):
            assert name in fams, name
        s = batcher.stats()
        assert s["completed_requests"] == 1 and s["batches"] == 1
    finally:
        batcher.close()


def test_batcher_abandoned_submit_skipped_at_drain():
    """A submit() that times out while queued is marked abandoned: the
    worker never burns a device call on it and counts it."""
    import time

    from unionml_tpu.serving.batcher import MicroBatcher

    calls = []

    def slow(feats):
        calls.append(feats.shape[0])
        time.sleep(0.4)
        return feats

    reg = MetricsRegistry()
    batcher = MicroBatcher(
        slow, max_batch_size=1, max_wait_ms=1.0, registry=reg
    )
    try:
        # req1 occupies the worker; req2 times out while still queued
        t1 = threading.Thread(
            target=lambda: batcher.submit(np.ones((1, 2)), timeout=10)
        )
        t1.start()
        time.sleep(0.1)
        with pytest.raises(TimeoutError):
            batcher.submit(np.full((1, 2), 2.0), timeout=0.05)
        t1.join()
        batcher.submit(np.full((1, 2), 3.0), timeout=10)
        assert batcher._m_abandoned.value == 1
        assert len(calls) == 2  # the abandoned request never ran
        assert "abandoned" not in str(calls)
    finally:
        batcher.close()


def test_trainer_publishes_through_registry():
    import jax.numpy as jnp

    from unionml_tpu.execution import run_step_trainer

    reg = MetricsRegistry()

    def step(state, batch):
        x, y = batch
        return state, {"loss": jnp.mean((x.sum(axis=1) - y) ** 2)}

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = rng.normal(size=(64,)).astype(np.float32)
    run_step_trainer(
        step_fn=step, state={"w": jnp.zeros(4)}, features=x, targets=y,
        num_epochs=5, batch_size=4, donate_state=False, registry=reg,
    )
    fams = parse_prometheus_text(reg.exposition())
    assert "unionml_trainer_step_ms" in fams
    assert "unionml_trainer_steps_total" in fams
    steps_sample = fams["unionml_trainer_steps_total"]["samples"][0]
    assert float(steps_sample[2]) == 80  # 5 epochs * 16 batches
    # loss gauge was published at a window boundary (window=50 < 80)
    assert "unionml_trainer_loss" in fams
    assert "unionml_trainer_samples_per_sec" in fams


# ------------------------------------------------------ /metrics smoke


def validate_exposition_strict(text: str) -> dict:
    """Line-by-line exposition-format validation (beyond the substring
    checks this module started with): HELP precedes TYPE precedes
    samples for every family, no family appears twice, labels parse
    with escaping, every value parses, and histogram series are
    internally consistent per labelset — cumulative bucket counts
    nondecreasing, ``+Inf`` last and equal to ``_count``, ``_sum``
    present. Returns the parsed families."""
    families = parse_prometheus_text(text)  # raises on malformed lines
    seen_help = []
    state: dict = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert name not in seen_help, f"family {name} repeated"
            seen_help.append(name)
            state[name] = "help"
            continue
        if line.startswith("# TYPE "):
            name = line.split(" ", 3)[2]
            assert state.get(name) == "help", f"TYPE before HELP: {line!r}"
            state[name] = "type"
            continue
        base = re.sub(r"_(bucket|sum|count)$", "", line.split("{")[0].split(" ")[0])
        owner = base if base in state else line.split("{")[0].split(" ")[0]
        assert state.get(owner) == "type", f"sample before TYPE: {line!r}"
    for name, fam in families.items():
        if fam["type"] != "histogram":
            continue
        # group by labelset minus 'le'
        series: dict = {}
        for sample, labels, value in fam["samples"]:
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            ))
            series.setdefault(key, {})[
                (sample, labels.get("le"))
            ] = float(value.replace("+Inf", "inf"))
        for key, samples in series.items():
            buckets = [
                (float(le.replace("+Inf", "inf")), v)
                for (s, le), v in samples.items()
                if s == f"{name}_bucket"
            ]
            assert buckets, f"{name}{key}: no buckets"
            buckets.sort()
            counts = [v for _, v in buckets]
            assert counts == sorted(counts), (
                f"{name}{key}: non-monotonic buckets {counts}"
            )
            assert buckets[-1][0] == float("inf"), f"{name}{key}: no +Inf"
            count = samples.get((f"{name}_count", None))
            assert count == buckets[-1][1], (
                f"{name}{key}: _count {count} != +Inf {buckets[-1][1]}"
            )
            assert (f"{name}_sum", None) in samples, f"{name}{key}: no _sum"
    return families


def test_exposition_strict_validation_catches_defects():
    """The validator itself must reject broken expositions, or the
    concurrency smoke below is vacuous."""
    good = "# HELP a_total x\n# TYPE a_total counter\na_total 1\n"
    validate_exposition_strict(good)
    with pytest.raises(AssertionError):  # sample before TYPE
        validate_exposition_strict("# HELP a_total x\na_total 1\n")
    with pytest.raises(AssertionError):  # family repeated
        validate_exposition_strict(good + good)
    with pytest.raises(AssertionError):  # non-monotonic histogram
        validate_exposition_strict(
            "# HELP h_ms x\n# TYPE h_ms histogram\n"
            'h_ms_bucket{le="1"} 5\nh_ms_bucket{le="+Inf"} 3\n'
            "h_ms_sum 1\nh_ms_count 3\n"
        )


def test_metrics_scrape_under_concurrent_traffic():
    """Concurrency smoke: scrape /metrics repeatedly while request
    threads stream predicts, validating the exposition line-by-line
    each time — a torn render (half-updated histogram, interleaved
    family) must never reach a scraper."""
    import urllib.request

    from unionml_tpu import Dataset, Model
    from unionml_tpu.model import ModelArtifact
    from unionml_tpu.serving.http import ServingApp

    dataset = Dataset(name="concurrency_smoke_data", targets=[])

    @dataset.reader
    def reader() -> list:
        return []

    stub = Model(name="concurrency_smoke", init=lambda: {"w": 1}, dataset=dataset)

    @stub.trainer
    def trainer(p: dict, features: list) -> dict:
        return p

    @stub.predictor
    def predictor(p: dict, feats: list) -> list:
        return [float(np.asarray(f).sum()) for f in feats]

    stub.artifact = ModelArtifact({"w": 1}, {}, {})
    app = ServingApp(stub, registry=MetricsRegistry())
    host, port = app.serve(port=0, blocking=False)
    base = f"http://{host}:{port}"
    stop = threading.Event()
    errors: list = []

    def client(i):
        body = json.dumps({"features": [[float(i), 1.0]]}).encode()
        while not stop.is_set():
            try:
                req = urllib.request.Request(
                    f"{base}/predict", data=body,
                    headers={"Content-Type": "application/json"},
                )
                urllib.request.urlopen(req, timeout=30).read()
            except Exception as exc:  # surfaced after the join
                errors.append(f"client: {exc!r}")
                return

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(4)
    ]
    try:
        for t in threads:
            t.start()
        for _ in range(15):
            with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
                text = resp.read().decode()
            fams = validate_exposition_strict(text)
            # the standard process gauges ride every scrape
            assert "process_start_time_seconds" in fams
            assert "unionml_tpu_build_info" in fams
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        app.shutdown()
    assert not errors, errors
    # traffic actually flowed while we scraped
    rows = [
        s for s in fams["unionml_http_requests_total"]["samples"]
        if s[1].get("path") == "/predict"
    ]
    assert rows and float(rows[0][2]) > 0


def test_process_and_build_info_gauges():
    """Satellite: process_start_time_seconds + build_info on the
    default registry (standard Prometheus conventions), and published
    into isolated registries on demand."""
    import time as _time

    text = telemetry.get_registry().exposition()
    row = next(
        line for line in text.splitlines()
        if line.startswith("process_start_time_seconds ")
    )
    start_s = float(row.split(" ", 1)[1])
    assert 0 < start_s <= _time.time()

    import jax  # noqa: F401 — backend label resolves once jax is loaded

    reg = MetricsRegistry()
    telemetry.publish_process_metrics(reg)
    fams = parse_prometheus_text(reg.exposition())
    assert fams["process_start_time_seconds"]["type"] == "gauge"
    sample = fams["unionml_tpu_build_info"]["samples"][0]
    assert set(sample[1]) == {"version", "jax_version", "backend"}
    assert sample[2] == "1"
    # jax is loaded in the test process: the backend label is real
    assert sample[1]["backend"] == "cpu"
    # republishing with the same labels never duplicates the child
    telemetry.publish_process_metrics(reg)
    fams = parse_prometheus_text(reg.exposition())
    live = [
        s for s in fams["unionml_tpu_build_info"]["samples"]
        if s[2] == "1"
    ]
    assert len(live) == 1


def test_percentile_summary_moved_to_telemetry_with_compat_shim():
    """Satellite: percentile_summary lives in telemetry; the old
    serving._stats import keeps working."""
    from unionml_tpu.serving._stats import percentile_summary as compat
    from unionml_tpu.telemetry import percentile_summary

    assert compat is percentile_summary
    s = percentile_summary([3.0, 1.0, 2.0])
    assert s == {"p50": 2.0, "p95": 3.0, "p99": 3.0, "mean": 2.0, "n": 3}
    # StepTimer shares it: summary() carries the full summary dict
    from unionml_tpu.diagnostics import StepTimer

    t = StepTimer(window=2)
    for _ in range(7):
        t.tick(4)
    s = t.summary()
    assert s["samples_per_sec"]["n"] == len(t.rates)
    assert s["samples_per_sec_median"] == s["samples_per_sec"]["p50"]


def test_sliding_samples_quantiles():
    """SlidingSamples (the router's hedge-delay tracker): bounded
    window, nearest-rank percentiles (the repo-wide formula), default
    before any sample, old regimes age out."""
    from unionml_tpu.telemetry import SlidingSamples

    with pytest.raises(ValueError):
        SlidingSamples(maxlen=0)
    s = SlidingSamples(maxlen=4)
    assert s.percentile(0.95, default=1.5) == 1.5
    with pytest.raises(ValueError):
        s.percentile(0.0)
    for v in (10.0, 20.0, 30.0, 40.0):
        s.add(v)
    assert len(s) == 4
    assert s.percentile(0.5) == 20.0      # ceil(0.5*4)-1 = index 1
    assert s.percentile(0.95) == 40.0
    # a new regime pushes the old one out of the bounded window
    for v in (1.0, 1.0, 1.0, 1.0):
        s.add(v)
    assert s.percentile(0.95) == 1.0


def test_metrics_smoke_servingapp_scrape():
    """CI smoke (tier-1-safe, JAX_PLATFORMS=cpu, no TPU): start a
    ServingApp over a stub predictor, scrape GET /metrics on a real
    socket, and validate the exposition parses end to end."""
    import urllib.request

    from unionml_tpu import Dataset, Model
    from unionml_tpu.model import ModelArtifact
    from unionml_tpu.serving.http import ServingApp

    dataset = Dataset(name="metrics_smoke_data", targets=[])

    @dataset.reader
    def reader() -> list:
        return []

    stub = Model(name="metrics_smoke", init=lambda: {"w": 1}, dataset=dataset)

    @stub.trainer
    def trainer(p: dict, features: list) -> dict:
        return p

    @stub.predictor
    def predictor(p: dict, feats: list) -> list:
        return [float(np.asarray(f).sum()) for f in feats]

    stub.artifact = ModelArtifact({"w": 1}, {}, {})
    app = ServingApp(stub, registry=MetricsRegistry())
    host, port = app.serve(port=0, blocking=False)
    base = f"http://{host}:{port}"
    try:
        body = json.dumps({"features": [[1.0, 2.0]]}).encode()
        req = urllib.request.Request(
            f"{base}/predict", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert json.loads(resp.read()) == [3.0]
        # the handler records its request series in a `finally` AFTER
        # the response bytes are flushed, so a scrape racing the
        # /predict handler thread can observe the registry a beat
        # before the sample lands — retry briefly (the race window is
        # microseconds; this bounds the wait, it never masks a missing
        # series)
        predict_rows: list = []
        deadline = time.monotonic() + 5.0
        while True:
            with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
                assert resp.headers["Content-Type"].startswith("text/plain")
                text = resp.read().decode()
            fams = parse_prometheus_text(text)  # raises on malformed lines
            assert fams["unionml_http_requests_total"]["type"] == "counter"
            predict_rows = [
                s for s in fams["unionml_http_requests_total"]["samples"]
                if s[1]["path"] == "/predict"
            ]
            if predict_rows or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert predict_rows and predict_rows[0][1]["status"] == "200"
        assert fams["unionml_http_request_ms"]["type"] == "histogram"
    finally:
        app.shutdown()
