"""The host-span readers on a small annotated trace recorded on the chip.

``data/tiny_annotated_v5e.xplane.pb`` (``record_tiny_annotated.py``, PR 25): a
few passes of the tiny MoE engine with two slots, four requests submitted
together, then three steps of the tiny ViT, Python tracer off;
``tiny_annotated_v5e.json`` holds the engine's own request timelines and its
perf plane's report of the same seconds. ``tiny_v5e.xplane.pb`` (PR 24) has no
annotation at all, as a trace of the parent has none.
"""

import importlib.util
import json
import shutil
from pathlib import Path

import pytest

from chipbench import hostspans, xplane
from chipbench.run import RunView

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[1]
NEW_READERS = (
    "dispatcher_pass_ms_p50", "dispatcher_enqueue_pct", "admission_starved_slot_pct",
    "prefill_inflight_wait_ms_p50", "harvest_lag_ms_p50", "train_feed_wait_ms",
)


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", ROOT / "layer_metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _view(tmp_path, trace_file, extra):
    """A ``RunView`` over one recorded trace, as a traced run leaves it."""
    shutil.copy(DATA / trace_file, tmp_path / trace_file)
    record = {"trace_dir": str(tmp_path), "records": [], "timelines": [], "occupancy": None, **extra}
    return RunView(record, {}, {}, {})


@pytest.fixture
def annotated(tmp_path):
    return _view(tmp_path, "tiny_annotated_v5e.xplane.pb", json.loads((DATA / "tiny_annotated_v5e.json").read_text()))


def test_every_new_reader_finds_a_number_on_the_annotated_trace(annotated):
    values = {name: _reader(name).read(annotated) for name in NEW_READERS}
    assert all(isinstance(v, float) for v in values.values()), values
    assert 0.0 < values["dispatcher_pass_ms_p50"] < 50.0
    assert 0.0 <= values["dispatcher_enqueue_pct"] <= 100.0
    assert 0.0 < values["admission_starved_slot_pct"] <= 100.0
    assert 0.0 <= values["prefill_inflight_wait_ms_p50"] < 50.0
    assert 0.0 <= values["harvest_lag_ms_p50"] < 50.0
    assert 0.0 < values["train_feed_wait_ms"] < 50.0


def test_every_new_reader_finds_nothing_on_a_trace_without_annotations(tmp_path):
    # the parent's program opens no annotation and its perf plane has no window sums
    old = _view(tmp_path, "tiny_v5e.xplane.pb", {"occupancy": {"occupancy_ratio": 0.5, "ring_passes": 3}})
    assert hostspans.of_run(old) is None
    assert {name: _reader(name).read(old) for name in NEW_READERS} == {name: None for name in NEW_READERS}
    # nor on a run that was not traced at all
    bare = RunView({"trace_dir": None, "records": [], "timelines": [], "occupancy": None}, {}, {}, {})
    assert {name: _reader(name).read(bare) for name in NEW_READERS} == {name: None for name in NEW_READERS}
    assert hostspans.describe(bare) is None and hostspans.ttft_budget(bare) is None


def test_annotations_by_hand_count(annotated):
    spans = hostspans.of_run(annotated)
    counts = {name: len(events) for name, events in spans.by_name.items()}
    # four requests, one admission a pass; six tokens each fit one chunk of eight steps
    assert counts["engine.admit"] == counts["engine.admit.enqueue"] == 4
    assert counts["engine.pass"] == HAND["passes"]
    assert counts["engine.dispatch_chunk"] == counts["engine.dispatch_chunk.enqueue"] == HAND["chunks"]
    # the harvester reads back every prefill and every chunk
    waits = spans.named("engine.harvest_wait")
    assert sum(e.stats["kind"] == "prefill" for e in waits) == 4
    assert sum(e.stats["kind"] == "chunk" for e in waits) == HAND["chunks"]
    assert counts["train.step"] == 3 and counts["train.feed_wait"] == 4
    assert [e.stats["step_num"] for e in spans.named("train.step")] == [0, 1, 2]
    # a thread is found by the spans on it: dispatcher, harvester and the train loop's
    lines = {name: {e.line for e in events} for name, events in spans.by_name.items()}
    dispatcher = lines["engine.pass"]
    assert len(dispatcher) == 1
    for name in ("engine.admit", "engine.admit.enqueue", "engine.dispatch_chunk", "engine.poll"):
        assert lines[name] == dispatcher
    assert lines["engine.harvest_wait"] == lines["engine.harvest_process"] != dispatcher
    assert lines["train.step"] == lines["train.feed_wait"]
    assert lines["train.step"].isdisjoint(dispatcher | lines["engine.harvest_wait"])
    # every nested span lies inside its parent
    for enq in spans.named("engine.admit.enqueue"):
        (admit,) = [a for a in spans.named("engine.admit") if a.stats["rid"] == enq.stats["rid"]]
        assert admit.start_s <= enq.start_s and enq.end_s <= admit.end_s
        assert any(p.start_s <= admit.start_s and admit.end_s <= p.end_s for p in spans.named("engine.pass"))


def test_the_clocks_join(annotated):
    spans = hostspans.of_run(annotated)
    pairs = hostspans.clock_pairs(spans, annotated.record["timelines"])
    assert len(pairs) == 8  # admit and admit.enqueue of four requests
    clock = hostspans.clock_offset(pairs)
    assert clock["pairs"] == 8
    assert clock["spread_s"] < 1e-4 and clock["worst_s"] < 1e-4
    # the recorded admit span and its annotation are one span: same length through the offset
    recorded = {rid: {s["name"]: s for s in recorded} for rid, _meta, recorded in annotated.record["timelines"]}
    for ann in spans.named("engine.admit"):
        mine = recorded[ann.stats["rid"]]["admit"]
        assert mine["start_s"] - clock["offset_s"] == pytest.approx(ann.start_s, abs=1e-4)
        assert mine["end_s"] - clock["offset_s"] == pytest.approx(ann.end_s, abs=1e-4)


def test_the_pairing_rule():
    # by hand: runs 0 and 1 were enqueued before the trace began, but only run 1 starts after
    # the first traced enqueue; the plain rule gives the first enqueue the first run that starts after it
    runs = [(0.5, 1.0), (2.0, 2.5), (3.0, 3.5), (6.0, 6.5)]
    assert hostspans.pair_in_order([1.2, 1.4, 5.0, 7.0], runs) == [1, 2, 3, None]
    assert hostspans.pair_in_order([], runs) == []
    assert hostspans.pair_in_order([0.1], []) == [None]
    # the shift is one for all: a run that would start before its own enqueue began moves every pair
    assert hostspans.pair_in_order([0.1, 2.1], runs) == [1, 2]


def test_the_readback_settles_a_run_enqueued_before_the_trace():
    # by hand (the case seen on the chip): run 1 was enqueued before the trace began but starts after
    # the first traced enqueue (1.2). The plain rule pairs every enqueue one run too early...
    runs = [(0.5, 1.0), (2.0, 2.5), (3.0, 3.5), (6.0, 6.5), (8.0, 8.5)]
    starts = [1.2, 2.6, 5.0]
    assert hostspans.pair_in_order(starts, runs) == [1, 2, 3]
    # ...but the first tokens were read back just after runs 2, 3, 4 ended, and no run ends after its
    # own readback: the shift grows by one and stops there (run 3 ends after the first readback)
    assert hostspans.pair_in_order(starts, runs, [3.51, 6.52, 8.51]) == [2, 3, 4]
    # a readback the trace does not hold constrains nothing; with none at all the plain rule stands
    assert hostspans.pair_in_order(starts, runs, [3.51, None, None]) == [2, 3, 4]
    assert hostspans.pair_in_order(starts, runs, [None, None, None]) == [1, 2, 3]
    # a true pairing is left alone: readbacks just behind runs 1, 2, 3
    assert hostspans.pair_in_order(starts, runs, [2.51, 3.52, 6.51]) == [1, 2, 3]
    # and a run past the trace's end pairs nothing
    assert hostspans.pair_in_order([1.2, 2.6, 5.0, 7.0], runs, [3.51, 6.52, 8.51, None]) == [2, 3, 4, None]


def test_each_enqueue_finds_its_prefill_run(annotated, monkeypatch):
    runs = annotated.trace.module_runs(r"^jit_prefill\(")
    chunks = annotated.trace.module_runs(r"^jit_decode_chunk\(")
    assert len(runs) == 4 and len(chunks) == HAND["chunks"]
    pieces = hostspans.prefill_pieces(annotated)
    assert [p["rid"] for p in pieces] == [e.stats["rid"] for e in hostspans.of_run(annotated).named("engine.admit.enqueue")]
    # by hand on this trace: nothing was in flight when it began, so the k-th enqueue started the k-th run
    assert [(p["run_start_s"], p["run_end_s"]) for p in pieces] == runs
    for p in pieces:
        assert 0.0 < p["inflight_wait_s"] < 0.05      # the run starts after its enqueue began
        assert 0.0 <= p["harvest_lag_s"] < 0.05       # and is read back after it ended
        assert p["admit_to_enqueue_s"] >= 0.0
    share = hostspans.pairing_share(annotated)
    assert share == {"enqueues": 4, "paired": 4, "checked_against_harvest": 4, "early": 0, "late": 0}
    # a pairing shifted by one run is seen from the harvester's side
    shifted = [dict(p, run_end_s=runs[max(i - 1, 0)][1]) for i, p in enumerate(pieces)]
    monkeypatch.setattr(hostspans, "prefill_pieces", lambda run: shifted)
    assert hostspans.pairing_share(annotated)["late"] == 3
    # the device ran what the dispatcher enqueued, in its order: each chunk after the prefills before it
    assert sorted(runs + chunks) == sorted(runs + chunks, key=lambda r: r[1])


# counted by hand in chiprun_out/pr25/rec.log and dump_trace.py's listing of the recorded file
HAND = {"passes": 4, "chunks": 4}
