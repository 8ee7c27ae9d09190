"""The program's host spans, read from the profiler's own trace.

What a traced run holds beside the device planes (looked at by hand, PR 25,
on a CPU session and on ``tests/data/tiny_annotated_v5e.xplane.pb``): the
plane ``/host:CPU`` has one line per host thread, named by the OS (on the
chip every Python thread's line reads ``python3``, the engine's two among
them; the runtime's own threads have lines without a name), and
every ``jax.profiler.TraceAnnotation`` the program opened while the session
ran is one event on its thread's line: the name the program gave it
(``engine.pass``, ``engine.admit.enqueue``, ``train.step`` ...), a start and
a duration in nanoseconds from the same origin as the device planes, and
its metadata as ``stats`` (``rid``, ``seq``, ``reason`` ...). With the
Python tracer on, the same lines also hold its calls (``$file.py:12 f``);
annotations are told apart by their prefix. A thread is found by the spans
on it, never by its name.

Two clocks meet here. Device runs and annotations are on the trace's clock;
the program's per-request spans (``TraceRecorder``) and the client's times
are ``time.perf_counter()`` seconds. An annotation that the program also
recorded as a request's span (``engine.admit`` = ``admit``,
``engine.admit.enqueue`` = ``admit.enqueue``, matched by request id and
order) gives one (perf_counter, trace) pair of starts; the offset between
the clocks is the median over the pairs, and their spread says how good the
join is.
"""

from __future__ import annotations

import functools
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import xplane
from chipbench.yardstick import percentile

PREFIXES = ("engine.", "train.")

# annotation name -> the name of its twin in a request's recorded timeline
TWINS = {"engine.admit": "admit", "engine.admit.enqueue": "admit.enqueue"}


@dataclass(frozen=True)
class HostEvent:
    name: str
    start_s: float
    end_s: float
    line: int  # index of its thread's line in the host plane
    stats: dict = field(compare=False, hash=False, default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


class HostSpans:
    """The annotation events of one trace, by name, each list in start order."""

    def __init__(self, events: Sequence[HostEvent]):
        self.by_name: Dict[str, List[HostEvent]] = {}
        for ev in sorted(events, key=lambda e: e.start_s):
            self.by_name.setdefault(ev.name, []).append(ev)

    def named(self, name: str) -> List[HostEvent]:
        return self.by_name.get(name, [])

    def __bool__(self) -> bool:
        return bool(self.by_name)


@functools.lru_cache(maxsize=2)
def load(path: str) -> HostSpans:
    from jax.profiler import ProfileData

    events: List[HostEvent] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIXES):
                    events.append(HostEvent(
                        name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, i,
                        dict(ev.stats),
                    ))
    return HostSpans(events)


def of_run(run) -> Optional[HostSpans]:
    """The annotations of a traced run, or ``None``: no trace, or a trace of
    a program that opens none (the parent of PR 25)."""
    trace_dir = run.record.get("trace_dir")
    path = xplane.find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    spans = load(path)
    return spans if spans else None


# ---- the two clocks


def clock_pairs(spans: HostSpans, timelines: Sequence[tuple]) -> List[Tuple[float, float]]:
    """(perf_counter start, trace start) of every annotation that a request's
    timeline also holds: same request id, same name, k-th with k-th."""
    by_rid: Dict[Tuple[str, str], List[HostEvent]] = {}
    for ann, twin in TWINS.items():
        for ev in spans.named(ann):
            by_rid.setdefault((ev.stats.get("rid"), twin), []).append(ev)
    pairs: List[Tuple[float, float]] = []
    for rid, _meta, recorded in timelines:
        for twin in TWINS.values():
            anns = by_rid.get((rid, twin))
            if not anns:
                continue
            mine = sorted(s["start_s"] for s in recorded if s["name"] == twin)
            if len(mine) == len(anns):  # a request cut by the trace's edge pairs nothing
                pairs.extend(zip(mine, (ev.start_s for ev in anns)))
    return pairs


def clock_offset(pairs: Sequence[Tuple[float, float]]) -> Optional[dict]:
    """``perf_counter = trace + offset_s``: the median over the pairs, with
    their spread (5th to 95th percentile of the pairs' own offsets) and the
    widest deviation from the median."""
    if not pairs:
        return None
    diffs = [p - t for p, t in pairs]
    mid = statistics.median(diffs)
    return {
        "offset_s": mid, "pairs": len(diffs),
        "spread_s": percentile(diffs, 95) - percentile(diffs, 5),
        "worst_s": max(abs(d - mid) for d in diffs),
    }


def recorded_spans(run) -> Dict[str, Dict[str, dict]]:
    """{request id: {span name: span}} of the engine's own recorded timelines
    (a name recorded more than once keeps its last span)."""
    return {rid: {s["name"]: s for s in spans} for rid, _meta, spans in run.record.get("timelines") or []}


def offset_of_run(run) -> Optional[dict]:
    spans = of_run(run)
    if spans is None:
        return None
    return clock_offset(clock_pairs(spans, run.record.get("timelines") or []))


# ---- an enqueue and the device run it started


def pair_in_order(starts: Sequence[float], runs: Sequence[Tuple[float, float]],
                  read_back: Optional[Sequence[Optional[float]]] = None) -> List[Optional[int]]:
    """For each enqueue start (ascending), the index of the device run it
    started, or ``None``. One thread enqueues and the device runs in order,
    so the k-th traced enqueue started run ``k + c``: ``c`` counts the runs
    at the head of the trace whose enqueues came before it began.

    Without ``read_back`` the rule is the plain one: ``c`` is the least
    shift that lets every run start after its own enqueue began (the first
    enqueue takes the first run that starts after it). That is too early by
    one for every request when a run enqueued before the trace starts after
    the first traced enqueue, which a deep in-flight queue makes likely (seen
    on the chip, PR 25: harvest lag 233 ms where 2 ms is true). ``read_back``
    (for each enqueue the time its request's first token had been read back,
    where the trace holds it) settles that: no run can end after its own
    readback, so ``c`` grows as long as that still holds for every pair, and
    the largest such ``c`` leaves each readback just behind its run."""
    n = len(starts)

    def fits(c: int) -> bool:
        return all(runs[k + c][0] >= starts[k] for k in range(n) if k + c < len(runs))

    c = 0
    while c < len(runs) and not fits(c):
        c += 1
    if read_back is not None:
        def not_early(shift: int) -> Optional[bool]:
            checked = [(k, rb) for k, rb in enumerate(read_back) if rb is not None and k + shift < len(runs)]
            return all(runs[k + shift][1] <= rb for k, rb in checked) if checked else None

        while not_early(c + 1):
            c += 1
    return [k + c if k + c < len(runs) else None for k in range(n)]


def prefill_pieces(run) -> Optional[List[dict]]:
    """One entry per monolithic prefill enqueued in the traced seconds and
    paired with its ``jit_prefill`` run, all on the trace's clock but
    ``prefill_end_s``: the request's recorded ``prefill`` span's end brought
    over through the clock offset (absent when the offset or the request's
    timeline is)."""
    spans = of_run(run)
    if spans is None or run.trace is None:
        return None
    enqueues = [e for e in spans.named("engine.admit.enqueue") if e.stats.get("program") == "prefill"]
    runs = run.trace.module_runs(r"^jit_prefill\(")
    if not enqueues or not runs:
        return None
    offset = offset_of_run(run)
    recorded = recorded_spans(run)
    admits = spans.named("engine.admit")
    read_back = {
        e.stats.get("req"): e.end_s for e in spans.named("engine.harvest_wait") if e.stats.get("kind") == "prefill"
    }
    pairing = pair_in_order(
        [e.start_s for e in enqueues], runs, [read_back.get(e.stats.get("rid")) for e in enqueues],
    )
    out = []
    for enq, j in zip(enqueues, pairing):
        if j is None:
            continue
        rid = enq.stats.get("rid")
        piece = {
            "rid": rid, "enqueue_s": enq.start_s, "enqueue_len_s": enq.seconds,
            "run_start_s": runs[j][0], "run_end_s": runs[j][1],
            "inflight_wait_s": runs[j][0] - enq.start_s,
        }
        admit = next((a for a in admits if a.stats.get("rid") == rid and a.start_s <= enq.start_s <= a.end_s), None)
        if admit is not None:
            piece["admit_to_enqueue_s"] = enq.start_s - admit.start_s
        mine = recorded.get(rid, {})
        if offset is not None and "prefill" in mine:
            piece["prefill_end_s"] = mine["prefill"]["end_s"] - offset["offset_s"]
            piece["harvest_lag_s"] = piece["prefill_end_s"] - runs[j][1]
        out.append(piece)
    return out


def pairing_share(run) -> Optional[dict]:
    """How many monolithic prefill enqueues the trace holds and how many
    found their run; and, where the request's harvest is in the trace too,
    how many pairs it contradicts: ``early`` = the first token was read back
    before the paired run ended (the pair is too late), ``late`` = it was
    read back only after the next prefill run had ended too (the pair is too
    early, or the harvester lags by a whole admission)."""
    spans = of_run(run)
    pieces = prefill_pieces(run)
    if spans is None or pieces is None:
        return None
    n = sum(1 for e in spans.named("engine.admit.enqueue") if e.stats.get("program") == "prefill")
    read_back = {
        e.stats.get("req"): e.end_s for e in spans.named("engine.harvest_wait") if e.stats.get("kind") == "prefill"
    }
    run_ends = sorted(e for _, e in run.trace.module_runs(r"^jit_prefill\("))
    checked = [p for p in pieces if p["rid"] in read_back]
    early = sum(1 for p in checked if read_back[p["rid"]] < p["run_end_s"])
    late = sum(
        1 for p in checked
        if any(p["run_end_s"] < later <= read_back[p["rid"]] for later in run_ends)
    )
    return {"enqueues": n, "paired": len(pieces), "checked_against_harvest": len(checked), "early": early, "late": late}


# ---- the budget of a request's time to first token


BUDGET_PIECES = ("queue", "admit", "inflight_wait", "prefill_device", "harvest_lag", "http")


def ttft_budget(run) -> Optional[dict]:
    """Medians, in ms, of the pieces of the client's time to first token
    (from the send) over the requests prefilled in the traced seconds, and
    the median of each request's own remainder (medians of pieces do not add
    up; one request's pieces do). The pieces follow one another: ``queue``
    (submit to admission, recorded span), ``admit`` (start of
    ``engine.admit`` to the start of its enqueue), ``inflight_wait`` (to the
    device start of its ``jit_prefill``), ``prefill_device``,
    ``harvest_lag`` (device end to the end of the recorded ``prefill``
    span), ``http`` (what the client saw beyond the engine's own queue +
    prefill)."""
    pieces = prefill_pieces(run)
    if not pieces:
        return None
    # the engine's timeline of a request hangs under the HTTP server's span of it
    http = {meta.get("span_id"): rid for rid, meta, _ in run.record["timelines"] if meta.get("kind") == "http"}
    http_rid = {
        rid: http[meta["parent_span_id"]] for rid, meta, _ in run.record["timelines"]
        if meta.get("kind") != "http" and meta.get("parent_span_id") in http
    }
    recorded = recorded_spans(run)
    client = {r["rid"]: r for r in run.record["records"] if r.get("rid") and r["t_tokens"] and not r["error"]}
    rows = []
    for p in pieces:
        mine, rec = recorded.get(p["rid"], {}), client.get(http_rid.get(p["rid"]))
        if rec is None or "harvest_lag_s" not in p or "admit_to_enqueue_s" not in p or "queue" not in mine:
            continue
        ttft = rec["t_tokens"][0] - rec["sent"]
        row = {
            "queue": mine["queue"]["end_s"] - mine["queue"]["start_s"],
            "admit": p["admit_to_enqueue_s"],
            "inflight_wait": p["inflight_wait_s"],
            "prefill_device": p["run_end_s"] - p["run_start_s"],
            "harvest_lag": p["harvest_lag_s"],
            "http": ttft - (mine["prefill"]["end_s"] - mine["queue"]["start_s"]),
        }
        row["ttft"] = ttft
        row["remainder"] = ttft - sum(row[k] for k in BUDGET_PIECES)
        rows.append(row)
    if not rows:
        return None
    out = {f"{k}_ms_p50": percentile([r[k] for r in rows], 50) * 1e3 for k in BUDGET_PIECES + ("ttft", "remainder")}
    out["remainder_abs_ms_p50"] = percentile([abs(r["remainder"]) for r in rows], 50) * 1e3
    out["requests"] = len(rows)
    return out


def describe(run) -> Optional[dict]:
    """What PERF.md quotes from a traced serving run: the clock join, the
    pairing, the budget, and the counters of the window."""
    if of_run(run) is None:
        return None
    occ = run.record.get("occupancy") or {}
    return {
        "clock": offset_of_run(run), "pairing": pairing_share(run), "ttft_budget_ms": ttft_budget(run),
        "counters": {k: occ.get(k) for k in (
            "ring_passes", "total_passes", "passes", "window_s", "window_dispatched_slot_steps",
            "window_occupied_slot_steps", "starved_slot_steps", "admissions", "prefill_tokens", "polls",
            "dispatcher_s",
        )},
    }
