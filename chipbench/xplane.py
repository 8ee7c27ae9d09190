"""Reduction of a JAX profiler trace (``*.xplane.pb``) to numbers.

What a v5e trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per program
execution (``jit_step(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per HLO instruction executed, named by its HLO text
(``%fusion.12 = ...``); ops inside a ``while`` lie inside the ``while``
event on the same line. The plane ``/host:CPU`` has a line ``python`` with
the Python tracer's calls (``$file.py:123 func``). All times are
nanoseconds from one origin. Read with ``jax.profiler.ProfileData`` alone.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")


def op_short_name(hlo_text: str) -> str:
    """``%multiply_reduce_fusion.12 = f32[...] fusion(...)`` -> ``multiply_reduce_fusion``."""
    m = _OP_NAME.match(hlo_text.strip())
    return m.group(1) if m else hlo_text[:40]


@dataclass
class DevicePlane:
    name: str
    modules: List[Tuple[str, float, float]] = field(default_factory=list)  # (name, start_s, end_s)
    ops: List[Tuple[str, float, float]] = field(default_factory=list)      # (hlo text, start_s, end_s)


@dataclass
class Trace:
    devices: List[DevicePlane]
    host_calls: List[Tuple[float, float, str]]  # (start_s, end_s, name), python tracer

    # ---- device busy / idle ------------------------------------------------

    def busy_intervals(self, dev: DevicePlane) -> List[Interval]:
        return union_intervals((s, e) for _, s, e in dev.ops)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips traced."""
        if not self.devices:
            return 0.0
        return sum(total_length(self.busy_intervals(d)) for d in self.devices) / len(self.devices)

    def device_span_s(self) -> float:
        spans = [
            (min(s for _, s, _ in d.ops), max(e for _, _, e in d.ops))
            for d in self.devices if d.ops
        ]
        if not spans:
            return 0.0
        return max(e for _, e in spans) - min(s for s, _ in spans)

    def idle_gaps(self, top: int = 10, min_gap_s: float = 20e-6) -> List[Tuple[str, float]]:
        """The idle time of the first chip, summed by what the host was doing
        in each gap (the innermost Python call covering the gap's middle);
        gaps shorter than ``min_gap_s`` go under one label."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0])
        by_label: Dict[str, float] = {}
        calls = sorted(self.host_calls)
        starts = [c[0] for c in calls]
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            gap = s1 - e0
            if gap <= 0:
                continue
            if gap < min_gap_s:
                label = "shorter_gaps__not_labelled"
            else:
                mid = (e0 + s1) / 2
                label = "no_python_call_recorded"
                # innermost = the latest-started call that still covers mid
                i = bisect.bisect_right(starts, mid) - 1
                scanned = 0
                while i >= 0 and scanned < 2000:
                    s, e, name = calls[i]
                    if e >= mid:
                        label = name
                        break
                    i -= 1
                    scanned += 1
            by_label[label] = by_label.get(label, 0.0) + gap
        return sorted(by_label.items(), key=lambda kv: -kv[1])[:top]

    # ---- operations ------------------------------------------------------------

    def op_totals(self, top: int = 10) -> List[Tuple[str, float]]:
        """Device seconds by operation name, first chip; a ``while`` holds
        the operations of its body, so it is a container, not a leaf."""
        if not self.devices:
            return []
        tot: Dict[str, float] = {}
        for text, s, e in self.devices[0].ops:
            k = op_short_name(text)
            tot[k] = tot.get(k, 0.0) + (e - s)
        return sorted(tot.items(), key=lambda kv: -kv[1])[:top]

    def module_runs(self, pattern: str, dev: int = 0) -> List[Interval]:
        """(start_s, end_s) of every execution of the programs whose name
        matches ``pattern`` on chip ``dev``."""
        rx = re.compile(pattern)
        if dev >= len(self.devices):
            return []
        return [(s, e) for n, s, e in self.devices[dev].modules if rx.search(n)]

    def ops_matching(self, pattern: str, dev: int = 0) -> List[Interval]:
        rx = re.compile(pattern)
        if dev >= len(self.devices):
            return []
        return [(s, e) for t, s, e in self.devices[dev].ops if rx.search(t)]


def union_intervals(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total_length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of the (unioned) intervals ``a`` that no interval of ``b`` covers."""
    out: List[Interval] = []
    b = union_intervals(b)
    j = 0
    for s, e in union_intervals(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DevicePlane] = []
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DevicePlane(plane.name)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [
                        (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
                elif line.name == "XLA Ops":
                    dev.ops = [
                        (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python":
                    host = [
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                         ev.name.lstrip("$").replace(" ", "_"))
                        for ev in line.events
                    ]
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(devices, host)


COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|collective-broadcast)")
CONTAINERS = ("while", "conditional", "call")
