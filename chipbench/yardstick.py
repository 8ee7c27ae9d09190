"""Arithmetic every cell shares: percentiles, the peaks table, result printing.

Kept here so that no later PR can change how a number is reduced.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Sequence

ROOT = Path(__file__).resolve().parent


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics. ``telemetry.percentile_summary`` takes the nearest rank,
    which steps by a whole request when the sample changes by one; the
    interpolated form moves smoothly, which is what a judged median wants."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def load_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``. A device that is not in the table is an
    error, never a default: a share of an unknown peak means nothing."""
    table = json.loads((ROOT / "peaks.json").read_text())
    if device_kind not in table:
        raise SystemExit(
            f"chipbench: device kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with their source"
        )
    return table[device_kind]


def say(msg: str) -> None:
    """An earlier line of the run: everything but the last line of stdout."""
    print(f"[chipbench] {msg}", flush=True)


def roofline_s(flops: float, bytes_moved: float, peaks: dict):
    """(least seconds the chip could take, which bound sets it)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
