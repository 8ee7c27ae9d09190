"""Per-layer metric ``prefill_device_ms_p50``: layer "engine device programs", unit ms, moves ``tpot_ms_p50``."""

from chipbench.yardstick import percentile

LAYER = "engine device programs"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Median device time of one prefill program, from the profiler trace."""
    if run.trace is None:
        return None
    runs = run.trace.module_runs(r"^jit_prefill\(")
    return percentile([(e - s) * 1e3 for s, e in runs], 50) if runs else None
