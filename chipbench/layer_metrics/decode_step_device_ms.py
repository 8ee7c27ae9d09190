"""Per-layer metric ``decode_step_device_ms``: layer "engine device programs", unit ms, moves ``tpot_ms_p50``."""

LAYER = "engine device programs"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Device time of one decode step: the decode chunk program's median
    time over the steps in a chunk."""
    step = run.decode_step_s()
    return step and step * 1e3
