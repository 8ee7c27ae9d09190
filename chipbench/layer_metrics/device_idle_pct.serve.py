"""Per-layer metric ``device_idle_pct.serve``: layer "device", unit %, moves ``tpot_ms_p50``."""

LAYER = "device"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Share of the traced seconds in which no operation ran on the chip."""
    return run.device_idle_pct()
