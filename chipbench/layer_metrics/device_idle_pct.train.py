"""Per-layer metric ``device_idle_pct.train``: layer "device", unit %, moves ``train_samples_per_s``."""

LAYER = "device"
UNIT = "%"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    """Share of the traced steps' time in which no operation ran on the chips."""
    return run.device_idle_pct()
