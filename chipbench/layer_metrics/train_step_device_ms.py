"""Per-layer metric ``train_step_device_ms``: layer "train step", unit ms, moves ``train_samples_per_s``."""

LAYER = "train step"
UNIT = "ms"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    """Median device time of the train step program (the program that takes
    most of the traced device time)."""
    step = run.train_step_s()
    return step and step * 1e3
