"""Per-layer metric ``train_step_unscoped_pct``: layer "train step", unit %, moves ``train_samples_per_s``."""

from chipbench import opscopes

LAYER = "train step"
UNIT = "%"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    """The share of the operations' time inside whole traced runs of the train step's
    program that no model part owns: the by-part readers' own health."""
    return opscopes.train_step_unscoped_pct(run)
