"""Per-layer metric ``train_step_ffn_ms``: layer "train step", unit ms, moves ``train_samples_per_s``."""

from chipbench import opscopes

LAYER = "train step"
UNIT = "ms"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    """Device time of one train step in the part ``ffn`` (``mlp`` / ``moe`` /
    ``shared_expert``, the router to the combine under them), forward and
    backward together: over the operations inside the whole traced runs of the
    step's program (the one ``train_step_device_ms`` reads), per run. It
    includes the optimizer's update of the part's weights: XLA fuses each
    weight's update into the fusion that makes its gradient, and a fusion's
    first ``op_name`` speaks for it (of ViT-B/16's ~3-4 ms of adam, 0.017
    stand alone under ``optimizer``; PR 38). ``None`` where nothing was
    traced or the part has no instruction."""
    return opscopes.train_step_part_ms(run, "ffn")
