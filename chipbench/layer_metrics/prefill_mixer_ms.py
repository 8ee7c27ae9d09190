"""Per-layer metric ``prefill_mixer_ms``: layer "engine device programs", unit ms, moves ``tpot_ms_p50``."""

from chipbench import opscopes

LAYER = "engine device programs"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Mean device time of one prefill in the part ``mixer`` (``attn`` / ``gdn`` and
    everything under them: projections, rotary, the paged / latent / flash /
    state kernels): over the operations inside the whole traced
    ``jit_prefill`` runs (the runs ``prefill_device_ms_p50`` reads), per run.
    A mean, so that parts add up. ``None`` where nothing was traced or the
    part has no instruction."""
    return opscopes.prefill_part_ms(run, "mixer")
