"""Per-layer metric ``gdn_state_ms_per_step``: layer "kernels", unit ms, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_hybrid

LAYER = "kernels"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Device time the gated-delta-rule state update takes of one decode
    step, all linear layers together: the operations named
    ``gated_delta_step`` inside whole traced ``jit_decode_chunk`` runs
    (``opsbytes_hybrid.kernel_ms_per_step``, as ``paged_attn_ms_per_step``
    reads its kernel). ``None`` where the program has no such kernel."""
    return opsbytes_hybrid.kernel_ms_per_step(run, opsbytes_hybrid.GDN_STEP_KERNEL)
