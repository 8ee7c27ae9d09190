"""Per-layer metric ``latent_attn_ms_per_step``: layer "kernels", unit ms, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_hybrid, opsbytes_latent

LAYER = "kernels"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Device time the absorbed latent attention takes of one decode step,
    all layers together: the operations named ``paged_latent_attention``
    inside whole traced ``jit_decode_chunk`` runs, over those runs x
    ``chunk_steps`` (``opsbytes_hybrid.kernel_ms_per_step``, as
    ``paged_attn_ms_per_step`` reads its kernel). ``None`` where the
    program has no such kernel."""
    return opsbytes_hybrid.kernel_ms_per_step(run, opsbytes_latent.LATENT_KERNEL)
