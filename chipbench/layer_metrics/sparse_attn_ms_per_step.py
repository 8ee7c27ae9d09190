"""Per-layer metric ``sparse_attn_ms_per_step``: layer "kernels", unit ms, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_sparse

LAYER = "kernels"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Device time one decode step's attention over the selected positions
    takes, all layers together: the operations named
    ``paged_sparse_attention`` (the gather of the picked rows and the
    softmax over them) inside whole traced ``jit_decode_chunk`` runs, over
    those runs x ``chunk_steps``. ``None`` where the program has no such
    operation."""
    return opsbytes_sparse.scoped_ms_per_step(run, opsbytes_sparse.SPARSE_ATTENTION_SCOPES)
