"""Per-layer metric ``index_select_ms_per_step``: layer "kernels", unit ms, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_sparse

LAYER = "kernels"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Device time one decode step spends choosing the positions its
    attention reads, all layers together: the operations under the scopes
    ``indexer`` (the indexer's projections and the kernel
    ``paged_index_scores``) and ``select`` (the exact top-k), inside whole
    traced ``jit_decode_chunk`` runs, over those runs x ``chunk_steps``
    (``opsbytes_sparse.scoped_ms_per_step``). ``None`` where the program has
    no such scope."""
    return opsbytes_sparse.scoped_ms_per_step(run, opsbytes_sparse.INDEX_SELECT_SCOPES)
