"""Per-layer metric ``index_scores_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_sparse

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time the chip could take to read the indexer keys of the
    positions visible to the sequences live in the traced seconds once a
    layer, or to multiply them by every indexer head's query, whichever is
    longer (``opsbytes_sparse.index_scores_cost``), over the traced time of
    the kernel ``paged_index_scores`` a step."""
    return opsbytes_sparse.kernel_roofline(
        run, frozenset({"paged_index_scores"}),
        lambda cfg, load: opsbytes_sparse.index_scores_cost(cfg, load[1]), "index scores",
    )
