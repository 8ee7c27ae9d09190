"""Per-layer metric ``sparse_attn_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_sparse

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time the chip could take to read the keys and values of the
    positions selected by the sequences live in the traced seconds
    (``min(length, topk)`` each) once a layer, or to score and weigh them for
    every query head, whichever is longer
    (``opsbytes_sparse.sparse_attention_cost``), over
    ``sparse_attn_ms_per_step``."""
    return opsbytes_sparse.kernel_roofline(
        run, opsbytes_sparse.SPARSE_ATTENTION_SCOPES,
        lambda cfg, load: opsbytes_sparse.sparse_attention_cost(cfg, load[2]), "sparse attention",
    )
