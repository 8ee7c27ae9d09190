"""Per-layer metric ``sparse_moe_decode_step_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_sparse
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time one decode step of the sparse-attention mixture model
    could take on this chip for the sequences live in the traced seconds
    (the weights once, of the routed experts those the live rows are expected
    to touch; the indexer key of every visible position and the keys and
    values of the selected ones only; the selection's own time counted as
    zero; ``opsbytes_sparse.decode_step_cost``) over the traced step time."""
    step = run.decode_step_s()
    if not step or "sa_config" not in run.config:
        return None
    load = opsbytes_sparse.traced_load(run, run.config)
    if load is None:
        return None
    flops, moved = opsbytes_sparse.decode_step_cost(run.config, *load)
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"sparse mixture decode step: {flops / 1e9:.1f} GFLOP, {moved / 1e9:.2f} GB for {load[0]:.1f} live "
        f"sequences seeing {load[1]:.0f} positions, selecting {load[2]:.0f} "
        f"({opsbytes_sparse.experts_touched(run.config, load[0]):.1f} experts a layer touched); {bound}-bound, "
        f"least {least * 1e3:.3f} ms, traced {step * 1e3:.3f} ms")
    return 100.0 * least / step
