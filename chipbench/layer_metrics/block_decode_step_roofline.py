"""Per-layer metric ``block_decode_step_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_block
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time one forward of the model that generates by blocks
    could take on this chip for the sequences live in the traced seconds
    (the weights once, of the routed experts those that ``block_length x
    live`` rows are expected to touch; the visible keys and values once a
    forward; the head over every row; ``opsbytes_block.forward_cost``) over
    the traced step time: a step of this cell's chunk is one forward."""
    step = run.decode_step_s()
    if not step or "generation" not in run.config:
        return None
    load = opsbytes_block.traced_load(run)
    if load is None:
        return None
    flops, moved = opsbytes_block.forward_cost(run.config, *load)
    least, bound = roofline_s(flops, moved, run.peaks)
    rows = run.config["generation"]["block_length"] * load[0]
    say(f"block forward: {flops / 1e9:.1f} GFLOP, {moved / 1e9:.2f} GB for {load[0]:.1f} live sequences "
        f"({rows:.0f} rows, {opsbytes_block.experts_touched(run.config, rows):.1f} experts a layer touched) "
        f"seeing {load[1]:.0f} positions; {bound}-bound, least {least * 1e3:.3f} ms, traced {step * 1e3:.3f} ms")
    return 100.0 * least / step
