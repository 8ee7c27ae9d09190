"""Per-layer metric ``block_attn_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_block
from chipbench.run import _load_reader
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time the chip could take to read the keys and values of the
    positions visible to the sequences live in the traced seconds once a
    layer (2,048 B each at the published widths: once a forward for the
    block's four queries, not once a token), or to score and weigh them for
    every query head of every query, whichever is longer
    (``opsbytes_block.block_attention_cost``), over the time the kernel
    ``paged_attention`` takes of a forward (the reader
    ``paged_attn_ms_per_step``'s own number)."""
    if "generation" not in run.config:
        return None
    ms = _load_reader("paged_attn_ms_per_step").read(run)   # a step of this cell's chunk is a forward
    load = opsbytes_block.traced_load(run)
    if not ms or load is None:
        return None
    flops, moved = opsbytes_block.block_attention_cost(run.config, load[1])
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"block attention: {moved / 1e9:.3f} GB, {flops / 1e9:.1f} GFLOP for {load[0]:.1f} live sequences "
        f"seeing {load[1]:.0f} positions; {bound}-bound, least {least * 1e3:.3f} ms, traced {ms:.3f} ms a forward")
    return 100.0 * least * 1e3 / ms
