"""Per-layer metric ``gdn_state_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_hybrid
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time the chip could take to read and write the state of the
    sequences live in the traced seconds, in every linear layer
    (``opsbytes_hybrid.state_step_cost``), over ``gdn_state_ms_per_step``."""
    ms = opsbytes_hybrid.kernel_ms_per_step(run, opsbytes_hybrid.GDN_STEP_KERNEL)
    load = opsbytes_hybrid.traced_load(run)
    if not ms or load is None or "layer_types" not in run.config:
        return None
    flops, moved = opsbytes_hybrid.state_step_cost(run.config, load[0])
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"state update: {moved / 1e9:.2f} GB, {flops / 1e9:.1f} GFLOP for {load[0]:.1f} live sequences; "
        f"{bound}-bound, least {least * 1e3:.3f} ms, traced {ms:.3f} ms a step")
    return 100.0 * least * 1e3 / ms
