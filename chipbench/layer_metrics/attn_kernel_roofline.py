"""Per-layer metric ``attn_kernel_roofline``: layer "kernels", unit %, moves ``train_samples_per_s``."""

from chipbench import opsbytes
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    """The least time the fused attention kernels of one step could take
    (forward and backward, all layers) over their traced device time."""
    if run.trace is None:
        return None
    pattern = run.train_step_pattern()
    if pattern is None:
        return None
    calls = run.trace.ops_matching(r"custom-call.*tpu_custom_call")
    steps = len(run.trace.module_runs(pattern))
    if not calls or not steps:
        return None
    per_step = sum(e - s for s, e in calls) / steps
    flops, moved = opsbytes.vit_attention_cost(run.config, run.record["batch"] // run.record["chips"])
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"attention kernels: {len(calls) // steps} calls a step, {per_step * 1e3:.3f} ms a step; "
        f"{bound}-bound, least {least * 1e3:.3f} ms")
    return 100.0 * least / per_step
