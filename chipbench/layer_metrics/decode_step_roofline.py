"""Per-layer metric ``decode_step_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time one decode step could take on this chip (its weights,
    the experts the batch routes to, the live keys and values; see
    ``opsbytes.decode_step_cost``) over the traced step time."""
    step = run.decode_step_s()
    occ = run.record.get("occupancy")
    if not step or not occ:
        return None
    tokens = occ["occupancy_ratio"] * run.record["slots"]
    flops, moved = opsbytes.decode_step_cost(run.config, tokens, run.mean_live_kv_tokens())
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"decode step: {flops / 1e9:.1f} GFLOP, {moved / 1e9:.2f} GB for {tokens:.1f} live sequences; "
        f"{bound}-bound, least {least * 1e3:.3f} ms, traced {step * 1e3:.3f} ms")
    return 100.0 * least / step
