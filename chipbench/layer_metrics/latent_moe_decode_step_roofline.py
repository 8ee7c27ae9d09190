"""Per-layer metric ``latent_moe_decode_step_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_hybrid, opsbytes_latent
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time one decode step of the latent-attention mixture model
    could take on this chip for the sequences live in the traced seconds
    and their cached positions (the weights once, of the routed experts
    those the live rows are expected to touch; the live latent rows; top-k
    and shared experts' operations; ``opsbytes_latent.decode_step_cost``)
    over the traced step time."""
    step = run.decode_step_s()
    load = opsbytes_hybrid.traced_load(run)
    if not step or load is None or "kv_lora_rank" not in run.config:
        return None
    flops, moved = opsbytes_latent.decode_step_cost(run.config, *load)
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"latent mixture decode step: {flops / 1e9:.1f} GFLOP, {moved / 1e9:.2f} GB for {load[0]:.1f} live "
        f"sequences holding {load[1]:.0f} positions ({opsbytes_latent.experts_touched(run.config, load[0]):.1f} "
        f"experts a layer touched); {bound}-bound, least {least * 1e3:.3f} ms, traced {step * 1e3:.3f} ms")
    return 100.0 * least / step
