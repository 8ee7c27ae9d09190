"""Per-layer metric ``latent_attn_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_hybrid, opsbytes_latent
from chipbench.yardstick import roofline_s, say

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The least time the chip could take to read the latent rows of the
    sequences live in the traced seconds once a layer, or to score and
    weigh them for every head, whichever is longer
    (``opsbytes_latent.latent_attention_cost``), over
    ``latent_attn_ms_per_step``."""
    ms = opsbytes_hybrid.kernel_ms_per_step(run, opsbytes_latent.LATENT_KERNEL)
    load = opsbytes_hybrid.traced_load(run)
    if not ms or load is None or "kv_lora_rank" not in run.config:
        return None
    flops, moved = opsbytes_latent.latent_attention_cost(run.config, load[1])
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"latent attention: {moved / 1e9:.3f} GB, {flops / 1e9:.1f} GFLOP for {load[1]:.0f} cached positions "
        f"of {load[0]:.1f} live sequences; {bound}-bound, least {least * 1e3:.3f} ms, traced {ms:.3f} ms a step")
    return 100.0 * least * 1e3 / ms
