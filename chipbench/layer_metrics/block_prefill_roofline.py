"""Per-layer metric ``block_prefill_roofline``: layer "kernels", unit %, moves ``tpot_ms_p50``."""

from chipbench import opsbytes_block
from chipbench.yardstick import roofline_s

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """As ``prefill_roofline``, with the operations and bytes of a prefill
    that yields no token (attention over the block-causal half square, no
    head; ``opsbytes_block.prefill_cost``): the least time the traced
    prefills could take at their prompts' true lengths over the device time
    of the traced prefill programs."""
    if run.trace is None or "generation" not in run.config:
        return None
    runs = run.trace.module_runs(r"^jit_prefill\(")
    lens = run.prompt_lengths_prefilled_while_traced()
    if not runs or not lens:
        return None
    least = sum(roofline_s(*opsbytes_block.prefill_cost(run.config, n), run.peaks)[0] for n in lens)
    # the k prefills of the traced seconds against the k prompts admitted in them
    k = min(len(runs), len(lens))
    spent = sum(e - s for s, e in runs) * k / len(runs)
    return 100.0 * (least * k / len(lens)) / spent
