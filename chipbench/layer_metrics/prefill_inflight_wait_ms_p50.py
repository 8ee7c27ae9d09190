"""Per-layer metric ``prefill_inflight_wait_ms_p50``: layer "engine host side", unit ms, moves ``tpot_ms_p50``."""

import json

from chipbench import hostspans
from chipbench.yardstick import percentile, say

LAYER = "engine host side"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "program_span"


def read(run):
    """Median time from the start of ``engine.admit.enqueue`` to the device
    start of that request's ``jit_prefill`` run (``hostspans.pair_in_order``),
    over the prefills enqueued in the traced seconds: how long a prefill sits
    behind the work already enqueued. Also prints what PERF.md quotes from a
    traced run (clock join, pairing, TTFT budget, the window's counters)."""
    pieces = hostspans.prefill_pieces(run)
    if not pieces:
        return None
    say("host spans: " + json.dumps(hostspans.describe(run)))
    return percentile([p["inflight_wait_s"] * 1e3 for p in pieces], 50)
