"""Per-layer metric ``ttft_tail_ms_p95``: layer "engine host side", unit ms, moves ``tpot_ms_p50``."""

from chipbench.yardstick import percentile

LAYER = "engine host side"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "host_clock"


def read(run):
    """95th percentile of the time to first token at the client, from the due
    time: the wait behind the decode chunks already enqueued."""
    vals = run.record["times"]["ttft"]
    return percentile(vals, 95) if vals else None
