"""Per-layer metric ``ttft_ms_p50``: layer "engine host side", unit ms, moves ``tpot_ms_p50``."""

from chipbench.yardstick import percentile

LAYER = "engine host side"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "host_clock"


def read(run):
    """Median time to first token at the client, from the request's due time."""
    vals = run.record["times"]["ttft"]
    return percentile(vals, 50) if vals else None
