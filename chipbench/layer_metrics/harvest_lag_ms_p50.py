"""Per-layer metric ``harvest_lag_ms_p50``: layer "engine host side", unit ms, moves ``tpot_ms_p50``."""

from chipbench import hostspans
from chipbench.yardstick import percentile

LAYER = "engine host side"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "program_span"


def read(run):
    """Median time from the device end of a request's ``jit_prefill`` run to
    the end of its recorded ``prefill`` span (the harvester has read the first
    token back), through the clock offset: the in-order readback's lag."""
    lags = [p["harvest_lag_s"] * 1e3 for p in hostspans.prefill_pieces(run) or [] if "harvest_lag_s" in p]
    return percentile(lags, 50) if lags else None
