"""Per-layer metric ``req_ms_per_token_p50``: layer "service", unit ms/token, moves ``tpot_ms_p50``."""

from chipbench.yardstick import percentile

LAYER = "service"
UNIT = "ms/token"
MOVES = "tpot_ms_p50"
SOURCE = "host_clock"


def read(run):
    """Median over requests of ``(t_last - t_due) / n_out`` at the client:
    queueing, time to first token and decode in one number (the Orca / vLLM
    "normalized latency"). Not judged: between seeds it spread by 7.5 % on the
    chip (PR 24), because the arrivals of a seed decide the queueing."""
    vals = run.record["times"]["per_token"]
    return percentile(vals, 50) if vals else None
