"""Per-layer metric ``queue_wait_ms_p90``: layer "engine host side", unit ms, moves ``tpot_ms_p50``."""

from chipbench.yardstick import percentile

LAYER = "engine host side"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "program_span"


def read(run):
    """90th percentile of the engine's queue span (submit to admission) over
    the requests due in the window."""
    engine = run.engine_spans_by_http_rid()
    waits = [
        (engine[r["rid"]]["queue"][1] - engine[r["rid"]]["queue"][0]) * 1e3
        for r in run.record["records"]
        if r["in_window"] and r.get("rid") in engine and "queue" in engine[r["rid"]]
    ]
    return percentile(waits, 90) if waits else None
