"""Per-layer metric ``http_added_ttft_ms_p50``: layer "HTTP transport", unit ms, moves ``tpot_ms_p50``."""

from chipbench.yardstick import percentile

LAYER = "HTTP transport"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "program_span"


def read(run):
    """Client time to first token (from the send) minus the engine's own for
    the same request: what ``serving/http.py`` and the socket add."""
    engine = run.engine_spans_by_http_rid()
    added = []
    for r in run.record["records"]:
        spans = engine.get(r.get("rid"))
        if not r["in_window"] or r["error"] or not r["t_tokens"] or not spans:
            continue
        if "queue" in spans and "prefill" in spans:
            own = spans["prefill"][1] - spans["queue"][0]
            added.append(((r["t_tokens"][0] - r["sent"]) - own) * 1e3)
    return percentile(added, 50) if added else None
