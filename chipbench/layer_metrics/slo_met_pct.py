"""Per-layer metric ``slo_met_pct``: layer "service", unit %, moves ``tpot_ms_p50``."""

LAYER = "service"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "host_clock"


def read(run):
    """Share of the requests due in the window that met both limits of the
    traffic file (time to first token, mean gap); a failed request misses."""
    lim = run.traffic["slo"]
    recs = [r for r in run.record["records"] if r["in_window"]]
    if not recs:
        return None
    met = 0
    for r in recs:
        if r["error"] or len(r["tokens"]) != r["n_asked"] or not r["t_tokens"]:
            continue
        ttft = (r["t_tokens"][0] - r["due"]) * 1e3
        n = len(r["tokens"])
        gap = (r["t_tokens"][-1] - r["t_tokens"][0]) * 1e3 / (n - 1) if n > 1 else 0.0
        met += ttft <= lim["ttft_ms"] and gap <= lim["gap_ms"]
    return 100.0 * met / len(recs)
