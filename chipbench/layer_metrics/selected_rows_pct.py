"""Per-layer metric ``selected_rows_pct``: layer "engine host side", unit %, moves ``tpot_ms_p50``."""

LAYER = "engine host side"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "program_counter"


def read(run):
    """Of the cached rows that the window's dispatched decode steps let
    their live sequences see, the share the learned selection lets their
    attention read: the perf plane's ``selected_positions`` over
    ``visible_positions``. The engine **reckons** both on the host
    (``min(rows, topk)`` of each live row's fill at every dispatched step);
    it is the density the rooflines' byte counts assume, not a reading of
    what the device fetched. ``None`` where the program keeps no such
    counter or counts nothing (a module without a selection)."""
    occ = run.record.get("occupancy") or {}
    visible = occ.get("visible_positions")
    if not visible or "selected_positions" not in occ:
        return None
    return 100.0 * occ["selected_positions"] / visible
