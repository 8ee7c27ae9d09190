"""Per-layer metric ``slot_occupancy_pct``: layer "engine host side", unit %, moves ``tpot_ms_p50``."""

LAYER = "engine host side"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "program_counter"


def read(run):
    """Occupied slot-steps over dispatched slot-steps in the window, from the
    program's ``ServingPerfPlane`` pass accounting."""
    occ = run.record.get("occupancy")
    return None if not occ else 100.0 * occ["occupancy_ratio"]
