"""Per-layer metric ``admission_starved_slot_pct``: layer "engine host side", unit %, moves ``tpot_ms_p50``."""

LAYER = "engine host side"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "program_counter"


def read(run):
    """Empty slot-steps of the chunks dispatched while a request waited, over
    all dispatched slot-steps of the window (``ServingPerfPlane``'s plain
    sums, which a wrapped ring cannot cut short): the part of the empty
    slots that a faster admission could fill."""
    occ = run.record.get("occupancy") or {}
    dispatched = occ.get("window_dispatched_slot_steps")
    if not dispatched or "starved_slot_steps" not in occ:
        return None
    return 100.0 * occ["starved_slot_steps"] / dispatched
