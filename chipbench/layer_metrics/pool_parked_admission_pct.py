"""Per-layer metric ``pool_parked_admission_pct``: layer "engine host side", unit %, moves ``tpot_ms_p50``."""

LAYER = "engine host side"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "program_counter"


def read(run):
    """Admissions of the window that found a free slot and no pool blocks,
    and waited, over all its admissions (``ServingPerfPlane``'s plain sums):
    whether the pool or the slots bound the batch. ``None`` where the
    program does not count them."""
    occ = run.record.get("occupancy") or {}
    if not occ.get("admissions") or "admissions_parked_on_pool" not in occ:
        return None
    return 100.0 * occ["admissions_parked_on_pool"] / occ["admissions"]
