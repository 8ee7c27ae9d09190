"""Per-layer metric ``dispatcher_enqueue_pct``: layer "engine host side", unit %, moves ``tpot_ms_p50``."""

LAYER = "engine host side"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "program_counter"


def read(run):
    """The share of the window that the dispatcher thread spent inside its
    jitted calls (prefill and decode chunk, with their host-to-device
    arguments), from ``ServingPerfPlane``'s ``dispatcher_s``. Near 100: an
    enqueue blocks and the pass is paced by the device. Near 0 with
    ``no_credit`` polls: the dispatcher runs ahead and waits for credits."""
    occ = run.record.get("occupancy") or {}
    phases, window = occ.get("dispatcher_s"), occ.get("window_s")
    if not phases or not window:
        return None
    return 100.0 * phases["enqueue"] / window
