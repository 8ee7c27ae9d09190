"""Per-layer metric ``tokens_per_forward``: layer "engine host side", unit tokens/forward, moves ``tpot_ms_p50``."""

LAYER = "engine host side"
UNIT = "tokens/forward"
MOVES = "tpot_ms_p50"
SOURCE = "program_counter"


def read(run):
    """Tokens decided a forward by a module that generates by blocks: the
    perf plane's ``tokens_decided`` over ``block_forwards`` of the window
    (the entries the window's live slot-forwards decided, over those
    forwards, commit forwards included). Under ``low_confidence_static``
    with as many denoising steps as a block has entries it is
    ``Bk / (Bk + 1)`` by construction, a little more for the last block of
    each request, which takes no commit forward; a schedule that decides
    several entries a forward, or spares the commit, moves it first.
    ``None`` where the program keeps no such counter or counted no forward
    (another module, or the parent's program)."""
    occ = run.record.get("occupancy") or {}
    forwards = occ.get("block_forwards")
    if not forwards or "tokens_decided" not in occ:
        return None
    return occ["tokens_decided"] / forwards
