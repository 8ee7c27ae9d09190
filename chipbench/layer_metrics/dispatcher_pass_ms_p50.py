"""Per-layer metric ``dispatcher_pass_ms_p50``: layer "engine host side", unit ms, moves ``tpot_ms_p50``."""

from chipbench import hostspans
from chipbench.yardstick import percentile

LAYER = "engine host side"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "program_span"


def read(run):
    """Median length of ``engine.pass``: one iteration of the dispatcher
    loop that admitted or dispatched something, over the traced seconds."""
    spans = hostspans.of_run(run)
    passes = spans.named("engine.pass") if spans is not None else []
    return percentile([e.seconds * 1e3 for e in passes], 50) if passes else None
