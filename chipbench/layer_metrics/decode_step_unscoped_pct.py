"""Per-layer metric ``decode_step_unscoped_pct``: layer "engine device programs", unit %, moves ``tpot_ms_p50``."""

from chipbench import opscopes

LAYER = "engine device programs"
UNIT = "%"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """The share of the operations' time inside whole traced ``jit_decode_chunk``
    runs that no model part owns (a bare ``jit(decode_chunk)/op``, or no
    ``tf_op`` and no copy's category): the by-part readers' own health."""
    return opscopes.unscoped_pct(run, opscopes.DECODE)
