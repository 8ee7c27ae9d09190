"""Per-layer metric ``decode_step_glue_ms``: layer "engine device programs", unit ms, moves ``tpot_ms_p50``."""

from chipbench import opscopes

LAYER = "engine device programs"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"


def read(run):
    """Device time of one decode step in the part ``glue`` (block-level norms and
    residual adds, the embedding, ``commit``, ``step_io``):
    ``opscopes.part_of`` by the trace's own ``tf_op``, over the operations
    inside whole traced ``jit_decode_chunk`` runs, per run and
    ``chunk_steps``. ``None`` where nothing was traced or the part has no
    instruction."""
    return opscopes.decode_step_part_ms(run, "glue")
