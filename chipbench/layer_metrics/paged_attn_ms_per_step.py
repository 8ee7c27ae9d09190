"""Per-layer metric ``paged_attn_ms_per_step``: layer "kernels", unit ms, moves ``tpot_ms_p50``."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tpot_ms_p50"
SOURCE = "device_trace"

KERNEL = r"^%?paged_attention[.\s=]"  # the HLO instruction the pallas_call's name gives


def read(run):
    """Device time the paged decode attention kernel takes of one decode
    step, all layers together: the durations of the operations named
    ``paged_attention`` inside whole traced ``jit_decode_chunk`` runs, over
    those runs x ``chunk_steps``. A run is whole if it holds as many kernel
    calls as the fullest run traced (one the trace cut into holds fewer)."""
    if run.trace is None:
        return None
    kernel = run.trace.ops_matching(KERNEL)
    per_run = []
    for start, end in run.trace.module_runs(r"^jit_decode_chunk\("):
        inside = [e - s for s, e in kernel if start <= s and e <= end]
        per_run.append((len(inside), sum(inside)))
    calls = max((n for n, _ in per_run), default=0)
    if not calls:
        return None
    whole = [t for n, t in per_run if n == calls]
    return 1e3 * sum(whole) / (len(whole) * run.record["chunk_steps"])
