"""Per-layer metric ``train_loop_overhead_ms``: layer "train loop", unit ms, moves ``train_samples_per_s``."""

LAYER = "train loop"
UNIT = "ms"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    """Host wall time per step minus the traced device time of one step: what
    the loop around the step costs. The wall time is taken over the steps
    after the trace to the window's end (closed by ``block_until_ready``):
    the whole window of a traced run also holds the profiler's own stall."""
    step = run.train_step_s()
    if not step or not run.record.get("clean_s_per_step"):
        return None
    return (run.record["clean_s_per_step"] - step) * 1e3
