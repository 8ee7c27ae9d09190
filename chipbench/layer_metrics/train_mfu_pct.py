"""Per-layer metric ``train_mfu_pct``: layer "train step", unit %, moves ``train_samples_per_s``."""

from chipbench import opsbytes

LAYER = "train step"
UNIT = "%"
MOVES = "train_samples_per_s"
SOURCE = "host_clock"


def read(run):
    """Forward + backward FLOPs per sample (from shapes) times samples per
    second over chips times the bf16 peak: a utilization, not a roofline."""
    flops = opsbytes.train_flops_per_sample(run.config)
    rate = run.record["end_to_end"]["train_samples_per_s"]
    if run.record.get("clean_s_per_step"):  # a traced run: the steps after the trace
        rate = run.record["batch"] / run.record["clean_s_per_step"]
    return 100.0 * flops * rate / (run.record["chips"] * run.peaks["bf16_flops_per_s"])
