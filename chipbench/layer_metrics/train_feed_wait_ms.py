"""Per-layer metric ``train_feed_wait_ms``: layer "train loop", unit ms, moves ``train_samples_per_s``."""

from chipbench import hostspans

LAYER = "train loop"
UNIT = "ms"
MOVES = "train_samples_per_s"
SOURCE = "program_span"


def read(run):
    """Mean length of ``train.feed_wait`` (the loop's ``next()`` on its feed)
    over the traced steps: what the step loop waits for its batch."""
    spans = hostspans.of_run(run)
    waits = spans.named("train.feed_wait") if spans is not None else []
    return sum(e.seconds for e in waits) * 1e3 / len(waits) if waits else None
