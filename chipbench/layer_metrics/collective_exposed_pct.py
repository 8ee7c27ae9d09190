"""Per-layer metric ``collective_exposed_pct``: layer "train step across chips", unit %, moves ``train_samples_per_s``."""

from chipbench import xplane

LAYER = "train step across chips"
UNIT = "%"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    """Time in which a collective runs and no compute does, over the traced
    time, on the worst chip."""
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    worst = 0.0
    for d in run.trace.devices:
        coll, comp = [], []
        for text, s, e in d.ops:
            name = xplane.op_short_name(text)
            if xplane.COLLECTIVE.search(name):
                coll.append((s, e))
            elif name not in xplane.CONTAINERS:
                comp.append((s, e))
        if not d.ops:
            continue
        span = max(e for _, _, e in d.ops) - min(s for _, s, _ in d.ops)
        exposed = xplane.total_length(xplane.subtract(coll, comp))
        worst = max(worst, 100.0 * exposed / span)
    return worst
