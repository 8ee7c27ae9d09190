"""A run's traffic as a function of its traffic file and ``--seed`` alone.

Every seed gets the same count and the same multiset of lengths, in another
order, so that two seeds ask the system for the same work: what differs
between runs of a cell is then the system, not the draw.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np


def lognormal_midpoints(n: int, median: float, sigma: float, lo: int, hi: int) -> List[int]:
    """``n`` lengths at the quantile midpoints ``(i + 0.5) / n`` of a
    lognormal(median, sigma), rounded and clipped to ``[lo, hi]``."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def draw_requests(mix: dict, seed: int, seconds: float, vocab_size: int) -> List[dict]:
    """The open-loop schedule of one run: a ramp before the window and the
    window itself, each a Poisson process given its count (sorted uniforms).

    Returns dicts with ``due_s`` (relative to the window's start, negative in
    the ramp), ``prompt`` (token ids), ``max_new_tokens`` and ``in_window``.
    """
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    rate = float(mix["rate_per_s"])
    ramp_s = float(mix["ramp_s"])
    out: List[dict] = []
    for start, span, in_window in ((-ramp_s, ramp_s, False), (0.0, float(seconds), True)):
        n = int(round(rate * span))
        p, o = mix["prompt_tokens"], mix["output_tokens"]
        prompts = lognormal_midpoints(n, p["median"], p["sigma"], p["min"], p["max"])
        outputs = lognormal_midpoints(n, o["median"], o["sigma"], o["min"], o["max"])
        # independent permutations: prompt and output lengths are uncorrelated
        prompts = [prompts[i] for i in rng.permutation(n)]
        outputs = [outputs[i] for i in rng.permutation(n)]
        due = np.sort(rng.uniform(0.0, span, n)) + start
        for t, pl, ol in zip(due, prompts, outputs):
            out.append({
                "due_s": float(t),
                "prompt": rng.integers(1, vocab_size, pl).tolist(),
                "max_new_tokens": int(ol),
                "in_window": in_window,
            })
    return out
