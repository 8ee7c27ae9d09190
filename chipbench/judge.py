"""The comparisons that decide ``correct``, and the reference's side of them.

Every number compared is printed beside its limit in every run. The limits
live in the configuration files (key ``correct``), each with the readings it
was set from (PERF.md, section 2).
"""

from __future__ import annotations

import functools
import statistics
from typing import Callable, Dict, List, Sequence

from chipbench.yardstick import say


# ----------------------------------------------------------------- training


def reference_three_steps(loss_fn: Callable, params, batches: Sequence, *, lr: float) -> dict:
    """Follow the first three steps in float32: each step's loss, the norm of
    every leaf of the first gradient, and the norm of every leaf's change
    after the three. ``loss_fn(params, batch) -> (objective, reported)``.
    One step is one program, run three times: three steps unrolled into one
    program take the chip's compiler three times as long."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference.common import adamw_init, adamw_update

    def norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree_util.tree_leaves(tree)]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def one_step(p, opt, batch):
        (_, reported), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        p, opt = adamw_update(p, grads, opt, lr=lr)
        return p, opt, reported, norms(grads)

    to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t))
    diff_norms = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(lambda x, y: x - y.astype(jnp.float32), a, b)))
    with jax.default_matmul_precision("highest"):
        p = to_f32(params)
        opt = adamw_init(p)
        losses, g1 = [], None
        for batch in batches:
            p, opt, reported, g = one_step(p, opt, batch)
            losses.append(float(reported))
            g1 = g1 if g1 is not None else [float(x) for x in jax.device_get(g)]
        dp = [float(x) for x in jax.device_get(diff_norms(p, params))]
    return {"losses": losses, "grad_norms": g1, "update_norms": dp}


def worst_leaf_gap(got: Sequence[float], want: Sequence[float]) -> float:
    """The widest gap between two lists of per-leaf norms, each measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(want)
    return max(abs(g - w) / max(w, floor, 1e-30) for g, w in zip(got, want))


def compare_training(got: dict, want: dict, limits: dict) -> Dict[str, dict]:
    loss_gap = max(abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"]))
    return {
        "loss_rel_gap": {"value": loss_gap, "limit": limits["loss_rel_gap"]},
        "grad_norm_gap_worst_leaf": {
            "value": worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
            "limit": limits["grad_norm_gap_worst_leaf"],
        },
        "update_norm_gap_worst_leaf": {
            "value": worst_leaf_gap(got["update_norms"], want["update_norms"]),
            "limit": limits["update_norm_gap_worst_leaf"],
        },
    }


# ------------------------------------------------------------------ serving


def served_logit_gaps(forward: Callable, samples: List[dict], pad_to: int, *,
                      control_forward: Callable = None) -> dict:
    """For each sampled request run the reference once over its prompt with
    its served tokens and read, at every served position, how far the served
    token's logit lies below the reference's best. With ``control_forward``
    also read the gap of the token that the control puts first there.
    ``forward(tokens [1, pad_to]) -> logits [1, pad_to, vocab]``.

    Returns the widest and the mean gap of each. The widest catches a token
    altered where it is produced; it swings by its nature, because a bf16
    router at a near-tie picks another expert than the float32 reference
    does, and that one token's logits then move by more than rounding. The
    mean is steady from seed to seed and is what a lower precision moves."""
    import numpy as np

    served, control = [], []
    for s in samples:
        seq = np.zeros((1, pad_to), np.int32)
        full = list(s["prompt"]) + list(s["tokens"])
        seq[0, :len(full)] = full
        first = len(s["prompt"]) - 1  # the row that predicts tokens[0]
        rows = np.asarray(forward(seq))[0][first:first + len(s["tokens"])]
        best = rows.max(-1)
        served.append(best - rows[np.arange(len(rows)), np.asarray(s["tokens"])])
        if control_forward is not None:
            crow = np.asarray(control_forward(seq))[0][first:first + len(s["tokens"])]
            control.append(best - rows[np.arange(len(rows)), crow.argmax(-1)])

    def stats(parts):
        if not parts:
            return None
        x = np.concatenate(parts)
        return {"max": float(x.max()), "mean": float(x.mean()), "p99": float(np.percentile(x, 99)),
                "share_over_half": float((x > 0.5).mean())}

    return {"served": stats(served), "control": stats(control), "tokens": int(sum(len(x) for x in served))}


def verdict(numbers: Dict[str, dict]) -> bool:
    """Print each number beside its limit; true if every one is within it."""
    ok = True
    for name, n in numbers.items():
        good = n["value"] <= n["limit"] and n["value"] == n["value"]
        ok = ok and good
        say(f"correct: {name} = {n['value']:.6g} (limit {n['limit']:.6g}) {'ok' if good else 'FAILED'}")
    return ok
