"""What the references share: the matmul with its precision, the losses, AdamW."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# precision of the control runs (see judge.py): None is the reference itself
_FP8 = "fp8"
_INT4 = "int4"


def fake_fp8(x):
    """Round ``x`` to float8_e4m3fn with a per-tensor scale, back in float32."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def fake_int4(w):
    """Round a weight to symmetric int4 per output channel (last axis)."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True), 1e-30) / 7.0
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def make_mm(control=None):
    """``mm(a, w)`` contracting a's last axis with w's first, float32 at the
    highest precision. ``control='fp8'`` rounds both operands to fp8 first;
    ``control='int4'`` rounds the weight to int4."""

    def mm(a, w):
        a = a.astype(jnp.float32)
        w = w.astype(jnp.float32)
        if control == _FP8:
            a, w = fake_fp8(a), fake_fp8(w)
        elif control == _INT4:
            w = fake_int4(w.reshape(-1, w.shape[-1])).reshape(w.shape)
        return jnp.tensordot(a, w, axes=1, precision=jax.lax.Precision.HIGHEST)

    return mm


def softmax_ce(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def adamw_init(params):
    """Zero moments laid out like the (float32) parameters. Called eagerly:
    ``zeros_like`` then keeps each leaf's sharding, where zeros made inside a
    jitted program come out replicated on every chip."""
    return {
        "mu": jax.tree_util.tree_map(jnp.zeros_like, params),
        "nu": jax.tree_util.tree_map(jnp.zeros_like, params),
        "count": jnp.zeros((), jnp.int32),
    }


def adamw_update(params, grads, opt, *, lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0):
    """Adam with bias correction and decoupled weight decay (Loshchilov &
    Hutter 2019), as optax's ``scale_by_adam`` chain computes it."""
    count = opt["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def upd(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + eps)
        return p - lr * (step + weight_decay * p)

    return jax.tree_util.tree_map(upd, params, mu, nu), {"mu": mu, "nu": nu, "count": count}
