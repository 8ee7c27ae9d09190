"""Fused LayerNorm / RMSNorm Pallas kernels (+ residual-add variant).

Why a kernel at all: much of a ViT-B step is VPU elementwise work, with
LayerNorm among the biggest bandwidth consumers (not measured on the
current chip). XLA's LayerNorm is already a fused reduce+normalize, but its BACKWARD
materializes the saved mean/rstd and runs separate reduction passes for
dgamma/dbeta and dx; this kernel pair instead:

- forward: one pass over a row block — fp32 statistics, normalize,
  scale/shift, cast — with NO saved statistics (round-2 Pallas lesson:
  writing small per-row stats forces lane-major relayouts that cost
  more than recomputing the reductions in the backward);
- backward: one pass recomputes the statistics from x and produces dx
  plus PER-BLOCK partial dgamma/dbeta rows ([grid, D], summed in fp32
  outside the kernel — a [G, D] tree-sum is one cheap XLA reduce);
- the ``*_add_*`` variants fuse the transformer residual add
  (``s = x + r; y = norm(s)``) into the same pass, saving one full
  [rows, D] HBM round trip per block in both directions.

Layout: inputs flatten to [rows, D]; D must be a multiple of 128
(lane width). Row blocks of 256 keep bf16 tiles aligned (16-sublane
multiples) and fit VMEM with room for the fp32 intermediates.

No reference counterpart — the reference has no kernels (SURVEY.md §2:
"100% Python, no native components").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK_ROWS = 256


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _stats(x32, *, rms: bool, eps: float):
    if rms:
        mu = 0.0
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return mu, jax.lax.rsqrt(var + eps)


# --------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------- #


def _valid_rows(block: int, rows: int):
    """Row-validity column for the current grid block, or None when the
    grid divides evenly. The trailing block reads padding garbage —
    harmless for per-row outputs (out-of-bounds writes are dropped) but
    it MUST be zeroed out of cross-row dgamma/dbeta sums, and zeroed on
    input so a garbage row's NaN stats can't poison 0*NaN."""
    if rows % block == 0:
        return None
    start = pl.program_id(0) * block
    idx = start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    return idx < rows


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, eps, rms, rows):
    x32 = x_ref[...].astype(jnp.float32)
    valid = _valid_rows(x_ref.shape[0], rows)
    if valid is not None:
        x32 = jnp.where(valid, x32, 0.0)
    mu, rstd = _stats(x32, rms=rms, eps=eps)
    xhat = (x32 - mu) * rstd
    out = xhat * g_ref[...].astype(jnp.float32)
    if b_ref is not None:
        out = out + b_ref[...].astype(jnp.float32)
    y_ref[...] = out.astype(y_ref.dtype)


def _add_fwd_kernel(x_ref, r_ref, g_ref, b_ref, s_ref, y_ref, *, eps, rms, rows):
    s32 = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    valid = _valid_rows(x_ref.shape[0], rows)
    if valid is not None:
        s32 = jnp.where(valid, s32, 0.0)
    s_ref[...] = s32.astype(s_ref.dtype)
    mu, rstd = _stats(s32, rms=rms, eps=eps)
    xhat = (s32 - mu) * rstd
    out = xhat * g_ref[...].astype(jnp.float32)
    if b_ref is not None:
        out = out + b_ref[...].astype(jnp.float32)
    y_ref[...] = out.astype(y_ref.dtype)


def _bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dg_ref, db_ref, *, eps, rms, rows):
    """Recompute stats, emit dx and this block's dgamma/dbeta partials.

    dx = rstd * (dyg - mean(dyg) - xhat * mean(dyg * xhat))   (LayerNorm)
    dx = rstd * (dyg - xhat * mean(dyg * xhat))               (RMSNorm)
    where dyg = dy * gamma. dgamma = sum(dy * xhat); dbeta = sum(dy).
    """
    x32 = x_ref[...].astype(jnp.float32)
    dy32 = dy_ref[...].astype(jnp.float32)
    valid = _valid_rows(x_ref.shape[0], rows)
    if valid is not None:
        x32 = jnp.where(valid, x32, 0.0)
        dy32 = jnp.where(valid, dy32, 0.0)
    mu, rstd = _stats(x32, rms=rms, eps=eps)
    xhat = (x32 - mu) * rstd
    dyg = dy32 * g_ref[...].astype(jnp.float32)
    c2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
    if rms:
        dx = rstd * (dyg - xhat * c2)
    else:
        c1 = jnp.mean(dyg, axis=-1, keepdims=True)
        dx = rstd * (dyg - c1 - xhat * c2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # partials are written as (8, D) tiles (TPU min sublane count): the
    # sum in row 0, zero elsewhere — the outer fp32 reduce over ALL rows
    # absorbs the zeros for free
    pad7 = ((0, 7), (0, 0))
    dg_ref[...] = jnp.pad(jnp.sum(dy32 * xhat, axis=0, keepdims=True), pad7)
    if db_ref is not None:
        db_ref[...] = jnp.pad(jnp.sum(dy32, axis=0, keepdims=True), pad7)


# --------------------------------------------------------------------- #
# pallas_call wrappers over [rows, D]
# --------------------------------------------------------------------- #


def _row_grid(rows: int):
    block = min(_BLOCK_ROWS, rows)
    # ceil grid: the trailing partial block is masked inside the kernels
    return pl.cdiv(rows, block), block


def _check_lanes(d: int) -> None:
    """Mosaic requires the last dim to tile 128 lanes; fail with a clear
    message instead of a lowering error deep inside pallas_call (CPU
    interpret mode has no lane layout and accepts any width — the tiny
    test configs rely on that)."""
    if d % 128 and not _interpret():
        raise ValueError(
            f"fused norm requires the feature dim to be a multiple of 128 "
            f"(TPU lane width), got {d}; use the xla norm impl for this "
            "model size"
        )


def _norm_fwd(x, gamma, beta, *, eps, rms):
    rows, d = x.shape
    _check_lanes(d)
    grid, block = _row_grid(rows)
    row_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    args = [x, gamma[None, :]]
    in_specs = [row_spec, vec_spec]
    if beta is not None:
        args.append(beta[None, :])
        in_specs.append(vec_spec)
        kernel = functools.partial(_fwd_kernel, eps=eps, rms=rms, rows=rows)
    else:
        kernel = functools.partial(
            lambda x_ref, g_ref, y_ref, **kw: _fwd_kernel(
                x_ref, g_ref, None, y_ref, **kw
            ),
            eps=eps, rms=rms, rows=rows,
        )
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=_interpret(),
    )(*args)


def _norm_add_fwd(x, r, gamma, beta, *, eps, rms):
    rows, d = x.shape
    _check_lanes(d)
    grid, block = _row_grid(rows)
    row_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    args = [x, r, gamma[None, :]]
    in_specs = [row_spec, row_spec, vec_spec]
    if beta is not None:
        args.append(beta[None, :])
        in_specs.append(vec_spec)
        kernel = functools.partial(_add_fwd_kernel, eps=eps, rms=rms, rows=rows)
    else:
        kernel = functools.partial(
            lambda x_ref, r_ref, g_ref, s_ref, y_ref, **kw: _add_fwd_kernel(
                x_ref, r_ref, g_ref, None, s_ref, y_ref, **kw
            ),
            eps=eps, rms=rms, rows=rows,
        )
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((rows, d), x.dtype),
        ],
        interpret=_interpret(),
    )(*args)


def _norm_bwd(x, gamma, dy, *, eps, rms, with_beta):
    rows, d = x.shape
    _check_lanes(d)
    grid, block = _row_grid(rows)
    row_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    part_spec = pl.BlockSpec((8, d), lambda i: (i, 0))
    out_specs = [row_spec, part_spec]
    out_shape = [
        jax.ShapeDtypeStruct((rows, d), x.dtype),
        jax.ShapeDtypeStruct((grid * 8, d), jnp.float32),
    ]
    if with_beta:
        kernel = functools.partial(_bwd_kernel, eps=eps, rms=rms, rows=rows)
        out_specs.append(part_spec)
        out_shape.append(jax.ShapeDtypeStruct((grid * 8, d), jnp.float32))
    else:
        kernel = functools.partial(
            lambda x_ref, g_ref, dy_ref, dx_ref, dg_ref, **kw: _bwd_kernel(
                x_ref, g_ref, dy_ref, dx_ref, dg_ref, None, **kw
            ),
            eps=eps, rms=rms, rows=rows,
        )
    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[row_spec, vec_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
    )(x, gamma[None, :], dy)
    dx, dg_parts = outs[0], outs[1]
    dgamma = dg_parts.sum(axis=0)
    dbeta = outs[2].sum(axis=0) if with_beta else None
    return dx, dgamma, dbeta


# --------------------------------------------------------------------- #
# public ops (custom_vjp; arbitrary leading dims)
# --------------------------------------------------------------------- #


def _flatten(x):
    return x.reshape((-1, x.shape[-1])), x.shape


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, gamma, beta, eps: float = 1e-6, rms: bool = False):
    """``layer_norm(x) * gamma + beta`` over the last axis, one fused
    pass each way. ``rms=True`` drops mean subtraction and ``beta``
    (pass ``beta=None``) — Llama-style RMSNorm."""
    x2, shape = _flatten(x)
    return _norm_fwd(x2, gamma, beta, eps=eps, rms=rms).reshape(shape)


def _fln_fwd(x, gamma, beta, eps, rms):
    return fused_layer_norm(x, gamma, beta, eps, rms), (x, gamma)


def _fln_bwd(eps, rms, res, dy):
    x, gamma = res
    x2, shape = _flatten(x)
    dy2, _ = _flatten(dy)
    dx, dgamma, dbeta = _norm_bwd(
        x2, gamma, dy2, eps=eps, rms=rms, with_beta=not rms
    )
    return dx.reshape(shape), dgamma, dbeta


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_add_layer_norm(x, r, gamma, beta, eps: float = 1e-6, rms: bool = False):
    """``s = x + r; y = norm(s)`` in one pass; returns ``(s, y)``.

    The transformer-block pattern ``s = residual + branch; h = norm(s)``
    re-reads ``s`` immediately — fusing the add saves one [rows, D] HBM
    round trip each way. The backward folds the norm's ds into the
    incoming residual gradient, so ``ds_total`` flows to BOTH x and r.
    """
    x2, shape = _flatten(x)
    r2, _ = _flatten(r)
    s, y = _norm_add_fwd(x2, r2, gamma, beta, eps=eps, rms=rms)
    return s.reshape(shape), y.reshape(shape)


def _faln_fwd(x, r, gamma, beta, eps, rms):
    s, y = fused_add_layer_norm(x, r, gamma, beta, eps, rms)
    return (s, y), (s, gamma)


def _faln_bwd(eps, rms, res, grads):
    s, gamma = res
    ds_in, dy = grads
    s2, shape = _flatten(s)
    dy2, _ = _flatten(dy)
    dx, dgamma, dbeta = _norm_bwd(
        s2, gamma, dy2, eps=eps, rms=rms, with_beta=not rms
    )
    ds_total = dx.reshape(shape) + ds_in
    return ds_total, ds_total, dgamma, dbeta


fused_add_layer_norm.defvjp(_faln_fwd, _faln_bwd)


def fused_rms_norm(x, scale, eps: float = 1e-5):
    """Llama-style RMSNorm through the fused kernel pair."""
    return fused_layer_norm(x, scale, None, eps, True)
