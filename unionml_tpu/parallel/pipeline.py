"""Pipeline parallelism: GPipe-style microbatch schedule as one SPMD program.

No reference counterpart (SURVEY.md §5.7/§7 — PP is a TPU-native
first-class addition). Design is scaling-book-style SPMD pipelining rather
than a host-side scheduler: every pipeline stage lives on one slice of the
``pipeline`` mesh axis, the whole schedule (fill, steady state, drain) is a
single ``lax.scan`` inside ``shard_map``, and activations move between
neighbouring stages with ``lax.ppermute`` over ICI. Because the schedule is
one traced program, ``jax.grad`` differentiates straight through it —
backward ppermutes are the transposed forward ones — so pipeline-parallel
*training* needs no bespoke backward scheduler.

Memory: each stage rematerializes its microbatch activations on the
backward pass (``jax.checkpoint`` around the stage body), the standard
GPipe memory/compute trade.

Usage shape: stack per-stage parameters on a leading axis (stage s owns
``stacked_params[s]``), pick ``num_microbatches >= num_stages`` to keep the
bubble fraction at ``(n-1)/(m+n-1)``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from jax import lax


def _unstack_local(tree: Any) -> Any:
    """Drop the singleton leading (stage) axis of a per-device param shard."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def pipeline_spmd(
    stage_fn: Callable,
    stage_params: Any,
    microbatches: jnp.ndarray,
    *,
    axis: str = "pipeline",
    remat: bool = True,
) -> jnp.ndarray:
    """Run the GPipe schedule *inside* shard_map.

    ``stage_fn(params, x) -> y`` is this stage's computation; ``stage_params``
    the local stage's params; ``microbatches`` [M, mb, ...] — the full
    microbatched input, identical on every stage (only stage 0 consumes it).
    Returns [M, mb, ...] outputs, valid on the LAST stage (zeros elsewhere —
    callers psum or mask; see :func:`pipeline_apply`).
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    num_micro, mb = microbatches.shape[0], microbatches.shape[1:]
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    ticks = num_micro + n - 1

    # state: the activation currently entering this stage
    state0 = jnp.zeros(mb, microbatches.dtype)
    out0 = jnp.zeros((num_micro,) + mb, microbatches.dtype)

    def tick(carry, t):
        state, out = carry
        # stage 0 ingests microbatch t during the fill/steady phase
        feed = microbatches[jnp.minimum(t, num_micro - 1)]
        state = jnp.where(idx == 0, feed.astype(state.dtype), state)
        y = fn(stage_params, state)
        # last stage banks microbatch t-(n-1) once the pipe is full
        done = t - (n - 1)
        out = lax.cond(
            done >= 0,
            lambda o: o.at[jnp.maximum(done, 0)].set(
                jnp.where(idx == n - 1, y.astype(o.dtype), o[jnp.maximum(done, 0)])
            ),
            lambda o: o,
            out,
        )
        state = lax.ppermute(y, axis, fwd_perm)
        return (state, out), None

    (_, out), _ = lax.scan(tick, (state0, out0), jnp.arange(ticks))
    # replicate the last stage's outputs to every stage so downstream
    # (loss) code is stage-agnostic: zeros elsewhere → psum == broadcast
    return lax.psum(jnp.where(idx == n - 1, out, jnp.zeros_like(out)), axis)


def pipeline_apply(
    stage_fn: Callable,
    stacked_params: Any,
    batch: jnp.ndarray,
    *,
    mesh,
    axis: str = "pipeline",
    num_microbatches: int,
    remat: bool = True,
    data_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Globally-shaped pipeline execution (jit-able, differentiable).

    ``stacked_params``: pytree with a leading stage axis of size
    ``mesh.shape[axis]``; ``batch``: [B, ...] with ``B`` divisible by
    ``num_microbatches``. Returns [B, ...] outputs replicated over ``axis``.

    ``data_axis`` composes PP x DP: the microbatch dimension shards over
    that mesh axis (each data shard runs its own pipeline over the same
    stage weights; ppermute/psum stay on the ``pipeline`` axis), so the
    per-device microbatch is ``B / num_microbatches / mesh.shape[data_axis]``.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    b = batch.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by num_microbatches {num_microbatches}")
    if num_microbatches < n:
        raise ValueError(
            f"num_microbatches {num_microbatches} < pipeline stages {n}: "
            f"the bubble would dominate; use at least one microbatch per stage"
        )
    mb_rows = b // num_microbatches
    if data_axis is not None:
        if data_axis not in mesh.shape:
            raise ValueError(
                f"data_axis {data_axis!r} is not a mesh axis {tuple(mesh.shape)}"
            )
        if mb_rows % mesh.shape[data_axis]:
            raise ValueError(
                f"microbatch rows {mb_rows} not divisible by data axis size "
                f"{mesh.shape[data_axis]}"
            )

    micro = batch.reshape((num_microbatches, mb_rows) + batch.shape[1:])

    # the scan carry is one microbatch-shaped activation, so every stage
    # must map [mb, ...] -> same shape/dtype; fail here with a clear error
    # rather than deep inside shard_map tracing
    local_params = jax.eval_shape(
        lambda p: _unstack_local(p), stacked_params
    )
    mb_shape = jax.ShapeDtypeStruct(micro.shape[1:], micro.dtype)
    out_shape = jax.eval_shape(stage_fn, local_params, mb_shape)
    if out_shape.shape != mb_shape.shape or out_shape.dtype != mb_shape.dtype:
        raise ValueError(
            f"pipeline stage_fn must preserve activation shape/dtype "
            f"(scan carry): got {out_shape.shape}/{out_shape.dtype} from "
            f"{mb_shape.shape}/{mb_shape.dtype}. Fold projections/dtype "
            f"casts into the last stage's OUTPUT consumer instead, or pad "
            f"activations to a common shape."
        )

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    batch_spec = P(None, data_axis) if data_axis is not None else P()

    def body(params, mb):
        return pipeline_spmd(
            stage_fn, _unstack_local(params), mb, axis=axis, remat=remat
        )

    out = shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(stacked_params, micro)
    return out.reshape((b,) + out.shape[2:])


def stack_stage_params(per_stage: list) -> Any:
    """Stack a list of per-stage param pytrees along a new leading axis.

    Shard the result's leading axis over ``pipeline`` with
    ``unionml_tpu.models.PIPELINE_PARTITION_RULES`` (which targets only
    the ``stages/`` subtree, leaving embed/head alone).
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)
