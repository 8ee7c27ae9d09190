"""Ring attention: sequence-parallel attention over the ICI ring.

Long-context strategy (SURVEY.md §5.7): q/k/v are sharded along the
sequence axis of the mesh; each device holds a [B, S/n, H, D] shard. The
algorithm rotates the K/V shards around the ring with ``lax.ppermute``
(ICI neighbor exchange) for n steps; every device accumulates blockwise
online-softmax partial results for its local queries against each visiting
K/V shard, normalizing once after the last step. Communication overlaps
compute because ppermute of step i+1's shard is issued while step i's
blockwise accumulation runs (XLA schedules the overlap; the per-step
compute is itself a lax.scan over KV blocks).

Causal masking uses **global** positions: the visiting shard at step s on
device r originates from device (r - s) mod n, so its kv offset is known
statically per step.

The public entry :func:`ring_attention` wraps the per-shard body in
``shard_map`` over the mesh's sequence axis; :func:`ring_attention_sharded`
is the raw collective body for use inside an existing shard_map/pjit
(e.g. the Llama trainer).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax import lax

from unionml_tpu.ops.attention import NEG_INF, _blockwise_accumulate, _repeat_kv


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis: str = "sequence",
    causal: bool = False,
    block_size: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-shard ring attention body (call inside shard_map).

    ``q, k, v``: local shards [B, S_local, H, D]; returns the local output
    shard. Requires every device's shard to have equal length.
    """
    n = lax.axis_size(axis)
    my_idx = lax.axis_index(axis)
    batch, s_local, num_q_heads, head_dim = q.shape
    # NOTE: GQA kv shards rotate un-repeated — _blockwise_accumulate expands
    # kv heads locally, so ppermute moves kv_heads/q_heads of the naive bytes
    scale_ = scale if scale is not None else head_dim**-0.5

    q_offset = my_idx * s_local

    out0 = jnp.zeros((batch, s_local, num_q_heads, head_dim), jnp.float32)
    m0 = jnp.full((batch, s_local, num_q_heads), NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, s_local, num_q_heads), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        out, m, l, k_cur, v_cur = carry
        # the shard visiting at step s came from device (my_idx - s) mod n
        kv_offset = ((my_idx - s) % n) * s_local
        # rotate while computing: XLA overlaps the ppermute with the scan
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        out, m, l = _blockwise_accumulate(
            q, k_cur, v_cur,
            causal=causal, block_size=block_size, scale=scale_,
            q_offset=q_offset, kv_offset=kv_offset,
            acc=(out, m, l),
        )
        return (out, m, l, k_nxt, v_nxt), None

    (out, m, l, _, _), _ = lax.scan(
        step, (out0, m0, l0, k, v), jnp.arange(n)
    )
    return (out / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    *,
    axis: str = "sequence",
    causal: bool = False,
    block_size: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Ring attention over globally-shaped [B,S,H,D] tensors.

    Shards the sequence axis over ``mesh[axis]``, runs the ring, and
    returns the globally-shaped output (sharded the same way).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis, None, None)
    body = functools.partial(
        ring_attention_sharded, axis=axis, causal=causal,
        block_size=block_size, scale=scale,
    )
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


# --------------------------------------------------------------------- #
# ring flash: the Pallas flash kernel as the per-step local compute
# --------------------------------------------------------------------- #
#
# SURVEY.md §5.7 calls for "ring attention as a Pallas kernel with
# ppermute-style KV rotation over ICI". The blockwise body above is pure
# XLA; here each ring step instead runs the VMEM-tiled flash kernel
# (ops/flash_attention.py) on (local q, visiting kv) and the per-step
# partial outputs are merged by their logsumexp:
#
#   lse   = logaddexp(lse_a, lse_b)
#   out   = out_a * exp(lse_a - lse) + out_b * exp(lse_b - lse)
#
# Because shards are contiguous sequence chunks, the visiting shard is
# either entirely in the past (full attention), the diagonal (standard
# causal, q_len == kv_len), or entirely in the future (skipped) — a
# 3-way lax.switch keeps the kernel's causal flag static.
#
# The backward is the FlashAttention-2 scheme ring-ified: the saved
# GLOBAL lse and delta = rowsum(dO * O) drive the per-step _flash_bwd
# kernels; dq accumulates locally while dk/dv accumulate on buffers that
# rotate WITH their kv shards, arriving home after the full loop.

from unionml_tpu.ops.flash_attention import (  # noqa: E402
    _flash_bwd_bhsd,
    _flash_fwd_bhsd,
    _from_bhsd,
    _interpret,
    _to_bhsd,
)


def _merge_partial(acc_out, acc_lse, out_i, lse_i):
    """Merge NORMALIZED partials by logsumexp: the invariant is
    ``acc_out = sum_j out_j * exp(lse_j - acc_lse)`` — each update
    reweights both sides by their share of the new total.
    [BH, S, D] fp32 / [BH, S, 1] fp32."""
    both_empty = jnp.logical_and(acc_lse <= NEG_INF / 2, lse_i <= NEG_INF / 2)
    m = jnp.maximum(acc_lse, lse_i)
    w_acc = jnp.exp(acc_lse - m)
    w_i = jnp.exp(lse_i - m)
    total = jnp.maximum(w_acc + w_i, 1e-30)
    out = (acc_out * w_acc + out_i * w_i) / total
    lse = jnp.where(both_empty, NEG_INF, m + jnp.log(total))
    return out, lse


def _ring_flash_fwd_steps(q_bhsd, k0, v0, *, axis, causal, scale, block_q, block_kv,
                          num_heads):
    """Run the ring. ``q_bhsd``: [B*H, S_loc, D]; ``k0, v0``: 4D
    [B, S_loc, KVH, D] (rotate unrepeated). Returns (out fp32, lse)."""
    n = lax.axis_size(axis)
    my_idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    interpret = _interpret()
    bh, s_loc, d = q_bhsd.shape

    def flash(kv, causal_flag):
        k_r = _to_bhsd(_repeat_kv(kv[0], num_heads))
        v_r = _to_bhsd(_repeat_kv(kv[1], num_heads))
        return _flash_fwd_bhsd(
            q_bhsd, k_r, v_r, causal=causal_flag, scale=scale,
            block_q=block_q, block_kv=block_kv, interpret=interpret,
        )

    def step(carry, s):
        out, lse, k_cur, v_cur = carry
        kv_src = (my_idx - s) % n
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        if causal:
            rel = jnp.where(kv_src < my_idx, 0, jnp.where(kv_src == my_idx, 1, 2))
            out_i, lse_i = lax.switch(
                rel,
                [
                    lambda kv: flash(kv, False),
                    lambda kv: flash(kv, True),
                    lambda kv: (
                        jnp.zeros((bh, s_loc, d), q_bhsd.dtype),
                        jnp.full((bh, s_loc, 1), NEG_INF, jnp.float32),
                    ),
                ],
                (k_cur, v_cur),
            )
        else:
            out_i, lse_i = flash((k_cur, v_cur), False)
        out, lse = _merge_partial(out, lse, out_i.astype(jnp.float32), lse_i)
        return (out, lse, k_nxt, v_nxt), None

    out0 = jnp.zeros((bh, s_loc, d), jnp.float32)
    lse0 = jnp.full((bh, s_loc, 1), NEG_INF, jnp.float32)
    (out, lse, _, _), _ = lax.scan(step, (out0, lse0, k0, v0), jnp.arange(n))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis, causal, scale, block_q, block_kv):
    out, _ = _ring_flash_fwd(q, k, v, axis, causal, scale, block_q, block_kv)
    return out


def _ring_flash_fwd(q, k, v, axis, causal, scale, block_q, block_kv):
    num_heads = q.shape[2]
    q_bhsd = _to_bhsd(q)
    out, lse = _ring_flash_fwd_steps(
        q_bhsd, k, v, axis=axis, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, num_heads=num_heads,
    )
    out = out.astype(q.dtype)
    b = q.shape[0]
    return _from_bhsd(out, b, num_heads), (q, k, v, out, lse)


def _ring_flash_bwd(axis, causal, scale, block_q, block_kv, residuals, g):
    q, k, v, out_bhsd, lse = residuals
    b, s_loc, h, d = q.shape
    kv_heads = k.shape[2]
    n = lax.axis_size(axis)
    my_idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    interpret = _interpret()

    q_bhsd = _to_bhsd(q)
    do = _to_bhsd(g)
    delta = jnp.sum(
        do.astype(jnp.float32) * out_bhsd.astype(jnp.float32), axis=-1, keepdims=True
    )

    def flash_bwd(kv, causal_flag):
        """Returns (dq_i [BH,S,D], dk_i, dv_i 4D [B,S,KVH,D])."""
        k_r = _to_bhsd(_repeat_kv(kv[0], h))
        v_r = _to_bhsd(_repeat_kv(kv[1], h))
        dq_i, dk_r, dv_r = _flash_bwd_bhsd(
            q_bhsd, k_r, v_r, do, lse, delta,
            causal=causal_flag, scale=scale, block_q=block_q, block_kv=block_kv,
            interpret=interpret,
        )
        dk_i = _from_bhsd(dk_r, b, h)
        dv_i = _from_bhsd(dv_r, b, h)
        if kv_heads != h:
            group = h // kv_heads
            dk_i = dk_i.reshape(b, s_loc, kv_heads, group, d).sum(3)
            dv_i = dv_i.reshape(b, s_loc, kv_heads, group, d).sum(3)
        return dq_i, dk_i, dv_i

    def step(carry, s):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        kv_src = (my_idx - s) % n
        # issue the next kv rotation BEFORE the backward kernels (same as
        # the forward) so the ICI transfer overlaps the Pallas compute;
        # only dk/dv depend on this step's accumulation
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        if causal:
            rel = jnp.where(kv_src < my_idx, 0, jnp.where(kv_src == my_idx, 1, 2))
            dq_i, dk_i, dv_i = lax.switch(
                rel,
                [
                    lambda kv: flash_bwd(kv, False),
                    lambda kv: flash_bwd(kv, True),
                    lambda kv: (
                        jnp.zeros_like(q_bhsd),
                        jnp.zeros((b, s_loc, kv_heads, d), k.dtype),
                        jnp.zeros((b, s_loc, kv_heads, d), v.dtype),
                    ),
                ],
                (k_cur, v_cur),
            )
        else:
            dq_i, dk_i, dv_i = flash_bwd((k_cur, v_cur), False)
        dq = dq + dq_i.astype(dq.dtype)
        dk_cur = dk_cur + dk_i.astype(dk_cur.dtype)
        dv_cur = dv_cur + dv_i.astype(dv_cur.dtype)
        # gradient accumulators rotate with their kv shards: after the
        # full loop both are back at the shard's home device
        dk_nxt = lax.ppermute(dk_cur, axis, perm)
        dv_nxt = lax.ppermute(dv_cur, axis, perm)
        return (dq, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    dq0 = jnp.zeros_like(q_bhsd, jnp.float32)
    dk0 = jnp.zeros((b, s_loc, kv_heads, d), jnp.float32)
    dv0 = jnp.zeros((b, s_loc, kv_heads, d), jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(n)
    )
    return (
        _from_bhsd(dq, b, h).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis: str = "sequence",
    causal: bool = False,
    block_size: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-shard ring attention with Pallas flash local compute.

    Call inside shard_map with ``axis`` bound; ``q, k, v`` are local
    [B, S_local, H, D] shards (kv may have fewer GQA heads). Differentiable
    end to end (ring-level custom VJP; FlashAttention-2 backward kernels
    per step).
    """
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    return _ring_flash(q, k, v, axis, causal, scale_, block_size, block_size)


def ring_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    *,
    axis: str = "sequence",
    causal: bool = False,
    block_size: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Ring flash attention over globally-shaped [B,S,H,D] tensors."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis, None, None)
    body = functools.partial(
        ring_flash_attention_sharded, axis=axis, causal=causal,
        block_size=block_size, scale=scale,
    )
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
