"""Pallas TPU flash-attention kernels (forward + FlashAttention-2 backward).

VMEM-tiled attention with online softmax: the forward grid walks
``(batch*heads, q_blocks, kv_blocks)`` with the KV dimension innermost —
TPU grids execute sequentially, so fp32 accumulators in VMEM scratch carry
across KV iterations (running max / normalizer / weighted sum), and the
normalized output plus the per-row logsumexp are written once on the last
KV block. Causal q/kv block pairs that are fully masked are predicated out
with ``pl.when`` (no MXU work issued).

The backward is the FlashAttention-2 recomputation scheme as two Pallas
kernels (no stored score matrix):

- ``dq`` kernel, grid ``(bh, q_blocks, kv_blocks)`` (KV innermost):
  recomputes ``p = exp(s - lse)`` per tile and accumulates
  ``dq += ds @ k`` in VMEM scratch.
- ``dkv`` kernel, grid ``(bh, kv_blocks, q_blocks)`` (Q innermost):
  accumulates ``dv += pᵀ @ dO`` and ``dk += dsᵀ @ q``.

``delta = rowsum(dO * O)`` is computed outside the kernels (XLA fuses it).
Matmul operands stay in the input dtype (bf16 on TPU) with fp32
accumulation via ``preferred_element_type`` so the MXU runs at full rate;
softmax statistics are fp32 throughout. Block shapes default to 128×128
(MXU-shaped); ragged tails are handled by masking.

On non-TPU backends (CPU tests) the kernels run in interpreter mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _tile_masks(q_start, kv_start, block_q, block_kv, q_len, kv_len, causal,
                kv_start_valid=None, causal_block=1):
    """Validity (+ causal) mask for one [BQ, BKV] score tile.

    Causal alignment is bottom-right (the KV-cache decode convention,
    matching ``mha_reference``): with q_len < kv_len the queries are the
    LAST q_len positions, so query i sits at global position
    ``i + (kv_len - q_len)``.

    ``kv_start_valid``: optional traced scalar — kv positions BELOW it are
    masked out (left-padded prompt slots in generation prefill).

    ``causal_block`` (a power of two): causal across blocks of that many
    positions and bidirectional inside one: a query sees every kv position
    up to the end of its own block, ``kv_pos <= (q_pos | (block - 1))``.
    """
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.logical_and(q_pos < q_len, kv_pos < kv_len)
    if causal:
        q_end = q_pos + (kv_len - q_len)
        if causal_block > 1:
            q_end = q_end | (causal_block - 1)
        mask = jnp.logical_and(mask, q_end >= kv_pos)
    if kv_start_valid is not None:
        mask = jnp.logical_and(mask, kv_pos >= kv_start_valid)
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_kv,
                num_kv_blocks, q_len, kv_len, padded=False, pad_div=1,
                causal_block=1):
    if padded:
        # the padded path is forward-only (generation prefill): no
        # backward ever reads the lse, so it is neither declared nor
        # written (pure HBM savings in the memory-bound long-prefill
        # regime)
        pad_ref, o_ref, acc_ref, m_ref, l_ref = rest
        lse_ref = None
    else:
        pad_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    kv_start = ki * block_kv
    # causal: skip kv blocks entirely in the future of this q block
    # bottom-right causal: query block's last GLOBAL position is
    # q_start + block_q - 1 + (kv_len - q_len)
    # (under block-causal masking the tile's last query sees to the end of
    # its block: at most causal_block - 1 positions further)
    run = jnp.logical_or(
        jnp.logical_not(causal),
        kv_start <= (
            q_start + block_q - 1 + (kv_len - q_len) if causal_block == 1
            else (q_start + block_q - 1 + (kv_len - q_len)) | (causal_block - 1)
        ),
    )
    # pad lives in SMEM as a whole per-BATCH vector (a (1,1) VMEM block
    # would break Mosaic's (8,128) minimum-tile rule); the grid row is
    # batch*heads, so divide the head factor back out
    pad = pad_ref[pl.program_id(0) // pad_div] if padded else None
    if padded:
        # skip kv blocks that lie entirely inside this row's left padding
        run = jnp.logical_and(run, kv_start + block_kv - 1 >= pad)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                               # [BQ, D] input dtype
        k = k_ref[0]                               # [BKV, D]
        # zero padded kv rows: OOB block reads are undefined (NaN in
        # interpret mode) and 0 * NaN would contaminate the p @ v matmul
        kv_valid = (kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_kv, 1), 0)) < kv_len
        v = jnp.where(kv_valid, v_ref[0], jnp.zeros_like(v_ref[0]))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # [BQ, BKV] fp32

        mask = _tile_masks(q_start, kv_start, block_q, block_kv, q_len, kv_len,
                           causal, kv_start_valid=pad, causal_block=causal_block)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]                          # [BQ, 1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # padded rows (l == 0) get lse = 0 so the backward's
            # exp(NEG_INF - lse) stays 0 instead of overflowing
            lse_ref[0] = jnp.where(
                l_ref[:] > 0.0,
                m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30)),
                0.0,
            )


def _flash_fwd_bhsd(q, k, v, *, causal, scale, block_q, block_kv, interpret):
    """q,k,v: [BH, S, D] (kv heads already repeated) → (out, lse[BH,S,1])."""
    from jax.experimental.pallas import tpu as pltpu

    bh, q_len, head_dim = q.shape
    kv_len = k.shape[1]
    block_q = min(block_q, q_len)
    block_kv = min(block_kv, kv_len)
    num_q_blocks = pl.cdiv(q_len, block_q)
    num_kv_blocks = pl.cdiv(kv_len, block_kv)

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
        q_len=q_len,
        kv_len=kv_len,
    )
    grid = (bh, num_q_blocks, num_kv_blocks)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_kv, head_dim), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_kv, head_dim), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q_len, head_dim), q.dtype),
            jax.ShapeDtypeStruct((bh, q_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _flash_fwd_padded(q, k, v, pad_b, *, causal, scale, block_q, block_kv,
                      interpret, causal_block=1):
    """Forward-only padded flash over UNREPEATED GQA heads.

    q: [B, S, H, D]; k/v: [B, S, KVH, D] — the kv operands stay at
    kv-head width (the grid's kv index maps fold the q-head group back
    to its kv head), so no [B, S, H, D] repeated copies are ever
    materialized — this path exists for long-prefill memory, where a
    num_heads/num_kv_heads repeat would multiply fresh-k/v HBM by 4 at
    Llama geometry. ``pad_b``: [B] int32 per-BATCH first-visible kv
    position (SMEM; the kernel divides the head factor out of the grid
    row).
    """
    from jax.experimental.pallas import tpu as pltpu

    b, q_len, h, head_dim = q.shape
    kvh = k.shape[2]
    group = h // kvh
    kv_len = k.shape[1]
    qb = _to_bhsd(q)                      # [B*H, S, D]
    kb = _to_bhsd(k)                      # [B*KVH, S, D]
    vb = _to_bhsd(v)
    block_q = min(block_q, q_len)
    block_kv = min(block_kv, kv_len)
    num_q_blocks = pl.cdiv(q_len, block_q)
    num_kv_blocks = pl.cdiv(kv_len, block_kv)

    def kv_row(bh):
        return (bh // h) * kvh + (bh % h) // group

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
        q_len=q_len,
        kv_len=kv_len,
        padded=True,
        pad_div=h,
        causal_block=causal_block,
    )
    grid = (b * h, num_q_blocks, num_kv_blocks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec(
                (1, block_kv, head_dim), lambda bh, qi, ki: (kv_row(bh), ki, 0)
            ),
            pl.BlockSpec(
                (1, block_kv, head_dim), lambda bh, qi, ki: (kv_row(bh), ki, 0)
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, head_dim), lambda bh, qi, ki: (bh, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, q_len, head_dim), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb, jnp.asarray(pad_b, jnp.int32))
    return _from_bhsd(out, b, h)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *,
               scale, causal, block_q, block_kv, num_kv_blocks, q_len, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    kv_start = ki * block_kv
    # bottom-right causal: query block's last GLOBAL position is
    # q_start + block_q - 1 + (kv_len - q_len)
    run = jnp.logical_or(
        jnp.logical_not(causal),
        kv_start <= q_start + block_q - 1 + (kv_len - q_len),
    )

    @pl.when(run)
    def _compute():
        # zero padded rows: ragged-tail OOB block reads are undefined (NaN
        # in interpret mode) and would poison the accumulators via 0 * NaN
        q_valid = (q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)) < q_len
        kv_valid = (kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_kv, 1), 0)) < kv_len
        q = jnp.where(q_valid, q_ref[0], jnp.zeros_like(q_ref[0]))
        k = jnp.where(kv_valid, k_ref[0], jnp.zeros_like(k_ref[0]))
        v = jnp.where(kv_valid, v_ref[0], jnp.zeros_like(v_ref[0]))
        do = jnp.where(q_valid, do_ref[0], jnp.zeros_like(do_ref[0]))
        lse = jnp.where(q_valid, lse_ref[0], 0.0)   # [BQ, 1] fp32
        delta = jnp.where(q_valid, delta_ref[0], 0.0)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_masks(q_start, kv_start, block_q, block_kv, q_len, kv_len, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [BQ, BKV] fp32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                           # [BQ, BKV]
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *,
                scale, causal, block_q, block_kv, num_q_blocks, q_len, kv_len):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    kv_start = ki * block_kv
    run = jnp.logical_or(
        jnp.logical_not(causal),
        q_start + block_q - 1 + (kv_len - q_len) >= kv_start,
    )

    @pl.when(run)
    def _compute():
        # zero padded rows (see _dq_kernel)
        q_valid = (q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)) < q_len
        kv_valid = (kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_kv, 1), 0)) < kv_len
        q = jnp.where(q_valid, q_ref[0], jnp.zeros_like(q_ref[0]))
        k = jnp.where(kv_valid, k_ref[0], jnp.zeros_like(k_ref[0]))
        v = jnp.where(kv_valid, v_ref[0], jnp.zeros_like(v_ref[0]))
        do = jnp.where(q_valid, do_ref[0], jnp.zeros_like(do_ref[0]))
        lse = jnp.where(q_valid, lse_ref[0], 0.0)   # [BQ, 1]
        delta = jnp.where(q_valid, delta_ref[0], 0.0)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_masks(q_start, kv_start, block_q, block_kv, q_len, kv_len, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [BQ, BKV]
        p_cast = p.astype(do.dtype)
        dv_acc[:] += jax.lax.dot_general(
            p_cast, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                           # [BKV, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                           # [BQ, BKV]
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                           # [BKV, D]

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_bhsd(q, k, v, do, lse, delta, *, causal, scale, block_q, block_kv,
                    interpret):
    """[BH, S, D] gradients via the two FlashAttention-2 backward kernels."""
    from jax.experimental.pallas import tpu as pltpu

    bh, q_len, head_dim = q.shape
    kv_len = k.shape[1]
    block_q = min(block_q, q_len)
    block_kv = min(block_kv, kv_len)
    num_q_blocks = pl.cdiv(q_len, block_q)
    num_kv_blocks = pl.cdiv(kv_len, block_kv)

    q_spec = pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, i, 0))
    kv_spec_dq = pl.BlockSpec((1, block_kv, head_dim), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q,
            block_kv=block_kv, num_kv_blocks=num_kv_blocks,
            q_len=q_len, kv_len=kv_len,
        ),
        grid=(bh, num_q_blocks, num_kv_blocks),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, q_len, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dkv grid: kv blocks outer, q blocks inner
    q_spec_kv = pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, j, 0))
    kv_spec_kv = pl.BlockSpec((1, block_kv, head_dim), lambda b, i, j: (b, i, 0))
    row_spec_kv = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_kv=block_kv, num_q_blocks=num_q_blocks,
            q_len=q_len, kv_len=kv_len,
        ),
        grid=(bh, num_kv_blocks, num_q_blocks),
        in_specs=[q_spec_kv, kv_spec_kv, kv_spec_kv, q_spec_kv, row_spec_kv, row_spec_kv],
        out_specs=[kv_spec_kv, kv_spec_kv],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kv_len, head_dim), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_len, head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_kv):
    out, _ = _flash_fwd_res(q, k, v, causal, scale, block_q, block_kv)
    return out


def _flash_fwd_res(q, k, v, causal, scale, block_q, block_kv):
    from unionml_tpu.ops.attention import _repeat_kv

    num_q_heads = q.shape[2]
    k_r = _repeat_kv(k, num_q_heads)
    v_r = _repeat_kv(v, num_q_heads)
    out_bhsd, lse = _flash_fwd_bhsd(
        _to_bhsd(q), _to_bhsd(k_r), _to_bhsd(v_r),
        causal=causal, scale=scale, block_q=block_q, block_kv=block_kv,
        interpret=_interpret(),
    )
    b, _, h, _ = q.shape
    return _from_bhsd(out_bhsd, b, h), (q, k, v, out_bhsd, lse)


def _flash_bwd(causal, scale, block_q, block_kv, residuals, g):
    from unionml_tpu.ops.attention import _repeat_kv

    q, k, v, out_bhsd, lse = residuals
    b, s, h, d = q.shape
    kv_heads = k.shape[2]
    k_r = _repeat_kv(k, h)
    v_r = _repeat_kv(v, h)
    do = _to_bhsd(g)
    delta = jnp.sum(
        do.astype(jnp.float32) * out_bhsd.astype(jnp.float32), axis=-1, keepdims=True
    )
    dq, dk_r, dv_r = _flash_bwd_bhsd(
        _to_bhsd(q), _to_bhsd(k_r), _to_bhsd(v_r), do, lse, delta,
        causal=causal, scale=scale, block_q=block_q, block_kv=block_kv,
        interpret=_interpret(),
    )
    dq = _from_bhsd(dq, b, h)
    dk = _from_bhsd(dk_r, b, h)
    dv = _from_bhsd(dv_r, b, h)
    if kv_heads != h:
        # GQA: sum gradients over the repeated query-head groups
        group = h // kv_heads
        kv_len = k.shape[1]
        dk = dk.reshape(b, kv_len, kv_heads, group, d).sum(axis=3)
        dv = dv.reshape(b, kv_len, kv_heads, group, d).sum(axis=3)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_res, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: Optional[int] = None,
    kv_valid_start: Optional[jnp.ndarray] = None,
    causal_block: int = 1,
) -> jnp.ndarray:
    """Flash attention over [B,S,H,D] tensors (GQA-aware, differentiable).

    Block defaults are path-dependent (``block_kv=None`` picks them):
    the differentiable path uses 512×512 — TPU grids pay a fixed
    per-program cost, so fewer/bigger blocks win as long as the working
    set fits VMEM (measured on v5e: 512-blocks are ~2x faster than
    128-blocks at S=4096 and ~7x faster than XLA attention forward at
    that length); the forward-only padded path (``kv_valid_start``)
    widens kv blocks to ``min(2048·128/head_dim, kv_len)`` — measured
    6.5% end-to-end at 4k prompts. Blocks are clamped to the sequence
    length, so short sequences degenerate to a single tile per
    (batch, head) — the best flash configuration there too.

    ``kv_valid_start``: optional [B] int32 — per-row first visible kv
    position; kv positions below it are masked out (left-padded prompts
    in generation prefill). FORWARD-ONLY: this path has no backward
    (generation never differentiates); differentiating it raises.
    Fully-masked query rows (q inside the padding) return zeros.

    ``causal_block`` (a power of two, with ``causal`` and
    ``kv_valid_start``): the block-causal mask of a model that generates by
    blocks — causal across blocks of that many positions, bidirectional
    inside one. 1 is plain causal masking.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if causal_block != 1:
        if causal_block < 1 or causal_block & (causal_block - 1):
            raise ValueError(f"causal_block {causal_block} must be a power of two")
        if not causal or kv_valid_start is None:
            raise ValueError(
                "causal_block is a mask of the forward-only path: pass causal=True "
                "and kv_valid_start (zeros for a prompt that is not left-padded)"
            )
    if kv_valid_start is None:
        # training/differentiable path: 512x512 is the measured optimum
        # (docstring above)
        return _flash(q, k, v, causal, scale, block_q, block_kv or 512)
    if block_kv is None:
        # forward-only padded path (generation prefill): wider kv blocks
        # amortize the per-program grid cost — measured end-to-end 6.5%
        # at 1.5B x 4k prompts (block_kv 512 -> 2048, 883 -> 829 ms).
        # 2048 was the largest that compiled at head_dim 128; scale the
        # cap down for larger head dims so the kv VMEM tile footprint
        # (block_kv x head_dim) stays at the measured-safe budget
        cap = min(2048, max(512, 2048 * 128 // q.shape[-1]))
        block_kv = min(cap, k.shape[1])
    return _flash_fwd_padded(
        q, k, v, kv_valid_start,
        causal=causal, scale=scale, block_q=block_q, block_kv=block_kv,
        interpret=_interpret(), causal_block=causal_block,
    )
