"""Paged-attention decode: block-table KV gather with online softmax.

The decode companion to the engine's block-paged KV pool
(:mod:`unionml_tpu.serving.kv_pool`): per layer the KV cache is a
global pool ``[num_blocks, block_size, kv_heads, head_dim]`` and each
resident slot owns an int32 block table mapping logical rows to pool
blocks. One decode step attends each slot's single query against its
table-addressed blocks — the PagedAttention formulation (Kwon et al.,
SOSP 2023) on the TPU layout this repo already uses for its flash
kernels.

Two implementations behind one dispatcher:

- :func:`paged_attention_reference` — pure JAX: ``jnp.take`` gathers
  the table's blocks into a contiguous ``[B, W*block, Hk, D]`` view and
  runs the SAME masked math as the contiguous engine path
  (:func:`~unionml_tpu.ops.attention.cached_attention` /
  ``quantized_cache_attention``). Columns past a row's length carry a
  ``-1e30`` bias, so their softmax weights underflow to exact zeros and
  the outputs are **bit-identical** to the contiguous cache path on the
  same values — the CPU/tier-1 parity anchor every paged-engine test
  asserts against.
- the Pallas kernel (``impl="pallas"``) — grid ``(batch, table_width)``
  with the block dimension innermost: the block table rides in as a
  **scalar-prefetch** operand so each grid step's BlockSpec index map
  selects the pool block to DMA (no gathered copy of the cache is ever
  materialized — the entire point: decode reads exactly the blocks a
  sequence owns). fp32 online-softmax accumulators (running max /
  normalizer / weighted sum) live in VMEM scratch and carry across the
  block iterations, the same scheme as
  :mod:`~unionml_tpu.ops.flash_attention`; blocks entirely past a
  row's length are predicated out with ``pl.when``. GQA reads the pool
  at kv-head width (no head repeat): a block tile is viewed as
  ``[block * Hk, D]`` (a bitcast of the pool), ONE matmul scores every
  q head against every (position, kv head) row, and a mask keeps each
  q head's own kv head — Mosaic cannot lay out per-head sublane slices
  of a ``[block, Hk, D]`` tile, and the step is bound by the HBM read,
  not the MXU. int8 KV pools fold their per-(row, head) dequant scales
  into the score/weight math in-kernel (never a dequantized pool copy;
  the fp32 scale planes ride as one lane-dense ``[1, block * Hk]`` row
  per block, which costs an XLA relayout of the planes per call) — the
  same numerics contract as the existing kernels: fp32 softmax
  statistics, MXU matmuls in the input dtype with fp32 accumulation,
  outputs equal to the reference up to float reduction order.

``impl="auto"`` picks the kernel on TPU and the reference elsewhere
(CPU tests run the kernel in interpreter mode only when asked).
Interpret mode proves the math, not that Mosaic accepts the kernel:
``tests/unit/test_tpu_compile.py`` compiles it for v5e at the Llama-3-8B
and 16/16-MHA geometries, and ``chip_smoke.py`` runs it there.
Block-size tuning is data-driven via the paged leg of
``benchmarks/attn_kernels.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

__all__ = ["paged_attention", "paged_attention_reference"]


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _check_shapes(q, k, v, block_table, lengths, k_scale, v_scale):
    if q.ndim != 3:
        raise ValueError(f"q must be [batch, q_heads, head_dim], got {q.shape}")
    if k.ndim != 4 or v.shape != k.shape:
        raise ValueError(
            "k/v pools must be [num_blocks, block_size, kv_heads, "
            f"head_dim], got {k.shape} / {v.shape}"
        )
    if block_table.ndim != 2 or block_table.shape[0] != q.shape[0]:
        raise ValueError(
            f"block_table must be [batch, table_width], got "
            f"{block_table.shape} for batch {q.shape[0]}"
        )
    if lengths.shape != (q.shape[0],):
        raise ValueError(
            f"lengths must be [batch], got {lengths.shape}"
        )
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale come together (int8 pools)")
    if q.shape[1] % k.shape[2]:
        raise ValueError(
            f"q heads {q.shape[1]} must be a multiple of kv heads "
            f"{k.shape[2]}"
        )


def paged_attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Pure-JAX paged decode attention (the parity/CPU path).

    ``jnp.take`` flattens the block table into a contiguous per-row KV
    view, then runs the exact contiguous-cache decode math
    (:func:`~unionml_tpu.ops.attention._grouped_cache_attention` with
    the same ``-1e30`` bias construction the engine's contiguous path
    uses) — masked tail columns contribute exact zeros, so outputs are
    bit-identical to a contiguous cache holding the same rows.

    Shapes: ``q`` [B, Hq, D]; ``k``/``v`` [N, block, Hk, D] (int8 with
    fp32 ``k_scale``/``v_scale`` [N, block, Hk]); ``block_table``
    [B, W] int32; ``lengths`` [B] int32 (visible rows per batch row —
    a decode step passes ``fill + 1`` so the just-written row sees
    itself). Returns [B, Hq, D] in ``q.dtype``.
    """
    from unionml_tpu.ops.attention import _grouped_cache_attention

    _check_shapes(q, k, v, block_table, lengths, k_scale, v_scale)
    batch, w = block_table.shape
    block = k.shape[1]
    flat = block_table.reshape(-1)

    def gather(pool):
        g = jnp.take(pool, flat, axis=0)          # [B*W, block, ...]
        return g.reshape((batch, w * block) + pool.shape[2:])

    gk, gv = gather(k), gather(v)
    gks = None if k_scale is None else gather(k_scale)
    gvs = None if v_scale is None else gather(v_scale)
    # the engine's contiguous decode bias, verbatim: kv slot j visible
    # to the (single) query iff j <= q_pos, with q_pos = lengths - 1
    kv_pos = jnp.arange(w * block)[None, :]
    visible = kv_pos[None] <= (lengths.astype(jnp.int32) - 1)[:, None, None]
    bias = jnp.where(visible, 0.0, NEG_INF)[:, None]   # [B, 1, 1, W*block]
    out = _grouped_cache_attention(
        q[:, None], gk, gv, k_scale=gks, v_scale=gvs, bias=bias, scale=scale,
    )
    return out[:, 0]


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  scale, block, kv_heads, group, num_blocks, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    w = pl.program_id(1)
    q_heads = kv_heads * group
    cols = block * kv_heads

    @pl.when(w == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    # skip blocks entirely past this row's visible rows (pl.when: no
    # MXU work issued; the DMA fetched the trash block the host parks
    # out-of-range table entries on)
    run = w * block < length

    @pl.when(run)
    def _compute():
        q = q_ref[0]                               # [Hq, D] input dtype
        # the block tile arrives flattened [block * Hk, D]: row
        # r = pos * Hk + head. ONE matmul scores every q head against
        # every (pos, head) row and the mask keeps each q head's own
        # kv head — the no-repeat GQA read without per-head sublane
        # slices of the tile (which Mosaic cannot lay out). The
        # off-head columns are wasted MXU work on a step the HBM read
        # bounds.
        k = k_ref[0].astype(q.dtype)
        v = v_ref[0].astype(q.dtype)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        q_head = jax.lax.broadcasted_iota(jnp.int32, (q_heads, 1), 0)
        valid = (col % kv_heads == q_head // group) & (
            w * block + col // kv_heads < length
        )                                          # [Hq, cols]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                  # [Hq, cols] fp32
        if quantized:
            # int8 pool: per-(row, head) dequant scale folds into
            # the scores (k) and softmax weights (v) — the
            # _grouped_cache_attention contract, in-kernel
            s = s * ks_ref[0]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:]                          # [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        corr = jnp.exp(
            jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe)
        )
        # the normalizer sums the UNSCALED softmax weights; the
        # v dequant scale rides only the weighted-value matmul
        # (the _grouped_cache_attention contract)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0]
        # zero invalid value rows: 0-weight x garbage must stay 0.
        # The row-oriented mask comes from its own iota — reshaping
        # the [1, cols] one is a lane->sublane cast Mosaic refuses.
        row = jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
        v = jnp.where(w * block + row // kv_heads < length, v, 0)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(w == num_blocks - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        ).astype(o_ref.dtype)


def _paged_pallas(q, k, v, block_table, lengths, *, k_scale, v_scale,
                  scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    batch, q_heads, head_dim = q.shape
    num_pool_blocks, block, kv_heads, _ = k.shape
    w = block_table.shape[1]
    group = q_heads // kv_heads
    cols = block * kv_heads
    quantized = k_scale is not None

    def kv_map(b, wi, table, lens):
        return (table[b, wi], 0, 0)

    def q_map(b, wi, table, lens):
        return (b, 0, 0)

    # [N, block, Hk, D] -> [N, block * Hk, D] merges the two middle
    # dims under an unchanged minor dim: a bitcast of the pool on TPU
    # (checked in the compiled HLO at head_dim 128), never a copy
    in_specs = [
        pl.BlockSpec((1, q_heads, head_dim), q_map),
        pl.BlockSpec((1, cols, head_dim), kv_map),
        pl.BlockSpec((1, cols, head_dim), kv_map),
    ]
    operands = [
        q,
        k.reshape(num_pool_blocks, cols, head_dim),
        v.reshape(num_pool_blocks, cols, head_dim),
    ]
    if quantized:
        # scale planes ride as one lane-dense row per block, matching
        # the score columns
        in_specs += [pl.BlockSpec((1, 1, cols), kv_map)] * 2
        operands += [
            k_scale.reshape(num_pool_blocks, 1, cols),
            v_scale.reshape(num_pool_blocks, 1, cols),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, q_heads, head_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((q_heads, head_dim), jnp.float32),
            pltpu.VMEM((q_heads, 1), jnp.float32),
            pltpu.VMEM((q_heads, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel,
        scale=scale,
        block=block,
        kv_heads=kv_heads,
        group=group,
        num_blocks=w,
        quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, q_heads, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_attention",
    )(
        block_table.astype(jnp.int32), lengths.astype(jnp.int32), *operands
    )


def paged_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Single-step decode attention over a block-paged KV pool.

    Shapes: ``q`` [B, Hq, D] (one query per row — the decode step);
    ``k``/``v`` [num_blocks, block, Hk, D] pools (bf16, or int8 with
    fp32 ``k_scale``/``v_scale`` [num_blocks, block, Hk]);
    ``block_table`` [B, W] int32 (entries past a row's coverage point
    at the trash block); ``lengths`` [B] int32 visible rows. Returns
    [B, Hq, D] in ``q.dtype``.

    ``impl``: ``"reference"`` (pure JAX gather — bit-identical to the
    contiguous cache path, the tier-1/CPU anchor), ``"pallas"`` (the
    scalar-prefetch kernel; interpreter mode off-TPU), or ``"auto"``
    (pallas on TPU, reference elsewhere).
    """
    _check_shapes(q, k, v, block_table, lengths, k_scale, v_scale)
    if impl == "auto":
        impl = "reference" if _interpret() else "pallas"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if impl == "reference":
        return paged_attention_reference(
            q, k, v, block_table, lengths,
            k_scale=k_scale, v_scale=v_scale, scale=scale,
        )
    if impl != "pallas":
        raise ValueError(f"unknown paged attention impl {impl!r}")
    return _paged_pallas(
        q, k, v, block_table, lengths,
        k_scale=k_scale, v_scale=v_scale, scale=scale,
        interpret=_interpret(),
    )
