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
- the Pallas kernel (``impl="pallas"``) — the grid is the rows
  (``(batch,)``, in order), and a row's **groups** are a loop inside its
  grid step: a group is P pool blocks, P worked out from the shapes
  (:func:`_pages_per_step`: 512 KV rows a group, fewer where the gather
  buffers would outgrow their VMEM budget, never more than the table is
  wide), and the loop's trip count is ``cdiv(min(length, W * block),
  P * block)``, read from the scalar-prefetched lengths. So a call costs
  its rows plus the groups that hold visible KV, whatever the table's
  width (``max_new_tokens`` and the largest bucket); as a grid dimension
  the groups cost a step each, scored or not. A row of length 0 — what
  :class:`~unionml_tpu.models.layers.Attention` hands over for the rows
  the engine says are not live — starts no copy, runs no iteration and
  writes zeros: on a v5e about 0.3 us (PERF.md, section 6, PR 28). The
  pools stay in HBM (``memory_space=pl.ANY``, no gathered copy of the
  cache is ever materialized) and the block table and lengths ride in as
  **scalar-prefetch** operands: an iteration reads the group's table
  entries and starts one async copy per pool block that holds visible
  rows — blocks are not contiguous in the pool — into a double-buffered
  VMEM scratch, and the next group's copies (the same row's, or the
  first group of the next row that sees anything) fly while this one is
  scored. The table's last group may be partial, and no entry past the
  table is read. fp32 online-softmax accumulators (running max /
  normalizer / weighted sum) live in VMEM scratch and carry across a
  row's groups, the same scheme as
  :mod:`~unionml_tpu.ops.flash_attention`. GQA reads the pool at
  kv-head width (no head repeat), in one of two schemes, by what the call
  hands over. **Two pools** (``k`` and ``v``): the gathered group is viewed
  as ``[P * block * Hk, D]``, ONE matmul scores every q head against every
  (position, kv head) row, and a mask keeps each q head's own kv head: a
  ``[Hq, P * block * Hk]`` score tile (:func:`score_tile`). With one query
  a row the tile is short and the copies bind, not the masked columns.
  **One pool of fused rows** (``v=None``: a position's key heads and its
  value heads behind them, :func:`_fused_kernel`): each key head's rows are
  read out of the buffer and scored against that head's own ``queries x
  group`` query rows, ``[queries * group, P * block]`` a key head, and its
  value rows weighed by those scores. A head's rows lie ``2 Hk`` buffer
  rows apart; Mosaic lays out no such slice of a *value*, but it does a
  strided load of a *ref*'s 32-bit sublanes: a bfloat16 buffer is read as
  words that hold two stored heads of one position, the group's first half
  of positions and its second apart, and a shift, a mask and an or put a
  head's rows of both halves together
  (PERF.md, section 6, PR 45: with 4 queries a row over 4 + 4 stored heads
  the one-matmul tile was ``[128, 4096]`` a group, seven columns of eight
  masked away, and the vector unit bound it at four times its bytes' time).
  Over fused rows the queries of a row may each come with a limit of their
  own (``limits=``: two blocks of positions in one call, the earlier blind
  to the later; a third scalar-prefetched operand, applied where the
  length's mask is, while the copies and the walk go by the row's length).
  int8 KV pools fold their per-(row, head) dequant scales into the
  score/weight math in-kernel (never a dequantized pool copy; the fp32
  scale planes ride as one lane-dense ``[1, block * Hk]`` row per block,
  which costs an XLA relayout of the planes per call) — the same
  numerics contract as the existing kernels: fp32 softmax statistics,
  MXU matmuls in the input dtype with fp32 accumulation, outputs equal
  to the reference up to float reduction order. The grid runs in order
  (a row prefetches for the next one), so a two-core chip does not split
  the batch.

``impl="auto"`` picks the kernel on TPU and the reference elsewhere
(CPU tests run the kernel in interpreter mode only when asked).
Interpret mode proves the math, not that Mosaic accepts the kernel:
``tests/unit/test_tpu_compile.py`` compiles it for v5e at the Llama-3-8B
and 16/16-MHA geometries and at the benchmark's two serving shapes (32
rows over a 101-block table at GQA 32/8, over a 261-block one at MHA
32/32), and ``chip_smoke.py`` runs it there. What it
costs on the chip is the benchmark's ``paged_attn_ms_per_step``
(``chipbench/layer_metrics/``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

__all__ = [
    "latent_attention", "paged_attention", "paged_attention_reference",
    "paged_index_scores", "paged_index_scores_reference",
    "paged_latent_attention", "paged_latent_attention_reference",
    "paged_sparse_attention", "paged_sparse_attention_reference", "score_tile",
]


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _check_shapes(q, k, v, block_table, lengths, k_scale, v_scale, limits=None):
    if limits is not None and (q.ndim != 4 or limits.shape != q.shape[:2]):
        raise ValueError(
            f"limits are [batch, queries] beside q [batch, queries, q_heads, head_dim], got {limits.shape} "
            f"for q {q.shape}"
        )
    if v is None:
        # one pool of fused rows: a position's key heads, its value heads behind
        if k.ndim != 4 or k.shape[2] % 2 or k_scale is not None or v_scale is not None:
            raise ValueError(
                "a fused pool (v=None) is [num_blocks, block_size, 2 * kv_heads, head_dim] "
                f"without scales, got {k.shape}"
            )
        k, v = k[:, :, :k.shape[2] // 2], k[:, :, k.shape[2] // 2:]
    if q.ndim not in (3, 4):
        raise ValueError(
            "q must be [batch, q_heads, head_dim] or [batch, queries, q_heads, "
            f"head_dim], got {q.shape}"
        )
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
    if q.shape[-2] % k.shape[2]:
        raise ValueError(
            f"q heads {q.shape[-2]} must be a multiple of kv heads "
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
    limits: Optional[jnp.ndarray] = None,
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
    itself). Returns [B, Hq, D] in ``q.dtype``. ``q`` [B, Q, Hq, D] is Q
    queries a row that share the row's length (every one sees all of its
    visible rows): returns [B, Q, Hq, D]. ``v=None``: ``k`` is one pool of
    fused rows [N, block, 2 Hk, D], the key heads first. ``limits`` [B, Q]
    int32: query ``j`` of row ``b`` sees the first ``min(lengths[b],
    limits[b, j])`` rows.
    """
    from unionml_tpu.ops.attention import _grouped_cache_attention

    _check_shapes(q, k, v, block_table, lengths, k_scale, v_scale, limits)
    if v is None:
        k, v = k[:, :, :k.shape[2] // 2], k[:, :, k.shape[2] // 2:]
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
    last = (lengths.astype(jnp.int32) - 1)[:, None]
    if limits is not None:
        last = jnp.minimum(last, limits.astype(jnp.int32) - 1)
    visible = kv_pos[None] <= last[:, :, None]
    bias = jnp.where(visible, 0.0, NEG_INF)[:, None]   # [B, 1, 1 or Q, W*block]
    out = _grouped_cache_attention(
        q[:, None] if q.ndim == 3 else q, gk, gv,
        k_scale=gks, v_scale=gvs, bias=bias, scale=scale,
    )
    return out[:, 0] if q.ndim == 3 else out


# KV rows (positions) one group gathers and scores. A group costs a fixed
# price (the DMA issue, two matmuls' latency) whatever it scores, so it is
# made wide enough that the price is paid a few times a row and not once
# a pool block. On a v5e 256 and 512 tie at chat lengths (a few hundred
# rows) and 512 wins on long rows; 128 and 1024 lose at both (PERF.md,
# section 6, PR 26). At MHA the VMEM budget below makes it 128 rows, and
# 256 there (8 MiB of buffers) is no faster: a full group is bound by its
# 2 MB of copies, not by the fixed price (PERF.md, section 6, PR 28).
_ROWS_PER_STEP = 512
# what the two double-buffered K and V gather buffers may take of VMEM
_KV_BUFFER_BYTES = 4 * 1024 * 1024


def _pages_per_step(block, kv_heads, head_dim, itemsize, width):
    """Pool blocks one group of a row holds, from what the call can see:
    as many as make ``_ROWS_PER_STEP`` KV rows, fewer where four buffers
    of that many rows (K and V, each double-buffered) would pass
    ``_KV_BUFFER_BYTES``, never more than the table is wide."""
    row_bytes = kv_heads * head_dim * itemsize
    rows = min(_ROWS_PER_STEP, _KV_BUFFER_BYTES // (4 * row_bytes))
    return max(1, min(rows // block, width))


def score_tile(block, q_heads, kv_heads, head_dim, itemsize, width, *, queries=1, fused=False):
    """``[query rows, columns]`` of the float32 score tile one group of the
    kernel works on, from the shapes of the call: two pools are scored by
    one matmul of every query row with every (position, kv head) row of the
    group; a fused pool a key head at a time, each against its own
    ``queries x group`` query rows and the group's positions (the tile is
    the key heads' together)."""
    positions = _pages_per_step(block, kv_heads, head_dim, itemsize, width) * block
    return [queries * q_heads, positions if fused else positions * kv_heads]


def _paged_kernel(table_ref, len_ref, q_ref, *rest, scale, block, kv_heads,
                  group, width, pages, quantized, queries=1):
    from jax.experimental.pallas import tpu as pltpu

    # K and V pools (and, for int8 pools, their scale planes) in HBM,
    # the output, then one double-buffered gather buffer per pool
    n = 4 if quantized else 2
    pools, o_ref, bufs = rest[:n], rest[n], rest[n + 1:2 * n + 1]
    sem, state, acc_ref, m_ref, l_ref = rest[2 * n + 1:]
    k_buf, v_buf, *scale_bufs = bufs
    b = pl.program_id(0)
    batch = pl.num_programs(0)
    # a row's queries lie head-major behind one another: query row
    # j * Hq + h is head h of query j, and all of them share the length
    q_heads = kv_heads * group * queries
    rows = pages * block                   # KV positions a group holds
    cols = rows * kv_heads                 # (position, kv head) columns

    def visible(row):
        # a stale length may not reach past the table
        return jnp.clip(len_ref[row], 0, width * block)

    def copies(row, grp, slot, fn):
        """``fn`` on the copy of every pool block of (row, grp) that holds
        visible rows, into buffer ``slot``: the same descriptors start a
        gather and wait for it."""
        def page(j, carry):
            src = table_ref[row, grp * pages + j]
            for pool, buf in zip(pools, bufs):
                fn(pltpu.make_async_copy(pool.at[src], buf.at[slot, j], sem.at[slot]))
            return carry
        live_pages = jnp.minimum(pl.cdiv(visible(row), block) - grp * pages, pages)
        jax.lax.fori_loop(0, live_pages, page, 0)

    # state[0]: the buffer the next group to run reads; state[1]: whether
    # an earlier row already started that group's gather
    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    length = visible(b)
    groups = pl.cdiv(length, rows)         # this row's trip count

    def score(g, slot):
        """Fold group ``g`` of this row, gathered in buffer ``slot``, into
        the online-softmax state."""
        q = q_ref[0]                               # [Hq, D] input dtype
        # the gathered pages lie flattened [pages * block * Hk, D]: row
        # r = pos * Hk + head. ONE matmul scores every q head against
        # every (pos, head) row and the mask keeps each q head's own
        # kv head — the no-repeat GQA read without per-head sublane
        # slices of the tile (which Mosaic cannot lay out). The MXU's
        # time is the loading of the K tiles either way; the off-head
        # columns cost vector work on the score tile.
        k = k_buf[slot].reshape(cols, -1).astype(q.dtype)
        v = v_buf[slot].reshape(cols, -1).astype(q.dtype)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        q_head = jax.lax.broadcasted_iota(jnp.int32, (q_heads, 1), 0)
        if queries > 1:
            q_head = q_head % (kv_heads * group)
        # pages past the row's last were not copied (the buffer holds an
        # earlier group's rows there): the length mask covers them
        seen = g * rows + col // kv_heads < length  # [1, cols]
        valid = (col % kv_heads == q_head // group) & seen  # [Hq, cols]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                  # [Hq, cols] fp32
        if quantized:
            # int8 pool: per-(row, head) dequant scale folds into
            # the scores (k) and softmax weights (v) — the
            # _grouped_cache_attention contract, in-kernel
            s = s * scale_bufs[0][slot].reshape(1, cols)
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
            # an uncopied page's scale is whatever the buffer held, and
            # 0 x NaN is NaN
            p = p * jnp.where(seen, scale_bufs[1][slot].reshape(1, cols), 0.0)
        # zero invalid value rows: 0-weight x garbage must stay 0.
        # The row-oriented mask comes from its own iota — reshaping
        # the [1, cols] one is a lane->sublane cast Mosaic refuses.
        row = jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
        v = jnp.where(g * rows + row // kv_heads < length, v, 0)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    # a row that sees nothing starts no copy and runs no group
    @pl.when(groups > 0)
    def _walk():
        first = state[0]

        @pl.when(state[1] == 0)
        def _start_own():
            copies(b, 0, first, lambda c: c.start())

        # the next row that sees anything: its first group is gathered
        # while this row's last one is scored
        nxt_b = jax.lax.while_loop(
            lambda r: (r < batch) & (len_ref[jnp.minimum(r, batch - 1)] <= 0),
            lambda r: r + 1,
            b + 1,
        )
        has_next = nxt_b < batch

        def one_group(g, slot):
            last = g + 1 == groups

            # the next group that will run; its gather flies while this
            # one is scored
            @pl.when(jnp.logical_not(last) | has_next)
            def _start_next():
                copies(
                    jnp.where(last, nxt_b, b), jnp.where(last, 0, g + 1),
                    1 - slot, lambda c: c.start(),
                )

            copies(b, g, slot, lambda c: c.wait())
            score(g, slot)
            return 1 - slot

        state[0] = jax.lax.fori_loop(0, groups, one_group, first)
        state[1] = has_next.astype(jnp.int32)

    # zeros for a row that saw nothing
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _paged_pallas(q, k, v, block_table, lengths, *, k_scale, v_scale,
                  scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    # Q queries a row ride as Q x Hq query rows of one score tile
    out_shape, queries = q.shape, 1 if q.ndim == 3 else q.shape[1]
    q = q.reshape(q.shape[0], -1, q.shape[-1])
    batch, q_heads, head_dim = q.shape
    num_pool_blocks, block, kv_heads, _ = k.shape
    w = block_table.shape[1]
    page_cols = block * kv_heads
    quantized = k_scale is not None
    pages = _pages_per_step(block, kv_heads, head_dim, k.dtype.itemsize, w)

    def q_map(b, table, lens):
        return (b, 0, 0)

    # the pools stay in HBM and the kernel gathers a group's pages
    # itself. [N, block, Hk, D] -> [N, block * Hk, D] merges the two
    # middle dims under an unchanged minor dim: a bitcast of the pool on
    # TPU (checked in the compiled HLO at head_dim 128), never a copy
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, q_heads, head_dim), q_map), hbm, hbm]
    operands = [
        q,
        k.reshape(num_pool_blocks, page_cols, head_dim),
        v.reshape(num_pool_blocks, page_cols, head_dim),
    ]
    scratch = [
        pltpu.VMEM((2, pages, page_cols, head_dim), k.dtype),
        pltpu.VMEM((2, pages, page_cols, head_dim), v.dtype),
    ]
    if quantized:
        # scale planes ride as one lane-dense row per block, matching
        # the score columns
        in_specs += [hbm, hbm]
        operands += [
            k_scale.reshape(num_pool_blocks, 1, page_cols),
            v_scale.reshape(num_pool_blocks, 1, page_cols),
        ]
        scratch += [pltpu.VMEM((2, pages, 1, page_cols), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((q_heads, head_dim), jnp.float32),
        pltpu.VMEM((q_heads, 1), jnp.float32),
        pltpu.VMEM((q_heads, 1), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, q_heads, head_dim), q_map),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_kernel,
        scale=scale,
        block=block,
        kv_heads=kv_heads,
        group=q_heads // queries // kv_heads,
        width=w,
        pages=pages,
        quantized=quantized,
        queries=queries,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, q_heads, head_dim), q.dtype),
        # a row starts the gather of the next row's first group: the
        # grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_attention",
    )(
        block_table.astype(jnp.int32), lengths.astype(jnp.int32), *operands
    ).reshape(out_shape)


def _fused_kernel(table_ref, len_ref, *rest, scale, block, kv_heads, group,
                  width, pages, queries, limited=False):
    """:func:`_paged_kernel`'s walk over ONE pool whose rows hold a
    position's key heads and, behind them, its value heads. A group's blocks
    lie in the buffer as they lie in the pool, ``[positions * 2 Hk, D]``:
    row ``r`` is stored head ``r % (2 Hk)`` of position ``r // (2 Hk)``. Each
    key head's rows are taken out of the buffer (:func:`stored_head`) and
    scored against that head's own query rows alone: a ``[queries * group,
    positions]`` score tile a key head, where one matmul of every query row
    with every stored row makes ``[queries * Hq, positions * 2 Hk]`` and
    masks all but one column in ``2 Hk`` away. ``limited``: a third
    scalar-prefetched operand holds a limit a query (``[batch * queries]``),
    and query ``j`` of a row sees the positions under ``min(length,
    limit[j])``; the copies and the walk go by the row's length alone."""
    from jax.experimental.pallas import tpu as pltpu

    lim_ref, rest = (rest[0], rest[1:]) if limited else (None, rest)
    q_ref, pool, o_ref, buf, sem, state, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    batch = pl.num_programs(0)
    q_heads = kv_heads * group
    stored = 2 * kv_heads
    rows = pages * block                   # KV positions a group holds
    page_rows = block * stored             # buffer rows a pool block takes
    # values a 32-bit word of the buffer holds: consecutive buffer rows,
    # which are consecutive stored heads of one position
    packing = 4 // buf.dtype.itemsize

    def visible(row):
        # a stale length may not reach past the table
        return jnp.clip(len_ref[row], 0, width * block)

    def copies(row, grp, slot, fn):
        """``fn`` on the copy of every pool block of (row, grp) that holds
        visible rows, into buffer ``slot``. A copy's descriptor is a chain
        of ~40 scalar operations (the table entry, two addresses, their
        bounds) that the scalar unit runs one behind the other: four
        blocks a loop turn are four chains side by side."""
        def page(j, carry):
            src = table_ref[(row * width + grp * pages) + j]
            at = pl.ds(pl.multiple_of(j * page_rows, page_rows), page_rows)
            fn(pltpu.make_async_copy(pool.at[src], buf.at[slot, at], sem.at[slot]))
            return carry

        def four(i, carry):
            for j in range(4):
                page(4 * i + j, carry)
            return carry
        live_pages = jnp.minimum(pl.cdiv(visible(row), block) - grp * pages, pages)
        jax.lax.fori_loop(0, live_pages // 4, four, 0)
        jax.lax.fori_loop(live_pages // 4 * 4, live_pages, page, 0)

    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    length = visible(b)
    groups = pl.cdiv(length, rows)

    def own_rows(head):
        """Where key head ``head``'s query rows lie among the row's ``queries
        x Hq`` (query ``j``'s head ``h`` is row ``j * Hq + h``)."""
        return [pl.ds(j * q_heads + head * group, group) for j in range(queries)]

    def own(ref, head):
        return jnp.concatenate([ref[at] for at in own_rows(head)], axis=0)

    def own_queries():
        """The queries that read each key head, ``[queries * group, D]``:
        picked in float32 (whole sublane tiles where ``group`` is 8 rows)."""
        q_all = q_ref[0].astype(jnp.float32)
        picked = [[q_all[at.start:at.start + group] for at in own_rows(h)] for h in range(kv_heads)]
        return [jnp.concatenate(parts, axis=0).astype(q_ref.dtype) for parts in picked]

    def own_limits():
        """``[queries * group, 1]``: the positions each of a key head's query
        rows sees (query ``j``'s rows are ``j * group .. (j + 1) * group -
        1`` of them, for every head)."""
        at = jax.lax.broadcasted_iota(jnp.int32, (queries * group, 1), 0)
        limit = jnp.zeros_like(at)
        for j in range(queries):
            limit = jnp.where((at >= j * group) & (at < (j + 1) * group), lim_ref[b * queries + j], limit)
        return jnp.minimum(limit, length)

    half = rows // 2

    def position_of(index):
        """The position of the group that row ``index`` of a stored head's
        ``[positions, D]`` holds: of 16-bit rows the even ones come from the
        group's first half of positions and the odd ones from its second."""
        return index if packing == 1 else index % 2 * half + index // 2

    def stored_head(slot, head, seen_from=None):
        """``[positions, D]`` of stored head ``head`` of the group in buffer
        ``slot``: every ``stored``-th row of the buffer. 32-bit rows are read
        so. Of 16-bit rows two lie in one 32-bit sublane, so the buffer is
        read as words that hold stored heads ``2i`` and ``2i + 1`` of one
        position, the group's first half of positions and its second apart,
        and this head's halves of a first-half and a second-half position's
        word make one word: the rows come out in ``position_of``'s order,
        which a softmax over them does not see. ``seen_from``: the group's
        first position, and a position past the row's length then reads
        zero."""
        def seen(first, count):
            at = jax.lax.broadcasted_iota(jnp.int32, (count, 1), 0)
            return seen_from + first + at < length

        if packing == 1:
            out = buf[slot, pl.ds(head, rows, stride=stored)]
            return out if seen_from is None else jnp.where(seen(0, rows), out, 0)
        words = buf.bitcast(jnp.uint32)           # [2, positions * Hk, D]: row = position * Hk + head // 2
        low, high = (
            words[slot, pl.ds(first * kv_heads + head // 2, half, stride=kv_heads)] for first in (0, half)
        )
        if seen_from is not None:
            low = jnp.where(seen(0, half), low, jnp.uint32(0))
            high = jnp.where(seen(half, half), high, jnp.uint32(0))
        if head % 2:
            word = (low >> 16) | (high & jnp.uint32(0xFFFF0000))
        else:
            word = (low & jnp.uint32(0xFFFF)) | (high << 16)
        return pltpu.bitcast(word, buf.dtype)     # row 2s: the low half of word s

    def score(g, slot, q_own, limit):
        """Fold group ``g`` of this row, gathered in buffer ``slot``, into
        the online-softmax state, a key head at a time."""
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        # pages past the row's last were not copied (the buffer holds an
        # earlier group's rows there): the length mask covers them
        # [1, positions]; with a limit a query [queries * group, positions]
        seen = g * rows + position_of(col) < (length if limit is None else limit)
        for head, q in enumerate(q_own):           # [queries * group, D]
            k = stored_head(slot, head).astype(q.dtype)
            # zero unseen value rows: 0-weight x garbage must stay 0
            v = stored_head(slot, kv_heads + head, seen_from=g * rows).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale                              # [queries * group, positions]
            s = jnp.where(seen, s, NEG_INF)
            m_prev = own(m_ref, head)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
            p = jnp.exp(s - m_safe)                # exactly 0 where unseen
            corr = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
            l_new = own(l_ref, head) * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = own(acc_ref, head) * corr + jax.lax.dot_general(
                p.astype(q.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            for j, at in enumerate(own_rows(head)):
                part = slice(j * group, (j + 1) * group)
                m_ref[at], l_ref[at], acc_ref[at] = m_new[part], l_new[part], acc[part]

    # a row that sees nothing starts no copy and runs no group
    @pl.when(groups > 0)
    def _walk():
        first = state[0]

        @pl.when(state[1] == 0)
        def _start_own():
            copies(b, 0, first, lambda c: c.start())

        # the next row that sees anything: its first group is gathered
        # while this row's last one is scored
        nxt_b = jax.lax.while_loop(
            lambda r: (r < batch) & (len_ref[jnp.minimum(r, batch - 1)] <= 0),
            lambda r: r + 1,
            b + 1,
        )
        has_next = nxt_b < batch
        q_own = own_queries()                      # once a row
        limit = own_limits() if limited else None

        def one_group(g, slot):
            last = g + 1 == groups

            @pl.when(jnp.logical_not(last) | has_next)
            def _start_next():
                copies(
                    jnp.where(last, nxt_b, b), jnp.where(last, 0, g + 1),
                    1 - slot, lambda c: c.start(),
                )

            copies(b, g, slot, lambda c: c.wait())
            score(g, slot, q_own, limit)
            return 1 - slot

        state[0] = jax.lax.fori_loop(0, groups, one_group, first)
        state[1] = has_next.astype(jnp.int32)

    # zeros for a row that saw nothing
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


# one trace and one lowering for every layer of a program (see
# ``_sparse_pallas``): the key heads are unrolled in the kernel's body
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _fused_pallas(q, pool, block_table, lengths, limits=None, *, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    out_shape, queries = q.shape, 1 if q.ndim == 3 else q.shape[1]
    q = q.reshape(q.shape[0], -1, q.shape[-1])
    batch, q_rows, head_dim = q.shape
    num_pool_blocks, block, stored, _ = pool.shape
    kv_heads = stored // 2
    w = block_table.shape[1]
    pages = _pages_per_step(block, kv_heads, head_dim, pool.dtype.itemsize, w)

    def q_map(b, *prefetched):
        return (b, 0, 0)

    # the table flat, so that an entry is one scalar load; so the limits
    prefetched = [block_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32)]
    if limits is not None:
        prefetched.append(limits.astype(jnp.int32).reshape(-1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetched),
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, q_rows, head_dim), q_map), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, q_rows, head_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages * block * stored, head_dim), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((q_rows, head_dim), jnp.float32),
            pltpu.VMEM((q_rows, 1), jnp.float32),
            pltpu.VMEM((q_rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _fused_kernel, scale=scale, block=block, kv_heads=kv_heads,
        group=q_rows // queries // kv_heads, width=w, pages=pages, queries=queries,
        limited=limits is not None,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, q_rows, head_dim), q.dtype),
        # a row starts the gather of the next row's first group: in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(
        # [N, block, 2 Hk, D] -> [N, block * 2 Hk, D] merges the two middle
        # dims under an unchanged minor dim: the pool as it lies
        *prefetched, q, pool.reshape(num_pool_blocks, block * stored, head_dim),
    ).reshape(out_shape)


def paged_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: Optional[jnp.ndarray],
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    limits: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-step decode attention over a block-paged KV pool: two pools
    are scored a group by one matmul of every query row with every
    (position, kv head) row and a mask, one pool of fused rows (``v=None``)
    a key head at a time against that head's own query rows.

    Shapes: ``q`` [B, Hq, D] (one query per row — the decode step), or
    [B, Q, Hq, D] for Q queries a row that share its ``lengths`` entry and
    see every visible row, each other's included (a block of positions
    that attend one another: the pool's rows are read once for all Q, and
    the result has ``q``'s shape). ``limits`` [B, Q] int32, over one fused
    pool: the queries of a row do not share one limit, query ``j`` sees the
    row's first ``min(lengths[b], limits[b, j])`` positions (two blocks of
    positions in one call, the earlier blind to the later; what is copied
    and walked goes by ``lengths`` alone). ``v=None``: ``k`` is one pool of fused
    rows ``[num_blocks, block, 2 Hk, D]``, a position's key heads and its
    value heads behind them (``KVRows(fused=True)``: with 4 + 4 heads of 128
    one whole tile a position; values of 16 or 32 bits): the kernel copies
    a block once and scores each key head's rows against that head's own
    query rows, at any number of queries;
    ``k``/``v`` [num_blocks, block, Hk, D] pools (bf16, or int8 with
    fp32 ``k_scale``/``v_scale`` [num_blocks, block, Hk]);
    ``block_table`` [B, W] int32 (entries past a row's coverage point
    at the trash block); ``lengths`` [B] int32 visible rows (0 for a row
    whose output nobody reads: the kernel gathers nothing for it and
    writes zeros, the reference an average of the table's rows). Returns
    [B, Hq, D] in ``q.dtype``.

    ``impl``: ``"reference"`` (pure JAX gather — bit-identical to the
    contiguous cache path, the tier-1/CPU anchor), ``"pallas"`` (the
    scalar-prefetch kernel; interpreter mode off-TPU), or ``"auto"``
    (pallas on TPU, reference elsewhere).
    """
    _check_shapes(q, k, v, block_table, lengths, k_scale, v_scale, limits)
    if impl == "auto":
        impl = "reference" if _interpret() else "pallas"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if impl == "reference":
        return paged_attention_reference(
            q, k, v, block_table, lengths,
            k_scale=k_scale, v_scale=v_scale, scale=scale, limits=limits,
        )
    if impl != "pallas":
        raise ValueError(f"unknown paged attention impl {impl!r}")
    if v is None:
        if k.dtype.itemsize not in (2, 4) or k.shape[1] % 2:
            raise ValueError(
                "the kernel reads fused rows of 16 or 32 bits a value in blocks of an even number of "
                f"positions, got {k.dtype} {k.shape}"
            )
        return _fused_pallas(q, k, block_table, lengths, limits, scale=scale, interpret=_interpret())
    if limits is not None:
        raise ValueError("the kernel takes a limit a query over one pool of fused rows only (v=None)")
    return _paged_pallas(
        q, k, v, block_table, lengths,
        k_scale=k_scale, v_scale=v_scale, scale=scale,
        interpret=_interpret(),
    )


# --------------------------------------------------------- latent attention
#
# Latent attention (DeepSeek-V2's MLA) caches one row a position that all
# heads share: ``value_dim`` compressed values ``c_kv`` followed by the
# rotated key values ``k_rope``. In the *absorbed* form a query head is
# carried into the latent space (``q_lat = q_nope W_uk^T``, followed by its
# rotated part), scores are ``q . row`` over the whole row, and the
# weighted sum is taken over the row's first ``value_dim`` columns: the
# pool is the keys and the values at once, every head reads the same rows,
# and no per-head key or value exists for a cached position.


def latent_attention(q, rows, bias, *, value_dim: int, scale: float):
    """Absorbed latent attention in plain JAX: ``q`` [B, S, H, W] queries
    in the latent space, ``rows`` [B, L, W] cached latents, ``bias``
    broadcastable to [B, H, S, L] (``NEG_INF`` hides a position). Returns
    [B, S, H, value_dim] in ``q.dtype``; float32 scores and softmax. Off
    the TPU the operands are float32 too (the CPU's runtime refuses some
    bfloat16 products with a float32 sum)."""
    dtype = jnp.float32 if _interpret() else q.dtype
    s = jnp.einsum("bshw,blw->bhsl", q.astype(dtype), rows.astype(dtype), preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s * scale + bias, axis=-1)
    out = jnp.einsum(
        "bhsl,blv->bshv", p.astype(dtype), rows[..., :value_dim].astype(dtype),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _check_latent_shapes(q, pool, block_table, lengths, value_dim):
    if q.ndim != 3 or pool.ndim != 3 or q.shape[-1] != pool.shape[-1]:
        raise ValueError(
            "q must be [batch, heads, width] and the pool [num_blocks, "
            f"block_size, width], got {q.shape} / {pool.shape}"
        )
    if block_table.ndim != 2 or block_table.shape[0] != q.shape[0]:
        raise ValueError(
            f"block_table must be [batch, table_width], got {block_table.shape} for batch {q.shape[0]}"
        )
    if lengths.shape != (q.shape[0],):
        raise ValueError(f"lengths must be [batch], got {lengths.shape}")
    if not 0 < value_dim <= pool.shape[-1]:
        raise ValueError(f"value_dim {value_dim} is not within the row's width {pool.shape[-1]}")


def paged_latent_attention_reference(q, pool, block_table, lengths, *, value_dim: int, scale: float):
    """The plain gather: the table's blocks taken into a contiguous
    ``[B, W * block, width]`` view, then :func:`latent_attention` with the
    columns past a row's length hidden. The CPU's path and the kernel's
    parity anchor."""
    _check_latent_shapes(q, pool, block_table, lengths, value_dim)
    batch, w = block_table.shape
    block = pool.shape[1]
    rows = jnp.take(pool, block_table.reshape(-1), axis=0).reshape(batch, w * block, -1)
    visible = jnp.arange(w * block)[None, :] < lengths.astype(jnp.int32)[:, None]
    bias = jnp.where(visible, 0.0, NEG_INF)[:, None, None, :]
    return latent_attention(q[:, None], rows, bias, value_dim=value_dim, scale=scale)[:, 0]


_LATENT_ROWS_PER_STEP = 512  # positions a group gathers and scores


def _latent_kernel(table_ref, len_ref, q_ref, pool, o_ref, buf, sem, state,
                   acc_ref, m_ref, l_ref, *, scale, block, width, pages, value_dim):
    """:func:`_paged_kernel`'s walk (a grid step a row, its groups a loop,
    the next group's copies in flight while this one is scored) over one
    pool whose rows are the keys and, in their first ``value_dim``
    columns, the values, shared by every head: a group is scored by one
    ``[heads, W] x [W, rows]`` matmul and no mask tells heads apart."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    batch = pl.num_programs(0)
    rows = pages * block

    def visible(row):
        return jnp.clip(len_ref[row], 0, width * block)

    def copies(row, grp, slot, fn):
        def page(j, carry):
            src = table_ref[row, grp * pages + j]
            fn(pltpu.make_async_copy(pool.at[src], buf.at[slot, j], sem.at[slot]))
            return carry
        live_pages = jnp.minimum(pl.cdiv(visible(row), block) - grp * pages, pages)
        jax.lax.fori_loop(0, live_pages, page, 0)

    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    length = visible(b)
    groups = pl.cdiv(length, rows)

    def score(g, slot):
        q = q_ref[0]                                       # [H, W]
        k = buf[slot].reshape(rows, -1).astype(q.dtype)    # [rows, W]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        seen = g * rows + col < length                     # [1, rows]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                          # [H, rows]
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.where(seen, jnp.exp(s - m_safe), 0.0)
        corr = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # pages past the row's last were not copied: 0 x garbage must stay 0
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        v = jnp.where(g * rows + row < length, k[:, :value_dim], 0)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(groups > 0)
    def _walk():
        first = state[0]

        @pl.when(state[1] == 0)
        def _start_own():
            copies(b, 0, first, lambda c: c.start())

        nxt_b = jax.lax.while_loop(
            lambda r: (r < batch) & (len_ref[jnp.minimum(r, batch - 1)] <= 0),
            lambda r: r + 1,
            b + 1,
        )
        has_next = nxt_b < batch

        def one_group(g, slot):
            last = g + 1 == groups

            @pl.when(jnp.logical_not(last) | has_next)
            def _start_next():
                copies(
                    jnp.where(last, nxt_b, b), jnp.where(last, 0, g + 1),
                    1 - slot, lambda c: c.start(),
                )

            copies(b, g, slot, lambda c: c.wait())
            score(g, slot)
            return 1 - slot

        state[0] = jax.lax.fori_loop(0, groups, one_group, first)
        state[1] = has_next.astype(jnp.int32)

    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _latent_pallas(q, pool, block_table, lengths, *, value_dim, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, row_width = q.shape
    _, block, _ = pool.shape
    w = block_table.shape[1]
    pages = max(1, min(_LATENT_ROWS_PER_STEP // block, w))
    # the heads are the matmuls' rows: whole bfloat16 sublane tiles of them
    padded = -(-heads // 16) * 16
    if padded != heads:
        q = jnp.pad(q, ((0, 0), (0, padded - heads), (0, 0)))

    def q_map(b, table, lens):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, padded, row_width), q_map), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, padded, value_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages, block, row_width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((padded, value_dim), jnp.float32),
            pltpu.VMEM((padded, 1), jnp.float32),
            pltpu.VMEM((padded, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, scale=scale, block=block, width=w, pages=pages, value_dim=value_dim,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, padded, value_dim), q.dtype),
        # a row starts the gather of the next row's first group: in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)
    return out[:, :heads]


def paged_latent_attention(q, pool, block_table, lengths, *, value_dim: int,
                           scale: float, impl: str = "auto"):
    """Single-step absorbed latent attention over a block-paged latent pool.

    Shapes: ``q`` [B, H, W] (one query per row and head, in the latent
    space: ``q_nope W_uk^T`` followed by the rotated part); ``pool``
    [num_blocks, block, W] latent rows (``W = value_dim + rope_dim``: the
    compressed values, then the shared rotated key); ``block_table``
    [B, table_width] int32; ``lengths`` [B] int32 visible rows (0 for a row
    whose output nobody reads: the kernel gathers nothing for it and writes
    zeros). ``scale`` is the published softmax scale (the *expanded* head
    width's, not ``W``'s). Returns [B, H, value_dim] in ``q.dtype``: the
    weighted latents, which the caller carries through ``W_uv``.

    ``impl``: ``"reference"`` (the plain gather), ``"pallas"`` (the kernel
    ``paged_latent_attention``; interpreter mode off-TPU) or ``"auto"``
    (pallas on TPU, reference elsewhere)."""
    _check_latent_shapes(q, pool, block_table, lengths, value_dim)
    if impl == "auto":
        impl = "reference" if _interpret() else "pallas"
    if impl == "reference":
        return paged_latent_attention_reference(
            q, pool, block_table, lengths, value_dim=value_dim, scale=scale,
        )
    if impl != "pallas":
        raise ValueError(f"unknown paged latent attention impl {impl!r}")
    return _latent_pallas(
        q, pool, block_table, lengths, value_dim=value_dim, scale=scale, interpret=_interpret(),
    )


# --------------------------------------------------------- sparse attention
#
# Learned sparse attention (:mod:`unionml_tpu.ops.sparse_attention`) keeps,
# beside a position's keys and values, one key of an indexer, in a pool
# buffer of its own with the same blocks: ``[num_blocks, block, stored]``,
# the key in the first columns of a row of whole 128-lane tiles. A decode
# step reads every visible position's indexer key (:func:`paged_index_scores`),
# selects as a mask over the row's table (``sparse_attention.top_k_mask``),
# and walks the row's live blocks of keys and values with that mask
# (:func:`paged_sparse_attention`). The form in which the selection reaches
# attention decides both costs: as positions it takes a sort of every row's
# whole table to make and a gather of ``topk`` tiles a row to use, live or
# not; as a mask it takes neither, and the read costs what the live rows
# hold (PERF.md, section 6, PR 42).


def _check_index_shapes(index_q, index_w, pool, block_table, lengths):
    if index_q.ndim != 3 or pool.ndim != 3 or index_q.shape[-1] != pool.shape[-1]:
        raise ValueError(
            "index_q must be [batch, heads, width] and the pool [num_blocks, "
            f"block_size, width], got {index_q.shape} / {pool.shape}"
        )
    if index_w.shape != index_q.shape[:2]:
        raise ValueError(f"index_w must be [batch, heads], got {index_w.shape} for queries {index_q.shape}")
    if block_table.ndim != 2 or block_table.shape[0] != index_q.shape[0]:
        raise ValueError(
            f"block_table must be [batch, table_width], got {block_table.shape} for batch {index_q.shape[0]}"
        )
    if lengths.shape != (index_q.shape[0],):
        raise ValueError(f"lengths must be [batch], got {lengths.shape}")


def paged_index_scores_reference(index_q, index_w, pool, block_table, lengths):
    """The plain gather: the table's blocks of indexer keys taken into a
    contiguous ``[B, W * block, width]`` view, then
    ``sparse_attention.index_scores``, ``-inf`` past a row's length."""
    from unionml_tpu.ops.sparse_attention import index_scores

    _check_index_shapes(index_q, index_w, pool, block_table, lengths)
    batch, w = block_table.shape
    keys = jnp.take(pool, block_table.reshape(-1), axis=0).reshape(batch, w * pool.shape[1], -1)
    scores = index_scores(index_q[:, None], keys, index_w[:, None])[:, 0]
    visible = jnp.arange(keys.shape[1])[None, :] < lengths.astype(jnp.int32)[:, None]
    return jnp.where(visible, scores, -jnp.inf)


_INDEX_ROWS_PER_STEP = 512  # positions a group gathers and scores


def _index_kernel(table_ref, len_ref, q_ref, w_ref, pool, o_ref, buf, sem, state,
                  *, block, width, pages):
    """:func:`_latent_kernel`'s walk over the pool of indexer keys: a group
    is scored by one ``[heads, W] x [W, rows]`` matmul, ReLU, the heads'
    weighted sum, and written to its row of the output; groups past the
    row's length keep the ``-inf`` the output starts with."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    batch = pl.num_programs(0)
    rows = pages * block

    def visible(row):
        return jnp.clip(len_ref[row], 0, width * block)

    def copies(row, grp, slot, fn):
        def page(j, carry):
            src = table_ref[row, grp * pages + j]
            fn(pltpu.make_async_copy(pool.at[src], buf.at[slot, j], sem.at[slot]))
            return carry
        live_pages = jnp.minimum(pl.cdiv(visible(row), block) - grp * pages, pages)
        jax.lax.fori_loop(0, live_pages, page, 0)

    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    o_ref[...] = jnp.full_like(o_ref, -jnp.inf)
    length = visible(b)
    groups = pl.cdiv(length, rows)

    def score(g, slot):
        q = q_ref[0]                                       # [Hi, W]
        k = buf[slot].reshape(rows, -1).astype(q.dtype)    # [rows, W]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )                                                  # [Hi, rows]
        total = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)
        total = jnp.where(total == 0.0, 0.0, total)
        # pages past the row's last were not copied: the length hides them
        o_ref[0, pl.ds(g, 1), :] = jnp.where(g * rows + col < length, total, -jnp.inf)

    @pl.when(groups > 0)
    def _walk():
        first = state[0]

        @pl.when(state[1] == 0)
        def _start_own():
            copies(b, 0, first, lambda c: c.start())

        nxt_b = jax.lax.while_loop(
            lambda r: (r < batch) & (len_ref[jnp.minimum(r, batch - 1)] <= 0),
            lambda r: r + 1,
            b + 1,
        )
        has_next = nxt_b < batch

        def one_group(g, slot):
            last = g + 1 == groups

            @pl.when(jnp.logical_not(last) | has_next)
            def _start_next():
                copies(
                    jnp.where(last, nxt_b, b), jnp.where(last, 0, g + 1),
                    1 - slot, lambda c: c.start(),
                )

            copies(b, g, slot, lambda c: c.wait())
            score(g, slot)
            return 1 - slot

        state[0] = jax.lax.fori_loop(0, groups, one_group, first)
        state[1] = has_next.astype(jnp.int32)


def _index_pallas(index_q, index_w, pool, block_table, lengths, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, row_width = index_q.shape
    _, block, _ = pool.shape
    w = block_table.shape[1]
    pages = max(1, min(_INDEX_ROWS_PER_STEP // block, w))
    groups, rows = -(-w // pages), pages * block
    # the heads are the matmul's rows: whole bfloat16 sublane tiles of them
    # (a padded head has weight 0)
    padded = -(-heads // 16) * 16
    if padded != heads:
        index_q = jnp.pad(index_q, ((0, 0), (0, padded - heads), (0, 0)))
        index_w = jnp.pad(index_w, ((0, 0), (0, padded - heads)))

    def row_map(b, table, lens):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, padded, row_width), row_map), pl.BlockSpec((1, padded, 1), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, groups, rows), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages, block, row_width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_index_kernel, block=block, width=w, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, groups, rows), jnp.float32),
        # a row starts the gather of the next row's first group: in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_index_scores",
    )(
        block_table.astype(jnp.int32), lengths.astype(jnp.int32), index_q,
        index_w.astype(jnp.float32)[..., None], pool,
    )
    return out.reshape(batch, groups * rows)[:, :w * block]


def paged_index_scores(index_q, index_w, pool, block_table, lengths, *, impl: str = "auto"):
    """A decode step's index scores over a block-paged pool of indexer keys.

    Shapes: ``index_q`` [B, Hi, W] (one rotated query a row and indexer
    head, zero past the key's width), ``index_w`` [B, Hi] the heads'
    weights, ``pool`` [num_blocks, block, W] the cached keys (one key head),
    ``block_table`` [B, table_width] int32, ``lengths`` [B] int32 visible
    rows. Returns float32 [B, table_width * block]: ``sum_j w_j relu(q_j .
    k_s)`` for each of the row's positions, ``-inf`` past its length (a row
    of length 0 reads nothing and is all ``-inf``).

    ``impl``: ``"reference"`` (the plain gather), ``"pallas"`` (the kernel
    ``paged_index_scores``; interpreter mode off-TPU) or ``"auto"`` (pallas
    on TPU, reference elsewhere)."""
    _check_index_shapes(index_q, index_w, pool, block_table, lengths)
    if impl == "auto":
        impl = "reference" if _interpret() else "pallas"
    if impl == "reference":
        return paged_index_scores_reference(index_q, index_w, pool, block_table, lengths)
    if impl != "pallas":
        raise ValueError(f"unknown paged index scores impl {impl!r}")
    return _index_pallas(index_q, index_w, pool, block_table, lengths, interpret=_interpret())


def _check_sparse_shapes(q, kv, block_table, lengths, selected):
    batch, q_heads, head_dim = q.shape
    if kv.ndim != 4 or kv.shape[-1] != head_dim or kv.shape[2] % 2 or q_heads % (kv.shape[2] // 2):
        raise ValueError(
            "the pool must be [num_blocks, block_size, 2 * kv_heads, head_dim] with the query heads a "
            f"multiple of the kv heads, got {kv.shape} for q {q.shape}"
        )
    if block_table.ndim != 2 or block_table.shape[0] != batch or lengths.shape != (batch,):
        raise ValueError(
            f"block_table must be [batch, table_width] and lengths [batch], got {block_table.shape} / "
            f"{lengths.shape} for batch {batch}"
        )
    want = (batch, block_table.shape[1] * kv.shape[1])
    if selected.shape != want:
        raise ValueError(f"selected must be [batch, table_width * block_size] = {want}, got {selected.shape}")


def paged_sparse_attention_reference(q, kv, block_table, lengths, selected, *, scale: float):
    """The plain gather: the table's blocks taken into a contiguous
    ``[B, W * block, 2 Hk, D]`` view, then grouped-query softmax attention
    over the positions ``selected`` keeps (float32 scores and softmax;
    float32 operands off the TPU). The CPU's path and the kernel's parity
    anchor; ``lengths`` is not read (nothing past a length is selected)."""
    _check_sparse_shapes(q, kv, block_table, lengths, selected)
    batch, q_heads, head_dim = q.shape
    kv_heads = kv.shape[2] // 2
    dtype = jnp.float32 if _interpret() else q.dtype
    rows = jnp.take(kv, block_table.reshape(-1), axis=0).reshape((batch, -1) + kv.shape[2:]).astype(dtype)
    grouped = q.reshape(batch, kv_heads, q_heads // kv_heads, head_dim).astype(dtype)
    keys, values = rows[:, :, :kv_heads], rows[:, :, kv_heads:]
    s = jnp.einsum("bkgd,blkd->bkgl", grouped, keys, preferred_element_type=jnp.float32) * scale
    seen = selected[:, None, None, :]
    m = jnp.max(jnp.where(seen, s, NEG_INF), axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgl,blkd->bkgd", p.astype(dtype), values, preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


# positions a group gathers and scores (see ``_ROWS_PER_STEP``: the same
# trade, one pool of 2 KB rows where that kernel has two of 1 KB). On a
# v5e, us a layer at 16 live rows x 8,192 / 6 x 8,192 of the served cell's
# pool: 505.2 / 196.3 at 256, 401.8 / 158.3 at 512, 368.7 / 146.9 at 1,024
# (a group's fixed price is ~0.4 us; 4 MB of buffers; PERF.md, section 6,
# PR 42)
_SPARSE_ROWS_PER_STEP = 1024


def _columns_of(mask_ref, g, heads):
    """Group ``g`` of ``mask_ref`` [1, groups, positions] -> [1, positions *
    heads]: column ``c`` holds position ``c // heads``'s entry. A lane tile
    of the result draws on ``128 // heads`` lanes of one tile of the mask: a
    gather within the tile, which costs a bundle or two (``jnp.repeat``
    lowers to twenty times that)."""
    positions = mask_ref.shape[2]
    if 128 % heads or positions % 128:
        return jnp.repeat(mask_ref[0, pl.ds(g, 1), :], heads, axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    # the eight groups that share a sublane tile with g, and g's row of them
    # on every sublane
    base, own = pl.multiple_of(g // 8 * 8, 8), jnp.full((8, 128), g % 8, jnp.int32)
    each, tiles = 128 // heads, []
    for t in range(positions // 128):
        source = jnp.take_along_axis(mask_ref[0, pl.ds(base, 8), pl.ds(t * 128, 128)], own, axis=0)
        tiles += [jnp.take_along_axis(source, each * j + lane // heads, axis=1) for j in range(heads)]
    return jnp.concatenate(tiles, axis=1)[:1]


def _sparse_kernel(table_ref, len_ref, q_ref, mask_ref, pool, o_ref, buf, sem, state,
                   acc_ref, m_ref, l_ref, *, scale, block, kv_heads, group, width, pages):
    """:func:`_latent_kernel`'s walk over the pool whose rows hold a
    position's key heads and, behind them, its value heads. A group's blocks
    lie in the buffer as they lie in the pool, flattened ``[positions * 2 Hk,
    D]``: row ``r`` is head ``r % (2 Hk)`` of position ``r // (2 Hk)``. ONE
    matmul scores every query head against every row (:func:`_paged_kernel`'s
    scheme), the scores move ``Hk`` columns up, from a position's key head to
    its value head, and there each query head keeps its own head's columns
    *of the selected positions*: the weights then stand over the value rows,
    zero over everything else, and one more matmul with the same buffer sums
    them. ``mask_ref`` holds the row's selection a position, group by group."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    batch = pl.num_programs(0)
    heads = 2 * kv_heads
    rows = pages * block
    cols = rows * heads
    q_heads = kv_heads * group

    def visible(row):
        return jnp.clip(len_ref[row], 0, width * block)

    def copies(row, grp, slot, fn):
        def page(j, carry):
            src = table_ref[row, grp * pages + j]
            fn(pltpu.make_async_copy(pool.at[src], buf.at[slot, j], sem.at[slot]))
            return carry
        live_pages = jnp.minimum(pl.cdiv(visible(row), block) - grp * pages, pages)
        jax.lax.fori_loop(0, live_pages, page, 0)

    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0
        # a page no copy has reached yet weighs zero, and 0 x what fast
        # memory happened to hold may be NaN: from here on the buffers hold
        # zeros or rows of the pool, which are finite
        buf[...] = jnp.zeros_like(buf)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    length = visible(b)
    groups = pl.cdiv(length, rows)

    def score(g, slot):
        q = q_ref[0]                                       # [Hq, D]
        kv = buf[slot].reshape(cols, -1).astype(q.dtype)   # [cols, D]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                          # [Hq, cols]
        # a key head's score to its value head's column (the last position's
        # value columns wrap to the first's key columns, which nobody keeps)
        s = pltpu.roll(s, kv_heads, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        q_head = jax.lax.broadcasted_iota(jnp.int32, (q_heads, 1), 0)
        # nothing past the row's length is selected, so a page that was not
        # copied (an earlier group's rows, or zeros) weighs nothing
        picked = _columns_of(mask_ref, g, heads) > 0.0         # [1, cols]
        valid = (col % heads == kv_heads + q_head // group) & picked
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        corr = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(q.dtype), kv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(groups > 0)
    def _walk():
        first = state[0]

        @pl.when(state[1] == 0)
        def _start_own():
            copies(b, 0, first, lambda c: c.start())

        nxt_b = jax.lax.while_loop(
            lambda r: (r < batch) & (len_ref[jnp.minimum(r, batch - 1)] <= 0),
            lambda r: r + 1,
            b + 1,
        )
        has_next = nxt_b < batch

        def one_group(g, slot):
            last = g + 1 == groups

            @pl.when(jnp.logical_not(last) | has_next)
            def _start_next():
                copies(
                    jnp.where(last, nxt_b, b), jnp.where(last, 0, g + 1),
                    1 - slot, lambda c: c.start(),
                )

            copies(b, g, slot, lambda c: c.wait())
            score(g, slot)
            return 1 - slot

        state[0] = jax.lax.fori_loop(0, groups, one_group, first)
        state[1] = has_next.astype(jnp.int32)

    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


# one trace and one lowering for every layer of a program: a model's layers
# call it with the same shapes, and a warm start pays the lowering (the
# compiled program comes from the cache, its key from the lowered text)
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _sparse_pallas(q, kv, block_table, lengths, selected, *, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    batch, q_heads, head_dim = q.shape
    num_pool_blocks, block, heads, _ = kv.shape
    w = block_table.shape[1]
    pages = max(1, min(_SPARSE_ROWS_PER_STEP // block, w))
    groups, rows = -(-w // pages), pages * block
    # the selection in whole groups, whole sublane tiles of them (nothing
    # past the table is selected)
    mask_groups = -(-groups // 8) * 8
    mask = jnp.pad(selected.astype(jnp.float32), ((0, 0), (0, mask_groups * rows - selected.shape[1])))
    mask = mask.reshape(batch, mask_groups, rows)

    def row_map(b, table, lens):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, q_heads, head_dim), row_map), pl.BlockSpec((1, mask_groups, rows), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, q_heads, head_dim), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages, block * heads, head_dim), kv.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((q_heads, head_dim), jnp.float32),
            pltpu.VMEM((q_heads, 1), jnp.float32),
            pltpu.VMEM((q_heads, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _sparse_kernel, scale=scale, block=block, kv_heads=heads // 2, group=q_heads // (heads // 2),
        width=w, pages=pages,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, q_heads, head_dim), q.dtype),
        # a row starts the gather of the next row's first group: in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_sparse_attention",
    )(
        # [N, block, 2 Hk, D] -> [N, block * 2 Hk, D] merges the two middle
        # dims under an unchanged minor dim: the pool as it lies
        block_table.astype(jnp.int32), lengths.astype(jnp.int32), q, mask,
        kv.reshape(num_pool_blocks, block * heads, head_dim),
    )


def paged_sparse_attention(q, kv, block_table, lengths, selected, *, scale: Optional[float] = None,
                           impl: str = "auto"):
    """Single-step decode attention over a row's *selected* positions.

    Shapes: ``q`` [B, Hq, D]; ``kv`` [num_blocks, block, 2 * Hk, D] the pool
    whose rows hold a position's key heads and, behind them, its value
    heads (``IndexedKVRows``), handed whole: never sliced into keys and
    values, copied or re-laid out; ``block_table`` [B, table_width] int32;
    ``lengths`` [B] int32 visible rows (0 for a row whose output nobody
    reads); ``selected`` [B, table_width * block] bool, the positions of
    each row that count (``sparse_attention.top_k_mask`` of its index
    scores: nothing past its length). Returns [B, Hq, D] in ``q.dtype``;
    zeros for a row that selects nothing. Grouped-query softmax attention
    over the selected positions, float32 scores, softmax and sums; a
    position that is not selected weighs exactly zero.

    ``impl``: ``"reference"`` (the plain gather of the table's blocks),
    ``"pallas"`` (the kernel ``paged_sparse_attention``; interpreter mode
    off-TPU) or ``"auto"`` (pallas on TPU, reference elsewhere). The kernel
    walks a row's live blocks as :func:`paged_attention` does (a grid step a
    row, ``cdiv(length, group)`` groups of its blocks, the next group's
    copies in flight while this one is scored): what it *fetches* is every
    visible row, what it *weighs* the selected ones, and a row of length 0
    walks nothing and returns zeros. **The pool holds finite values only**
    (zeros at the start, then real rows: the contract :func:`_paged_kernel`
    relies on for the tail of a last block): a fetched row that is not
    selected is multiplied by a zero weight, not skipped. Kernel and
    operations carry the scope ``paged_sparse_attention`` in a device trace."""
    _check_sparse_shapes(q, kv, block_table, lengths, selected)
    if impl == "auto":
        impl = "reference" if _interpret() else "pallas"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    with jax.named_scope("paged_sparse_attention"):
        if impl == "reference":
            return paged_sparse_attention_reference(q, kv, block_table, lengths, selected, scale=scale)
        if impl != "pallas":
            raise ValueError(f"unknown paged sparse attention impl {impl!r}")
        return _sparse_pallas(q, kv, block_table, lengths, selected, scale=scale, interpret=_interpret())
