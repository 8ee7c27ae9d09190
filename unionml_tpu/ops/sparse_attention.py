"""Learned sparse attention: an indexer scores the cached positions, the
``topk`` best are selected per query, and softmax attention runs over the
selected set only (DeepSeek-V3.2's sparse attention, as Keye-VL-2.0's
``sa_config`` configures it).

The *index score* of query ``t`` for cached position ``s`` is ``I[t, s] =
sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])`` over the indexer's heads
``j`` (one key head). The selection is **exact**: the ``topk`` visible
positions of largest score, ties cut towards the lower position (what
``jax.lax.top_k`` does), all of them while fewer are visible. It is made
as a **mask** over the positions (:func:`top_k_mask`), for a prompt and for a
decode step alike: the ``topk``-th largest score is found without a sort, by
a descent over the bits of the scores' ordered integer image
(:func:`kth_largest_key`: 16 counting passes of two bits), ``score > tau`` is
in, and of ``score == tau`` the first few by position. As a list of positions
(``jax.lax.top_k``: on the chip a sort of the whole axis) a decode step's
attention had to gather the picked rows one tile a pick; the mask lets it
walk the row's live blocks instead (PERF.md, section 6, PR 42).

:func:`sparse_attention` is the form over contiguous rows (a prompt, a
prefill chunk behind cached rows, a slot's rows). On a TPU, whole tiles:
:func:`select_mask_tiles` (index scores and selection of 128 queries in
fast memory, the mask out in tiles) and :func:`masked_attention` (the
softmax over the masked set). Elsewhere plain JAX: index scores, mask and
softmax in blocks of queries, so that neither ``[heads, S, L]`` array
exists whole. The decode step over a block pool is
:func:`~unionml_tpu.ops.paged_attention.paged_index_scores` and
:func:`~unionml_tpu.ops.paged_attention.paged_sparse_attention`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

__all__ = [
    "index_scores", "kth_largest_key", "masked_attention", "ordered_key", "select_mask_tiles",
    "sparse_attention", "top_k_mask",
]


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def index_scores(index_q, index_k, index_w):
    """``index_q`` [B, S, Hi, Di], ``index_k`` [B, L, Di], ``index_w``
    [B, S, Hi] -> float32 scores [B, S, L]. Products in the inputs' dtype
    with float32 sums (float32 operands off the TPU, whose CPU runtime
    refuses some bfloat16 products); ReLU and the weighted sum in float32;
    a zero is ``+0.0`` so that equal scores have one image in
    :func:`ordered_key`."""
    dtype = jnp.float32 if _interpret() else index_q.dtype
    s = jnp.einsum(
        "bshd,bld->bhsl", index_q.astype(dtype), index_k.astype(dtype), preferred_element_type=jnp.float32,
    )
    w = jnp.swapaxes(index_w.astype(jnp.float32), 1, 2)[..., None]       # [B, Hi, S, 1]
    total = jnp.sum(jnp.maximum(s, 0.0) * w, axis=1)
    return jnp.where(total == 0.0, 0.0, total)


def ordered_key(scores):
    """float32 -> uint32 whose unsigned order is the floats' order (``-inf``
    lowest; no NaN expected)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    flipped = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))   # negatives: magnitude reversed
    return jax.lax.bitcast_convert_type(flipped, jnp.uint32) ^ jnp.uint32(0x80000000)


# bits of the threshold a counting pass fixes: one layer of an 8,192-token
# prompt read 17.5 ms at 2, 18.9 at 1 and 23.9 at 4 (PERF.md, PR 40)
_SELECT_BITS = 2


def kth_largest_key(keys, k: int):
    """The ``k``-th largest of ``keys`` [..., L] uint32 along the last axis
    (the smallest where fewer than ``k`` exist), exactly and without a
    sort: the answer's bits are fixed from the top, two at a pass, each
    pass counting for every candidate digit how many keys reach it."""
    bits = _SELECT_BITS
    digits = jnp.arange(1, 1 << bits, dtype=jnp.uint32)
    tau = jnp.zeros(keys.shape[:-1], jnp.uint32)
    for shift in range(32 - bits, -1, -bits):
        cand = tau[..., None] | (digits << jnp.uint32(shift))                     # [..., 2^bits - 1]
        reach = jnp.sum(keys[..., None, :] >= cand[..., :, None], axis=-1)        # keys >= each candidate
        # candidates rise with the digit, so the counts fall: the digit is
        # the number of candidates that k keys still reach
        digit = jnp.sum(reach >= k, axis=-1).astype(jnp.uint32)
        tau = tau | (digit << jnp.uint32(shift))
    return tau


def top_k_mask(scores, k: int):
    """bool [..., L]: the ``k`` entries of largest ``scores`` along the last
    axis, ties towards the lower index; entries of ``-inf`` are never in."""
    keys = ordered_key(scores)
    tau = kth_largest_key(keys, k)[..., None]
    above = keys > tau
    ties = keys == tau
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first_ties = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room
    return (above | (ties & first_ties)) & (scores > -jnp.inf)


# what one block of queries may hold in float32 score arrays
_BLOCK_SCORE_BYTES = 256 * 1024 * 1024


def _query_block(seq: int, rows: int, heads: int) -> int:
    """Queries a block holds: the largest power-of-two divisor of ``seq``
    whose ``[heads, block, rows]`` float32 scores fit the budget."""
    block = seq
    while block > 8 and block % 2 == 0 and heads * block * rows * 4 > _BLOCK_SCORE_BYTES:
        block //= 2
    return block


# ---- the softmax over a masked set as a kernel: a whole prompt's attention
# in plain JAX moves its float32 ``[heads, block, L]`` scores through HBM
# three or four times; the kernel keeps a ``[group * 128, 512]`` tile of them
# in fast memory (the flash scheme: running max, normaliser and weighted sum
# carried across the key tiles), and the selection rides in as an int8 mask.

_MASKED_BLOCK_Q = 128    # queries a grid step holds (times the group's heads)
_MASKED_BLOCK_KV = 512   # keys a grid step scores them against


def _masked_kernel(limit_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, group):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(2), pl.program_id(3)
    bq, bkv = mask_ref.shape[-2:]

    @pl.when(j == 0)
    def _start():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # key tiles past the block's last visible position hold nothing selected
    # (none at all where it is -1: a tile of padding)
    @pl.when(j * bkv <= limit_ref[i])
    def _score():
        q = q_ref[0, 0].reshape(group * bq, -1)                     # [G * bq, D]
        k, v = k_ref[0], v_ref[0]                                   # [bkv, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                                   # [G * bq, bkv]
        one = mask_ref[...].reshape(bq, bkv).astype(jnp.float32) > 0.0
        seen = jnp.concatenate([one] * group, axis=0)               # every head of the group
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.where(seen, jnp.exp(s - m_safe), 0.0)
        corr = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)             # zeros for a query that sees nothing
        o_ref[0, 0] = out.reshape(group, bq, -1).astype(o_ref.dtype)


def masked_attention(q, k, v, mask, last_visible, *, scale: float):
    """Grouped-query softmax attention over the positions ``mask`` keeps, as
    the Pallas kernel ``sparse_prefill_attention``.

    ``q`` [B, S, Hq, D]; ``k`` / ``v`` [B, L, Hk, D]; ``mask`` [B, S, L]
    (bool or int8: nonzero keeps) or, as :func:`select_mask_tiles` writes
    it, [B, S // 128, L // 512, 128, 512]; ``last_visible`` [S // 128]
    int32, for each 128 queries the last position any of them may keep (key
    tiles past it are neither fetched nor scored; -1: a tile of padding,
    nothing is, and its output is zeros). ``S`` is whole tiles of 128
    queries, ``L`` of 512 keys, ``D`` of 128 lanes. Returns [B, S, Hq, D]
    in ``q.dtype``; float32 scores and softmax."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, q_heads, hd = q.shape
    rows, kv_heads = k.shape[1], k.shape[2]
    group = q_heads // kv_heads
    bq, bkv = _MASKED_BLOCK_Q, _MASKED_BLOCK_KV
    if not _kernel_fits(seq, rows, hd):
        raise ValueError(
            f"masked_attention takes whole tiles ({bq} queries, {bkv} keys, 128 lanes), "
            f"got {q.shape} over {k.shape}"
        )
    # a key head's queries together: [B, Hk, G, S, D]; keys and values as
    # lane-dense rows, a head a lane tile
    qg = jnp.transpose(q.reshape(batch, seq, kv_heads, group, hd), (0, 2, 3, 1, 4))
    kd, vd = k.reshape(batch, rows, kv_heads * hd), v.reshape(batch, rows, kv_heads * hd)

    def tile(i, j, limit):
        return jnp.minimum(j, jnp.maximum(limit[i], 0) // bkv)

    def kv_map(b, h, i, j, limit):
        return (b, tile(i, j, limit), h)

    if mask.ndim == 5:
        mask_spec = pl.BlockSpec(
            (1, 1, 1, bq, bkv), lambda b, h, i, j, limit: (b, i, tile(i, j, limit), 0, 0))
    else:
        mask_spec = pl.BlockSpec((1, bq, bkv), lambda b, h, i, j, limit: (b, i, tile(i, j, limit)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, kv_heads, seq // bq, rows // bkv),
        in_specs=[
            pl.BlockSpec((1, 1, group, bq, hd), lambda b, h, i, j, limit: (b, h, 0, i, 0)),
            pl.BlockSpec((1, bkv, hd), kv_map), pl.BlockSpec((1, bkv, hd), kv_map),
            mask_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, group, bq, hd), lambda b, h, i, j, limit: (b, h, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((group * bq, hd), jnp.float32), pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_masked_kernel, scale=scale, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kv_heads, group, seq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="sparse_prefill_attention",
    )(last_visible.astype(jnp.int32), qg, kd, vd, mask.astype(jnp.int8))
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(batch, seq, q_heads, hd)


def _kernel_fits(seq: int, rows: int, head_dim: int) -> bool:
    return seq % _MASKED_BLOCK_Q == 0 and rows % _MASKED_BLOCK_KV == 0 and head_dim % 128 == 0


def _use_kernel(seq: int, rows: int, head_dim: int) -> bool:
    """The kernel on a TPU where the shapes are whole tiles, plain JAX
    elsewhere (the tests hand interpret mode whole tiles through this)."""
    return not _interpret() and _kernel_fits(seq, rows, head_dim)


# ---- index scores and the selection as one kernel: in plain JAX a whole
# prompt's float32 ``[S, L]`` index scores go to HBM and the threshold's
# counting passes read them back sixteen times, for every key although half
# lie past the query. The kernel holds a tile of 128 queries' scores in fast
# memory as ordered integers, scores the keys up to the tile's last visible
# position and no further, fixes the threshold a bit at a pass there, and
# writes the int8 mask in the tiles the softmax kernel reads.

_SELECT_VMEM_BYTES = 48 * 1024 * 1024     # the scoped limit the kernel asks for
_SELECT_MAX_ROWS = 16 * 1024              # the longest row read on the chip: 8 MB of a tile's scores


def _select_kernel(limit_ref, iq_ref, w_ref, pos_ref, ikt_ref, valid_ref, tri_ref, mask_ref, keys_ref,
                   *, topk, heads):
    from jax.experimental import pallas as pl

    bq, bkv = mask_ref.shape[-2:]
    # chunks of keys that hold a position some query of the tile may see
    # (none for a tile of padding, whose limit is -1)
    chunks = (limit_ref[pl.program_id(1)] + bkv) // bkv
    pos, w = pos_ref[0], w_ref[0]                                   # [bq, 1], [bq, heads]
    lowest = jnp.int32(-2 ** 31)

    def visible(j):
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        return (kpos <= pos) & (valid_ref[0, j] > 0)                # [bq, bkv]

    def score(j, carry):
        keys_t = ikt_ref[0, j]                                      # [width, bkv]
        total = jnp.zeros((bq, bkv), jnp.float32)
        for h in range(heads):
            s = jnp.dot(iq_ref[0, h], keys_t, preferred_element_type=jnp.float32)
            total = total + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        total = jnp.where(total == 0.0, 0.0, total)                 # one image of zero
        bits = jax.lax.bitcast_convert_type(total, jnp.int32)
        # the floats' order as signed integers; a hidden key below them all
        ordered = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        keys_ref[j] = jnp.where(visible(j), ordered, lowest)
        return carry

    jax.lax.fori_loop(0, chunks, score, 0)

    def count(reaches):
        """How many of a query's keys ``reaches`` holds for: [bq, 1]."""
        def add(j, acc):
            hit = jnp.where(reaches(keys_ref[j]), 1, 0)
            for lane in range(0, bkv, 128):
                acc = acc + hit[:, lane:lane + 128]
            return acc
        acc = jax.lax.fori_loop(0, chunks, add, jnp.zeros((bq, 128), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the topk-th largest key, its bits fixed from the top, one a pass over
    # the tile's keys (two a pass read 16 % slower, four 2.3 x: PERF.md, PR
    # 40): ``tau`` is the key's image with the sign bit turned, whose
    # unsigned order is the keys' signed one (zero where fewer than topk
    # keys are visible)
    def fix_bit(p, tau):
        cand = tau | jnp.left_shift(jnp.int32(1), 31 - p)
        signed = cand ^ lowest
        return jnp.where(count(lambda c: c >= signed) >= topk, cand, tau)

    tau = jax.lax.fori_loop(0, 32, fix_bit, jnp.zeros((bq, 1), jnp.int32)) ^ lowest
    room = (topk - count(lambda c: c > tau)).astype(jnp.float32)    # ties to take, the first by position

    def emit(j, seen):
        c = keys_ref[j]
        ties = c == tau
        # ties up to and with each key: a product with the upper triangle
        upto = jnp.dot(jnp.where(ties, 1.0, 0.0).astype(tri_ref.dtype), tri_ref[...],
                       preferred_element_type=jnp.float32)
        keep = ((c > tau) | (ties & (seen + upto <= room))) & visible(j)
        mask_ref[0, 0, j] = jnp.where(keep, 1, 0).astype(mask_ref.dtype)
        return seen + upto[:, bkv - 1:bkv]

    jax.lax.fori_loop(0, chunks, emit, jnp.zeros((bq, 1), jnp.float32))


def select_mask_tiles(index_q, index_k, index_w, q_pos, kv_valid, last_visible, *, topk: int):
    """The selection of :func:`top_k_mask` over :func:`index_scores`, as the
    Pallas kernel ``sparse_prefill_select``: for every query the ``topk``
    visible positions of largest index score (ties towards the lower
    position), all of them while fewer are visible.

    ``index_q`` [B, S, Hi, Di], ``index_k`` [B, L, Di], ``index_w`` [B, S,
    Hi]; ``q_pos`` [B, S] (row ``j`` is visible to a query iff ``j <=
    q_pos``), ``kv_valid`` [B, L] or None hides rows on top of that;
    ``last_visible`` [S // 128] as :func:`masked_attention` takes it: keys
    past it are not scored, and a tile of -1 is left unwritten. Returns the
    int8 mask in tiles, [B, S // 128, L // 512, 128, 512]: tile ``[b, i,
    j]`` holds queries ``128 i ...`` against keys ``512 j ...``, and is
    written only where ``512 j <= last_visible[i]``. ``S`` whole tiles of
    128, ``L`` of 512, ``Di <= 128``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, heads, width = index_q.shape
    rows = index_k.shape[1]
    bq, bkv = _MASKED_BLOCK_Q, _MASKED_BLOCK_KV
    if not _select_fits(seq, rows, width):
        raise ValueError(
            f"select_mask_tiles takes whole tiles ({bq} queries, {bkv} keys) of at most "
            f"{_SELECT_MAX_ROWS} keys, 128 wide, got {index_q.shape} over {index_k.shape}"
        )
    interpret = _interpret()
    dtype = jnp.float32 if interpret else index_q.dtype
    pad = 128 - width
    # a head's queries together, whole lanes: [B, Hi, S, 128]; the keys a
    # chunk at a time with the positions on the lanes: [B, L // 512, 128, 512]
    iq = jnp.pad(jnp.swapaxes(index_q, 1, 2).astype(dtype), ((0, 0), (0, 0), (0, 0), (0, pad)))
    ikt = jnp.pad(index_k.astype(dtype), ((0, 0), (0, 0), (0, pad))).reshape(batch, rows // bkv, bkv, 128)
    ikt = jnp.swapaxes(ikt, 2, 3)
    valid = jnp.ones((batch, rows), jnp.int32) if kv_valid is None else kv_valid.astype(jnp.int32)
    valid = valid.reshape(batch, rows // bkv, 1, bkv)
    tri = (jnp.arange(bkv)[:, None] <= jnp.arange(bkv)[None, :]).astype(dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, seq // bq),
        in_specs=[
            pl.BlockSpec((1, heads, bq, 128), lambda b, i, limit: (b, 0, i, 0)),
            pl.BlockSpec((1, bq, heads), lambda b, i, limit: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, limit: (b, i, 0)),
            pl.BlockSpec((1, rows // bkv, 128, bkv), lambda b, i, limit: (b, 0, 0, 0)),
            pl.BlockSpec((1, rows // bkv, 1, bkv), lambda b, i, limit: (b, 0, 0, 0)),
            pl.BlockSpec((bkv, bkv), lambda b, i, limit: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows // bkv, bq, bkv), lambda b, i, limit: (b, i, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rows // bkv, bq, bkv), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, heads=heads),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, seq // bq, rows // bkv, bq, bkv), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_SELECT_VMEM_BYTES,
        ),
        interpret=interpret,
        name="sparse_prefill_select",
    )(
        last_visible.astype(jnp.int32), iq, index_w.astype(jnp.float32),
        jnp.broadcast_to(q_pos, (batch, seq)).astype(jnp.int32)[..., None], ikt, valid, tri,
    )


def _select_fits(seq: int, rows: int, width: int) -> bool:
    tiles = seq % _MASKED_BLOCK_Q == 0 and rows % _MASKED_BLOCK_KV == 0
    return tiles and rows <= _SELECT_MAX_ROWS and width <= 128


def sparse_attention(q, k, v, index_q, index_k, index_w, q_pos, kv_valid: Optional[jnp.ndarray] = None,
                     *, topk: int, scale: float):
    """Selected-set attention over contiguous rows.

    ``q`` [B, S, Hq, D]; ``k`` / ``v`` [B, L, Hk, D]; ``index_q`` [B, S, Hi,
    Di], ``index_k`` [B, L, Di], ``index_w`` [B, S, Hi]; ``q_pos`` [B, S] the
    queries' row numbers (row ``j`` is visible to a query iff ``j <=
    q_pos``), ``kv_valid`` [B, L] hides rows on top of that. Returns
    [B, S, Hq, D] in ``q.dtype``; float32 scores and softmax. With ``L <=
    topk`` every visible row is selected and no index score is computed.

    On a TPU where the shapes are whole tiles, two kernels: index scores
    and the selection in :func:`select_mask_tiles`
    (``sparse_prefill_select``), the softmax over the selected set in
    :func:`masked_attention` (``sparse_prefill_attention``); both stop at a
    tile's last visible key. A query whose own row ``kv_valid`` hides is
    padding (a right-padded prompt's tail): a tile of 128 such queries is
    skipped by both and its output is zeros. Elsewhere plain JAX, a block
    of queries at a time: :func:`index_scores`, :func:`top_k_mask`, the
    softmax."""
    batch, seq, q_heads, head_dim = q.shape
    rows, kv_heads = k.shape[1], k.shape[2]
    group = q_heads // kv_heads
    kernel = _use_kernel(seq, rows, head_dim)
    if kernel and rows > topk and _select_fits(seq, rows, index_q.shape[-1]):
        pos = jnp.broadcast_to(q_pos, (batch, seq))
        own = jnp.clip(pos, 0, rows - 1)
        real = pos >= 0 if kv_valid is None else jnp.take_along_axis(kv_valid, own, axis=1)
        last = jnp.where(real, jnp.minimum(pos, rows - 1), -1).reshape(batch, -1, _MASKED_BLOCK_Q)
        last = jnp.max(last, axis=(0, 2))                      # -1: a tile of padding
        with jax.named_scope("select"):
            tiles = select_mask_tiles(index_q, index_k, index_w, pos, kv_valid, last, topk=topk)
        return masked_attention(q, k, v, tiles, last, scale=scale)
    dtype = jnp.float32 if _interpret() else q.dtype
    kv_pos = jnp.arange(rows)
    block = _query_block(seq, rows, max(q_heads, index_q.shape[2]))
    if kernel and block % _MASKED_BLOCK_Q:
        block = seq  # the kernel's own tiles bound its memory

    def one_block(xs):
        qb, iq, iw, pos = xs                                   # [B, block, ...]
        visible = kv_pos[None, None, :] <= pos[..., None]      # [B, block, L]
        if kv_valid is not None:
            visible = visible & kv_valid[:, None, :]
        if rows > topk:
            with jax.named_scope("indexer"):
                scores = jnp.where(visible, index_scores(iq, index_k, iw), -jnp.inf)
            with jax.named_scope("select"):
                visible = top_k_mask(scores, topk)
        if kernel:
            last = jnp.max(pos.reshape(batch, -1, _MASKED_BLOCK_Q), axis=(0, 2))
            return masked_attention(qb, k, v, visible, jnp.minimum(last, rows - 1), scale=scale)
        qg = qb.astype(dtype).reshape(batch, block, kv_heads, group, head_dim)
        s = jnp.einsum("bqhgd,blhd->bhgql", qg, k.astype(dtype), preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(visible[:, None, None], s, NEG_INF), axis=-1)
        o = jnp.einsum(
            "bhgql,blhd->bqhgd", p.astype(dtype), v.astype(dtype), preferred_element_type=jnp.float32,
        )
        return o.reshape(batch, block, q_heads, head_dim).astype(q.dtype)

    def blocks(x):
        return jnp.moveaxis(x.reshape((batch, seq // block, block) + x.shape[2:]), 1, 0)

    xs = (blocks(q), blocks(index_q), blocks(index_w), blocks(jnp.broadcast_to(q_pos, (batch, seq))))
    if seq == block:
        return one_block(jax.tree_util.tree_map(lambda x: x[0], xs))
    out = jax.lax.map(one_block, xs)                           # [nb, B, block, Hq, D]
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, q_heads, head_dim)
