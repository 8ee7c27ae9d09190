"""Pallas TPU fused multi-head attention for short sequences.

At ViT/BERT sequence lengths (a few hundred tokens) attention is
*overhead*-bound, not memory-bound: a flash-style kernel with one program
per (batch, head) pays the fixed per-program pipeline cost 768 times for
microseconds of MXU work each (measured on v5e: ~1.2 us/program floor —
more than the matmuls themselves). This kernel instead runs ONE program
per batch element — grid ``(B,)`` — and loops over heads inside the
program, with the full ``S x S`` fp32 score tile resident in VMEM (200 KB
at S=224; use :mod:`unionml_tpu.ops.flash_attention` beyond ~1k tokens
where the tile stops fitting).

The backward is a single program per batch element too: with the whole
sequence in VMEM there is no cross-program accumulation, so softmax is
simply recomputed per head (no logsumexp residual) and dq/dk/dv are
written in one pass — five small matmuls per head, all fp32-accumulated
on the MXU via ``preferred_element_type``.

Layout (:func:`attention_layout`): the kernels take the projections' own
``[B, S, H*D]`` and find the heads in it as whole 128-lane tiles. A head of
``D = 128 n`` lanes is ``n`` tiles. At ``D = 64`` a tile holds heads ``2j``
and ``2j + 1``: head ``2j``'s scores are ``dot(where(lane < 64, q2, 0), k2^T)``
— the zeros take the neighbour out of the contraction exactly, and a 64-deep
contraction occupied the 128-deep MXU anyway — its ``dot(e, v2)`` is right in
its own 64 lanes, and one ``where`` joins the two heads' results before one
lane-dense store. Slicing lanes *inside* a tile is what is not free; whole
tiles are, and no tensor between the projections and the kernel has a minor
axis narrower than a tile. (Until PR 39 the tensors were transposed to
``[B, H, S, D]``: every 64-wide head lay alone in 128-lane tiles, so the
kernel, and the projections XLA folded the transpose into, moved 2.11 bytes
a byte of values at ViT-B's shape.) A width that fits no tile (80, 96, an odd
head count at 64) still takes those blocks, a head a block row.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from unionml_tpu.ops.flash_attention import NEG_INF, _interpret

# Above this sequence length the S x S fp32 score tile (plus operands)
# stops fitting comfortably in VMEM; callers should use flash_attention.
MAX_FUSED_SEQ = 1024

LANES = 128  # a vector register's minor axis: what a tensor's minor axis is padded to

# Scores are computed in log2 space: log2(e) is folded into the q
# pre-scale outside the kernel, softmax uses exp2 (the VPU-native op exp
# lowers to anyway, minus the input multiply), and the backward folds the
# compensating ln(2) into its existing 1/z row factor.
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


class AttentionLayout(NamedTuple):
    """What :func:`attention_layout` decides, and what it costs in memory."""

    layout: str          # "rows": [B, S, H*D] blocks; "heads": [B, H, S, D]
    heads_per_tile: int  # heads that share a 128-lane tile ("rows"), else 1
    stored_bytes: int    # one batch element's tensor as the chip tiles it
    value_bytes: int     # the same tensor's values alone


def _tiled_bytes(rows, cols, itemsize):
    """Bytes of a [rows, cols] array in the chip's tiles: 128 lanes by 8
    sublanes of 32 bits, a narrower dtype packing 2 or 4 rows a sublane."""
    sublanes = 8 * max(1, 4 // itemsize)
    return -(-rows // sublanes) * sublanes * -(-cols // LANES) * LANES * itemsize


def _heads_layout(seq, heads, head_dim, dtype):
    """A head a block row, ``[B, H, S, D]``: fits any width, and pads a
    head's ``D`` lanes to a whole tile."""
    itemsize = jnp.dtype(dtype).itemsize
    return AttentionLayout(
        "heads", 1, heads * _tiled_bytes(seq, head_dim, itemsize),
        seq * heads * head_dim * itemsize,
    )


def attention_layout(seq, heads, head_dim, dtype) -> AttentionLayout:
    """The blocks the kernels take at a shape: a function of the shape alone.

    ``"rows"`` keeps the projections' own ``[B, S, H*D]`` and takes the
    heads as whole lane tiles of it: ``128 // D`` heads a tile where ``D``
    divides 128 (two at 64), a head over ``D // 128`` tiles where ``D`` is a
    multiple of 128. No axis is narrower than a tile, so the tensor is
    stored at its values' size but for the rows' padding (ViT-B: 197 rows in
    208, 1.056 x). Any other width (80, 96, an odd head count at 64) keeps
    ``"heads"``, which stores a 64-wide head in 128 lanes (2.11 x).
    """
    width = heads * head_dim
    if head_dim % LANES == 0:
        group = 1
    elif LANES % head_dim == 0 and width % LANES == 0:
        group = LANES // head_dim
    else:
        return _heads_layout(seq, heads, head_dim, dtype)
    itemsize = jnp.dtype(dtype).itemsize
    return AttentionLayout(
        "rows", group, _tiled_bytes(seq, width, itemsize), seq * width * itemsize
    )


class _Blocks(NamedTuple):
    """How a program finds head ``h`` in its block (static)."""

    rows: bool     # the block is [1, S, H*D], else [1, H, S, D]
    head_dim: int
    group: int     # heads a lane tile

    @classmethod
    def of(cls, taken: AttentionLayout, head_dim):
        return cls(taken.layout == "rows", head_dim, taken.heads_per_tile)

    def pack(self, x):
        """``[B, S, H, D]`` as the kernels take it: the projections' own
        ``[B, S, H*D]`` (a reshape XLA drops, where the projections make and
        take that width: models/layers.py does), or ``[B, H, S, D]``."""
        b, s, h, d = x.shape
        return x.reshape(b, s, h * d) if self.rows else x.transpose(0, 2, 1, 3)

    def unpack(self, x, shape):
        return x.reshape(shape) if self.rows else x.transpose(0, 2, 1, 3)

    def _lanes(self, h):
        width = self.group * self.head_dim
        lo = (h // self.group) * width
        return slice(lo, lo + width)

    def tile(self, ref, h):
        """The ``[S, W]`` slab that holds head ``h``: its own ``D`` lanes,
        or the whole lane tile it shares with its neighbours."""
        return ref[0, :, self._lanes(h)] if self.rows else ref[0, h]

    def _mine(self, h, like, masks):
        """Where head ``h``'s lanes lie in a tile shaped like ``like``. A
        kernel makes each mask once (``masks``, its own dict) and every head
        after that costs it one ``select``: the kernels are unrolled over
        the heads, and what a head adds to the traced program is paid again
        in every process's set-up."""
        t = h % self.group
        key = (t, like.shape, like.dtype.itemsize)
        if key not in masks:
            lane = jax.lax.broadcasted_iota(jnp.int32, like.shape, like.ndim - 1)
            mine = None if t == 0 else lane >= t * self.head_dim
            if t < self.group - 1:
                below = lane < (t + 1) * self.head_dim
                mine = below if mine is None else mine & below
            masks[key] = mine
        return masks[key]

    def own(self, x, h, masks):
        """``x`` with the tile's other heads zeroed: as a matmul operand the
        zeros take those heads out of the contraction exactly."""
        if self.group == 1:
            return x
        key = (x.shape, x.dtype)
        if key not in masks:
            masks[key] = jax.lax.full_like(x, 0)
        return jax.lax.select(self._mine(h, x, masks), x, masks[key])

    def put(self, ref, h, x, tile, masks):
        """Join head ``h``'s ``[S, W]`` result to the tile its neighbours
        have filled so far; the last head of a tile stores it, lane-dense."""
        if h % self.group:
            x = jax.lax.select(self._mine(h, x, masks), x, tile)
        if (h + 1) % self.group:
            return x
        if self.rows:
            ref[0, :, self._lanes(h)] = x.astype(ref.dtype)
        else:
            ref[0, h] = x.astype(ref.dtype)
        return None


def _causal_mask(s_len):
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (s_len, s_len), 0)
    kv_pos = jax.lax.broadcasted_iota(jnp.int32, (s_len, s_len), 1)
    return q_pos >= kv_pos


def _raw_scores(q, k, causal):
    """[S, S] fp32 scores; q is pre-scaled by the caller (the 1/sqrt(D)
    and log2(e) factors ride the [S, D] tensor outside the kernel — XLA
    fuses them into the projection — instead of an [S, S] multiply here).
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # [S, S] fp32
    if causal:
        s = jnp.where(_causal_mask(s.shape[0]), s, NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, num_heads, blocks):
    # software-pipelined head loop: head h's QK^T (MXU) is emitted before
    # head h-1's softmax (VPU) + PV (MXU), so the two heads' independent
    # MXU/VPU work sits adjacent for the scheduler to overlap. (Writing
    # the softmax max/denominator out as [B, H, S] residuals for the
    # backward was tried and measured SLOWER — the lane-major stat writes
    # force in-kernel relayouts that cost more than the two [S, S]
    # reductions they save.)
    masks = {}

    def start(h):
        q = blocks.own(blocks.tile(q_ref, h), h, masks)
        return _raw_scores(q, blocks.tile(k_ref, h), causal)

    def finish(h, s, tile):
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp2(s - m)                        # scores are log2-scaled
        z = jnp.sum(e, axis=-1, keepdims=True)
        v = blocks.tile(v_ref, h)
        o = jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # [S, W] fp32: h's lanes count
        return blocks.put(o_ref, h, o / z, tile, masks)   # deferred normalization

    s_prev, tile = start(0), None
    for h in range(1, num_heads):
        s_next = start(h)
        tile = finish(h - 1, s_prev, tile)
        s_prev = s_next
    finish(num_heads - 1, s_prev, tile)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, dq_ref, dk_ref, dv_ref, *,
                causal, num_heads, blocks):
    # same software pipelining as the forward: head h's two big MXU
    # products (scores recompute + dp) are emitted before head h-1's
    # VPU-heavy softmax/ds work
    masks = {}

    def start(h):
        q = blocks.own(blocks.tile(q_ref, h), h, masks)
        do = blocks.own(blocks.tile(do_ref, h), h, masks)
        s = _raw_scores(q, blocks.tile(k_ref, h), causal)
        dp = jax.lax.dot_general(
            do, blocks.tile(v_ref, h), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # [S, S]
        return s, dp

    def finish(h, s, dp, tiles):
        dq_tile, dk_tile, dv_tile = tiles
        # unmasked tiles: the lanes of h's neighbours hold products that
        # ``put`` drops; only delta sums over lanes and needs h's alone
        q = blocks.tile(q_ref, h)
        do = blocks.tile(do_ref, h).astype(jnp.float32)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp2(s - m)                        # [S, S] fp32, log2 space
        z = jnp.sum(e, axis=-1, keepdims=True)
        # dv = p^T do = e^T (do / z): row-scale the [S, W] side, not p
        dv = jax.lax.dot_general(
            e.astype(q.dtype), (do / z).astype(q.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # delta = sum(p * dp) = sum(do * o) — the flash-attention identity
        # (sum_j p_ij (do_i . v_j) = do_i . o_i) turns an [S, S] multiply
        # + reduce into an [S, D] one over the saved forward output
        delta = jnp.sum(
            blocks.own(do * blocks.tile(o_ref, h).astype(jnp.float32), h, masks),
            axis=-1, keepdims=True,
        )
        # ds = p * (dp - delta) * ln2: the ln2 compensates d(exp2)/dx and
        # cancels against the caller's log2(e) pre-scale in dq/dk; q came
        # in pre-scaled so the chain rule's scale factor also lives outside
        ds = (e * (dp - delta) * (LN2 / z)).astype(q.dtype)
        dq = jax.lax.dot_general(
            ds, blocks.tile(k_ref, h), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return (
            blocks.put(dq_ref, h, dq, dq_tile, masks),
            blocks.put(dk_ref, h, dk, dk_tile, masks),
            blocks.put(dv_ref, h, dv, dv_tile, masks),
        )

    (s_prev, dp_prev), tiles = start(0), (None, None, None)
    for h in range(1, num_heads):
        s_next, dp_next = start(h)
        tiles = finish(h - 1, s_prev, dp_prev, tiles)
        s_prev, dp_prev = s_next, dp_next
    finish(num_heads - 1, s_prev, dp_prev, tiles)


def _call(kernel, out_count, *blocks_in):
    """One program a batch element over whole blocks of ``blocks_in``."""
    x = blocks_in[0]
    spec = pl.BlockSpec((1,) + x.shape[1:], lambda i: (i,) + (0,) * (x.ndim - 1))
    out = jax.ShapeDtypeStruct(x.shape, x.dtype)
    return pl.pallas_call(
        kernel,
        grid=(x.shape[0],),
        in_specs=[spec] * len(blocks_in),
        out_specs=spec if out_count == 1 else [spec] * out_count,
        out_shape=out if out_count == 1 else [out] * out_count,
        interpret=_interpret(),
    )(*blocks_in)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused(q, k, v, causal, num_heads, blocks):
    """q, k, v as the kernels take them (``_Blocks``), q pre-scaled."""
    return _fused_fwd(q, k, v, causal, num_heads, blocks)[0]


def _fused_fwd(q, k, v, causal, num_heads, blocks):
    kernel = functools.partial(
        _fwd_kernel, causal=causal, num_heads=num_heads, blocks=blocks
    )
    out = _call(kernel, 1, q, k, v)
    # the output is a residual: the backward's delta term needs only
    # rowsum(do * o), not the [S, S] probability tile
    return out, (q, k, v, out)


def _fused_bwd(causal, num_heads, blocks, residuals, g):
    q, k, v, out = residuals
    kernel = functools.partial(
        _bwd_kernel, causal=causal, num_heads=num_heads, blocks=blocks
    )
    return tuple(_call(kernel, 3, q, k, v, g, out))


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Fused short-sequence attention over [B,S,H,D] tensors (differentiable).

    GQA-aware: kv heads are repeated to query heads *outside* the
    custom-vjp kernel, so the repeat's own VJP group-sums dk/dv
    automatically. Sequences longer than :data:`MAX_FUSED_SEQ` should use
    :func:`unionml_tpu.ops.flash_attention.flash_attention` instead.
    """
    if q.shape[1] > MAX_FUSED_SEQ:
        raise ValueError(
            f"fused_attention is for short sequences (<= {MAX_FUSED_SEQ}); "
            f"got {q.shape[1]} — use flash_attention"
        )
    if k.shape[1] != q.shape[1]:
        # the kernel's k/v blocks are shaped from q: unequal lengths would
        # silently read only the first q_len keys
        raise ValueError(
            f"fused_attention requires q_len == kv_len (got {q.shape[1]} vs "
            f"{k.shape[1]}) — use flash_attention or the xla reference"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    num_heads = q.shape[2]
    if k.shape[2] != num_heads:
        from unionml_tpu.ops.attention import _repeat_kv

        k = _repeat_kv(k, num_heads)
        v = _repeat_kv(v, num_heads)
    _, seq, _, head_dim = q.shape
    blocks = _Blocks.of(attention_layout(seq, num_heads, head_dim, q.dtype), head_dim)
    # scale (and the exp2 log2(e) base change) rides q outside the kernel
    # (fused into the projection by XLA) rather than the [S, S] score tile
    # inside it; the VJP factor on dq, like the layout's own, is autodiff's
    # here, outside the custom_vjp
    scaled = blocks.pack(q) * jnp.asarray(scale * LOG2E, q.dtype)
    out = _fused(scaled, blocks.pack(k), blocks.pack(v), causal, num_heads, blocks)
    return blocks.unpack(out, q.shape)
