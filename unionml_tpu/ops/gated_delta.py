"""Gated delta rule: the linear-attention layer's recurrence.

Per head the layer keeps a state ``S`` in R^{d_k x d_v} (float32) and, for
token t with key ``k`` and query ``q`` (unit length), value ``v``, decay
``alpha = exp(g)`` and write strength ``beta``::

    S <- alpha S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q / sqrt(d_k)

(Yang et al., "Gated Delta Networks", 2024; the same recurrence as
``transformers``' ``torch_recurrent_gated_delta_rule``, which anchors the
tests). The state's size does not depend on the sequence's length: a
serving engine keeps one per slot beside its paged keys and values.

Two forms of the same function:

- :func:`gated_delta_chunked` for a sequence (prefill, and one chunk of a
  chunked prefill: it takes the state in and gives it back): chunks of
  64 tokens in the WY / UT-transform form, plain XLA, a ``lax.scan`` over
  the chunks. Positions at or beyond ``valid_len`` are the identity on
  the state (a right-padded bucket leaves it as the last real token did).
- :func:`gated_delta_step` for one token a sequence (decode). On a TPU it
  is the Pallas kernel named ``gated_delta_step``: the resident state is
  aliased in and out, read and written once, and rows whose ``live`` flag
  is false are neither read nor written. Its other operands arrive with
  ``d_k`` or ``d_v`` on the lanes, as the projections make them: keys and
  queries a row a head (turned into the state's columns inside the kernel,
  a few KB in VMEM), values a row a state row, alpha and beta as scalars
  (:func:`step_operand_bytes` counts them: 3.6 MB a call at 32 x 30 heads
  of 96 x 192, where columns of 2 or 4 lanes took 26 MB). The XLA version
  of the same function is the CPU path and the parity anchor
  (``impl="reference"``).

**State layout.** A state row is stored with ``pack`` heads side by side
along the value axis, ``[heads / pack, d_k, pack * d_v]``, ``pack`` the
least count that makes the minor axis a multiple of the 128 lanes
(:func:`heads_per_row`; 2 at ``d_v`` 192). On a TPU an array's minor axis
is padded to the lane count in HBM too, so ``[.., 96, 192]`` would hold
and move a third more bytes than the state has. :func:`pack_state` /
:func:`unpack_state` convert; both forms here take and return the packed
layout.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "gated_delta_chunked", "gated_delta_step", "gated_delta_step_reference",
    "heads_per_row", "state_shape", "pack_state", "unpack_state", "step_operand_bytes",
]

CHUNK = 64
# the chunked form's small float32 matmuls (64 x 64 x d): on a TPU the
# default precision rounds float32 operands to bfloat16, and the decayed
# keys and the solved transform are not bfloat16 numbers. They are a few
# percent of a prefill's operations (chipbench/opsbytes_hybrid.py).
_PRECISION = jax.lax.Precision.HIGHEST
# state bytes one grid step of the decode kernel moves each way, at least:
# a grid step's fixed price is 0.15-0.35 us (PERF.md, section 6, PR 26)
_STEP_BYTES = 512 * 1024


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


# ------------------------------------------------------------ state layout


def heads_per_row(heads: int, d_v: int) -> int:
    """Heads stored side by side in one state row: the least count that
    makes ``pack * d_v`` a multiple of 128 lanes, or 1 where the heads do
    not divide by it."""
    pack = 128 // math.gcd(d_v, 128)
    return pack if heads % pack == 0 else 1


def state_shape(heads: int, d_k: int, d_v: int) -> Tuple[int, int, int]:
    """Shape of one sequence's packed state."""
    pack = heads_per_row(heads, d_v)
    return (heads // pack, d_k, pack * d_v)


def pack_state(state: jnp.ndarray) -> jnp.ndarray:
    """``[B, H, d_k, d_v]`` -> ``[B, H / pack, d_k, pack * d_v]``."""
    b, h, dk, dv = state.shape
    pack = heads_per_row(h, dv)
    s = state.reshape(b, h // pack, pack, dk, dv)
    return jnp.swapaxes(s, 2, 3).reshape(b, h // pack, dk, pack * dv)


def unpack_state(packed: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``[B, H / pack, d_k, pack * d_v]`` -> ``[B, H, d_k, d_v]``."""
    b, rows, dk, width = packed.shape
    pack = heads // rows
    s = packed.reshape(b, rows, dk, pack, width // pack)
    return jnp.swapaxes(s, 2, 3).reshape(b, heads, dk, width // pack)


# ----------------------------------------------------------------- prefill


def gated_delta_chunked(q, k, v, g, beta, state, valid_len=None):
    """The recurrence over a sequence, in chunks of 64.

    ``q``, ``k`` [B, T, H, d_k] (unit length; the 1/sqrt(d_k) is applied
    here), ``v`` [B, T, H, d_v], ``g`` (log decay, <= 0) and ``beta``
    [B, T, H], ``state`` packed float32 (:func:`state_shape`),
    ``valid_len`` [B] int or None (every position real). Returns
    ``(o [B, T, H, d_v] float32, state)``. Outputs at positions at or
    beyond ``valid_len`` are not meaningful.

    Within a chunk the updates ``u`` solve a unit lower-triangular system
    (the WY form): ``(I + tril(diag(beta) K K^T * decay, -1)) U =
    diag(beta) (V - decay_in K S)``; across chunks the state is carried by
    the scan. Everything is float32.
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if valid_len is not None:
        real = jnp.arange(t)[None, :] < jnp.asarray(valid_len).reshape(b, 1)
        # alpha 1 and beta 0: the identity on the state
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    pad = -t % CHUNK
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (g, beta))
    n = (t + pad) // CHUNK

    def chunks(x):  # [B, T, H, ...] -> [n, B, H, CHUNK, ...]
        x = x.reshape((b, n, CHUNK) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(x) for x in (q * dk ** -0.5, k, v, g, beta))
    mm = functools.partial(jnp.matmul, precision=_PRECISION)
    gc = jnp.cumsum(g, axis=-1)                                  # [n, B, H, C]
    # decay from position j to position i >= j of one chunk
    low = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    decay = jnp.where(low, jnp.exp(jnp.where(low, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    k_beta = k * beta[..., None]
    a = jnp.where(jnp.tril(low, -1), mm(k_beta, jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    system = a + jnp.eye(CHUNK, dtype=f32)
    rhs = jnp.concatenate([v * beta[..., None], k_beta * jnp.exp(gc)[..., None]], axis=-1)
    solved = jax.lax.linalg.triangular_solve(system, rhs, left_side=True, lower=True)
    v_in, k_cum = solved[..., :dv], solved[..., dv:]
    qk = jnp.where(low, mm(q, jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    q_in = q * jnp.exp(gc)[..., None]
    g_end = gc[..., -1]
    k_out = k * jnp.exp(g_end[..., None] - gc)[..., None]

    def one_chunk(s, xs):
        v_in, k_cum, qk, q_in, k_out, g_end = xs
        u = v_in - mm(k_cum, s)                                  # [B, H, C, d_v]
        o = mm(q_in, s) + mm(qk, u)
        s = s * jnp.exp(g_end)[..., None, None] + mm(jnp.swapaxes(k_out, -1, -2), u)
        return s, o

    s, o = jax.lax.scan(one_chunk, unpack_state(state.astype(f32), h), (v_in, k_cum, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * CHUNK, h, dv)
    return o[:, :t], pack_state(s).astype(state.dtype)


# ------------------------------------------------------------------ decode


def gated_delta_step_reference(q, k, v, g, beta, state, live=None):
    """One token a sequence, plain XLA: ``q``, ``k`` [B, H, d_k], ``v``
    [B, H, d_v], ``g``, ``beta`` [B, H], ``state`` packed, ``live`` [B]
    bool or None. Returns ``(o [B, H, d_v] float32, state)``; a row that
    is not live keeps its state."""
    h, dk = q.shape[1], q.shape[2]
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    s = unpack_state(state.astype(f32), h) * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.sum(s * (q * dk ** -0.5)[..., None], axis=-2)
    new = pack_state(s).astype(state.dtype)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return o, new


def _step_kernel(src_ref, live_ref, alpha_ref, beta_ref, kq_ref, v_ref, s_ref, o_ref, s_out_ref, *,
                 rows, pack, dv):
    """One grid step: ``rows`` state rows ([d_k, pack * d_v] each) of one
    sequence. ``kq_ref`` [1, 1, 2 * rows * pack, d_k] holds the keys of the
    step's ``rows * pack`` heads, a row each, then their queries; ``v_ref``
    [1, 1, rows, pack * d_v] the values, a state row's heads side by side;
    ``alpha_ref`` and ``beta_ref`` [B, H] are scalars in SMEM."""
    del src_ref
    j, b = pl.program_id(0), pl.program_id(1)

    @pl.when(live_ref[b] == 0)
    def _dead():
        # the blocks of a dead row are another row's (see the index map):
        # nothing is written to its state; its output is never used
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[b] != 0)
    def _live():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, pack * dv), 1)
        # the state wants a key down its d_k sublanes. The step's keys and
        # queries arrive d_k on the lanes, as the projections make them (a
        # few KB), and are turned here, in VMEM, once
        cols = kq_ref[0, 0].T                                   # [d_k, 2 * rows * pack]

        def spread(per_head):
            """``pack`` columns [d_k, 1] or scalars [1, 1], one a head ->
            [d_k or 1, pack * d_v]: head p's over head p's lanes."""
            out = per_head[-1]
            for p in range(pack - 2, -1, -1):
                out = jnp.where(lane < (p + 1) * dv, per_head[p], out)
            return out

        def columns(first):
            return spread([cols[:, first + p:first + p + 1] for p in range(pack)])

        def scalars(ref, first):
            return spread([jnp.full((1, 1), ref[b, first + p]) for p in range(pack)])

        for r in range(rows):
            key, query = columns(r * pack), columns((rows + r) * pack)
            head = (j * rows + r) * pack                        # the state row's first head
            alpha, beta = scalars(alpha_ref, head), scalars(beta_ref, head)
            s = s_ref[0, r] * alpha
            u = beta * (v_ref[0, 0, r:r + 1] - jnp.sum(s * key, axis=0, keepdims=True))
            s = s + key * u
            s_out_ref[0, r] = s
            o_ref[0, 0, r:r + 1] = jnp.sum(s * query, axis=0, keepdims=True)


def _step_geometry(heads: int, d_k: int, d_v: int) -> Tuple[int, int, int, int]:
    """``(pack, rows, groups, width)``: heads a state row, state rows a grid
    step (the fewest that move ``_STEP_BYTES``, among the divisors of the
    row count), grid steps a sequence, and a state row's lanes."""
    n_rows, _, width = state_shape(heads, d_k, d_v)
    rows = next(
        (r for r in range(1, n_rows) if n_rows % r == 0 and r * d_k * width * 4 >= _STEP_BYTES), n_rows,
    )
    return heads // n_rows, rows, n_rows // rows, width


def step_operand_bytes(batch: int, heads: int, d_k: int, d_v: int) -> int:
    """Bytes one call of the decode kernel moves besides the state, as the
    chip holds them (float32, the two minor axes padded to (8, 128) tiles):
    keys and queries, values, alpha and beta in, the output out."""
    pack, rows, groups, width = _step_geometry(heads, d_k, d_v)
    shapes = [(batch, groups, 2 * rows * pack, d_k), (batch, heads), (batch, heads)]
    shapes += [(batch, groups, rows, width)] * 2
    return sum(math.prod(lead) * -(-r // 8) * 8 * -(-c // 128) * 128 * 4 for *lead, r, c in shapes)


def _step_pallas(q, k, v, g, beta, state, live, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    batch, h, dk = q.shape
    dv = v.shape[-1]
    pack, rows, groups, width = _step_geometry(h, dk, dv)
    f32 = jnp.float32

    def heads(x):  # [B, H, d_k] -> [B, groups, rows * pack, d_k]: a reshape, d_k stays minor
        return x.astype(f32).reshape(batch, groups, rows * pack, dk)

    kq = jnp.concatenate([heads(k), heads(q * dk ** -0.5)], axis=2)
    # a dead row names the blocks of the nearest live row before it (the
    # first live row, for the leading dead ones): with the batch as the
    # inner grid axis its block index then equals its neighbour's, and the
    # pipeline neither fetches nor writes back a block for it
    index = jnp.arange(batch, dtype=jnp.int32)
    last_live = jax.lax.cummax(jnp.where(live, index, -1))
    src = jnp.where(last_live >= 0, last_live, jnp.argmax(live).astype(jnp.int32))

    def own(j, b, *prefetched):
        return (b, j, 0, 0)

    def shared(j, b, src, *prefetched):
        return (src[b], j, 0, 0)

    state_block = pl.BlockSpec((1, rows, dk, width), shared)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(groups, batch),
        in_specs=[
            pl.BlockSpec((1, 1, 2 * rows * pack, dk), shared),
            pl.BlockSpec((1, 1, rows, width), shared),
            state_block,
        ],
        out_specs=[pl.BlockSpec((1, 1, rows, width), own), state_block],
    )
    o, new = pl.pallas_call(
        functools.partial(_step_kernel, rows=rows, pack=pack, dv=dv),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, groups, rows, width), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands count the four prefetched arrays: the state is the seventh
        input_output_aliases={6: 1},
        # a dead row's block index must equal its neighbour's: in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gated_delta_step",
    )(
        src, live.astype(jnp.int32), jnp.exp(g.astype(f32)), beta.astype(f32),
        kq, v.astype(f32).reshape(batch, groups, rows, width), state,
    )
    return o.reshape(batch, h, dv), new


def gated_delta_step(q, k, v, g, beta, state, live: Optional[jnp.ndarray] = None, *,
                     impl: str = "auto"):
    """One token a sequence; see :func:`gated_delta_step_reference` for
    the shapes. ``impl``: ``"reference"`` (XLA), ``"pallas"`` (the kernel;
    interpreter mode off a TPU; float32 packed state only) or ``"auto"``
    (the kernel on a TPU, XLA elsewhere)."""
    if impl == "auto":
        impl = "reference" if _interpret() else "pallas"
    if impl == "reference":
        return gated_delta_step_reference(q, k, v, g, beta, state, live)
    if impl != "pallas":
        raise ValueError(f"unknown gated delta step impl {impl!r}")
    if state.dtype != jnp.float32:
        raise ValueError(f"the gated_delta_step kernel keeps a float32 state, got {state.dtype}")
    if live is None:
        live = jnp.ones((q.shape[0],), bool)
    return _step_pallas(q, k, v, g, beta, state, live, interpret=_interpret())
