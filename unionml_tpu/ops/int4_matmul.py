"""Packed-int4 weight-only matmul (Pallas) — the decode bandwidth lever.

8B int8 serving is bound by HBM: every decoded token streams the full
weight set. int4 weights halve the bytes again — but XLA materializes
any unpack it is shown, so the int4 path stores TWO NIBBLES PER int8
BYTE and a Pallas kernel unpacks in VMEM, feeding the MXU directly —
HBM reads stay at the packed width. Not measured on the current chip.

Packing layout (``pack_int4``): output channels are tiled by ``TILE_N``;
within tile ``j`` the LOW nibbles hold channels ``[j*T, j*T + T/2)`` and
the HIGH nibbles ``[j*T + T/2, (j+1)*T)``, so the kernel's two
per-nibble matmuls write contiguous slabs and the output needs no
permutation. Mosaic constraints honored: nibble math runs in int32
(int8 shifts don't legalize), scales apply OUTSIDE the kernel (1D fp32
operands hit XLA/Mosaic layout mismatches), and the Pallas path engages
only for row counts ≤ ``MAX_PALLAS_ROWS`` and tile-divisible N — other
shapes (prefill's flattened rows, tiny test geometries) fall back to an
XLA unpack with identical semantics (prefill is compute-amortized; the
bandwidth lever only matters for decode).

Quantization (``quantize_kernel_int4``): symmetric per-output-channel
absmax/7 — coarser than int8's /127; serving quality at 4-bit normally
wants group-wise scales, which compose with this kernel (scales are
outside) but are not implemented here. The shipped recipe is the
latency configuration; quality evaluation needs real weights.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "MAX_PALLAS_ROWS",
    "int4_matmul",
    "pack_int4",
    "quantize_kernel_int4",
    "unpack_int4",
]

TILE_N = 512          # output-channel tile; N must divide by a tile choice
MAX_PALLAS_ROWS = 64  # decode/verify row counts; larger rows → XLA path


# per-program VMEM budget for the weight-side buffers: packed int8 +
# int32 nibble temps + bf16 operands ≈ 9 bytes per packed element; the
# v5e scoped-vmem limit is 16 MB per kernel (leave headroom for x/out)
_VMEM_WEIGHT_BYTES = 11_000_000


def _grid_for(n: int, k: int, shards: int = 1, group_size: int = 0):
    """Pick ``(tile_n, k_block)`` for N output channels at contraction
    width K. Mosaic needs the packed block's last dim (tile/2) to
    divide 128 or equal the full packed width, so multi-tile means
    tile ∈ {512, 256, 128}; any even N works single-tile. Big K blows
    the scoped-VMEM budget (the int32 unpack temps scale with K x TILE),
    so K splits into grid blocks with output accumulation — k_block
    halves until the weight-side buffers fit (K=14336 down-projections
    run tile 512 x k_block 3584). Returns ``(0, 0)`` when N is odd
    (cannot pack two nibbles per byte).

    ``shards``: tensor-parallel degree the packing must survive — the
    tile must divide the PER-DEVICE channel count ``n // shards`` so
    shard boundaries land on slab boundaries (any divisor of ``shards``
    then also works at serve time). A 128-tile is a valid PACKING but
    not a Pallas-servable block (its packed width 64 breaks the Mosaic
    lane rule unless it spans the whole array) — ``int4_matmul`` routes
    such layers through the XLA unpack path. ``group_size``: group-wise
    scale granularity — k_block additionally divides the group so each
    grid step's partial product carries ONE scale row (see
    :func:`int4_matmul`'s grouped path)."""
    if n % 2 or n % max(1, shards):
        return 0, 0
    local = n // max(1, shards)
    candidates = [t for t in (512, 256, 128) if local % t == 0]
    if not candidates and shards == 1:
        candidates = [n]  # single-tile: any even width
    for t in candidates:
        kb = _k_block_for(k, t, group_size)
        if kb:
            return t, kb
    return 0, 0


def _k_block_for(k: int, tile_n: int, group_size: int = 0) -> int:
    """The K grid block for a GIVEN tile: halve from K (or the scale
    group) until the weight-side VMEM buffers fit. Sized against the
    caller's actual tile — a first-fit recompute against a different
    candidate would fragment the K grid (review finding)."""
    kb = min(k, group_size) if group_size else k
    while 9 * kb * (tile_n // 2) > _VMEM_WEIGHT_BYTES and kb % 2 == 0:
        kb //= 2
    if 9 * kb * (tile_n // 2) <= _VMEM_WEIGHT_BYTES and (
        kb == k or kb % 128 == 0
    ):
        return kb
    return 0


def pack_int4(nibbles: jnp.ndarray, tile_n: int) -> jnp.ndarray:
    """Pack int8 nibble values (in [-8, 7]) ``[K, N]`` → ``[K, N/2]``
    int8, tile-slab order (see module docstring)."""
    k, n = nibbles.shape
    t = nibbles.reshape(k, n // tile_n, tile_n)
    lo = t[:, :, : tile_n // 2]
    hi = t[:, :, tile_n // 2 :]
    p = (lo.astype(jnp.uint8) & 0xF) | ((hi.astype(jnp.uint8) & 0xF) << 4)
    return p.reshape(k, n // 2).astype(jnp.int8)


def unpack_int4(packed: jnp.ndarray, tile_n: int) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: ``[K, N/2]`` int8 → ``[K, N]`` int8
    nibble values (the XLA-fallback dequant and the test oracle)."""
    k, half = packed.shape
    q = packed.astype(jnp.int32)
    hi = q >> 4
    lo = ((q & 15) ^ 8) - 8
    t = jnp.concatenate(
        [
            lo.reshape(k, half // (tile_n // 2), tile_n // 2),
            hi.reshape(k, half // (tile_n // 2), tile_n // 2),
        ],
        axis=2,
    )
    return t.reshape(k, 2 * half).astype(jnp.int8)


def _kernel(x_ref, wp_ref, o_ref):
    from jax.experimental import pallas as pl

    q = wp_ref[...].astype(jnp.int32)  # int8 shifts don't legalize in Mosaic
    hi = q >> 4                        # arithmetic shift == floor(q/16)
    lo = ((q & 15) ^ 8) - 8            # sign-extend the low nibble
    xb = x_ref[...]
    # weights convert to the CALLER'S compute dtype (the lm_head keeps
    # its fp32-logits contract; everything else runs bf16 on the MXU)
    y_lo = jax.lax.dot_general(
        xb, lo.astype(xb.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    y_hi = jax.lax.dot_general(
        xb, hi.astype(xb.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    partial_out = jnp.concatenate([y_lo, y_hi], axis=1)

    # K is blocked over the innermost grid dim with output accumulation
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial_out


@functools.partial(
    jax.jit, static_argnames=("n", "tile_n", "k_block", "interpret")
)
def _pallas_int4(x, packed, *, n: int, tile_n: int, k_block: int, interpret: bool):
    from jax.experimental import pallas as pl

    rows, k = x.shape
    grid = (n // tile_n, k // k_block)  # k innermost: accumulation order
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, k_block), lambda j, kb: (0, kb)),
            pl.BlockSpec((k_block, tile_n // 2), lambda j, kb: (kb, j)),
        ],
        out_specs=pl.BlockSpec((rows, tile_n), lambda j, kb: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        interpret=interpret,
    )(x, packed)


def _kernel_grouped(x_ref, wp_ref, s_ref, o_ref, *, ratio: int):
    """Group-wise variant: ``k_block`` divides the scale group, so this
    step's whole partial product carries ONE scale row — the scale
    multiply rides the small fp32 partial, never a materialized weight
    tile. ``s_ref`` holds the tile's FULL [K/g, tile] scale slab (a
    (1, tile) block would violate Mosaic's second-minor-divisible-by-8
    rule; the slab is ~64 KB and the kernel slices its group row
    dynamically — ``ratio = group_size / k_block`` maps the K grid
    index to it)."""
    from jax.experimental import pallas as pl

    q = wp_ref[...].astype(jnp.int32)
    hi = q >> 4
    lo = ((q & 15) ^ 8) - 8
    xb = x_ref[...]
    y_lo = jax.lax.dot_general(
        xb, lo.astype(xb.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    y_hi = jax.lax.dot_general(
        xb, hi.astype(xb.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    kb = pl.program_id(1)
    row = kb if ratio == 1 else jax.lax.div(kb, jnp.int32(ratio))
    # dynamic REF load (value-level dynamic_slice has no TC lowering)
    scale_row = s_ref[pl.dslice(row, 1), :]
    partial_out = jnp.concatenate([y_lo, y_hi], axis=1) * scale_row

    @pl.when(kb == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial_out


@functools.partial(
    jax.jit,
    static_argnames=("n", "tile_n", "k_block", "group_size", "interpret"),
)
def _pallas_int4_grouped(
    x, packed, scale_slab, *, n: int, tile_n: int, k_block: int,
    group_size: int, interpret: bool,
):
    """``scale``: fp32 [K/g, N] in NATURAL channel order — within tile
    ``j`` the kernel's ``concat([y_lo, y_hi])`` partial spans channels
    ``[j*t, (j+1)*t)`` contiguously (the pack layout's whole point), so
    the per-block [1, tile] scale slice lines up with no reorder."""
    from jax.experimental import pallas as pl

    rows, k = x.shape
    grid = (n // tile_n, k // k_block)
    # k_block | group_size: K-block kb reads scale row kb / ratio
    ratio = group_size // k_block
    return pl.pallas_call(
        functools.partial(_kernel_grouped, ratio=ratio),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, k_block), lambda j, kb: (0, kb)),
            pl.BlockSpec((k_block, tile_n // 2), lambda j, kb: (kb, j)),
            # full scale-row slab per tile (first dim equal to the
            # array's — Mosaic's block rule): the kernel slices its row
            pl.BlockSpec((k // group_size, tile_n), lambda j, kb: (0, j)),
        ],
        out_specs=pl.BlockSpec((rows, tile_n), lambda j, kb: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        interpret=interpret,
    )(x, packed, scale_slab)


def int4_matmul(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    tile_n: int,
    dtype=jnp.bfloat16,
    group_size: int = 0,
) -> jnp.ndarray:
    """``x [rows, K] @ W4`` where ``W4`` is ``pack_int4``-packed
    ``[K, N/2]`` with fp32 ``scale``: per-output-channel ``[N]``
    (``group_size=0``) or group-wise ``[K/group_size, N]`` — the
    standard 4-bit quality recipe (each K-group of an output channel
    carries its own scale; absmax outliers then poison ``group_size``
    weights instead of the whole column).

    Decode-sized row counts on TPU run the Pallas kernel (HBM reads at
    the packed width; grouped scales ride the small fp32 partials inside
    the kernel — K-blocks divide the group, so no weight tile is ever
    materialized at fp width); anything else takes the XLA unpack path —
    same math, standard traffic. The compute dtype follows ``dtype``
    when it is a float type (fp32 for the LM head's logits contract,
    bf16 otherwise), matching ``QuantizedDenseGeneral``'s behavior.
    """
    rows, k = x.shape
    n = scale.shape[-1]
    if group_size:
        if scale.ndim != 2 or scale.shape[0] != k // group_size:
            raise ValueError(
                f"group_size={group_size} needs scale [K/g, N] = "
                f"[{k // group_size}, {n}], got {scale.shape}"
            )
    elif scale.ndim != 1:
        raise ValueError(
            f"per-channel int4 needs scale [N], got {scale.shape} — pass "
            "group_size for group-wise scales"
        )
    compute = dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.bfloat16
    k_block = _k_block_for(k, tile_n, group_size) if tile_n > 0 else 0
    # Mosaic lane rule: the packed operand's block width (tile/2) must
    # be a multiple of 128 or span the whole packed array — a 128-tile
    # (TP-packed k/v geometry served on one chip) is a valid PACKING but
    # not a servable Pallas block, so it decodes via the XLA path
    mosaic_ok = tile_n % 256 == 0 or tile_n == n
    use_pallas = (
        0 < rows <= MAX_PALLAS_ROWS and tile_n > 0 and k_block > 0
        and mosaic_ok
    )
    if (
        group_size and group_size % 128 and tile_n > 0
        and 0 < rows <= MAX_PALLAS_ROWS
    ):
        # fires at trace time, once per compiled shape: the operator
        # asked for the decode-bandwidth configuration but loses it
        import warnings

        warnings.warn(
            f"int4 group_size={group_size} is not a multiple of 128: the "
            "Pallas decode kernel cannot block K below 128 (Mosaic lane "
            "rule, measured on v5e), so decode takes the XLA unpack path "
            "at full-width weight reads. Use group_size=128 to keep the "
            "packed-width bandwidth win (measured ~1.4% over "
            "per-channel).",
            stacklevel=2,
        )
    if use_pallas:
        interpret = jax.default_backend() != "tpu"
        if group_size:
            y = _pallas_int4_grouped(
                x.astype(compute), packed, scale, n=n, tile_n=tile_n,
                k_block=k_block, group_size=group_size, interpret=interpret,
            )
            return y.astype(dtype)
        y = _pallas_int4(
            x.astype(compute), packed, n=n, tile_n=tile_n,
            k_block=k_block, interpret=interpret,
        )
        return (y * scale).astype(dtype)
    w = unpack_int4(packed, tile_n)
    if group_size:
        # fallback (prefill / compute-bound shapes): dequantize at fp32
        # so group scales keep their precision, then one matmul
        w_f = w.astype(jnp.float32) * jnp.repeat(scale, group_size, axis=0)
        y = jax.lax.dot_general(
            x.astype(compute), w_f.astype(compute),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return y.astype(dtype)
    y = jax.lax.dot_general(
        x.astype(compute), w.astype(compute),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    return (y * scale).astype(dtype)


def quantize_kernel_int4(
    w2d: jnp.ndarray, tile_n: int, group_size: int = 0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int4: ``[K, N]`` fp → ``(packed [K, N/2] int8, scale)``.

    ``group_size=0``: per-output-channel absmax/7, scale ``[N]``.
    ``group_size=g``: per-(K-group, channel) absmax/7, scale ``[K/g, N]``
    — the 4-bit quality recipe (g must divide K; 128 is the standard
    point AND the smallest group the Pallas decode kernel can serve at
    packed-width reads — Mosaic blocks K in multiples of 128; smaller
    groups decode via the XLA path). ``tile_n`` must match the serving
    call's tile (it bakes the slab order into the packing)."""
    w = jnp.asarray(w2d, jnp.float32)
    k, n = w.shape
    if group_size:
        if group_size < 1 or k % group_size:
            raise ValueError(
                f"group_size {group_size} must divide K={k}"
            )
        g = w.reshape(k // group_size, group_size, n)
        absmax = jnp.max(jnp.abs(g), axis=1)             # [K/g, N]
        scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
        nib = jnp.clip(
            jnp.round(g / scale[:, None, :]), -8, 7
        ).astype(jnp.int8).reshape(k, n)
        return pack_int4(nib, tile_n), scale.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=0)                 # [N]
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    nib = jnp.clip(jnp.round(w / scale), -8, 7).astype(jnp.int8)
    return pack_int4(nib, tile_n), scale.astype(jnp.float32)


def tile_for(n: int, k: int, shards: int = 1) -> int:
    """The tile the serving layer should bake for ``N`` output channels
    at contraction width ``K`` (0 = no conforming tile; the layer must
    stay int8). ``shards``: the tensor-parallel degree the packing must
    survive — the tile must divide the per-device channel count so a
    ``tensor``-axis shard of the packed/scale columns stays a valid
    slab packing on every device (any divisor of ``shards`` also serves
    correctly; a FINER split than packed for does not)."""
    return _grid_for(n, k, shards=shards)[0]
