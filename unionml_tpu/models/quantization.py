"""Weight-only int8 quantization for serving.

No reference counterpart (the reference serves whatever sklearn/torch
object was trained — reference: unionml/fastapi.py:50-64). On TPU,
autoregressive decode is HBM-bandwidth-bound on *parameter reads* (every
generated token streams the full weight set through the MXU), so storing
matmul weights as int8 with per-output-channel fp scales roughly halves
decode latency versus bf16: XLA fuses the int8→bf16 convert into the
matmul, so HBM traffic is the int8 bytes. Quality: symmetric per-channel
weight-only int8 is the standard "free lunch" point — activations stay
bf16, no calibration data needed.

Two pieces:

- :class:`QuantizedDenseGeneral` — drop-in for the dense projections in
  :mod:`unionml_tpu.models.layers` (same ``(axis, features)`` geometry),
  storing ``kernel_q`` int8 ``[K, N]`` + ``scale`` fp32 ``[N]``.
- :func:`quantize_params` — convert a trained fp param tree into the
  quantized module's param structure (kernels reshaped to 2D, quantized
  per output channel; everything else passed through).

Llama opts in with ``LlamaConfig(quantized=True)`` — the same weights
trained unquantized load after :func:`quantize_params`.
"""

from __future__ import annotations

import re
from typing import Any, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


def _dense_geometry(x, axis, features):
    """Shared DenseGeneral geometry: normalize contraction axes, flatten
    the input to ``[..., K]`` and report ``(xt, lead, feats, k, n)``."""
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    axes = tuple(a % x.ndim for a in axes)
    feats = (features,) if isinstance(features, int) else tuple(features)
    k = int(np.prod([x.shape[a] for a in axes]))
    n = int(np.prod(feats))
    batch_axes = tuple(i for i in range(x.ndim) if i not in axes)
    xt = x.transpose(*batch_axes, *axes).reshape(
        tuple(x.shape[i] for i in batch_axes) + (k,)
    )
    return xt, xt.shape[:-1], feats, k, n


class QuantizedDenseGeneral(nn.Module):
    """Weight-only int8 dense layer matching DenseGeneral geometry.

    ``axis``: input dims to contract (int or tuple, negative indices);
    ``features``: output dims (int or tuple). The kernel is stored 2D
    ``[K, N]`` int8 with a per-output-channel fp32 ``scale`` ``[N]``.
    """

    features: Union[int, Sequence[int]]
    axis: Union[int, Sequence[int]] = -1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        xt, lead, feats, k, n = _dense_geometry(x, self.axis, self.features)
        kernel_q = self.param(
            "kernel_q", nn.initializers.zeros, (k, n), jnp.int8
        )
        scale = self.param("scale", nn.initializers.ones, (n,), jnp.float32)
        # int8 weights convert to the compute dtype inside the fused
        # matmul: HBM reads stay int8
        w = kernel_q.astype(self.dtype)
        y = jax.lax.dot_general(
            xt.astype(self.dtype), w,
            (((xt.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        y = (y * scale).astype(self.dtype)
        return y.reshape(lead + feats)


class Int4DenseGeneral(nn.Module):
    """Weight-only packed-int4 dense layer (DenseGeneral geometry).

    Stores ``kernel_p`` int8 ``[K, N/2]`` (two nibbles per byte, the
    tile-slab order of :mod:`unionml_tpu.ops.int4_matmul`) + fp32
    ``scale [N]`` — or ``scale_g [K/group_size, N]`` when ``group_size``
    is set (group-wise scales, the 4-bit quality recipe; the distinct
    name keeps the 2D leaf's partition rules separate from the 1D
    scale's). Decode-sized row counts run the Pallas kernel so HBM
    weight reads stay at the packed width; other shapes take the XLA
    unpack path with identical semantics.

    ``shards``: the tensor-parallel degree the packing tile must
    survive (``tile_for``'s shard-aligned slab rule) — set it on
    COLUMN-parallel sites (q/k/v, gate/up) when the tree is packed for
    TP; row-parallel sites (o, down, the K-sharded lm_head) keep 1.
    MUST match the ``tensor=`` the tree was quantized with, or the
    baked slab order and the layer's tile disagree and decode produces
    garbage (guarded by ``assert_int4_tp_compatible``).
    """

    features: Union[int, Sequence[int]]
    axis: Union[int, Sequence[int]] = -1
    dtype: Any = jnp.bfloat16
    group_size: int = 0
    shards: int = 1

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        from unionml_tpu.ops.int4_matmul import int4_matmul, tile_for

        xt, lead, feats, k, n = _dense_geometry(x, self.axis, self.features)
        tile = tile_for(n, k, shards=self.shards)
        if tile == 0 or (self.group_size and k % self.group_size):
            # untileable width (odd N, VMEM-oversized single tile) or a
            # K-group that doesn't divide this layer's contraction: the
            # SAME per-layer int8 fallback quantize_params(bits=4)
            # applies — param structure and math match kernel_q+scale,
            # so a mixed int4/int8 tree loads as one module family
            kernel_q = self.param(
                "kernel_q", nn.initializers.zeros, (k, n), jnp.int8
            )
            scale = self.param("scale", nn.initializers.ones, (n,), jnp.float32)
            y = jax.lax.dot_general(
                xt.astype(self.dtype), kernel_q.astype(self.dtype),
                (((xt.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return ((y * scale).astype(self.dtype)).reshape(lead + feats)
        kernel_p = self.param(
            "kernel_p", nn.initializers.zeros, (k, n // 2), jnp.int8
        )
        if self.group_size:
            scale = self.param(
                "scale_g", nn.initializers.ones,
                (k // self.group_size, n), jnp.float32,
            )
        else:
            scale = self.param("scale", nn.initializers.ones, (n,), jnp.float32)
        y = int4_matmul(
            xt.reshape(-1, k), kernel_p, scale, tile_n=tile,
            dtype=self.dtype, group_size=self.group_size,
        )
        return y.reshape(lead + feats)


def _quantize_kernel_2d(w2d: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-output-channel int8: returns (kernel_q, scale)."""
    w = jnp.asarray(w2d, jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=0)                       # [N]
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _quantize_expert_kernel(w3d: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(expert, out-channel) symmetric int8 for [E, K, N] MoE weights:
    the 2D recipe vmapped over the leading expert axis."""
    return jax.vmap(_quantize_kernel_2d)(jnp.asarray(w3d))


LLAMA_QUANT_PATTERNS = (
    r"attn/(q|k|v|o)$", r"mlp/(gate|up|down)$", r"lm_head$", r"moe$"
)


def quantize_params(
    params: Any,
    patterns: Sequence[str],
    *,
    bits: int = 8,
    group_size: int = 0,
    tensor: int = 1,
) -> Any:
    """Convert fp dense kernels to the quantized param structure.

    ``bits=4`` produces the packed-int4 layout (``kernel_p`` + ``scale``
    — :class:`Int4DenseGeneral`) for matching DENSE kernels; MoE expert
    blocks stay int8 (no int4 expert kernel). Layers with an odd output
    width also stay int8.

    ``group_size`` (bits=4 only): group-wise scales ``scale_g [K/g, N]``
    instead of per-channel ``[N]`` — the 4-bit quality recipe. The model
    config must carry the same ``int4_group`` so the module declares the
    matching leaf.

    ``tensor`` (bits=4 only): the tensor-parallel degree to pack for —
    COLUMN-parallel sites (q/k/v, gate/up) bake a tile dividing their
    per-device channel count so a ``tensor``-axis shard of the packed
    columns stays a valid slab packing (row-parallel o/down and the
    K-sharded lm_head are unaffected). The model config must carry the
    same ``int4_tp``.

    ``patterns`` is required (use :data:`LLAMA_QUANT_PATTERNS` for the
    Llama zoo model): a catch-all would silently mis-split kernels whose
    geometry this name-based dispatch doesn't know (e.g. BERT's
    ``attn_o``, ViT's 4D patch-embed conv).

    Walks the tree; any dict holding a ``kernel`` whose path matches one
    of ``patterns`` becomes ``{"kernel_q": int8 [K, N], "scale": [N]}``.
    The K/N split follows the layer geometry in
    :mod:`unionml_tpu.models.layers`: a projection named ``o`` contracts
    its LEADING dims (``[heads, dim, out]`` → K=heads*dim, N=out); every
    other projection contracts its single leading input dim
    (``[in, ...features]`` → K=in, N=prod(features)). A module with a
    differently-shaped multi-axis kernel needs its own conversion — this
    name-based dispatch covers the shipped model zoo only.
    Non-matching subtrees pass through unchanged.
    """
    compiled = [re.compile(p) for p in patterns]

    def walk(path, tree):
        if isinstance(tree, dict) and "w_gate" in tree and "w_down" in tree:
            # MoE expert block (ops/moe.py): [E, K, N] weights quantize
            # per (expert, out-channel); the fp32 router passes through
            joined = "/".join(path)
            if any(c.search(joined) for c in compiled):
                out = {}
                for name, v in tree.items():
                    if name in ("w_gate", "w_up", "w_down"):
                        q, scale = _quantize_expert_kernel(jnp.asarray(v))
                        out[f"{name}_q"] = q
                        out[f"{name}_scale"] = scale
                    else:
                        out[name] = v
                return out
        if isinstance(tree, dict) and "kernel" in tree and isinstance(
            tree["kernel"], (jnp.ndarray, np.ndarray)
        ):
            joined = "/".join(path)
            if any(c.search(joined) for c in compiled):
                w = jnp.asarray(tree["kernel"])
                # DenseGeneral geometry: the "o" projection contracts its
                # LEADING dims (heads, dim); every other projection
                # contracts the single leading input dim
                if path and path[-1] == "o":
                    k = int(np.prod(w.shape[:-1]))
                    w2d = w.reshape(k, w.shape[-1])
                else:
                    k = w.shape[0]
                    w2d = w.reshape(k, -1)
                if bits == 4:
                    from unionml_tpu.ops.int4_matmul import (
                        quantize_kernel_int4,
                        tile_for,
                    )

                    # column-parallel sites shard N: their tile must
                    # divide the per-device channel count (matches the
                    # shards= each Int4DenseGeneral site declares)
                    col_parallel = path and path[-1] in (
                        "q", "k", "v", "gate", "up"
                    )
                    shards = tensor if col_parallel else 1
                    tile = tile_for(w2d.shape[1], w2d.shape[0], shards=shards)
                    if tile and (
                        group_size == 0 or w2d.shape[0] % group_size == 0
                    ):
                        p, scale = quantize_kernel_int4(
                            w2d, tile, group_size=group_size
                        )
                        out = {
                            "kernel_p": p,
                            ("scale_g" if group_size else "scale"): scale,
                        }
                        for extra, v in tree.items():
                            if extra != "kernel":
                                out[extra] = v
                        return out
                    # odd output width / indivisible K-group: int8 below
                q, scale = _quantize_kernel_2d(w2d)
                out = {"kernel_q": q, "scale": scale}
                for extra, v in tree.items():
                    if extra != "kernel":
                        out[extra] = v
                return out
        if isinstance(tree, dict):
            return {k: walk(path + (k,), v) for k, v in tree.items()}
        return tree

    return walk((), params)
