"""Shared TPU-first building blocks for the model zoo.

No reference counterpart — the reference delegates modeling to
sklearn/torch/keras user code (reference: unionml/model.py:931-988 only
touches models to serialize them). Here the framework ships its own
flax.linen model family (BASELINE.json configs: MNIST-MLP, ViT-B/16,
BERT-base, Llama-3-8B) so trainer/predictor bodies are jit/pjit-native.

Design notes (TPU):
- All matmul-bearing layers keep a ``dtype`` (compute, default bfloat16)
  separate from ``param_dtype`` (float32 master weights) so the MXU runs
  bf16 while optimizer state stays fp32.
- Attention dispatches to the op family in :mod:`unionml_tpu.ops` —
  ``xla`` (fused reference), ``blockwise`` (online-softmax memory saver),
  ``flash`` (Pallas kernel), ``ring``/``ulysses`` (sequence-parallel,
  require a mesh axis).
- Kernel axes are named via ``nn.with_logical_partitioning``-free plain
  params; tensor-parallel layouts come from path-regex
  :class:`~unionml_tpu.parallel.sharding.PartitionRule`s instead, keeping
  modules decoupled from the mesh.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from unionml_tpu.ops.attention import attention as xla_attention
from unionml_tpu.ops.attention import blockwise_attention

Dtype = Any


# ---- what a decoder tells a serving engine about each layer's cache ----
# A cache-capable decoder has a ``cache_layout()`` method that returns one
# of these per layer, in layer order; its ``__call__`` takes and returns a
# per-layer ``cache`` tuple whose entries are what ``init`` builds.
# ``owns_rows`` says which residency a paged engine gives the layer: rows
# of its block pool (``KVRows``, ``LatentRows``, ``IndexedKVRows``) or a
# state per slot.


@dataclass(frozen=True)
class KVRows:
    """A layer whose cache grows by one row a token: keys and values of
    ``kv_heads x head_dim``, bf16 (or int8 with fp32 per-(row, head)
    scales). A paged engine gives such a layer rows of its block pool."""

    kv_heads: int
    head_dim: int
    quantized: bool = False
    # the rows' dtype where the module says (float32 in tests that compare
    # logits); None: bfloat16, or what ``init`` is handed
    dtype: Optional[str] = None
    # one buffer ``[.., 2 * kv_heads, head_dim]``, a position's key heads
    # and its value heads behind them, in the two buffers' place: with 4 + 4
    # heads of 128 a position is one whole bfloat16 tile of 8 x 128, where a
    # ``[.., 4, 128]`` buffer is tiled over four rows and re-laid out round
    # every scatter (as :class:`IndexedKVRows`' first buffer)
    fused: bool = False
    # the query heads that read the rows, as the module hands them to the
    # paged kernel (0: not said); not part of what a row is: what
    # ``engine.stats()["kv_pool"]["score_tile"]`` follows from
    q_heads: int = field(default=0, compare=False)
    owns_rows = True  # a row a token: a paged engine's pool holds them
    kind = "kv"

    def __post_init__(self):
        if self.fused and self.quantized:
            raise ValueError("a fused row of keys and values is not built for an int8 cache")

    def init(self, batch: int, rows: int, dtype: Dtype = jnp.bfloat16):
        shape = (batch, rows, self.kv_heads, self.head_dim)
        if self.quantized:
            q, s = jnp.zeros(shape, jnp.int8), jnp.ones(shape[:-1], jnp.float32)
            return (q, q, s, s)
        dtype = dtype if self.dtype is None else jnp.dtype(self.dtype)
        if self.fused:
            return (jnp.zeros((batch, rows, 2 * self.kv_heads, self.head_dim), dtype),)
        zeros = jnp.zeros(shape, dtype)
        return (zeros, zeros)

    def _itemsize(self) -> int:
        return 1 if self.quantized else jnp.dtype(self.dtype or jnp.bfloat16).itemsize

    def row_nbytes(self) -> int:
        """Bytes one cached position takes in this layer."""
        per_head = (self.head_dim + 4) if self.quantized else self._itemsize() * self.head_dim
        return 2 * self.kv_heads * per_head

    def pool_row_nbytes(self) -> int:
        """What a paged engine budgets a position at (the served models'
        head widths are whole 128-lane tiles: the chip pads nothing)."""
        return self.row_nbytes()

    @property
    def pool_row(self) -> Tuple[int, int, int]:
        """(heads, width, bytes a value) of a pool buffer's row: what the
        paged kernel's group size follows from."""
        return self.kv_heads, self.head_dim, self._itemsize()


@dataclass(frozen=True)
class LatentRows:
    """A layer whose cache grows by one row a token, and the row is the
    *latent* that all heads' keys and values are projections of (latent
    attention, DeepSeek-V2's MLA): ``latent_dim`` compressed values
    followed by ``rope_dim`` rotated key values that every head shares,
    bf16, in one buffer whose rows are ``stored_width`` wide: whole tiles
    of 128 lanes, the last columns zero. (The chip lays a 576-wide minor
    axis out in 640 lanes anyway, as it would a 512 and a 64 buffer, and
    its kernels copy whole tiles only: the padding is made explicit, at
    no cost in bytes.) The same lifetime as :class:`KVRows`: a paged
    engine gives it rows of its block pool, commits a prefill by block
    scatter, and a block prefix restores a sequence."""

    latent_dim: int
    rope_dim: int
    dtype: str = "bfloat16"  # one row feeds every head's keys and values
    owns_rows = True
    kind = "latent"

    @property
    def width(self) -> int:
        return self.latent_dim + self.rope_dim

    @property
    def stored_width(self) -> int:
        return -(-self.width // 128) * 128

    def init(self, batch: int, rows: int, dtype: Optional[Dtype] = None):
        return (jnp.zeros((batch, rows, self.stored_width), jnp.dtype(dtype or self.dtype)),)

    def row_nbytes(self) -> int:
        """Bytes of the values one cached position holds in this layer."""
        return jnp.dtype(self.dtype).itemsize * self.width

    def pool_row_nbytes(self) -> int:
        """Bytes a position takes as stored, which is what a paged engine
        budgets (576 bfloat16 values in 640 lanes: 1,280)."""
        return jnp.dtype(self.dtype).itemsize * self.stored_width

    @property
    def pool_row(self) -> Tuple[int, int, int]:
        """As ``KVRows.pool_row``: one head, the row as stored."""
        return 1, self.stored_width, jnp.dtype(self.dtype).itemsize


@dataclass(frozen=True)
class IndexedKVRows:
    """A layer whose cache grows by one row a token and the row is keys,
    values *and* one key of a learned indexer (sparse attention after
    DeepSeek-V3.2's: a light scorer reads every cached position's
    ``index_dim``-wide key, picks the positions a query attends, and only
    their keys and values are read). Two buffers with the same blocks, ids
    and lifetime as :class:`KVRows`' two: a paged engine gives the layer
    rows of its block pool, commits a prefill by block scatter, and a block
    prefix restores a sequence, indexer keys included.

    The first buffer holds a position's keys *and* values as ``2 *
    kv_heads`` rows of ``head_dim``: ``[.., 2 * kv_heads, head_dim]``, the
    key heads first. With 4 + 4 heads of 128 that is one whole bfloat16 tile
    of 8 x 128 a position: 2 KB that lie together, so a picked position is
    one fetch of one tile (rows of a ``[.., 1024]`` buffer are strips of
    many tiles, and gathering them read slower in a program whose picks
    were constants: the sizes are not to be trusted, PERF.md, PR 40), and the
    block scatter of a prefill keeps the pool's layout (a ``[.., 4, 128]``
    buffer is tiled over four rows and was re-laid out round every scatter).
    The second holds the indexer key ``index_stored`` wide: whole tiles of
    128 lanes, the last columns zero (a 64-wide bfloat16 minor axis lies in
    128 lanes on the chip whatever its shape says: the padding is made
    explicit and budgeted)."""

    kv_heads: int
    head_dim: int
    index_dim: int
    dtype: str = "bfloat16"
    owns_rows = True
    kind = "kv+index"

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def index_stored(self) -> int:
        return -(-self.index_dim // 128) * 128

    def init(self, batch: int, rows: int, dtype: Optional[Dtype] = None):
        dtype = jnp.dtype(dtype or self.dtype)
        return (
            jnp.zeros((batch, rows, 2 * self.kv_heads, self.head_dim), dtype),
            jnp.zeros((batch, rows, self.index_stored), dtype),
        )

    def row_nbytes(self) -> int:
        """Bytes of the values one cached position holds in this layer."""
        return jnp.dtype(self.dtype).itemsize * (2 * self.kv_width + self.index_dim)

    def pool_row_nbytes(self) -> int:
        """Bytes a position takes as stored, which is what a paged engine
        budgets (4 x 128 keys, as many values, a 64-wide indexer key in 128
        lanes, bfloat16: 2,304)."""
        return jnp.dtype(self.dtype).itemsize * (2 * self.kv_width + self.index_stored)

    @property
    def pool_row(self) -> Tuple[int, int, int]:
        """As ``KVRows.pool_row``: the keys' and values' buffers."""
        return self.kv_heads, self.head_dim, jnp.dtype(self.dtype).itemsize


@dataclass(frozen=True)
class SlotState:
    """A layer whose cache is a state of fixed size, whatever the
    sequence's length (a recurrent or linear-attention layer): arrays of
    ``shapes`` / ``dtypes`` per sequence. An engine keeps one per slot,
    writes it whole when a prefill ends, and a block prefix of the
    sequence does not restore it."""

    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    owns_rows = False  # one state per slot, whatever the sequence's length
    kind = "state"

    def init(self, batch: int, rows: int = 0):
        """``rows`` is taken for ``KVRows.init``'s sake: the state has none."""
        return tuple(
            jnp.zeros((batch,) + shape, jnp.dtype(dt))
            for shape, dt in zip(self.shapes, self.dtypes)
        )

    def nbytes(self) -> int:
        """Bytes one sequence's state takes in this layer."""
        return sum(
            int(np.prod(shape)) * jnp.dtype(dt).itemsize
            for shape, dt in zip(self.shapes, self.dtypes)
        )


# ---- what a decoder tells a serving engine about how it generates ----
# A decoder that does not emit one token a forward has a
# ``generation_scheme()`` method beside ``cache_layout()``; without one the
# engine decodes a token a step.

REMASKING_STRATEGIES = ("low_confidence_static", "low_confidence_dynamic")


@dataclass(frozen=True)
class BlockDiffusion:
    """Generation by diffusion over blocks: every position belongs to block
    ``t // block_length`` (aligned from position 0, prompt included);
    attention is causal across blocks and bidirectional inside one; a
    position's logits predict the token *at* that position. An open block's
    undecided entries hold ``mask_token_id``; one denoising forward over the
    block decides ``block_length // denoising_steps`` of them (the most
    confident; under ``low_confidence_dynamic`` every entry whose confidence
    passes ``threshold`` where at least that many do), and once none is
    left the block's rows are written from its final tokens (the commit),
    in the forward that is the next block's first denoising one."""

    block_length: int
    denoising_steps: int
    remasking: str = "low_confidence_static"
    threshold: float = 0.9
    mask_token_id: int = 0

    def __post_init__(self):
        bk = self.block_length
        if bk < 1 or bk & (bk - 1):
            raise ValueError(f"block_length {bk} must be a power of two")
        if not 1 <= self.denoising_steps <= bk or bk % self.denoising_steps:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} must divide block_length {bk}"
            )
        if self.remasking not in REMASKING_STRATEGIES:
            raise ValueError(
                f"remasking {self.remasking!r} is not one of {REMASKING_STRATEGIES}"
            )

    @property
    def per_forward(self) -> int:
        """Entries a denoising forward decides at the least."""
        return self.block_length // self.denoising_steps

    @property
    def forwards_per_block(self) -> int:
        """Forwards a whole block takes at the most: its denoising
        forwards (the commit of its final tokens' rows rides with the next
        block's first)."""
        return self.denoising_steps

    def choose(self, confidence, candidates):
        """bool like ``candidates`` [..., block_length]: the entries this
        forward decides, from each candidate's ``confidence`` (float32):
        the ``per_forward`` most confident, ties towards the lower
        position; under the dynamic rule every candidate over ``threshold``
        where at least ``per_forward`` are."""
        n = self.per_forward
        conf = jnp.where(candidates, confidence.astype(jnp.float32), -1.0)
        # rank = how many candidates come before this one (more confident,
        # or as confident at a lower position)
        ahead = (conf[..., None, :] > conf[..., :, None]) | (
            (conf[..., None, :] == conf[..., :, None])
            & (jnp.arange(self.block_length)[None, :] < jnp.arange(self.block_length)[:, None])
        )
        rank = jnp.sum(ahead & candidates[..., None, :], axis=-1)
        chosen = candidates & (rank < n)
        if self.remasking == "low_confidence_dynamic":
            over = candidates & (conf > self.threshold)
            chosen = jnp.where(jnp.sum(over, axis=-1, keepdims=True) >= n, over, chosen)
        return chosen


def merged_dot_general(lhs, rhs, dimension_numbers, precision=None, preferred_element_type=None):
    """``lax.dot_general`` as ``DenseGeneral`` calls it, over the kernel's
    axes merged to ``[K, N]``: a projection to ``(heads, head_dim)`` is one
    product of width ``heads * head_dim`` followed by a reshape, and one
    from it is a reshape followed by a product of that depth. The same sums;
    what differs is what XLA may lay out: a ``[B, S, H, D]`` result is tiled
    over its 64-wide minor axis (half of every tile padding), a
    ``[B, S, H*D]`` one is not, and the reshape back cancels against the
    fused attention kernel's own (ops/fused_attention.py)."""
    (lhs_contract, rhs_contract), (lhs_batch, _) = dimension_numbers
    n_contract = len(rhs_contract)
    trailing = tuple(range(lhs.ndim - n_contract, lhs.ndim))
    if (
        lhs_batch or rhs.ndim == 2 or tuple(lhs_contract) != trailing
        or tuple(rhs_contract) != tuple(range(n_contract))
    ):
        return jax.lax.dot_general(
            lhs, rhs, dimension_numbers, precision=precision,
            preferred_element_type=preferred_element_type,
        )
    features = rhs.shape[n_contract:]
    depth = math.prod(rhs.shape[:n_contract])
    flat = lhs.reshape(lhs.shape[: lhs.ndim - n_contract] + (depth,))
    out = jax.lax.dot_general(
        flat, rhs.reshape(depth, math.prod(features)),
        (((flat.ndim - 1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=preferred_element_type,
    )
    return out.reshape(out.shape[:-1] + features)


def make_dense(
    *,
    quantized: bool,
    features,
    name: str,
    dtype: Dtype,
    axis=-1,
    param_dtype: Dtype = jnp.float32,
    use_bias: bool = False,
    lora_rank: int = 0,
    lora_alpha: float = 16.0,
    weight_bits: int = 8,
    int4_group: int = 0,
    int4_shards: int = 1,
):
    """Dense-projection factory shared by every matmul site that supports
    the int8 weight-only serving path (Attention qkv/o, gated MLP,
    lm_head): one place to extend quantized-layer construction.

    ``lora_rank > 0`` swaps in :class:`~unionml_tpu.models.lora.
    LoRADenseGeneral` — same base parameter paths (fp ``kernel`` or int8
    ``kernel_q``+``scale``) plus trainable ``lora_a``/``lora_b`` adapters
    (QLoRA when combined with ``quantized=True``)."""
    if lora_rank > 0:
        # adapters compose with the fp or INT8 base only — silently
        # dropping an int4 request would train against the wrong base
        assert weight_bits == 8, "LoRA/QLoRA requires weight_bits=8"
        from unionml_tpu.models.lora import LoRADenseGeneral

        return LoRADenseGeneral(
            features=features, axis=axis, lora_rank=lora_rank,
            lora_alpha=lora_alpha, quantized=quantized, use_bias=use_bias,
            dtype=dtype, param_dtype=param_dtype, name=name,
        )
    if quantized:
        assert not use_bias, "quantized dense layers are bias-free"
        if weight_bits == 4:
            from unionml_tpu.models.quantization import Int4DenseGeneral

            return Int4DenseGeneral(
                features=features, axis=axis, dtype=dtype, name=name,
                group_size=int4_group, shards=int4_shards,
            )
        from unionml_tpu.models.quantization import QuantizedDenseGeneral

        return QuantizedDenseGeneral(features=features, axis=axis, dtype=dtype, name=name)
    return nn.DenseGeneral(
        features=features, axis=axis, use_bias=use_bias, dtype=dtype,
        param_dtype=param_dtype, name=name, dot_general=merged_dot_general,
    )


class RMSNorm(nn.Module):
    """Root-mean-square norm (Llama-style, no mean subtraction).

    ``impl="fused"`` routes through the Pallas kernel pair
    (:mod:`unionml_tpu.ops.fused_norm`) — same math (fp32 statistics,
    cast once), one fused pass per direction.
    """

    eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    impl: str = "xla"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.impl == "fused":
            from unionml_tpu.ops.fused_norm import fused_rms_norm

            return fused_rms_norm(x, scale, eps=self.eps).astype(self.dtype)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """LayerNorm through the fused Pallas kernel pair
    (:mod:`unionml_tpu.ops.fused_norm`), parameter-path compatible with
    ``nn.LayerNorm`` (``scale``/``bias`` at this module's level —
    checkpoints interchange freely).

    Model configs select the implementation at the CALL SITE: the
    default "xla" norm_impl uses plain ``nn.LayerNorm`` (identical graph
    and numerics for existing users — a wrapper here would either nest
    the param path or re-implement flax's statistics), and this module
    is instantiated only on the fused path.
    """

    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        from unionml_tpu.ops.fused_norm import fused_layer_norm

        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        return fused_layer_norm(x, scale, bias, self.eps).astype(self.dtype)


def llama3_rope_frequencies(
    freqs: jnp.ndarray,
    *,
    factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    original_max_len: int,
) -> jnp.ndarray:
    """Llama-3.1/3.2 long-context RoPE frequency rescaling.

    Wavelengths shorter than ``original_max_len / high_freq_factor`` keep
    their frequency, longer than ``original_max_len / low_freq_factor``
    divide by ``factor``, and the band between interpolates smoothly —
    the "llama3" ``rope_scaling`` scheme HF checkpoints carry in
    config.json. Verified against transformers' torch implementation in
    ``tests/unit/test_convert_hf_parity.py``.
    """
    wavelen = 2.0 * np.pi / freqs
    ratio = original_max_len / wavelen
    smooth = (ratio - low_freq_factor) / (high_freq_factor - low_freq_factor)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    return ((1.0 - smooth) / factor + smooth) * freqs


def rotary_embedding(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    theta: float = 10_000.0,
    scaling: Optional[Tuple[float, float, float, int]] = None,
) -> jnp.ndarray:
    """Apply rotary position embedding to ``x`` of shape (..., seq, heads, head_dim).

    ``positions``: integer array broadcastable to (..., seq). Llama-3 uses
    ``theta=500_000`` for long-context; classic RoPE uses 10_000.
    ``scaling``: optional llama3-type frequency rescale as a
    ``(factor, low_freq_factor, high_freq_factor, original_max_len)``
    tuple (hashable — it rides inside frozen model configs).
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is not None:
        factor, low, high, orig = scaling
        freqs = llama3_rope_frequencies(
            freqs, factor=factor, low_freq_factor=low,
            high_freq_factor=high, original_max_len=orig,
        )
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# the dispatcher's accepted impl names — validate against this instead of
# maintaining per-model copies
ATTN_IMPLS = (
    "auto", "xla", "blockwise", "flash", "fused", "ring", "ring_flash", "ulysses"
)


def _run_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    impl: str,
    causal: bool,
    sequence_axis: Optional[str],
) -> jnp.ndarray:
    """Dispatch (batch, seq, heads, head_dim) tensors to an attention op.

    ``"auto"`` picks fused below the measured short-seq crossover (equal
    q/kv lengths only), flash above it.
    """
    if impl == "auto":
        from unionml_tpu.ops.fused_attention import MAX_FUSED_SEQ

        impl = (
            "fused"
            if q.shape[1] <= MAX_FUSED_SEQ and k.shape[1] == q.shape[1]
            else "flash"
        )
    if impl == "xla":
        return xla_attention(q, k, v, causal=causal)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, causal=causal)
    if impl == "flash":
        from unionml_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal)
    if impl == "fused":
        from unionml_tpu.ops.fused_attention import fused_attention

        return fused_attention(q, k, v, causal=causal)
    if impl == "ring":
        from unionml_tpu.ops.ring_attention import ring_attention_sharded

        assert sequence_axis, "ring attention needs a sequence mesh axis"
        return ring_attention_sharded(q, k, v, axis=sequence_axis, causal=causal)
    if impl == "ring_flash":
        from unionml_tpu.ops.ring_attention import ring_flash_attention_sharded

        assert sequence_axis, "ring attention needs a sequence mesh axis"
        return ring_flash_attention_sharded(
            q, k, v, axis=sequence_axis, causal=causal
        )
    if impl == "ulysses":
        from unionml_tpu.ops.ulysses import ulysses_attention_sharded

        assert sequence_axis, "ulysses attention needs a sequence mesh axis"
        # the inner attention sees the FULL gathered sequence: "auto"
        # (fused short / flash long) keeps it memory-efficient instead of
        # materializing O(S^2) scores at the lengths SP targets
        return ulysses_attention_sharded(
            q, k, v, axis=sequence_axis, causal=causal, impl="auto"
        )
    raise ValueError(f"unknown attention impl {impl!r}")


class Attention(nn.Module):
    """Multi-head attention with grouped-query support and optional KV cache.

    Param layout: q/k/v/o projections as single dense kernels whose head
    axis is foldable for tensor parallelism (rules match ``attn/(q|k|v)``
    paths and shard the output features over the ``tensor`` axis; ``attn/o``
    shards input features, so TP needs exactly one psum per block — the
    Megatron layout realized by GSPMD instead of hand-written collectives).
    """

    num_heads: int
    num_kv_heads: Optional[int] = None  # GQA; None → MHA
    head_dim: Optional[int] = None
    rope: bool = False
    rope_theta: float = 10_000.0
    # RMS-normalise q and k over their FULL projected width, before the
    # heads are split (the Olmo 2/3 convention); ``qk_norm_eps`` is the
    # norms' epsilon. Adds ``q_norm`` / ``k_norm`` scales.
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    # llama3-type long-context frequency rescale:
    # (factor, low_freq_factor, high_freq_factor, original_max_len)
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    causal: bool = False
    attn_impl: str = "xla"
    # attention impl for FULL prefills (multi-token call on an empty
    # cache): "cached" = the masked cached_attention path (materializes
    # [B, H, S, max_len] fp32 scores — ~8 GB at 8B x 8k); "flash" = the
    # Pallas flash kernel over the FRESH post-RoPE k/v with a per-row
    # left-pad mask (no score buffer, the long-prefill memory/speed
    # lever). Only consulted when the caller passes full_prefill=True.
    prefill_impl: str = "cached"
    # decode attention impl for block-paged KV pools (the engine's
    # paged mode; only consulted when the caller passes block_table=):
    # "reference" = jnp.take gather, bit-identical to the contiguous
    # cache path; "pallas" = the scalar-prefetch gather kernel; "auto"
    # = pallas on TPU, reference elsewhere (ops/paged_attention.py).
    paged_impl: str = "auto"
    sequence_axis: Optional[str] = None
    quantized: bool = False  # weight-only quantized projections (serving)
    weight_bits: int = 8     # 8 = int8; 4 = packed-int4 (decode bandwidth)
    int4_group: int = 0      # >0: group-wise int4 scales (scale_g [K/g, N])
    int4_tp: int = 1         # TP degree the int4 packing must survive
    lora_rank: int = 0  # >0: trainable low-rank adapters on q/k/v/o
    lora_alpha: float = 16.0
    # biases on q/k/v/o (HF ViT/BERT-style checkpoints carry them; the
    # zoo's trained-from-scratch defaults stay bias-free)
    use_bias: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        kv: Optional[jnp.ndarray] = None,
        positions: Optional[jnp.ndarray] = None,
        cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
        cache_index: Optional[jnp.ndarray] = None,
        kv_mask: Optional[jnp.ndarray] = None,
        block_table: Optional[jnp.ndarray] = None,
        full_prefill: bool = False,
        live: Optional[jnp.ndarray] = None,
    ):
        """Returns ``out`` or ``(out, new_cache)`` when a cache is given.

        ``block_table``: int32 [batch, table_width] — marks ``cache`` as
        a BLOCK-PAGED pool (per buffer [num_blocks, block, kv_heads,
        head_dim]; int8 pools carry [num_blocks, block, kv_heads] scale
        planes) addressed through the table (entries past a row's
        coverage point at the trash block). Decode-step only: requires
        ``seq == 1`` and a vector ``cache_index`` (per-row fills); the
        step's k/v row scatters into pool block ``table[b, fill //
        block]`` at offset ``fill % block``, and attention reads
        through :func:`~unionml_tpu.ops.paged_attention.paged_attention`
        (``paged_impl`` picks the kernel) with ``lengths = fill + 1``
        (the just-written row sees itself). ``kv_mask`` must be None —
        visibility is derived from the fills. ``live``: bool [batch] —
        the rows of this paged decode step whose output is used. The
        others read with length 0 (their ``cache_index`` may be a retired
        sequence's), so the kernel gathers nothing for them; their k/v
        row is still written where the table says (the trash block).

        ``full_prefill``: STATIC caller promise that this multi-token
        cached call covers the entire visible history — the cache is
        empty, ``cache_index == 0``, and there is no shared prefix — so
        attention may run over the fresh k/v alone (``prefill_impl``
        decides how). The promise cannot be checked here (cache_index is
        traced); passing it on a chunked or prefix prefill silently drops
        the earlier context.

        ``kv``: optional (batch, kv_seq, features) source for CROSS
        attention — k/v project from it instead of ``x`` (q still from
        ``x``). Requires ``causal=False``, ``rope=False`` and no cache:
        the whole source is always visible, and inside a decode
        ``lax.scan`` the loop-invariant k/v projections are hoisted by
        XLA, so no cross-KV cache plumbing is needed. ``kv_mask`` then
        masks padded SOURCE positions ((batch, kv_seq), False = hidden).
        ``cache``: (k, v) of shape (batch, max_len, kv_heads, head_dim);
        ``cache_index``: current fill position (decode step) — a scalar
        int shared by every row, or an int vector ``[batch]`` of per-row
        fill positions (continuous-batching decode, where in-flight
        sequences sit at different depths);
        ``kv_mask``: optional bool (batch, max_len) — False slots are
        never attended to (left-padded prompts in generation).
        """
        batch, seq, features = x.shape
        kv_heads = self.num_kv_heads or self.num_heads
        head_dim = self.head_dim or features // self.num_heads
        dense = lambda feats, name, shards=1: make_dense(  # noqa: E731
            quantized=self.quantized, features=feats, axis=-1,
            dtype=self.dtype, param_dtype=self.param_dtype, name=name,
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            use_bias=self.use_bias, weight_bits=self.weight_bits,
            int4_group=self.int4_group, int4_shards=shards,
        )
        # q/k/v are COLUMN-parallel under TP (N sharded): their int4
        # packing tile must divide the per-device channel count. o is
        # row-parallel (K sharded, N whole) — shards stays 1.
        q = dense((self.num_heads, head_dim), "q", self.int4_tp)(x)
        if kv is not None:
            if self.causal or self.rope or cache is not None:
                raise ValueError(
                    "cross attention (kv=...) is incompatible with causal "
                    "masking, RoPE, and KV caches — the source is fully "
                    "visible and position-free"
                )
            k = dense((kv_heads, head_dim), "k")(kv)
            v = dense((kv_heads, head_dim), "v")(kv)
            # always the XLA op: q_len != kv_len in general (the Pallas
            # short-seq kernel assumes square score tiles), and XLA fuses
            # the modest [S_dec, S_enc] score chain well
            bias = (
                jnp.where(kv_mask[:, None, None, :], 0.0, -1e30)
                if kv_mask is not None
                else None
            )
            out = xla_attention(q, k, v, bias=bias)
            return make_dense(
                quantized=self.quantized, features=features, axis=(-2, -1),
                dtype=self.dtype, param_dtype=self.param_dtype, name="o",
                lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                use_bias=self.use_bias, weight_bits=self.weight_bits,
                int4_group=self.int4_group,
            )(out)
        k = dense((kv_heads, head_dim), "k", self.int4_tp)(x)
        v = dense((kv_heads, head_dim), "v", self.int4_tp)(x)
        if self.qk_norm:
            def full_width_norm(t, name):
                flat = t.reshape(batch, seq, -1)
                flat = RMSNorm(eps=self.qk_norm_eps, dtype=self.dtype, name=name)(flat)
                return flat.reshape(t.shape)

            q, k = full_width_norm(q, "q_norm"), full_width_norm(k, "k_norm")
        # a cache may hold more heads than the layer has (its owner
        # rounded a head count up to one that tiles densely on a TPU,
        # e.g. 30 -> 32): the extra heads are zeros through the cache and
        # the attention, and are dropped before the output projection
        cache_heads = kv_heads if cache is None else cache[0].shape[-2]
        if cache_heads != kv_heads:
            if self.num_heads != kv_heads or cache_heads < kv_heads:
                raise ValueError(
                    f"a cache of {cache_heads} heads for {kv_heads} kv heads "
                    "needs as many q as kv heads (and no fewer cache heads)"
                )
            extra = ((0, 0), (0, 0), (0, cache_heads - kv_heads), (0, 0))
            q, k, v = jnp.pad(q, extra), jnp.pad(k, extra), jnp.pad(v, extra)

        if positions is None:
            base = jnp.asarray(cache_index if cache_index is not None else 0)
            if base.ndim == 1:
                base = base[:, None]  # per-row fill positions (slot decode)
            positions = base + jnp.arange(seq)[None, :]
        if self.rope:
            q = rotary_embedding(
                q, positions, theta=self.rope_theta, scaling=self.rope_scaling
            )
            k = rotary_embedding(
                k, positions, theta=self.rope_theta, scaling=self.rope_scaling
            )

        new_cache = None
        if cache is not None:
            index = jnp.asarray(cache_index)
            if block_table is not None:
                # block-paged pool: decode-step writes scatter into the
                # table-addressed block row. The engine masks retired
                # slots' table rows to the trash block per step, so a
                # dead slot's write can never corrupt a recycled block.
                if seq != 1 or index.ndim != 1:
                    raise ValueError(
                        "block-paged caches support vector-index decode "
                        f"steps only (seq == 1), got seq={seq}, "
                        f"cache_index ndim {index.ndim}"
                    )
                if kv_mask is not None:
                    raise ValueError(
                        "kv_mask is incompatible with block_table — "
                        "paged visibility derives from the fills"
                    )
                blk = cache[0].shape[1]
                pid = jnp.take_along_axis(
                    block_table, (index // blk)[:, None], axis=1
                )[:, 0]
                off = index % blk

            def upd(buf, new, idx=index):
                # paged: one advanced-index scatter at (block, offset);
                # scalar index: one dynamic_update_slice at [_, idx, ...];
                # vector [batch] index: a vmapped slice-update (one scatter)
                # — the continuous-batching decode step where each slot
                # writes at its own depth
                new = new.astype(buf.dtype)
                if block_table is not None:
                    return buf.at[pid, off].set(new[:, 0])
                if idx.ndim == 1:
                    one = lambda c, n, i: jax.lax.dynamic_update_slice(  # noqa: E731
                        c, n, (i,) + (0,) * (c.ndim - 1)
                    )
                    return jax.vmap(one)(buf, new, idx)
                return jax.lax.dynamic_update_slice(
                    buf, new, (0, idx) + (0,) * (buf.ndim - 2)
                )

            if len(cache) == 4:
                # int8-quantized KV cache: (k_q, v_q, k_scale, v_scale),
                # scales per (batch, position, kv_head). Halves cache HBM
                # (the long-context serving bound) at the cost of one
                # int8 grid rounding per written position; the dequant
                # multiply fuses into the attention matmul reads.
                ck, cv, ks, vs = cache

                def quantize(x):
                    x32 = x.astype(jnp.float32)
                    s = jnp.max(jnp.abs(x32), axis=-1) / 127.0  # [B,S,H]
                    s = jnp.maximum(s, 1e-8)
                    q = jnp.clip(
                        jnp.round(x32 / s[..., None]), -127, 127
                    ).astype(jnp.int8)
                    return q, s

                k_q, k_s = quantize(k)
                v_q, v_s = quantize(v)
                ck, cv = upd(ck, k_q), upd(cv, v_q)
                ks, vs = upd(ks, k_s), upd(vs, v_s)
                new_cache = (ck, cv, ks, vs)
            else:
                ck, cv = cache
                ck, cv = upd(ck, k), upd(cv, v)
                new_cache = (ck, cv)
            out = None
            if block_table is not None:
                # paged decode read: gather-attend through the block
                # table (no contiguous cache view is ever materialized
                # on the kernel path); lengths = fill + 1 exposes the
                # row this step just wrote, matching the contiguous
                # path's self-visible kv_mask row
                from unionml_tpu.ops.paged_attention import paged_attention

                lengths = index + 1
                if live is not None:
                    lengths = jnp.where(live, lengths, 0)
                if len(cache) == 4:
                    out = paged_attention(
                        q[:, 0], ck, cv, block_table, lengths,
                        k_scale=ks, v_scale=vs, impl=self.paged_impl,
                    )[:, None]
                else:
                    out = paged_attention(
                        q[:, 0], ck, cv, block_table, lengths,
                        impl=self.paged_impl,
                    )[:, None]
            if full_prefill and seq > 1 and self.prefill_impl == "flash":
                # full-history prefill: attention over the FRESH post-RoPE
                # k/v through the Pallas flash kernel — no [B,H,S,max_len]
                # score buffer (the 8k x 8B OOM), better MXU tiling than
                # max_len-wide masked chunks. Left padding masks via the
                # kernel's per-row kv_valid_start (contiguous by the
                # generator's construction). With an int8 KV cache the
                # decode path reads quantized k/v while this reads exact —
                # slightly MORE accurate than the cached prefill.
                from unionml_tpu.ops.flash_attention import flash_attention

                # per-row LEADING-invalid count (argmax finds the first
                # True). Left-padded prompts (generate) get their pad
                # count; right-padded buckets (the engine's admissions)
                # get 0 — causal masking alone already hides trailing
                # garbage from every real query, and the garbage rows'
                # outputs/cache slots are discarded/masked downstream.
                pads = (
                    jnp.zeros((batch,), jnp.int32)
                    if kv_mask is None
                    else jnp.argmax(
                        kv_mask[:, :seq].astype(jnp.int32), axis=-1
                    ).astype(jnp.int32)
                )
                out = flash_attention(q, k, v, causal=True, kv_valid_start=pads)
            if out is None:
                # attend over the filled prefix only: kv slot j is visible
                # to query i iff j <= cache_index + i (covers decode seq=1
                # and cached prefill seq>1; unwritten slots are masked out)
                kv_pos = jnp.arange(ck.shape[1])[None, :]
                if index.ndim == 1:
                    q_pos = index[:, None, None] + jnp.arange(seq)[None, :, None]
                    visible = kv_pos[None] <= q_pos         # (batch, seq, max_len)
                    if kv_mask is not None:
                        visible = visible & kv_mask[:, None, :]
                    bias = jnp.where(visible, 0.0, -1e30)[:, None]
                else:
                    q_pos = index + jnp.arange(seq)[:, None]
                    visible = kv_pos <= q_pos               # (seq, max_len)
                    if kv_mask is not None:
                        # (batch, 1, seq, max_len): padded slots stay invisible
                        visible = visible[None] & kv_mask[:, None, :]
                        bias = jnp.where(visible, 0.0, -1e30)[:, None]
                    else:
                        bias = jnp.where(visible, 0.0, -1e30)[None, None]
                if len(cache) == 4:
                    from unionml_tpu.ops.attention import quantized_cache_attention

                    out = quantized_cache_attention(q, ck, cv, ks, vs, bias=bias)
                else:
                    # grouped GQA path: reads the cache at kv-head width (no
                    # repeat — measured 2x decode at 1.5B) and block-scans
                    # past the VMEM limit at long context
                    from unionml_tpu.ops.attention import cached_attention

                    out = cached_attention(
                        q, ck.astype(self.dtype), cv.astype(self.dtype), bias=bias
                    )
        else:
            out = _run_attention(
                q, k, v,
                impl=self.attn_impl,
                causal=self.causal,
                sequence_axis=self.sequence_axis,
            )
        out = make_dense(
            quantized=self.quantized, features=features, axis=(-2, -1),
            dtype=self.dtype, param_dtype=self.param_dtype, name="o",
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            use_bias=self.use_bias, weight_bits=self.weight_bits,
            int4_group=self.int4_group,
        )(out if out.shape[2] == self.num_heads else out[:, :, :self.num_heads])
        if cache is not None:
            return out, new_cache
        return out


class MlpBlock(nn.Module):
    """Transformer MLP: GELU (ViT/BERT) or SwiGLU (Llama)."""

    hidden_dim: int
    gated: bool = False  # True → SwiGLU
    quantized: bool = False  # weight-only quantized (bias-free gated form only)
    weight_bits: int = 8
    int4_group: int = 0      # >0: group-wise int4 scales (scale_g [K/g, N])
    int4_tp: int = 1         # TP degree the int4 packing must survive
    lora_rank: int = 0  # >0: trainable low-rank adapters on gate/up/down
    lora_alpha: float = 16.0
    # tanh-approximate GELU by default (one transcendental cheaper on the
    # VPU); HF BERT checkpoints were trained with erf GELU — loaders set
    # False for checkpoint-faithful inference (models/convert.py)
    gelu_approximate: bool = True
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        features = x.shape[-1]
        if self.quantized:
            assert self.gated, "quantized MlpBlock supports the bias-free gated form"
        dense = lambda feats, name, shards=1: make_dense(  # noqa: E731
            quantized=self.quantized, features=feats, dtype=self.dtype,
            param_dtype=self.param_dtype, use_bias=not self.gated, name=name,
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            weight_bits=self.weight_bits,
            int4_group=self.int4_group, int4_shards=shards,
        )
        if self.gated:
            # gate/up are column-parallel under TP (N sharded): their
            # int4 tile must divide the per-device width; down is
            # row-parallel and keeps shards=1
            gate = nn.silu(dense(self.hidden_dim, "gate", self.int4_tp)(x))
            up = dense(self.hidden_dim, "up", self.int4_tp)(x)
            return dense(features, "down")(gate * up)
        h = nn.gelu(
            dense(self.hidden_dim, "up")(x), approximate=self.gelu_approximate
        )
        return dense(features, "down")(h)
