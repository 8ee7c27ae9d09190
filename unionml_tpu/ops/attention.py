"""Multi-head attention: XLA reference + memory-efficient blockwise form.

Convention (all attention ops in this package): tensors are
``[batch, seq, heads, head_dim]`` ("BSHD"). GQA is supported everywhere —
``k``/``v`` may have fewer heads than ``q`` as long as the count divides.

- :func:`mha_reference` materializes the full [S, S] score matrix; XLA
  fuses the softmax chain well, and on TPU this is the fastest choice for
  short/medium sequences that fit HBM.
- :func:`blockwise_attention` never materializes scores: a ``lax.scan``
  over KV blocks with an **online softmax** (running max + normalizer),
  trading FLOPs for O(S·block) memory — the long-context building block
  that ring attention reuses per-shard.
- :func:`attention` dispatches between implementations.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jnp.ndarray, num_q_heads: int) -> jnp.ndarray:
    """GQA: repeat kv heads to match q heads."""
    num_kv_heads = k.shape[2]
    if num_kv_heads == num_q_heads:
        return k
    if num_q_heads % num_kv_heads:
        raise ValueError(f"q heads {num_q_heads} must be a multiple of kv heads {num_kv_heads}")
    return jnp.repeat(k, num_q_heads // num_kv_heads, axis=2)


def _causal_mask(q_len: int, kv_len: int, q_offset: int = 0, kv_offset: int = 0) -> jnp.ndarray:
    """[q_len, kv_len] bool mask, True where attention is allowed."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    kv_pos = kv_offset + jnp.arange(kv_len)[None, :]
    return q_pos >= kv_pos


def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    bias: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Full-score multi-head attention ([B,S,H,D] in/out).

    ``bias`` broadcasts against [B, H, Sq, Skv]; ``segment_ids`` ([B, S])
    restricts attention within equal segments (packed sequences).
    """
    *_, num_q_heads, head_dim = q.shape
    k = _repeat_kv(k, num_q_heads)
    v = _repeat_kv(v, num_q_heads)
    scale = scale if scale is not None else head_dim**-0.5

    # [B,H,Sq,Skv] scores on the MXU in fp32 for numerical stability
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        scores = scores + bias
    if causal:
        # bottom-right alignment: with q_len < kv_len the queries are the
        # LAST q_len positions (KV-cache decode), so offset q, not kv
        mask = _causal_mask(q.shape[1], k.shape[1], q_offset=k.shape[1] - q.shape[1])
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        scores = jnp.where(jnp.swapaxes(seg_mask, -1, -2), scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _grouped_cache_attention(
    q,
    k,
    v,
    *,
    k_scale=None,
    v_scale=None,
    bias=None,
    scale=None,
    block_threshold: int = 2048,
):
    """Shared engine for cached-decode attention (bf16 or int8 KV).

    ``k``/``v``: [B, S, Hk, D] (bf16, or int8 with ``k_scale``/``v_scale``
    fp32 [B, S, Hk] per-(position, head) dequant scales). Three design
    rules, each from a failure seen at the 8B geometry:

    - **No GQA repeat.** The group dim folds into the einsums (q reshaped
      to [B, Sq, Hk, G, D]) so the cache is read at its own byte size; a
      materialized repeat costs G x the cache traffic per decode step
      (4x at the 8B geometry).
    - **No dequantized copy.** int8 scales ride the small tensors —
      ``k_scale`` multiplies the scores, ``v_scale`` multiplies the
      softmax weights — so cache HBM reads stay int8.
    - **Bounded VMEM, no cache copies.** Above ``block_threshold`` keys
      the full-row softmax (f32[B, H, S] > 16 MB scoped VMEM at 8k) is
      replaced by an online-softmax ``lax.scan`` over block INDICES with
      ``dynamic_slice`` into the cache — passing cache blocks as scan
      operands would materialize a transposed copy of the whole cache
      every step (measured: 4 GB of HLO-temp copies at 8B/8k, an HBM
      OOM). A non-dividing tail slab is merged after the scan, so the
      cache is never padded (padding is a full copy too).

    ``bias`` must broadcast over heads (head dim 1) — every cache caller
    satisfies this. Output [B, Sq, Hq, D] in ``q.dtype``, equal to the
    materialized form up to float reduction order.
    """
    batch, q_len, num_q_heads, head_dim = q.shape
    num_kv_heads = k.shape[2]
    if num_q_heads % num_kv_heads:
        raise ValueError(
            f"q heads {num_q_heads} must be a multiple of kv heads {num_kv_heads}"
        )
    group = num_q_heads // num_kv_heads
    if bias is not None and bias.shape[1] != 1:
        raise ValueError(
            f"bias head dim must be 1 (broadcast over heads), got {bias.shape}"
        )
    scale = scale if scale is not None else head_dim**-0.5
    kv_len = k.shape[1]
    # [B, Sq, Hk, G, D]: contiguous head groups share a kv head (the
    # jnp.repeat layout _repeat_kv would produce)
    qg = q.reshape(batch, q_len, num_kv_heads, group, head_dim)

    def scores_for(k_c, ks_c, bias_c):
        """k-scale-folded scores for one key slab: [B, Hk, G, Q, K]."""
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, k_c.astype(q.dtype),
            preferred_element_type=jnp.float32,
        ) * scale
        if ks_c is not None:
            s = s * jnp.transpose(ks_c, (0, 2, 1))[:, :, None, None, :]
        if bias_c is not None:
            s = s + bias_c[:, :, None]  # [B,1,Q,K] -> [B,1,1,Q,K]
        return s

    def weighted_values(w, v_c, vs_c):
        if vs_c is not None:
            w = w * jnp.transpose(vs_c, (0, 2, 1))[:, :, None, None, :]
        return jnp.einsum(
            "bhgqk,bkhd->bqhgd", w.astype(q.dtype), v_c.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )

    if kv_len <= block_threshold:
        weights = jax.nn.softmax(scores_for(k, k_scale, bias), axis=-1)
        out = weighted_values(weights, v, v_scale)
        return out.reshape(batch, q_len, num_q_heads, head_dim).astype(q.dtype)

    block = block_threshold
    n_full, tail = divmod(kv_len, block)

    def slab(x, start, size, axis=1):
        return (
            None
            if x is None
            else jax.lax.dynamic_slice_in_dim(x, start, size, axis=axis)
        )

    def merge(carry, start, size):
        """Online-softmax update with the [start, start+size) key slab."""
        m, l, acc = carry
        s = scores_for(
            slab(k, start, size), slab(k_scale, start, size),
            slab(bias, start, size, axis=3),
        )
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        acc = acc * jnp.moveaxis(corr, 3, 1)[..., None] + weighted_values(
            p, slab(v, start, size), slab(v_scale, start, size)
        )
        return m_new, l, acc

    stat = (batch, num_kv_heads, group, q_len)
    carry = (
        jnp.full(stat, NEG_INF, jnp.float32),
        jnp.zeros(stat, jnp.float32),
        jnp.zeros((batch, q_len, num_kv_heads, group, head_dim), jnp.float32),
    )
    if n_full:
        carry, _ = jax.lax.scan(
            lambda c, start: (merge(c, start, block), None),
            carry,
            jnp.arange(n_full, dtype=jnp.int32) * block,
        )
    if tail:
        carry = merge(carry, n_full * block, tail)
    m, l, acc = carry
    out = acc / jnp.moveaxis(l, 3, 1)[..., None]
    return out.reshape(batch, q_len, num_q_heads, head_dim).astype(q.dtype)


def cached_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    bias: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_threshold: int = 2048,
) -> jnp.ndarray:
    """bf16 KV-cache decode attention: grouped GQA (no cache repeat),
    VMEM-bounded block scan at long context. See
    :func:`_grouped_cache_attention`."""
    return _grouped_cache_attention(
        q, k, v, bias=bias, scale=scale, block_threshold=block_threshold
    )


def quantized_cache_attention(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    v_q: jnp.ndarray,
    k_s: jnp.ndarray,
    v_s: jnp.ndarray,
    *,
    bias: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_threshold: int = 2048,
) -> jnp.ndarray:
    """int8 KV-cache decode attention, dequant scales folded into the
    attention math (never a dequantized cache copy). See
    :func:`_grouped_cache_attention`."""
    return _grouped_cache_attention(
        q, k_q, v_q, k_scale=k_s, v_scale=v_s, bias=bias, scale=scale,
        block_threshold=block_threshold,
    )


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    block_size: int = 512,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
) -> jnp.ndarray:
    """Online-softmax attention scanned over KV blocks ([B,S,H,D] in/out).

    Memory is O(Sq·block_size) instead of O(Sq·Skv). ``q_offset`` /
    ``kv_offset`` give the global positions of the local q/kv shards so
    ring attention can reuse this per rotation step with correct causal
    masking. With default (zero) offsets and ``q_len != kv_len``, causal
    masking is bottom-right aligned (queries are the last ``q_len``
    positions — the KV-cache decode convention, matching mha_reference).
    """
    if causal and q_offset == 0 and kv_offset == 0:
        q_offset = k.shape[1] - q.shape[1]
    out, _, _ = _blockwise_accumulate(
        q, k, v, causal=causal, block_size=block_size, scale=scale,
        q_offset=q_offset, kv_offset=kv_offset,
    )
    return out.astype(q.dtype)


def _blockwise_accumulate(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool,
    block_size: int,
    scale: Optional[float],
    q_offset: int = 0,
    kv_offset: int = 0,
    acc: Optional[tuple] = None,
):
    """Scan KV blocks, returning ``(out, running_max, normalizer)``.

    ``acc = (out_unnormalized, m, l)`` lets callers (ring attention) chain
    accumulation across KV shards and normalize once at the end.
    """
    batch, q_len, num_q_heads, head_dim = q.shape
    kv_len = k.shape[1]
    k = _repeat_kv(k, num_q_heads)
    v = _repeat_kv(v, num_q_heads)
    scale = scale if scale is not None else head_dim**-0.5

    block_size = min(block_size, kv_len)
    num_blocks = -(-kv_len // block_size)
    pad = num_blocks * block_size - kv_len
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # [num_blocks, B, block, H, D] for the scan carry-free xs
    k_blocks = k.reshape(batch, num_blocks, block_size, num_q_heads, head_dim).swapaxes(0, 1)
    v_blocks = v.reshape(batch, num_blocks, block_size, num_q_heads, head_dim).swapaxes(0, 1)

    q_pos = q_offset + jnp.arange(q_len)
    qf = q.astype(jnp.float32)

    if acc is None:
        out0 = jnp.zeros((batch, q_len, num_q_heads, head_dim), jnp.float32)
        m0 = jnp.full((batch, q_len, num_q_heads), NEG_INF, jnp.float32)
        l0 = jnp.zeros((batch, q_len, num_q_heads), jnp.float32)
    else:
        out0, m0, l0 = acc

    def body(carry, inputs):
        out_acc, m_acc, l_acc = carry
        blk_idx, k_blk, v_blk = inputs
        kv_pos = kv_offset + blk_idx * block_size + jnp.arange(block_size)

        # [B,H,Q,Bk] block scores in fp32
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        valid = kv_pos < (kv_offset + kv_len)
        mask = jnp.broadcast_to(valid[None, :], (q_len, block_size))
        if causal:
            mask = mask & (q_pos[:, None] >= kv_pos[None, :])
        s = jnp.where(mask[None, None], s, NEG_INF)

        m_blk = jnp.max(s, axis=-1)                      # [B,H,Q]
        m_new = jnp.maximum(m_acc, m_blk.transpose(0, 2, 1))  # [B,Q,H]
        # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe.transpose(0, 2, 1)[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(jnp.where(m_acc == NEG_INF, NEG_INF, m_acc - m_safe))
        l_new = l_acc * corr + jnp.sum(p, axis=-1).transpose(0, 2, 1)
        out_new = out_acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (out_new, m_new, l_new), None

    (out, m, l), _ = jax.lax.scan(
        body, (out0, m0, l0), (jnp.arange(num_blocks), k_blocks, v_blocks)
    )
    if acc is not None:
        return out, m, l
    return out / jnp.maximum(l, 1e-30)[..., None], m, l


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    impl: str = "xla",
    block_size: int = 512,
    **kwargs,
) -> jnp.ndarray:
    """Dispatch between attention implementations.

    impl: ``"xla"`` (full scores), ``"blockwise"`` (O(S·block) memory),
    ``"flash"`` (Pallas TPU kernel, long sequences), ``"fused"`` (Pallas
    one-program-per-batch kernel, fastest for short sequences), or
    ``"auto"`` — fused up to the measured v5e crossover (~1k tokens,
    where the single-tile score matrix stops fitting VMEM comfortably),
    flash beyond it.
    """
    if impl == "auto":
        from unionml_tpu.ops.fused_attention import MAX_FUSED_SEQ

        impl = (
            "fused"
            if q.shape[1] <= MAX_FUSED_SEQ and k.shape[1] == q.shape[1]
            else "flash"
        )
    if impl == "xla":
        return mha_reference(q, k, v, causal=causal, **kwargs)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, causal=causal, block_size=block_size, **kwargs)
    if impl == "flash":
        from unionml_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, **kwargs)
    if impl == "fused":
        from unionml_tpu.ops.fused_attention import fused_attention

        return fused_attention(q, k, v, causal=causal, **kwargs)
    raise ValueError(
        f"unknown attention impl {impl!r}; use auto|xla|blockwise|flash|fused"
    )
