"""SDAR's mixture-of-experts decoder (``sdar_moe``): a decoder that
generates by diffusion over blocks.

The configuration carries the Hugging Face keys one to one
(``JetLM/SDAR-30B-A3B-Chat`` ``config.json``). Pre-norm residual blocks
(``x += attn(norm(x)); x += moe(norm(x))``), a final RMSNorm, an untied
head: the body of :mod:`~unionml_tpu.models.keye_vl_moe` without the
indexer and with plain rotary.

- **Attention** (:class:`BlockCausalAttention`): ``num_attention_heads``
  queries over ``num_key_value_heads`` keys and values of ``head_dim``;
  queries and keys RMS-normalised per head; rotary with half-split pairs on
  plain positions. Every position belongs to block ``t // block_length``
  (aligned from position 0), and the mask is **block-causal**: ``s`` is
  visible to ``t`` iff ``s // Bk <= t // Bk``, causal across blocks and
  bidirectional inside one. Three forms read the cache (one fused
  :class:`~.layers.KVRows` a layer: keys and values of a position in one
  row, a whole tile at 4 + 4 heads of 128): a whole prompt over contiguous rows by
  the flash kernel with that mask (``prefill_impl="flash"``) or by masked
  scores; and a **forward over a block pool**, which writes its ``S`` rows
  (one block, or several behind one another) at ``fill .. fill + S - 1``
  and reads ``fill + S`` rows with ``S`` queries a sequence
  (:func:`~unionml_tpu.ops.paged_attention.paged_attention` with a query
  axis): the pool's rows are read once a forward for all ``S`` positions. A
  block's queries see one another and, where the forward carries more than
  one block, no later block's rows (a limit a query); rows that ``live``
  [B, S] leaves out are written to the pool's trash block, sent to no
  expert, and seen by no live row.
- **Mixture**: :class:`~unionml_tpu.ops.moe.MoEMlp` with the softmax router
  (top-k of the softmax, renormalised: ``norm_topk_prob``), no shared
  expert, in every layer.
- **What a logit predicts**: position ``t``'s logits predict the token *at*
  ``t`` (an undecided position holds the mask token's embedding and is
  asked what stands there); there is no shift by one.

:class:`SdarMoe` takes :class:`~unionml_tpu.models.llama.Llama`'s call
arguments. ``cache_layout()`` tells a serving engine that every layer owns
rows of keys and values; ``generation_scheme()`` that it generates by
blocks (:class:`~.layers.BlockDiffusion`: block length, denoising steps,
the rule that picks the entries a forward decides, the mask token).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import BlockDiffusion, KVRows, RMSNorm, make_dense, rotary_embedding
from unionml_tpu.ops.moe import MoEMlp, dispatch_plan
from unionml_tpu.ops.paged_attention import paged_attention


@dataclass(frozen=True)
class SdarMoeConfig:
    # ---- the published config's keys
    vocab_size: int = 151_936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32_768
    # ---- generation (the config gives none of it: the repository's defaults)
    block_length: int = 4
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151_669
    # ---- how this program runs it
    quantized: bool = False       # int8 weight-only for the projections and the experts
    prefill_impl: str = "cached"  # a whole prompt: "flash" (the kernel) or "cached" (masked scores)
    paged_impl: str = "auto"      # the pool's decode reads, as LlamaConfig's
    dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"  # the cached rows' (float32 in the tests that compare logits)

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @classmethod
    def from_hf(cls, hf: dict, **over) -> "SdarMoeConfig":
        """From a ``config.json``-style dict, with the generation settings
        under its ``generation`` key where it has one. What this module
        cannot run as published raises; keys it does not know are ignored."""
        for key, want in (("norm_topk_prob", True), ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                          ("attention_bias", False), ("use_sliding_window", False),
                          ("tie_word_embeddings", False), ("rope_scaling", None)):
            if hf.get(key, want) != want:
                raise ValueError(f"sdar_moe with {key} = {hf[key]!r} is not supported (only {want!r})")
        kwargs = {k: hf[k] for k in _PUBLISHED_KEYS if k in hf}
        kwargs["rope_theta"] = float(hf.get("rope_theta", cls.rope_theta))
        kwargs.update({k: v for k, v in (hf.get("generation") or {}).items() if k in _GENERATION_KEYS})
        kwargs.update(over)
        return cls(**kwargs)

    def to_hf(self) -> dict:
        """The published keys as a dict, the generation settings under
        ``generation`` (what the plain reference takes)."""
        out = {k: getattr(self, k) for k in _PUBLISHED_KEYS}
        out.update(rope_theta=self.rope_theta, norm_topk_prob=True, rope_scaling=None,
                   generation={k: getattr(self, k) for k in _GENERATION_KEYS})
        return out

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "SdarMoeConfig":
        kwargs = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, moe_intermediate_size=48, num_experts=8,
            num_experts_per_tok=2, rope_theta=10_000.0, max_position_embeddings=512,
            mask_token_id=vocab_size - 3,
        )
        kwargs.update(over)
        return SdarMoeConfig(**kwargs)


_PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok", "rms_norm_eps",
    "max_position_embeddings",
)
_GENERATION_KEYS = (
    "block_length", "denoising_steps", "remasking_strategy", "confidence_threshold", "mask_token_id",
)


class BlockCausalAttention(nn.Module):
    """The attention block. ``cache`` is a layer's entry of
    ``KVRows(fused=True).init``: one buffer of rows that hold a position's
    key heads and its value heads behind them, ``[B, L, 2 Hk, D]`` or, with
    ``block_table``, the pool's ``[num_blocks, block, 2 Hk, D]``. With
    ``block_table``, ``live`` is [B] (the sequences that run) or [B, S] (the
    rows that do: a prefix of each sequence's)."""

    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None, kv_mask=None,
                 block_table=None, full_prefill=False, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        batch, seq, _ = x.shape
        heads, kv_heads, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        bk, scale = cfg.block_length, hd ** -0.5

        def dense(features, name):
            return make_dense(quantized=cfg.quantized, features=features, dtype=dtype, name=name)

        if positions is None:
            base = jnp.asarray(cache_index if cache_index is not None else 0)
            positions = (base[:, None] if base.ndim == 1 else base) + jnp.arange(seq)[None, :]
        q = dense((heads, hd), "q")(x)
        k = dense((kv_heads, hd), "k")(x)
        v = dense((kv_heads, hd), "v")(x)
        q = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="q_norm")(q)
        k = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="k_norm")(k)
        q = rotary_embedding(q, positions, theta=cfg.rope_theta)
        k = rotary_embedding(k, positions, theta=cfg.rope_theta)

        def masked(q, keys, values, q_pos, hidden=None):
            """Scores under the block-causal mask: key position ``s`` is
            visible to the query at ``t`` iff ``s <= t | (Bk - 1)``."""
            from unionml_tpu.ops.attention import cached_attention

            visible = jnp.arange(keys.shape[1])[None, None, :] <= (q_pos | (bk - 1))[:, :, None]
            if hidden is not None:
                visible = visible & hidden[:, None, :]
            bias = jnp.where(visible, 0.0, -1e30)[:, None]
            return cached_attention(q, keys.astype(dtype), values.astype(dtype), bias=bias, scale=scale)

        new_cache = None
        if cache is None:
            if kv_mask is not None:
                raise ValueError("kv_mask requires a cache (generation path)")
            out = masked(q, k, v, jnp.broadcast_to(positions, (batch, seq)))
        else:
            # a cached row: a position's key heads, its value heads behind them
            (rows,) = cache
            row = jnp.concatenate([k, v], axis=2).astype(rows.dtype)
            index = jnp.asarray(cache_index)
            if block_table is not None:
                # one forward over a block or two: their rows are written
                # where the table says (an open block's are provisional until
                # a forward writes them from its final tokens) and all of
                # them are read
                if index.ndim != 1:
                    raise ValueError(
                        f"block-paged caches take a vector cache_index, got ndim {index.ndim}"
                    )
                if kv_mask is not None:
                    raise ValueError("kv_mask is incompatible with block_table")
                blk = rows.shape[1]
                at = index[:, None] + jnp.arange(seq)[None, :]
                # (a row that does not run may lie past the table)
                entry = jnp.minimum(at // blk, block_table.shape[1] - 1)
                pid, lengths = jnp.take_along_axis(block_table, entry, axis=1), index + seq
                if live is not None:
                    # a row that does not run goes to the trash block and no
                    # query sees it; a slot that runs nothing reads nothing
                    runs = jnp.broadcast_to(live[:, None] if live.ndim == 1 else live, (batch, seq))
                    pid = jnp.where(runs, pid, 0)
                    ran = jnp.sum(runs, axis=-1, dtype=index.dtype)
                    lengths = jnp.where(ran > 0, index + ran, 0)
                rows = rows.at[pid, at % blk].set(row)
                # more than one block: a query sees to the end of its own
                limits = (at | (bk - 1)) + 1 if seq > bk else None
                out = paged_attention(
                    q, rows, None, block_table, lengths, scale=scale, impl=cfg.paged_impl, limits=limits,
                )
            else:
                if index.ndim == 1:
                    def put(c, n):
                        return jax.vmap(
                            lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i,) + (0,) * (c.ndim - 1))
                        )(c, n.astype(c.dtype), index)
                else:
                    def put(c, n):
                        return jax.lax.dynamic_update_slice(
                            c, n.astype(c.dtype), (0, index) + (0,) * (c.ndim - 2)
                        )
                rows = put(rows, row)
                if full_prefill and seq > 1 and cfg.prefill_impl == "flash":
                    # a whole prompt from position 0: the fresh keys and values
                    # alone. A right-padded bucket needs no padding mask: a
                    # real query's block ends before the padding starts, or is
                    # the trailing partial block, whose rows are never committed
                    from unionml_tpu.ops.flash_attention import flash_attention

                    out = flash_attention(
                        q, k, v, causal=True, kv_valid_start=jnp.zeros((batch,), jnp.int32),
                        causal_block=bk,
                    )
                else:
                    base = index[:, None] if index.ndim == 1 else index[None, None]
                    q_pos = base + jnp.arange(seq)[None, :]
                    out = masked(
                        q, rows[:, :, :kv_heads], rows[:, :, kv_heads:],
                        jnp.broadcast_to(q_pos, (batch, seq)), kv_mask,
                    )
            new_cache = (rows,)
        out = make_dense(
            quantized=cfg.quantized, features=cfg.hidden_size, axis=(-2, -1), dtype=dtype, name="o",
        )(out)
        return out if cache is None else (out, new_cache)


class SdarMoeBlock(nn.Module):
    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None, kv_mask=None,
                 block_table=None, full_prefill=False, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        attn = BlockCausalAttention(cfg, name="attn")
        h = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="attn_norm")(x)
        if cache is None:
            a, new_cache = attn(h, positions=positions, kv_mask=kv_mask), None
        else:
            a, new_cache = attn(
                h, positions=positions, cache=cache, cache_index=cache_index, kv_mask=kv_mask,
                block_table=block_table, full_prefill=full_prefill, live=live,
            )
        x = x + a
        h = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="mlp_norm")(x)
        batch, seq = h.shape[:2]
        real = None
        if kv_mask is not None and cache is not None and block_table is None:
            # a token whose own cached row the mask hides is a right-padded
            # prompt's padding: it is sent to no expert
            base = jnp.asarray(cache_index)
            own = (base[:, None] if base.ndim == 1 else base) + jnp.arange(seq)[None, :]
            hidden = jnp.broadcast_to(kv_mask, (batch, kv_mask.shape[-1]))
            own = jnp.clip(jnp.broadcast_to(own, (batch, seq)), 0, hidden.shape[1] - 1)
            real = jnp.take_along_axis(hidden, own, axis=1)
        elif block_table is not None and live is not None:
            # nor are the rows of a slot that holds no unfinished request,
            # nor the rows of a forward that its slot does not run
            real = jnp.broadcast_to(live[:, None] if live.ndim == 1 else live, (batch, seq))
            # ... but the first row always is: a forward that no slot runs (a
            # chunk's steps after its last request ended) would hand the
            # grouped kernel no row at all, and its tile maps then point
            # before the first tile (``ops.moe._grouped_matmul_pallas``:
            # ``tiles_used - 1``), an out-of-bounds copy that halts the chip
            real = real.at[0, 0].set(True)
        routed, _ = MoEMlp(
            num_experts=cfg.num_experts, num_selected=cfg.num_experts_per_tok,
            hidden_dim=cfg.moe_intermediate_size, model_dim=cfg.hidden_size, quantized=cfg.quantized,
            dtype=dtype, name="moe",
        )(h, real)
        return x + routed, new_cache


class SdarMoe(nn.Module):
    config: SdarMoeConfig = field(default_factory=SdarMoeConfig)

    def cache_layout(self):
        """Every layer caches keys and values a token."""
        cfg = self.config
        dtype = None if cfg.cache_dtype == "bfloat16" else cfg.cache_dtype
        row = KVRows(
            cfg.num_key_value_heads, cfg.head_dim, dtype=dtype, fused=True, q_heads=cfg.num_attention_heads,
        )
        return (row,) * cfg.num_hidden_layers

    def generation_scheme(self) -> BlockDiffusion:
        """How a serving engine generates with this module: by blocks."""
        cfg = self.config
        return BlockDiffusion(
            block_length=cfg.block_length, denoising_steps=cfg.denoising_steps,
            remasking=cfg.remasking_strategy, threshold=cfg.confidence_threshold,
            mask_token_id=cfg.mask_token_id,
        )

    def moe_dispatch(self, tokens: int) -> Optional[dict]:
        """What a mixture layer does with a program of ``tokens`` rows
        (``ops.moe.dispatch_plan``), and which router sent them."""
        cfg = self.config
        plan = dispatch_plan(
            tokens, cfg.num_experts, cfg.num_experts_per_tok, quantized=cfg.quantized,
            model_dim=cfg.hidden_size, hidden_dim=cfg.moe_intermediate_size,
        )
        return {"router": "softmax", **plan}

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        *,
        positions: Optional[jnp.ndarray] = None,
        cache=None,
        cache_index: Optional[jnp.ndarray] = None,
        kv_mask: Optional[jnp.ndarray] = None,
        block_table: Optional[jnp.ndarray] = None,
        logit_index: Optional[jnp.ndarray] = None,
        full_prefill: bool = False,
        live: Optional[jnp.ndarray] = None,
    ):
        """logits [B, S, V]: row ``t`` predicts the token at ``t``. With
        ``cache`` (one ``KVRows`` entry per layer) returns ``(logits,
        new_cache)``. The arguments are ``Llama``'s; with ``block_table``
        the ``S`` tokens are whole blocks of a sequence, written at
        ``cache_index .. cache_index + S - 1`` (a multiple of the block
        length), a block's attending one another and the blocks before;
        ``live`` may then be [B, S], and ``logit_index`` [B, K] asks for
        the logits of K rows a sequence (``[B, K, V]``)."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype, name="embed")(tokens)
        new_cache = []
        for i in range(cfg.num_hidden_layers):
            x, c = SdarMoeBlock(cfg, name=f"block_{i}")(
                x, positions=positions, cache=None if cache is None else cache[i],
                cache_index=cache_index, kv_mask=kv_mask, block_table=block_table,
                full_prefill=full_prefill, live=live,
            )
            new_cache.append(c)
        if logit_index is not None:
            at = jnp.asarray(logit_index)
            if at.ndim == 2:
                x = jnp.take_along_axis(x, at[:, :, None], axis=1)
            else:
                x = x[jnp.arange(x.shape[0]), at][:, None, :]
        x = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="final_norm")(x)
        logits = make_dense(
            quantized=cfg.quantized, features=cfg.vocab_size, dtype=jnp.float32, name="lm_head",
        )(x.astype(jnp.float32))
        if cache is not None:
            return logits, tuple(new_cache)
        return logits


# for models.quantization.quantize_params: every wide matmul and the experts
SDAR_MOE_QUANT_PATTERNS = (r"attn/(q|k|v|o)$", r"lm_head$", r"moe$")
