"""Keye-VL-2.0's language model (``KeyeVL2``): grouped-query attention
over the positions a learned indexer selects, and a softmax-routed
mixture of experts in every layer.

The configuration carries the Hugging Face keys one to one
(``Kwai-Keye/Keye-VL-2.0-30B-A3B`` ``config.json``). Pre-norm residual
blocks (``x += attn(norm(x)); x += moe(norm(x))``), a final RMSNorm, an
untied head.

- **Attention** (:class:`IndexedSparseAttention`): ``num_attention_heads``
  queries over ``num_key_value_heads`` keys and values of ``head_dim``;
  queries and keys RMS-normalised per head; rotary with half-split pairs
  whose frequency pair ``i`` takes its angle from the temporal, height or
  width position by ``rope_scaling.mrope_section`` (the first 16 pairs
  temporal, the next 24 height, the last 24 width; a text token's three
  positions are equal and this is plain rotary). ``positions`` is ``[B, S]``
  or ``[3, B, S]``.
- **The indexer** (``sa_config``; DeepSeek-V3.2's sparse attention): from
  the same normed hidden state, ``indexer_num_heads`` rotated queries of
  ``indexer_head_dim``, one LayerNormed and rotated key a position (plain
  rotary on the temporal position) and a weight a head; the index score of
  query ``t`` for position ``s`` is ``sum_j w[t, j] relu(q[t, j] . k[s])``
  (DeepSeek's constant scales ``head_dim ** -0.5`` and ``heads ** -0.5`` are
  positive, change no selection and are dropped). A query attends the
  ``topk`` visible positions of largest score (all while fewer are visible;
  exact, ties towards the lower position) by ordinary softmax attention at
  scale ``head_dim ** -0.5``. ``q_chunk_size`` / ``kv_chunk_size`` are the
  tiles a scorer may work in and change no result.
- What is cached a position and layer: keys, values and the indexer's key
  (:class:`~.layers.IndexedKVRows`; each a lane-dense row). Two forms read them
  (:mod:`unionml_tpu.ops.sparse_attention`): over contiguous rows (a
  prompt, a chunk, a slot's rows) the scores, the selection as a mask and
  the softmax run in blocks of queries; a decode step over a block pool
  appends its row, scores the row's live blocks of indexer keys
  (``paged_index_scores``), selects as a mask over the row's table
  (``top_k_mask``), and walks the row's live blocks of keys and values
  with that mask (``paged_sparse_attention``: a position that is not
  selected weighs zero). A cache no longer than ``topk`` selects
  everything and takes ordinary attention (``paged_attention`` over a pool).
- **Mixture**: :class:`~unionml_tpu.ops.moe.MoEMlp` with the softmax router
  (top-k of the softmax, renormalised: ``norm_topk_prob``), no shared
  expert, in every layer (``decoder_sparse_step`` 1, no ``mlp_only_layers``).

Not here: the vision tower. ``positions`` of three axes is the seam its
tokens would enter by; no file in this repository gives its widths.

:class:`KeyeVLMoe` takes :class:`~unionml_tpu.models.llama.Llama`'s call
arguments; ``cache_layout()`` tells a serving engine that every layer owns
rows of keys, values and indexer keys.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import IndexedKVRows, RMSNorm, make_dense, rotary_embedding
from unionml_tpu.ops.moe import MoEMlp, dispatch_plan
from unionml_tpu.ops.paged_attention import paged_attention, paged_index_scores, paged_sparse_attention
from unionml_tpu.ops.sparse_attention import sparse_attention, top_k_mask


@dataclass(frozen=True)
class KeyeVLMoeConfig:
    # ---- the published config's keys (sa_config's flattened)
    vocab_size: int = 151_936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    rope_theta: float = 10_000_000.0
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262_144
    indexer_head_dim: int = 64
    indexer_num_heads: int = 16
    index_topk: int = 2048
    # ---- how this program runs it
    quantized: bool = False       # int8 weight-only for the projections and the experts
    # read by the engine, as LlamaConfig's ("cached" / "flash"); a whole prompt
    # runs the same blocks either way (``full_prefill`` changes nothing here)
    prefill_impl: str = "cached"
    paged_impl: str = "auto"      # the pool's decode reads, as LlamaConfig's
    dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"  # the cached rows' (float32 in the tests that compare logits)

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @classmethod
    def from_hf(cls, hf: dict, **over) -> "KeyeVLMoeConfig":
        """From a ``config.json``-style dict. What this module cannot run
        as published raises; keys it does not know are ignored."""
        for key, want in (("norm_topk_prob", True), ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                          ("attention_bias", False), ("use_sliding_window", False),
                          ("tie_word_embeddings", False)):
            if hf.get(key, want) != want:
                raise ValueError(f"KeyeVL2 with {key} = {hf[key]!r} is not supported (only {want!r})")
        sa = hf["sa_config"]
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("KeyeVL2's indexer with more than one key head is not supported")
        rope = hf.get("rope_scaling") or {}
        if rope.get("rope_type", rope.get("type", "default")) != "default":
            raise ValueError(f"KeyeVL2 with rope_scaling {rope!r} is not supported (only the default type)")
        kwargs = {k: hf[k] for k in _PUBLISHED_KEYS if k in hf}
        kwargs.update(
            rope_theta=float(hf.get("rope_theta", cls.rope_theta)),
            mrope_section=tuple(rope.get("mrope_section", (hf.get("head_dim", cls.head_dim) // 2,))),
            indexer_head_dim=sa["indexer_head_dim"], indexer_num_heads=sa["indexer_num_heads"],
            index_topk=sa["topk"],
        )
        kwargs.update(over)
        return cls(**kwargs)

    def to_hf(self) -> dict:
        """The published keys as a dict (what the plain reference takes)."""
        out = {k: getattr(self, k) for k in _PUBLISHED_KEYS}
        out.update(
            rope_theta=self.rope_theta, norm_topk_prob=True,
            rope_scaling={
                "mrope_section": list(self.mrope_section), "rope_type": "default", "type": "default",
            },
            sa_config={
                "indexer_head_dim": self.indexer_head_dim, "indexer_num_heads": self.indexer_num_heads,
                "indexer_num_kv_heads": 1, "topk": self.index_topk,
            },
        )
        return out

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "KeyeVLMoeConfig":
        kwargs = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, moe_intermediate_size=48, num_experts=8,
            num_experts_per_tok=2, rope_theta=10_000.0, mrope_section=(2, 3, 3),
            max_position_embeddings=512, indexer_head_dim=8, indexer_num_heads=4, index_topk=8,
        )
        kwargs.update(over)
        return KeyeVLMoeConfig(**kwargs)


# tokens a mixture layer takes at once (a longer prompt goes in parts)
MOE_ROWS = 4096

_PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok", "rms_norm_eps",
    "max_position_embeddings",
)


def multi_axis_rotary(x, positions, sections, *, theta: float):
    """Rotary with half-split pairs over ``x`` [B, S, H, D], frequency pair
    ``i`` turning by the axis of ``positions`` [3, B, S] that ``sections``
    gives it (the first ``sections[0]`` pairs by axis 0, and so on);
    ``positions`` [B, S] is one axis for every pair: plain rotary."""
    if positions.ndim == 2:
        return rotary_embedding(x, positions, theta=theta)
    half = x.shape[-1] // 2
    if sum(sections) != half or positions.shape[0] != len(sections):
        raise ValueError(
            f"mrope_section {tuple(sections)} must sum to half the head's width {half} and "
            f"positions {positions.shape} bring one axis a section"
        )
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    axis_of = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections), total_repeat_length=half)
    pos = jnp.take(jnp.moveaxis(positions, 0, -1).astype(jnp.float32), axis_of, axis=-1)  # [B, S, half]
    angles = pos * freqs
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# the descent is sixteen passes of a few operations each: every layer calls
# one trace and one lowering of it
_top_k_mask = jax.jit(top_k_mask, static_argnums=1)


def decode_read(table_positions: int, topk: int) -> str:
    """What a block-paged decode step compiles for a table of that many
    positions: ``"dense"`` (no longer than ``topk``, so every visible row is
    selected: ``paged_attention``) or ``"walk"`` (index scores, the
    selection as a mask, ``paged_sparse_attention`` over the row's live
    blocks)."""
    return "dense" if table_positions <= topk else "walk"


class IndexedSparseAttention(nn.Module):
    """The attention block. ``cache`` is a layer's entry of
    ``IndexedKVRows.init``: ``(keys and values, indexer keys)``, ``[B, L,
    ...]`` or, with ``block_table``, the pool's ``[num_blocks, block, ...]``."""

    config: KeyeVLMoeConfig

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None, kv_mask=None,
                 block_table=None, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        batch, seq, _ = x.shape
        heads, kv_heads, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        ih, idim, topk = cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.index_topk
        scale = hd ** -0.5

        def dense(features, name, quantized=cfg.quantized, out_dtype=dtype):
            return make_dense(quantized=quantized, features=features, dtype=out_dtype, name=name)

        if positions is None:
            base = jnp.asarray(cache_index if cache_index is not None else 0)
            positions = (base[:, None] if base.ndim == 1 else base) + jnp.arange(seq)[None, :]
        temporal = positions if positions.ndim == 2 else positions[0]
        q = dense((heads, hd), "q")(x)
        k = dense((kv_heads, hd), "k")(x)
        v = dense((kv_heads, hd), "v")(x)
        q = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="q_norm")(q)
        k = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="k_norm")(k)
        q = multi_axis_rotary(q, positions, cfg.mrope_section, theta=cfg.rope_theta)
        k = multi_axis_rotary(k, positions, cfg.mrope_section, theta=cfg.rope_theta)
        with jax.named_scope("indexer"):
            # the two narrow projections stay float: they decide a selection
            iq = rotary_embedding(dense((ih, idim), "index_q")(x), temporal, theta=cfg.rope_theta)
            ik = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=dtype, name="index_k_norm")(
                dense(idim, "index_k", quantized=False)(x)
            )
            ik = rotary_embedding(ik[:, :, None, :], temporal, theta=cfg.rope_theta)[:, :, 0]
            iw = dense(ih, "index_w", quantized=False, out_dtype=jnp.float32)(x.astype(jnp.float32))

        new_cache = None
        if cache is None:
            if kv_mask is not None:
                raise ValueError("kv_mask requires a cache (generation path)")
            out = sparse_attention(q, k, v, iq, ik, iw, jnp.arange(seq)[None, :], topk=topk, scale=scale)
        else:
            rows, index_keys = cache
            pad = index_keys.shape[-1] - idim
            ik_row = jnp.pad(ik, ((0, 0), (0, 0), (0, pad))).astype(index_keys.dtype)
            # a cached row: a position's key heads, its value heads behind them
            row = jnp.concatenate([k, v], axis=2).astype(rows.dtype)
            index = jnp.asarray(cache_index)
            if block_table is not None:
                if seq != 1 or index.ndim != 1:
                    raise ValueError(
                        "block-paged caches support vector-index decode steps only "
                        f"(seq == 1), got seq={seq}, cache_index ndim {index.ndim}"
                    )
                if kv_mask is not None:
                    raise ValueError("kv_mask is incompatible with block_table")
                blk = rows.shape[1]
                pid = jnp.take_along_axis(block_table, (index // blk)[:, None], axis=1)[:, 0]
                off = index % blk
                rows = rows.at[pid, off].set(row[:, 0])
                index_keys = index_keys.at[pid, off].set(ik_row[:, 0])
                lengths = index + 1 if live is None else jnp.where(live, index + 1, 0)
                if decode_read(block_table.shape[1] * blk, topk) == "dense":
                    # every visible row is selected: ordinary paged attention
                    out = paged_attention(
                        q[:, 0], rows[:, :, :kv_heads], rows[:, :, kv_heads:], block_table, lengths,
                        scale=scale, impl=cfg.paged_impl,
                    )
                else:
                    with jax.named_scope("indexer"):
                        scores = paged_index_scores(
                            jnp.pad(iq[:, 0], ((0, 0), (0, 0), (0, pad))), iw[:, 0], index_keys,
                            block_table, lengths, impl=cfg.paged_impl,
                        )
                    with jax.named_scope("select"):
                        selected = _top_k_mask(scores, topk)
                    out = paged_sparse_attention(
                        q[:, 0], rows, block_table, lengths, selected, scale=scale, impl=cfg.paged_impl,
                    )
                out = out[:, None]
            else:
                if index.ndim == 1:
                    def put(c, n):
                        return jax.vmap(
                            lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i,) + (0,) * (c.ndim - 1))
                        )(c, n, index)
                else:
                    def put(c, n):
                        return jax.lax.dynamic_update_slice(c, n, (0, index) + (0,) * (c.ndim - 2))
                rows, index_keys = put(rows, row), put(index_keys, ik_row)
                # position j is visible to query i iff j <= index + i
                q_pos = (index[:, None] if index.ndim == 1 else index[None, None]) + jnp.arange(seq)[None, :]
                out = sparse_attention(
                    q, rows[:, :, :kv_heads], rows[:, :, kv_heads:], iq, index_keys[..., :idim], iw,
                    q_pos, kv_mask, topk=topk, scale=scale,
                )
            new_cache = (rows, index_keys)
        out = make_dense(
            quantized=cfg.quantized, features=cfg.hidden_size, axis=(-2, -1), dtype=dtype, name="o",
        )(out)
        return out if cache is None else (out, new_cache)


class KeyeVLMoeBlock(nn.Module):
    config: KeyeVLMoeConfig

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None, kv_mask=None,
                 block_table=None, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        attn = IndexedSparseAttention(cfg, name="attn")
        h = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="attn_norm")(x)
        if cache is None:
            a, new_cache = attn(h, positions=positions, kv_mask=kv_mask), None
        else:
            a, new_cache = attn(
                h, positions=positions, cache=cache, cache_index=cache_index, kv_mask=kv_mask,
                block_table=block_table, live=live,
            )
        x = x + a
        h = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="mlp_norm")(x)
        moe = MoEMlp(
            num_experts=cfg.num_experts, num_selected=cfg.num_experts_per_tok,
            hidden_dim=cfg.moe_intermediate_size, model_dim=cfg.hidden_size, quantized=cfg.quantized,
            dtype=dtype, name="moe",
        )
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
        if seq > MOE_ROWS and seq % MOE_ROWS == 0:
            # a long prompt's rows go through the experts a part at a time:
            # routed rows are top-k times the tokens, and their gathered
            # copies are the largest arrays of a 16k prefill
            routed = jnp.concatenate([
                moe(h[:, i:i + MOE_ROWS], None if real is None else real[:, i:i + MOE_ROWS])[0]
                for i in range(0, seq, MOE_ROWS)
            ], axis=1)
        else:
            routed, _ = moe(h, real)
        return x + routed, new_cache


class KeyeVLMoe(nn.Module):
    config: KeyeVLMoeConfig = field(default_factory=KeyeVLMoeConfig)

    def cache_layout(self):
        """Every layer caches keys, values and an indexer key a token."""
        cfg = self.config
        row = IndexedKVRows(cfg.num_key_value_heads, cfg.head_dim, cfg.indexer_head_dim, cfg.cache_dtype)
        return (row,) * cfg.num_hidden_layers

    def decode_read(self, table_positions: int) -> str:
        """:func:`decode_read` of this model's ``topk`` (what a serving
        engine reports as ``stats()["attention"]["decode_read"]``)."""
        return decode_read(table_positions, self.config.index_topk)

    def moe_dispatch(self, tokens: int) -> Optional[dict]:
        """What a mixture layer does with a program of ``tokens`` rows
        (``ops.moe.dispatch_plan``), and which router sent them."""
        cfg = self.config
        rows = min(tokens, MOE_ROWS) if tokens % MOE_ROWS == 0 else tokens   # a long prompt goes in parts
        plan = dispatch_plan(
            rows, cfg.num_experts, cfg.num_experts_per_tok, quantized=cfg.quantized,
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
        """logits [B, S, V]; with ``cache`` (one ``IndexedKVRows`` entry per
        layer) returns ``(logits, new_cache)``. The arguments are
        ``Llama``'s; ``positions`` may be ``[3, B, S]`` (temporal, height,
        width). ``full_prefill`` is taken and changes nothing: a whole
        prompt runs the blocks every call over contiguous rows runs."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype, name="embed")(tokens)
        new_cache = []
        for i in range(cfg.num_hidden_layers):
            x, c = KeyeVLMoeBlock(cfg, name=f"block_{i}")(
                x, positions=positions, cache=None if cache is None else cache[i],
                cache_index=cache_index, kv_mask=kv_mask, block_table=block_table, live=live,
            )
            new_cache.append(c)
        if logit_index is not None:
            x = x[jnp.arange(x.shape[0]), jnp.asarray(logit_index)][:, None, :]
        x = RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name="final_norm")(x)
        logits = make_dense(
            quantized=cfg.quantized, features=cfg.vocab_size, dtype=jnp.float32, name="lm_head",
        )(x.astype(jnp.float32))
        if cache is not None:
            return logits, tuple(new_cache)
        return logits


# for models.quantization.quantize_params: every wide matmul and the experts
# (the indexer's key and weight projections stay float)
KEYE_VL_MOE_QUANT_PATTERNS = (r"attn/(q|k|v|o|index_q)$", r"lm_head$", r"moe$")
