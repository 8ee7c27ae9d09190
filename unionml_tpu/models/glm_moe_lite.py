"""GLM-4.7-Flash decoder (``glm4_moe_lite``): latent attention and a
sigmoid-routed mixture beside a shared expert.

The configuration carries the Hugging Face keys one to one
(``zai-org/GLM-4.7-Flash`` ``config.json``). Pre-norm residual blocks
(``x += attn(norm(x)); x += mlp(norm(x))``), a final RMSNorm, an untied
head.

- **Latent attention** (:class:`LatentAttention`, DeepSeek-V2's MLA). A
  token's queries come through a low-rank pair (``q_a``, RMSNorm,
  ``q_b``) as ``num_attention_heads`` heads of ``[q_nope ; q_rope]``; its
  keys and values come from one *latent* row ``[c_kv ; k_rope]``
  (``kv_a``: ``kv_lora_rank`` values that are RMS-normalised, and
  ``qk_rope_head_dim`` values that are rotated and shared by every
  head). The row is all that is cached (:class:`~.layers.LatentRows`).
  Two forms of one attention read it:

  - *expanded* (a prompt computed from nothing): ``[k_nope ; v]_h = c_kv
    W_kvb`` per head, and ordinary ``num_attention_heads``-head attention
    at head width ``qk_nope + qk_rope`` (the flash kernel where
    ``prefill_impl`` says so);
  - *absorbed* (every call that reads cached rows: a decode step over a
    block pool or a slot's rows, a prefill chunk, a prefill behind a cached
    prefix): ``q_lat = q_nope W_uk^T``, scores ``q_lat . c_kv + q_rope .
    k_rope`` over the cached rows, ``o_lat = sum p c_kv``, ``o_h = o_lat
    W_uv``: the same numbers, and no per-head key or value exists for a
    cached position. Over a block pool this is the kernel
    ``paged_latent_attention`` (:mod:`unionml_tpu.ops.paged_attention`).

  The softmax scale is ``(qk_nope + qk_rope) ** -0.5``; rotary positions
  pair dimensions ``(i, i + rope / 2)`` as the repo's other decoders do.
- **Mixture layers** (layer ``first_k_dense_replace`` on; the ones before
  are SwiGLU MLPs of ``intermediate_size``): :class:`~unionml_tpu.ops.moe.MoEMlp`
  with the sigmoid router (``noaux_tc``: top-k of ``sigmoid + bias``,
  weights the sigmoids normalised and times ``routed_scaling_factor``;
  one expert group) plus a shared SwiGLU expert of ``moe_intermediate_size
  x n_shared_experts`` added to the routed sum.

Not here: the multi-token-prediction layer (``num_nextn_predict_layers``:
a training head and a self-drafter; nothing serves it).

:class:`GlmMoeLite` takes :class:`~unionml_tpu.models.llama.Llama`'s call
arguments; ``cache_layout()`` tells a serving engine that every layer owns
latent rows.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import (
    LatentRows, MlpBlock, RMSNorm, make_dense, rotary_embedding,
)
from unionml_tpu.ops.attention import attention as xla_attention
from unionml_tpu.ops.moe import MoEMlp, dispatch_plan
from unionml_tpu.ops.paged_attention import (
    NEG_INF, latent_attention, paged_latent_attention,
)


@dataclass(frozen=True)
class GlmMoeLiteConfig:
    # ---- the published config's keys
    vocab_size: int = 154_880
    hidden_size: int = 2048
    intermediate_size: int = 10_240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.8
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 202_752
    # ---- how this program runs it
    quantized: bool = False       # int8 weight-only for the projections and the experts
    prefill_impl: str = "cached"  # read by the engine, as LlamaConfig's: "flash" for whole prompts
    paged_impl: str = "auto"      # the pool's decode read, as LlamaConfig's
    dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"  # the latent rows' (float32 in the tests that compare logits)

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def from_hf(cls, hf: dict, **over) -> "GlmMoeLiteConfig":
        """From a ``config.json``-style dict. What this module cannot run
        as published raises; keys it does not know are ignored."""
        for key, want in (("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True),
                          ("rope_scaling", None), ("topk_method", "noaux_tc")):
            if hf.get(key, want) != want:
                raise ValueError(f"glm4_moe_lite with {key} = {hf[key]!r} is not supported (only {want!r})")
        kwargs = {k: hf[k] for k in _PUBLISHED_KEYS if k in hf}
        kwargs["rope_theta"] = float(kwargs.get("rope_theta", cls.rope_theta))
        kwargs.update(over)
        return cls(**kwargs)

    def to_hf(self) -> dict:
        """The published keys as a dict (what the plain reference takes)."""
        return {k: getattr(self, k) for k in _PUBLISHED_KEYS}

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "GlmMoeLiteConfig":
        kwargs = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=20, n_routed_experts=8,
            num_experts_per_tok=2, rope_theta=10_000.0, max_position_embeddings=512,
        )
        kwargs.update(over)
        return GlmMoeLiteConfig(**kwargs)


_PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
    "routed_scaling_factor", "rope_theta", "rms_norm_eps", "max_position_embeddings",
)


class _UpProjection(nn.Module):
    """``kv_b``: the latent's up-projection to every head's ``[k_nope ; v]``,
    held as a weight and not applied here, because the two forms of the
    attention use it differently. Parameters as :func:`make_dense`'s
    (``kernel`` [in, out], or int8 ``kernel_q`` with a float32 ``scale``
    per output channel)."""

    features: int
    quantized: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, fan_in: int):
        """(weight [in, out] in the compute dtype, scale [out] float32 or None)."""
        if self.quantized:
            w = self.param("kernel_q", nn.initializers.zeros, (fan_in, self.features), jnp.int8)
            scale = self.param("scale", nn.initializers.ones, (self.features,), jnp.float32)
            return w.astype(self.dtype), scale
        w = self.param("kernel", nn.initializers.lecun_normal(), (fan_in, self.features), jnp.float32)
        return w.astype(self.dtype), None


class LatentAttention(nn.Module):
    """The attention block over a latent cache. ``cache`` is a layer's
    entry of ``LatentRows.init``: ``(rows,)``, ``[B, L, stored_width]`` or,
    with ``block_table``, the pool ``[num_blocks, block, stored_width]``."""

    config: GlmMoeLiteConfig

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None, kv_mask=None,
                 block_table=None, full_prefill=False, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        f32 = jnp.float32
        batch, seq, _ = x.shape
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        scale = cfg.qk_head_dim ** -0.5

        def dense(features, name):
            return make_dense(quantized=cfg.quantized, features=features, dtype=dtype, name=name)

        def norm(name):
            return RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name=name)

        if positions is None:
            base = jnp.asarray(cache_index if cache_index is not None else 0)
            positions = (base[:, None] if base.ndim == 1 else base) + jnp.arange(seq)[None, :]
        q = dense(heads * cfg.qk_head_dim, "q_b")(norm("q_a_norm")(dense(cfg.q_lora_rank, "q_a")(x)))
        q = q.reshape(batch, seq, heads, cfg.qk_head_dim)
        q_nope = q[..., :nope]
        q_rope = rotary_embedding(q[..., nope:], positions, theta=cfg.rope_theta)
        kv = dense(rank + rope, "kv_a")(x)
        c_kv = norm("kv_a_norm")(kv[..., :rank])
        k_rope = rotary_embedding(kv[..., None, rank:], positions, theta=cfg.rope_theta)[:, :, 0]
        w_up, w_scale = _UpProjection(heads * (nope + vd), cfg.quantized, dtype, name="kv_b")(rank)
        w_up = w_up.reshape(rank, heads, nope + vd)
        w_scale = None if w_scale is None else w_scale.reshape(heads, nope + vd)

        def expanded():
            """Every head's keys and values from this call's own latents."""
            with jax.named_scope("expand"):
                up = jnp.einsum("bsc,chd->bshd", c_kv, w_up, preferred_element_type=f32)
                up = (up if w_scale is None else up * w_scale).astype(dtype)
                k_rope_heads = jnp.broadcast_to(k_rope[:, :, None, :], (batch, seq, heads, rope))
                k = jnp.concatenate([up[..., :nope], k_rope_heads], axis=-1)
                return jnp.concatenate([q_nope, q_rope], axis=-1), k, up[..., nope:]

        def absorbed_query():
            """``[q_nope W_uk^T ; q_rope ; 0]``: the query in the row's space."""
            with jax.named_scope("absorb"):
                qn = q_nope if w_scale is None else (q_nope.astype(f32) * w_scale[:, :nope]).astype(dtype)
                q_lat = jnp.einsum("bshd,chd->bshc", qn, w_up[..., :nope], preferred_element_type=f32)
                return jnp.concatenate([q_lat.astype(dtype), q_rope], axis=-1)

        def from_latent(o_lat):
            """``o_lat W_uv``: the weighted latents to every head's values."""
            with jax.named_scope("absorb"):
                o = jnp.einsum("bshc,chd->bshd", o_lat, w_up[..., nope:], preferred_element_type=f32)
                return (o if w_scale is None else o * w_scale[:, nope:]).astype(dtype)

        new_cache = None
        if cache is None:
            if kv_mask is not None:
                raise ValueError("kv_mask requires a cache (generation path)")
            out = xla_attention(*expanded(), causal=True, scale=scale)
        else:
            (rows,) = cache
            pad = rows.shape[-1] - (rank + rope)
            row = jnp.concatenate([c_kv, k_rope], axis=-1).astype(rows.dtype)
            row = jnp.pad(row, ((0, 0), (0, 0), (0, pad)))
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
                rows = rows.at[pid, index % blk].set(row[:, 0])
                lengths = index + 1 if live is None else jnp.where(live, index + 1, 0)
                q_row = jnp.pad(absorbed_query()[:, 0], ((0, 0), (0, 0), (0, pad)))
                o_lat = paged_latent_attention(
                    q_row, rows, block_table, lengths, value_dim=rank, scale=scale, impl=cfg.paged_impl,
                )
                out = from_latent(o_lat[:, None])
            else:
                if index.ndim == 1:
                    rows = jax.vmap(
                        lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i, 0))
                    )(rows, row, index)
                else:
                    rows = jax.lax.dynamic_update_slice(rows, row, (0, index, 0))
                if full_prefill and seq > 1 and cfg.prefill_impl == "flash":
                    # the whole visible history is this call's own tokens
                    # (right-padded: causal alone hides the tail)
                    from unionml_tpu.ops.flash_attention import flash_attention

                    pads = (
                        jnp.zeros((batch,), jnp.int32) if kv_mask is None
                        else jnp.argmax(kv_mask[:, :seq].astype(jnp.int32), axis=-1).astype(jnp.int32)
                    )
                    out = flash_attention(*expanded(), causal=True, scale=scale, kv_valid_start=pads)
                else:
                    # position j is visible to query i iff j <= index + i
                    kv_pos = jnp.arange(rows.shape[1])
                    q_pos = index[:, None] if index.ndim == 1 else index[None, None]
                    q_pos = q_pos + jnp.arange(seq)[None, :]
                    visible = kv_pos[None, None, :] <= q_pos[..., None]      # [B or 1, S, L]
                    if kv_mask is not None:
                        visible = visible & kv_mask[:, None, :]
                    bias = jnp.where(visible, 0.0, NEG_INF)[:, None]
                    q_row = jnp.pad(absorbed_query(), ((0, 0), (0, 0), (0, 0), (0, pad)))
                    out = from_latent(latent_attention(q_row, rows, bias, value_dim=rank, scale=scale))
            new_cache = (rows,)
        out = dense(cfg.hidden_size, "o")(out.reshape(batch, seq, heads * vd))
        return out if cache is None else (out, new_cache)


class GlmMoeLiteBlock(nn.Module):
    config: GlmMoeLiteConfig
    mixture: bool

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None, kv_mask=None,
                 block_table=None, full_prefill=False, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)

        def norm(name):
            return RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name=name)

        attn = LatentAttention(cfg, name="attn")
        h = norm("attn_norm")(x)
        if cache is None:
            a, new_cache = attn(h, positions=positions, kv_mask=kv_mask), None
        else:
            a, new_cache = attn(
                h, positions=positions, cache=cache, cache_index=cache_index, kv_mask=kv_mask,
                block_table=block_table, full_prefill=full_prefill, live=live,
            )
        x = x + a
        h = norm("mlp_norm")(x)

        def swiglu(width, name):
            return MlpBlock(hidden_dim=width, gated=True, quantized=cfg.quantized, dtype=dtype, name=name)

        if not self.mixture:
            return x + swiglu(cfg.intermediate_size, "mlp")(h), new_cache
        routed, _ = MoEMlp(
            num_experts=cfg.n_routed_experts, num_selected=cfg.num_experts_per_tok,
            hidden_dim=cfg.moe_intermediate_size, model_dim=cfg.hidden_size, quantized=cfg.quantized,
            router="sigmoid", routed_scaling=cfg.routed_scaling_factor, dtype=dtype, name="moe",
        )(h)
        shared = swiglu(cfg.moe_intermediate_size * cfg.n_shared_experts, "shared_expert")(h)
        return x + routed + shared, new_cache


class GlmMoeLite(nn.Module):
    config: GlmMoeLiteConfig = field(default_factory=GlmMoeLiteConfig)

    def cache_layout(self):
        """Every layer caches one latent row a token."""
        cfg = self.config
        return (LatentRows(cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.cache_dtype),) * cfg.num_hidden_layers

    def moe_dispatch(self, tokens: int) -> Optional[dict]:
        """What a mixture layer does with a program of ``tokens`` rows
        (``ops.moe.dispatch_plan``), and which router sent them."""
        cfg = self.config
        if cfg.num_hidden_layers <= cfg.first_k_dense_replace:
            return None
        plan = dispatch_plan(
            tokens, cfg.n_routed_experts, cfg.num_experts_per_tok, quantized=cfg.quantized,
            model_dim=cfg.hidden_size, hidden_dim=cfg.moe_intermediate_size,
        )
        return {"router": "sigmoid", **plan}

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
        """logits [B, S, V]; with ``cache`` (one ``LatentRows`` entry per
        layer) returns ``(logits, new_cache)``. The arguments are
        ``Llama``'s."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype, name="embed")(tokens)
        new_cache = []
        for i in range(cfg.num_hidden_layers):
            x, c = GlmMoeLiteBlock(cfg, i >= cfg.first_k_dense_replace, name=f"block_{i}")(
                x, positions=positions, cache=None if cache is None else cache[i],
                cache_index=cache_index, kv_mask=kv_mask, block_table=block_table,
                full_prefill=full_prefill, live=live,
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
GLM_MOE_LITE_QUANT_PATTERNS = (
    r"attn/(q_a|q_b|kv_a|kv_b|o)$", r"(mlp|shared_expert)/(gate|up|down)$", r"lm_head$", r"moe$",
)
