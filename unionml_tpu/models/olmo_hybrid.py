"""Olmo-Hybrid decoder: gated-delta-rule layers beside full attention.

The ``olmo_hybrid`` architecture (``allenai/Olmo-Hybrid-7B``): three
linear-attention layers, then one full-attention layer, eight times. The
configuration carries the Hugging Face keys one to one (``layer_types``,
``linear_*``).

- **Linear-attention layer** (:class:`GatedDeltaNet`): q, k, v projections,
  a causal depthwise convolution over time (width
  ``linear_conv_kernel_dim``) and SiLU, unit-length q and k per head, then
  the gated delta rule (:mod:`unionml_tpu.ops.gated_delta`) with decay
  ``exp(-exp(A_log) softplus(x W_a + dt_bias))`` and write strength
  ``beta = sigmoid(x W_b)`` (doubled under ``linear_allow_neg_eigval``);
  the output is RMS-normalised per head, gated by ``silu(x W_g)`` and
  projected back. Its cache is a state of fixed size: the rule's ``S`` and
  the last ``width - 1`` pre-convolution rows.
- **Full-attention layer**: :class:`~unionml_tpu.models.layers.Attention`
  with q and k RMS-normalised over their full width and no rotary
  embedding (``rope_theta: null`` in the published config).
- **Block**: ``h = x + RMSNorm(Mixer(x)); out = h + RMSNorm(MLP(h))``, the
  family's norm-after-sublayer order; a final RMSNorm; an untied head.

:class:`OlmoHybrid` takes the same call arguments as
:class:`~unionml_tpu.models.llama.Llama` (``cache``, ``cache_index``,
``kv_mask``, ``block_table``, ``logit_index``, ``live``: the rows of a
decode step whose state may change). ``cache_layout()`` tells a
serving engine which layers own key/value rows and which a state
(``layers.KVRows`` / ``layers.SlotState``). In a cached multi-token call a
row's tokens must be real from its first position on (right padding, as
the engine's buckets are): a padded position is the identity on the state
and stays out of the convolution's tail.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import (
    Attention, KVRows, MlpBlock, RMSNorm, SlotState, make_dense,
)
from unionml_tpu.ops.gated_delta import (
    gated_delta_chunked, gated_delta_step, state_shape, step_operand_bytes,
)

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig:
    # ---- the published config's keys
    vocab_size: int = 100_352
    hidden_size: int = 3840
    intermediate_size: int = 11_008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65_536
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # ---- how this program runs it
    quantized: bool = False    # int8 weight-only for the wide projections
    prefill_impl: str = "cached"  # read by the engine, as LlamaConfig's
    dtype: str = "bfloat16"
    state_dtype: str = "float32"  # the rule's S; anything coarser drifts

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}"
            )
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.linear_num_value_heads != self.linear_num_key_heads:
            raise ValueError(
                "linear_num_value_heads != linear_num_key_heads is not "
                "supported (the published model has 30 of each)"
            )

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_cache_heads(self) -> int:
        """Heads a full-attention layer's cache holds: a count that is no
        multiple of 8 is rounded up to a multiple of 16. A bfloat16
        ``[.., heads, head_dim]`` array is padded to that on a TPU anyway,
        and the paged kernel's ``[blocks, block * heads, head_dim]`` view of
        the pool is then a bitcast and not a copy of the pool."""
        heads = self.num_key_value_heads
        return heads if heads % 8 == 0 else -(-heads // 16) * 16

    @property
    def conv_channels(self) -> int:
        return (
            2 * self.linear_num_key_heads * self.linear_key_head_dim
            + self.linear_num_value_heads * self.linear_value_head_dim
        )

    @classmethod
    def from_hf(cls, hf: dict, **over) -> "OlmoHybridConfig":
        """From a ``config.json``-style dict; keys it does not know (and
        ``rope_parameters``, which the model does not use) are ignored."""
        kwargs = {k: hf[k] for k in _PUBLISHED_KEYS if k in hf}
        kwargs["layer_types"] = tuple(hf["layer_types"])
        kwargs.update(over)
        return cls(**kwargs)

    def to_hf(self) -> dict:
        """The published keys as a dict (what the plain reference takes)."""
        return {k: getattr(self, k) for k in _PUBLISHED_KEYS} | {"layer_types": list(self.layer_types)}

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "OlmoHybridConfig":
        kwargs = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=512, layer_types=(LINEAR, LINEAR, LINEAR, FULL),
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=64,
        )
        kwargs.update(over)
        return OlmoHybridConfig(**kwargs)


_PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "rms_norm_eps", "max_position_embeddings", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "linear_allow_neg_eigval",
)


def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer. ``cache`` is ``(S, tail)``: the rule's
    packed float32 state ``[B, *state_shape]`` and the last ``width - 1``
    pre-convolution rows, oldest first, flattened ``[B, (width - 1) * C]``
    (flat, so that the minor axes tile densely on a TPU)."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, *, cache=None, cache_index=None, kv_mask=None, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        f32 = jnp.float32
        batch, seq, _ = x.shape
        heads, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
        width, channels = cfg.linear_conv_kernel_dim, cfg.conv_channels

        def dense(features, name):
            return make_dense(quantized=cfg.quantized, features=features, dtype=dtype, name=name)

        def gate(name):
            # 30 outputs wide: unquantised, float32 at full precision
            return nn.Dense(
                heads, use_bias=False, dtype=f32, precision=jax.lax.Precision.HIGHEST, name=name,
            )(x.astype(f32))

        qkv = jnp.concatenate(
            [dense(heads * dk, "q")(x), dense(heads * dk, "k")(x), dense(heads * dv, "v")(x)], axis=-1
        )
        conv = self.param("conv_kernel", nn.initializers.lecun_normal(), (width, channels), f32)
        a_log = self.param("A_log", _a_log_init, (heads,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (heads,), f32)
        with jax.named_scope("gates"):
            beta = jax.nn.sigmoid(gate("b")) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
            g = -jnp.exp(a_log) * jax.nn.softplus(gate("a") + dt_bias)

        step = cache is not None and seq == 1 and jnp.ndim(cache_index) == 1
        if cache is None:
            state = jnp.zeros((batch,) + state_shape(heads, dk, dv), f32)
            tail = jnp.zeros((batch, (width - 1) * channels), qkv.dtype)
        else:
            state, tail = cache
        with jax.named_scope("conv"):
            if step:
                # the flat tail's rows are lane-aligned slices of it: the taps
                # add in the prefill branch's order, and nothing is stacked
                taps = [tail[:, j * channels:(j + 1) * channels] for j in range(width - 1)] + [qkv[:, 0]]
                mixed = sum(conv[j] * taps[j].astype(f32) for j in range(width))[:, None]
                new_tail = jnp.concatenate([tail[:, channels:], taps[-1].astype(tail.dtype)], axis=1)
            else:
                history = tail.reshape(batch, width - 1, channels)
                rows = jnp.concatenate([history.astype(qkv.dtype), qkv], axis=1)  # [B, width-1+T, C]
                mixed = sum(conv[j] * rows[:, j:j + seq].astype(f32) for j in range(width))
                valid_len = None
                if cache is not None:
                    valid_len = jnp.full((batch,), seq, jnp.int32)
                    if kv_mask is not None:
                        base = jnp.asarray(cache_index if cache_index is not None else 0)
                        pos = base.reshape(-1, 1) + jnp.arange(seq)[None, :]
                        pos = jnp.broadcast_to(pos, (batch, seq))
                        seen = jnp.take_along_axis(kv_mask, pos, axis=1)
                        valid_len = jnp.sum(seen, axis=1).astype(jnp.int32)
                    # the rows of the last width-1 real tokens (the old tail's,
                    # where the chunk holds fewer)
                    new_tail = jax.vmap(
                        lambda r, n: jax.lax.dynamic_slice_in_dim(r, n, width - 1, axis=0)
                    )(rows, valid_len).reshape(batch, -1).astype(tail.dtype)
            mixed = jax.nn.silu(mixed)
            q, k, v = jnp.split(mixed, [heads * dk, 2 * heads * dk], axis=-1)
            q = _l2norm(q.reshape(batch, seq, heads, dk))
            k = _l2norm(k.reshape(batch, seq, heads, dk))
            v = v.reshape(batch, seq, heads, dv)
        with jax.named_scope("state_update"):
            if step:
                o, new_state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state, live)
                o = o[:, None]
            else:
                o, new_state = gated_delta_chunked(q, k, v, g, beta, state, valid_len)
        o = RMSNorm(eps=cfg.rms_norm_eps, dtype=f32, name="o_norm")(o)
        o = o * jax.nn.silu(dense(heads * dv, "g")(x).astype(f32).reshape(o.shape))
        out = dense(cfg.hidden_size, "o")(o.reshape(batch, seq, heads * dv).astype(dtype))
        if cache is None:
            return out
        return out, (new_state, new_tail)


class OlmoHybridBlock(nn.Module):
    config: OlmoHybridConfig
    layer_type: str

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None, kv_mask=None,
                 block_table=None, full_prefill=False, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        if self.layer_type == LINEAR:
            mixer = GatedDeltaNet(cfg, name="gdn")
            kwargs = {} if cache is None else dict(
                cache=cache, cache_index=cache_index, kv_mask=kv_mask, live=live,
            )
        else:
            mixer = Attention(
                num_heads=cfg.num_attention_heads, num_kv_heads=cfg.num_key_value_heads,
                head_dim=cfg.head_dim, rope=False, qk_norm=True, qk_norm_eps=cfg.rms_norm_eps,
                causal=True, prefill_impl=cfg.prefill_impl, quantized=cfg.quantized,
                dtype=dtype, name="attn",
            )
            kwargs = dict(positions=positions)
            if cache is not None:
                kwargs.update(
                    cache=cache, cache_index=cache_index, kv_mask=kv_mask,
                    block_table=block_table, full_prefill=full_prefill, live=live,
                )
            elif kv_mask is not None:
                raise ValueError("kv_mask requires a cache (generation path)")
        mixed = mixer(x, **kwargs)
        mixed, new_cache = mixed if cache is not None else (mixed, None)

        def norm(name):
            return RMSNorm(eps=cfg.rms_norm_eps, dtype=dtype, name=name)

        h = x + norm("mixer_norm")(mixed)
        mlp = MlpBlock(
            hidden_dim=cfg.intermediate_size, gated=True, quantized=cfg.quantized, dtype=dtype, name="mlp",
        )
        return h + norm("mlp_norm")(mlp(h)), new_cache


class OlmoHybrid(nn.Module):
    config: OlmoHybridConfig = field(default_factory=OlmoHybridConfig)

    def cache_layout(self):
        """Per layer: key/value rows for a full-attention layer, a state of
        fixed size (``S``, the convolution's tail) for a linear one."""
        cfg = self.config
        state = SlotState(
            shapes=(
                state_shape(cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                ((cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels,),
            ),
            dtypes=(cfg.state_dtype, cfg.dtype),
        )
        rows = KVRows(cfg.kv_cache_heads, cfg.head_dim, q_heads=cfg.kv_cache_heads)  # q padded with the cache
        return tuple(state if kind == LINEAR else rows for kind in cfg.layer_types)

    def step_operand_bytes(self, batch: int) -> int:
        """Bytes a linear layer's state kernel moves besides the states in
        one decode step of ``batch`` rows, as the chip tiles them."""
        cfg = self.config
        return step_operand_bytes(
            batch, cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
        )

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
        """logits [B, S, V]; with ``cache`` (one entry per layer, as
        ``cache_layout()`` describes) returns ``(logits, new_cache)``.
        ``positions`` is accepted for the engine's sake and unused: the
        model has no positional embedding. ``live`` [B] bool: in a decode
        step (``seq == 1``, vector ``cache_index``) only these rows'
        states change. The other arguments are ``Llama``'s."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype, name="embed")(tokens)
        new_cache = []
        for i, kind in enumerate(cfg.layer_types):
            x, c = OlmoHybridBlock(cfg, kind, name=f"block_{i}")(
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
