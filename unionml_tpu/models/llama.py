"""Llama-3-style decoder — the serving flagship (BASELINE.json config #5,
"Llama-3-8B FastAPI predictor serving (on-device batching on TPU)").

Architecture: RMSNorm, rotary embeddings (theta=500k), grouped-query
attention, SwiGLU MLP, untied LM head. Two execution modes:

- **full-sequence** (training / prefill): causal attention via the op
  family (xla / blockwise / flash Pallas / ring / ulysses — config knob);
- **cached decode**: a functional KV cache (pytree of per-layer (k, v)
  buffers, static max_len) threaded through ``__call__`` so the serving
  batcher jit-compiles ONE decode program with a dynamic fill index — no
  recompilation per token (SURVEY.md §7 hard part (e): bucketed shapes).

TP partition rules shard heads (q/k/v out-features, o in-features) and
SwiGLU hidden over the ``tensor`` axis; the embedding and LM head shard
vocab. FSDP fallback covers everything else.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import Attention, KVRows, MlpBlock, RMSNorm, make_dense
from unionml_tpu.ops.moe import MoEMlp, dispatch_plan
from unionml_tpu.parallel.sharding import PartitionRule

Cache = Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...]  # per-layer (k, v)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_dim: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 14_336
    rope_theta: float = 500_000.0
    # llama3-type long-context RoPE rescale, as the hashable tuple
    # (factor, low_freq_factor, high_freq_factor, original_max_len) —
    # what HF Llama-3.1/3.2 config.json carries as `rope_scaling`
    # (models/convert.py maps it; ops in models/layers.py)
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    norm_eps: float = 1e-5  # HF `rms_norm_eps` (1e-6 for Llama-2-era)
    max_len: int = 8192
    attn_impl: str = "xla"
    # attention impl for FULL prefills (empty cache, no prefix, no lead
    # chunks): "flash" runs the Pallas flash kernel over the fresh k/v —
    # no [B, H, S, max_len] score buffer, the long-prompt monolithic-
    # prefill memory/speed lever (see Attention.prefill_impl). "cached"
    # keeps the masked cached-attention path everywhere.
    prefill_impl: str = "cached"
    # decode attention over a BLOCK-PAGED KV pool (the engine's paged
    # mode; consulted only when block_table= is passed): "reference" =
    # jnp.take gather (bit-identical to the contiguous path — the
    # CPU/parity anchor), "pallas" = the scalar-prefetch gather kernel,
    # "auto" = pallas on TPU / reference elsewhere.
    paged_impl: str = "auto"
    # "fused" = Pallas RMSNorm kernel pair (ops/fused_norm.py)
    norm_impl: str = "xla"
    sequence_axis: Optional[str] = None
    quantized: bool = False  # weight-only quantized matmuls (serving path)
    # 8 = int8 (the default serving artifact); 4 = packed-int4 via the
    # Pallas decode kernel (ops/int4_matmul.py) — halves decode weight
    # traffic again. LoRA/QLoRA and MoE experts stay int8.
    weight_bits: int = 8
    # int4 quality/parallelism knobs (weight_bits=4 only). int4_group>0:
    # group-wise scales [K/g, N] (quantize_params(group_size=...) must
    # match). int4_tp>1: the tensor degree the packing tiles must
    # survive (quantize_params(tensor=...) must match) — serving at any
    # DIVISOR of int4_tp stays slab-aligned; a finer split does not.
    int4_group: int = 0
    int4_tp: int = 1
    remat: bool = False  # gradient checkpointing per block (long-context training)
    # mixture-of-experts MLPs (0 = dense). Experts shard over the mesh's
    # `expert` axis via LLAMA_MOE_PARTITION_RULES; GSPMD inserts the
    # dispatch collectives (see ops/moe.py for the explicit all_to_all op).
    num_experts: int = 0
    num_selected: int = 2
    # LoRA fine-tuning: rank-r adapters on attention q/k/v/o and dense-MLP
    # gate/up/down (models/lora.py). With quantized=True this is the QLoRA
    # configuration: int8 frozen base + bf16-computed fp32 adapters — the
    # single-chip 8B fine-tune path. MoE expert weights are NOT adapted
    # (MoEMlp has no lora path; attention adapters still apply).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # int8 KV cache (generation paths): halves cache HBM — the binding
    # constraint for long contexts and engine slot counts (an 8B 8k-ctx
    # batch-8 bf16 cache is ~8.6 GB, rivaling the int8 weights) — with
    # per-(position, kv_head) scales. init_cache builds the quantized
    # layout; Attention infers it from the cache structure.
    kv_quant: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_experts:
            if not 1 <= self.num_selected <= self.num_experts:
                raise ValueError(
                    f"num_selected={self.num_selected} must be in "
                    f"[1, num_experts={self.num_experts}]"
                )

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def mixtral_8x7b() -> "LlamaConfig":
        """Mixtral-8x7B geometry: Llama blocks + 8-expert top-2 MoE MLPs."""
        return LlamaConfig(
            vocab_size=32_000, hidden_dim=4096, num_layers=32, num_heads=32,
            num_kv_heads=8, mlp_dim=14_336, rope_theta=1e6, max_len=32_768,
            num_experts=8, num_selected=2,
        )

    @staticmethod
    def tiny(vocab_size: int = 512, **overrides) -> "LlamaConfig":
        kwargs = dict(
            vocab_size=vocab_size, hidden_dim=64, num_layers=2, num_heads=4,
            num_kv_heads=2, mlp_dim=128, max_len=256, rope_theta=10_000.0,
        )
        kwargs.update(overrides)
        return LlamaConfig(**kwargs)

    @property
    def head_dim(self) -> int:
        return self.hidden_dim // self.num_heads


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, *, positions=None, cache=None, cache_index=None,
                 kv_mask=None, block_table=None, full_prefill=False, live=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        attn = Attention(
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim,
            rope=True,
            rope_theta=cfg.rope_theta,
            rope_scaling=cfg.rope_scaling,
            causal=True,
            attn_impl=cfg.attn_impl,
            prefill_impl=cfg.prefill_impl,
            paged_impl=cfg.paged_impl,
            sequence_axis=cfg.sequence_axis,
            quantized=cfg.quantized,
            weight_bits=cfg.weight_bits,
            int4_group=cfg.int4_group,
            int4_tp=cfg.int4_tp,
            lora_rank=cfg.lora_rank,
            lora_alpha=cfg.lora_alpha,
            dtype=dtype,
            name="attn",
        )
        h = RMSNorm(eps=cfg.norm_eps, dtype=dtype, impl=cfg.norm_impl, name="attn_norm")(x)
        if cache is not None:
            a, new_cache = attn(
                h, positions=positions, cache=cache, cache_index=cache_index,
                kv_mask=kv_mask, block_table=block_table,
                full_prefill=full_prefill, live=live,
            )
        else:
            if kv_mask is not None:
                # the non-cache attention path has no mask plumbing; silently
                # ignoring the mask would attend padded tokens
                raise ValueError(
                    "kv_mask requires a KV cache (generation path); for "
                    "cache-free padded batches use segment_ids/bias on the "
                    "xla attention op instead"
                )
            a, new_cache = attn(h, positions=positions), None
        x = x + a
        h = RMSNorm(eps=cfg.norm_eps, dtype=dtype, impl=cfg.norm_impl, name="mlp_norm")(x)
        if cfg.num_experts:
            mlp_out, aux = MoEMlp(
                num_experts=cfg.num_experts, num_selected=cfg.num_selected,
                hidden_dim=cfg.mlp_dim, model_dim=cfg.hidden_dim,
                quantized=cfg.quantized, dtype=dtype, name="moe",
            )(h)
            # collected by lm_step via mutable=["aux_losses"] and added to
            # the CE loss with a load-balancing weight
            self.sow("aux_losses", "moe_load_balance", aux)
            x = x + mlp_out
        else:
            x = x + MlpBlock(
                hidden_dim=cfg.mlp_dim, gated=True, quantized=cfg.quantized,
                weight_bits=cfg.weight_bits,
                int4_group=cfg.int4_group, int4_tp=cfg.int4_tp,
                lora_rank=cfg.lora_rank, lora_alpha=cfg.lora_alpha,
                dtype=dtype, name="mlp",
            )(h)
        return x, new_cache


class Llama(nn.Module):
    config: LlamaConfig = field(default_factory=LlamaConfig)

    def cache_layout(self) -> Tuple[KVRows, ...]:
        """Every layer caches keys and values (see ``layers.KVRows``)."""
        cfg = self.config
        return (KVRows(cfg.num_kv_heads, cfg.head_dim, cfg.kv_quant, q_heads=cfg.num_heads),) * cfg.num_layers

    def moe_dispatch(self, tokens: int) -> Optional[dict]:
        """What a mixture layer does with a program of ``tokens`` rows
        (``ops.moe.dispatch_plan``); ``None`` for a dense model."""
        cfg = self.config
        if not cfg.num_experts:
            return None
        return dispatch_plan(
            tokens, cfg.num_experts, cfg.num_selected, quantized=cfg.quantized,
            model_dim=cfg.hidden_dim, hidden_dim=cfg.mlp_dim,
        )

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        *,
        positions: Optional[jnp.ndarray] = None,
        cache: Optional[Cache] = None,
        cache_index: Optional[jnp.ndarray] = None,
        kv_mask: Optional[jnp.ndarray] = None,
        block_table: Optional[jnp.ndarray] = None,
        logit_index: Optional[jnp.ndarray] = None,
        full_prefill: bool = False,
        live: Optional[jnp.ndarray] = None,
    ):
        """logits [B,S,V]; with ``cache`` returns (logits, new_cache).

        ``block_table``: int32 [B, table_width] — marks ``cache`` as a
        block-paged pool (per layer [num_blocks, block, kv_heads,
        head_dim]) addressed through the table; decode steps only
        (``seq == 1``, vector ``cache_index``). See
        :class:`~unionml_tpu.models.layers.Attention`.

        ``live``: bool [B] — the rows of a decode step whose logits are
        used (an engine's occupied, unfinished slots); a paged step reads
        no KV for the others.
        ``kv_mask``: bool (batch, max_len) — False cache slots are never
        attended to (left-padded prompts in generation).
        ``full_prefill``: static caller promise that this cached call
        covers the entire visible history (empty cache, index 0, no
        prefix) — lets ``cfg.prefill_impl == "flash"`` run attention over
        the fresh k/v alone (see Attention.full_prefill).
        ``logit_index``: optional int [B] — compute the LM head for only
        that position per row (returned logits are [B, 1, V]). Generation
        needs one next-token distribution, but the full-sequence head on
        a long prefill materializes [B, S, vocab] fp32 — 33 GB at 8B,
        batch 8, 8k context — so serving paths pass the last real
        position instead.
        """
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_dim, dtype=dtype, name="embed")(tokens)
        if positions is None and cache_index is not None:
            index = jnp.asarray(cache_index)
            if index.ndim == 1:  # per-row fill positions (slot decode)
                index = index[:, None]
            positions = index + jnp.arange(tokens.shape[1])[None, :]
        new_cache = []
        # remat: recompute block activations in the backward instead of
        # storing them — O(sqrt)-style memory for long-context training.
        # Decode (cache path) never remats: there is no backward.
        block_cls = (
            nn.remat(LlamaBlock, static_argnums=())
            if cfg.remat and cache is None
            else LlamaBlock
        )
        for i in range(cfg.num_layers):
            layer_cache = cache[i] if cache is not None else None
            x, c = block_cls(cfg, name=f"block_{i}")(
                x, positions=positions, cache=layer_cache, cache_index=cache_index,
                kv_mask=kv_mask, block_table=block_table,
                full_prefill=full_prefill, live=live,
            )
            new_cache.append(c)
        if logit_index is not None:
            idx = jnp.asarray(logit_index)
            x = x[jnp.arange(x.shape[0]), idx][:, None, :]  # [B, 1, D]
        x = RMSNorm(eps=cfg.norm_eps, dtype=dtype, impl=cfg.norm_impl, name="final_norm")(x)
        logits = make_dense(
            quantized=cfg.quantized, features=cfg.vocab_size,
            weight_bits=cfg.weight_bits,
            # lm_head is ROW-parallel under int4 TP (kernel_p K-sharded,
            # partial logits psum'd by GSPMD): 8B's 128256 channels have
            # no power-of-two tile split, but K=hidden always divides —
            # so shards stays 1 and the packing tile ignores TP
            int4_group=cfg.int4_group,
            dtype=jnp.float32, name="lm_head",
        )(x.astype(jnp.float32))
        if cache is not None:
            return logits, tuple(new_cache)
        return logits


def init_cache(
    config: LlamaConfig, batch: int, max_len: Optional[int] = None, dtype: Any = jnp.bfloat16
) -> Cache:
    """Zero-filled KV cache: per-layer (k, v) of [B, max_len, kv_heads, head_dim].

    With ``config.kv_quant`` each layer is instead
    ``(k_q int8, v_q int8, k_scale fp32 [B, max_len, kv_heads], v_scale)``
    — half the HBM of the bf16 form (int8 bytes + 1/32 scale overhead).
    """
    max_len = max_len or config.max_len
    if config.kv_quant and dtype != jnp.bfloat16:
        # the dtype arg governs the bf16 cache form only; silently
        # dropping an explicit request would be a trap
        raise ValueError(
            f"kv_quant caches are int8 + fp32 scales; dtype={dtype} "
            "cannot apply (drop the dtype argument or kv_quant)"
        )
    layer = KVRows(config.num_kv_heads, config.head_dim, config.kv_quant).init(batch, max_len, dtype)
    return (layer,) * config.num_layers


LLAMA_PARTITION_RULES = (
    # `$`-anchored so `kernel` never matches the quantized `kernel_q` params
    PartitionRule(r"attn/(q|k|v)/kernel$", (None, "tensor", None)),
    PartitionRule(r"attn/o/kernel$", ("tensor", None, None)),
    PartitionRule(r"mlp/(gate|up)/kernel$", (None, "tensor")),
    PartitionRule(r"mlp/down/kernel$", ("tensor", None)),
    PartitionRule(r"embed/embedding$", ("tensor", None)),
    PartitionRule(r"lm_head/kernel$", (None, "tensor")),
)

# int8 serving (LlamaConfig.quantized=True): kernels are 2D [K, N] with a
# per-output-channel scale [N]. Megatron layout carries over: qkv/gate/up/
# lm_head shard N (their scales shard with it); o/down shard K (their
# scales are replicated since N is unsharded).
LLAMA_QUANT_PARTITION_RULES = LLAMA_PARTITION_RULES + (
    PartitionRule(r"attn/(q|k|v)/kernel_q$", (None, "tensor")),
    PartitionRule(r"attn/(q|k|v)/scale$", ("tensor",)),
    PartitionRule(r"attn/o/kernel_q$", ("tensor", None)),
    PartitionRule(r"mlp/(gate|up)/kernel_q$", (None, "tensor")),
    PartitionRule(r"mlp/(gate|up)/scale$", ("tensor",)),
    PartitionRule(r"mlp/down/kernel_q$", ("tensor", None)),
    PartitionRule(r"lm_head/kernel_q$", (None, "tensor")),
    PartitionRule(r"lm_head/scale$", ("tensor",)),
)

# LoRA fine-tune configs (lora_rank > 0): adapter factors follow their
# base kernel's Megatron layout (rules in models/lora.py); the union
# covers fp and QLoRA (int8 base) alike.
from unionml_tpu.models.lora import LORA_PARTITION_RULES  # noqa: E402

LLAMA_LORA_PARTITION_RULES = LORA_PARTITION_RULES + LLAMA_QUANT_PARTITION_RULES

# packed-int4 serving (weight_bits=4): kernel_p is [K, N/2] (packed
# output channels). Megatron layout as int8 for q/k/v/gate/up (N
# sharded — a `tensor` shard of the packed/scale columns is
# self-consistent because the packing tile divides the per-device
# channel count when the tree is quantized with tensor=int4_tp; validate
# with assert_int4_tp_compatible) and o/down (K sharded). The lm_head is
# ROW-parallel (K sharded): 8B's 128256 channels have no power-of-two
# tile split, but K=hidden always divides, with GSPMD psum-ing the
# partial logits. Group-wise scales (`scale_g` [K/g, N]) follow their
# kernel: column-parallel sites shard N, row-parallel sites shard the
# K-group rows.
LLAMA_INT4_PARTITION_RULES = (
    # OVERRIDES (first match wins) of the inherited int8 lm_head rules:
    # the int4 lm_head is K-sharded, so its per-channel [vocab] scale is
    # replicated (the int8 rule would shard it against unsharded partial
    # logits, inserting a gather every decode step)
    PartitionRule(r"lm_head/scale$", ()),
    PartitionRule(r"lm_head/scale_g$", ("tensor", None)),
    PartitionRule(r"lm_head/kernel_p$", ("tensor", None)),
    PartitionRule(r"attn/(q|k|v)/scale_g$", (None, "tensor")),
    PartitionRule(r"attn/o/scale_g$", ("tensor", None)),
    PartitionRule(r"mlp/(gate|up)/scale_g$", (None, "tensor")),
    PartitionRule(r"mlp/down/scale_g$", ("tensor", None)),
) + LLAMA_QUANT_PARTITION_RULES + (
    PartitionRule(r"attn/(q|k|v)/kernel_p$", (None, "tensor")),
    PartitionRule(r"attn/o/kernel_p$", ("tensor", None)),
    PartitionRule(r"mlp/(gate|up)/kernel_p$", (None, "tensor")),
    PartitionRule(r"mlp/down/kernel_p$", ("tensor", None)),
)


def assert_int4_tp_compatible(config: "LlamaConfig", tensor: int) -> None:
    """Refuse tensor-parallel degrees whose per-device channel ranges
    split an int4 packing tile — a misaligned shard pairs nibbles with
    the wrong output channels and decodes GARBAGE with no exception.
    Call before sharding a ``weight_bits=4`` tree.

    With ``config.int4_tp`` set (the degree ``quantize_params(tensor=…)``
    packed for), any ``tensor`` DIVIDING it is slab-aligned — 8B packs
    for tp=8 with tiles q 512 / k,v 128 / gate,up 256. A tree packed at
    the default ``int4_tp=1`` keeps the old single-chip rule (8B then
    passes tp=2; k/v break at tp=4 — 1024/4 = 256 per device vs tile
    512). The lm_head is exempt: it shards K, which any degree divides.
    """
    from unionml_tpu.ops.int4_matmul import tile_for

    if tensor <= 1 or config.weight_bits != 4:
        return
    # column-parallel sites only (o/down/lm_head shard K — row sharding
    # leaves output channels whole)
    sites = (
        ("attn/q", config.num_heads * config.head_dim, config.hidden_dim),
        ("attn/k", config.num_kv_heads * config.head_dim, config.hidden_dim),
        ("mlp/gate", config.mlp_dim, config.hidden_dim),
    )
    for name, n, k in sites:
        tile = tile_for(n, k, shards=config.int4_tp)
        if tile and (n // tensor) % tile:
            raise ValueError(
                f"int4 layer {name}: {n} channels / tensor={tensor} = "
                f"{n // tensor} per device, not a multiple of the packing "
                f"tile {tile} (tree packed for int4_tp={config.int4_tp}) — "
                "the shard would unpack wrong channels. Re-quantize with "
                f"quantize_params(tensor={tensor}) and "
                f"LlamaConfig(int4_tp={tensor}), serve at a divisor of "
                f"{config.int4_tp}, or serve this model int8."
            )

# MoE configs (num_experts > 0): expert weights [E, d, h] shard E over the
# `expert` mesh axis (GSPMD turns the one-hot dispatch einsums into
# all_to_all on that axis) and the hidden dim over `tensor`; the router is
# replicated — it is tiny and every device routes its own tokens.
LLAMA_MOE_PARTITION_RULES = (
    PartitionRule(r"moe/w_(gate|up)$", ("expert", None, "tensor")),
    PartitionRule(r"moe/w_down$", ("expert", "tensor", None)),
    # int8 serving form: [E, K, N] weights + [E, N] scales
    PartitionRule(r"moe/w_(gate|up)_q$", ("expert", None, "tensor")),
    PartitionRule(r"moe/w_(gate|up)_scale$", ("expert", "tensor")),
    PartitionRule(r"moe/w_down_q$", ("expert", "tensor", None)),
    PartitionRule(r"moe/w_down_scale$", ("expert", None)),
    PartitionRule(r"moe/router_kernel$", (None,)),
    # includes the attention/mlp/lm_head int8 rules (supersets the fp set),
    # so one rule list covers fp and quantized MoE models alike
) + LLAMA_QUANT_PARTITION_RULES
