"""ViT — the data-parallel training flagship (BASELINE.json config #3,
"ViT-B/16 image classifier (pjit data-parallel over v5e-8 mesh)").

TPU-first choices: patchify is one strided conv (a big MXU matmul after
im2col — XLA lowers it directly), the encoder body is a `lax.scan`-free
stack of identical blocks (XLA caches the compiled block), compute in
bf16 with fp32 LayerNorm statistics, and the TP partition rules below give
the Megatron 2-collectives-per-block layout via GSPMD.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import Attention, LayerNorm, MlpBlock
from unionml_tpu.parallel.sharding import PartitionRule


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    attn_impl: str = "xla"
    # "fused" = Pallas LayerNorm kernel pair incl. residual-add fusion
    # (ops/fused_norm.py); "xla" = plain fp32-stats LayerNorm
    norm_impl: str = "xla"
    # HF ViT checkpoints carry q/k/v/o biases and use erf GELU; the
    # trained-from-scratch defaults stay bias-free/tanh. Checkpoint
    # loaders (models/convert.py) set both for faithful inference.
    qkv_bias: bool = False
    gelu_exact: bool = False
    dtype: str = "bfloat16"

    @staticmethod
    def base16(num_classes: int = 1000, attn_impl: str = "fused") -> "ViTConfig":
        # "fused" = Pallas one-program-per-batch attention over the
        # projections' own [B, S, 768] (two 64-wide heads a lane tile, so
        # nothing between q/k/v/o and the kernel is half padding): at S=197
        # it beats XLA attention fwd+bwd on v5e (see ops/fused_attention)
        return ViTConfig(num_classes=num_classes, attn_impl=attn_impl)

    @staticmethod
    def tiny(image_size: int = 32, num_classes: int = 10) -> "ViTConfig":
        return ViTConfig(
            image_size=image_size, patch_size=8, num_classes=num_classes,
            hidden_dim=64, num_layers=2, num_heads=4, mlp_dim=128,
        )


class ViTBlock(nn.Module):
    config: ViTConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        # default path stays plain nn.LayerNorm (identical graph/numerics
        # to pre-norm_impl builds); the fused module shares its param
        # names so either impl loads the other's checkpoints
        ln = lambda name: (  # noqa: E731
            LayerNorm(dtype=dtype, name=name)
            if cfg.norm_impl == "fused"
            else nn.LayerNorm(dtype=dtype, name=name)
        )
        attn = Attention(
            num_heads=cfg.num_heads, attn_impl=cfg.attn_impl,
            use_bias=cfg.qkv_bias, dtype=dtype, name="attn",
        )
        mlp = MlpBlock(
            hidden_dim=cfg.mlp_dim, gelu_approximate=not cfg.gelu_exact,
            dtype=dtype, name="mlp",
        )
        if cfg.norm_impl == "fused":
            # fuse the mid-block residual add into ln2's pass (one fewer
            # [B*S, D] HBM round trip each way); param tree unchanged
            h1 = ln("ln1")(x)
            s, h2 = _AddLayerNorm(dtype=cfg.dtype, name="ln2")(x, attn(h1))
            return s + mlp(h2)
        x = x + attn(ln("ln1")(x))
        x = x + mlp(ln("ln2")(x))
        return x


class _AddLayerNorm(nn.Module):
    """``s = x + branch; y = LayerNorm(s)`` through the fused kernel,
    parameter-compatible with :class:`LayerNorm` (``scale``/``bias``)."""

    eps: float = 1e-6
    dtype: str = "bfloat16"

    @nn.compact
    def __call__(self, x: jnp.ndarray, branch: jnp.ndarray):
        from unionml_tpu.ops.fused_norm import fused_add_layer_norm

        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        s, y = fused_add_layer_norm(x, branch, scale, bias, self.eps)
        return s, y.astype(jnp.dtype(self.dtype))


class ViT(nn.Module):
    config: ViTConfig = field(default_factory=ViTConfig)

    @nn.compact
    def __call__(self, images: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        p = cfg.patch_size
        # patchify: one conv == one big MXU matmul
        x = nn.Conv(
            cfg.hidden_dim, kernel_size=(p, p), strides=(p, p),
            padding="VALID", dtype=dtype, name="patch_embed",
        )(images.astype(dtype))
        batch = x.shape[0]
        x = x.reshape((batch, -1, cfg.hidden_dim))
        cls = self.param(
            "cls", nn.initializers.zeros, (1, 1, cfg.hidden_dim), jnp.float32
        ).astype(dtype)
        x = jnp.concatenate([jnp.broadcast_to(cls, (batch, 1, cfg.hidden_dim)), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, x.shape[1], cfg.hidden_dim),
            jnp.float32,
        )
        x = x + pos.astype(dtype)
        for i in range(cfg.num_layers):
            x = ViTBlock(cfg, name=f"block_{i}")(x)
        if cfg.norm_impl == "fused":
            x = LayerNorm(dtype=dtype, name="ln_final")(x)
        else:
            x = nn.LayerNorm(dtype=dtype, name="ln_final")(x)
        return nn.Dense(cfg.num_classes, dtype=jnp.float32, name="head")(x[:, 0])


# Megatron-style TP: qkv/up split output features over `tensor`,
# o/down split input features → one psum after attn, one after mlp.
VIT_PARTITION_RULES = (
    PartitionRule(r"attn/(q|k|v)/kernel$", (None, "tensor", None)),
    PartitionRule(r"attn/o/kernel$", ("tensor", None, None)),
    PartitionRule(r"mlp/up/kernel$", (None, "tensor")),
    PartitionRule(r"mlp/down/kernel$", ("tensor", None)),
    PartitionRule(r"patch_embed/kernel$", (None, None, None, "tensor")),
)
