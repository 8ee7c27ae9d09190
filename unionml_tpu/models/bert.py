"""BERT-style encoder — the remote fine-tune config (BASELINE.json
config #4, "BERT-base fine-tune via remote backend on TPU VM slice").

Encoder with learned positions, GELU MLP, post-LN blocks; heads for
sequence classification (fine-tune) and masked-LM (pretrain parity).
Padding is handled with an attention bias built from the input mask —
static shapes throughout so XLA compiles one program per bucket.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp
from flax import linen as nn

from unionml_tpu.models.layers import MlpBlock
from unionml_tpu.ops.attention import mha_reference
from unionml_tpu.parallel.sharding import PartitionRule


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_len: int = 512
    num_types: int = 2
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    num_classes: int = 2  # classification head width
    attn_impl: str = "xla"  # "fused" only when attention_mask is None
    # HF BERT checkpoints use erf GELU; the default tanh approximation is
    # one transcendental cheaper. Checkpoint loaders set True
    # (models/convert.py) for faithful pretrained inference.
    gelu_exact: bool = False
    dtype: str = "bfloat16"

    @staticmethod
    def base(num_classes: int = 2) -> "BertConfig":
        return BertConfig(num_classes=num_classes)

    @staticmethod
    def tiny(vocab_size: int = 1024, num_classes: int = 2) -> "BertConfig":
        return BertConfig(
            vocab_size=vocab_size, max_len=128, hidden_dim=64,
            num_layers=2, num_heads=4, mlp_dim=128, num_classes=num_classes,
        )


class BertBlock(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, bias: Optional[jnp.ndarray]) -> jnp.ndarray:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        head_dim = cfg.hidden_dim // cfg.num_heads
        from unionml_tpu.models.layers import ATTN_IMPLS, merged_dot_general

        # products of width heads * head_dim, as in layers.Attention: what
        # lies between the projections and a fused kernel stays lane-dense
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            features=feats, axis=-1, dtype=dtype, name=name,
            dot_general=merged_dot_general,
        )
        q = dense((cfg.num_heads, head_dim), "attn_q")(x)
        k = dense((cfg.num_heads, head_dim), "attn_k")(x)
        v = dense((cfg.num_heads, head_dim), "attn_v")(x)

        # BERT has no sequence mesh axis: the sequence-parallel impls can
        # never work here
        supported = tuple(
            i for i in ATTN_IMPLS if i not in ("ring", "ring_flash", "ulysses")
        )
        if cfg.attn_impl not in supported:
            raise ValueError(
                f"unknown attention impl {cfg.attn_impl!r}; use one of {supported}"
            )
        if bias is not None:
            # only the XLA reference takes an additive mask bias (padded
            # batches); other impls would silently ignore the padding
            attn = mha_reference(q, k, v, bias=bias)
        else:
            from unionml_tpu.models.layers import _run_attention

            attn = _run_attention(
                q, k, v, impl=cfg.attn_impl, causal=False, sequence_axis=None
            )
        attn = nn.DenseGeneral(
            features=cfg.hidden_dim, axis=(-2, -1), dtype=dtype, name="attn_o",
            dot_general=merged_dot_general,
        )(attn)
        x = nn.LayerNorm(dtype=dtype, name="ln1")(x + attn)
        h = MlpBlock(
            hidden_dim=cfg.mlp_dim, gelu_approximate=not cfg.gelu_exact,
            dtype=dtype, name="mlp",
        )(x)
        return nn.LayerNorm(dtype=dtype, name="ln2")(x + h)


class BertEncoder(nn.Module):
    config: BertConfig = field(default_factory=BertConfig)

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray,
        *,
        attention_mask: Optional[jnp.ndarray] = None,
        token_type_ids: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        seq = input_ids.shape[1]
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_dim, dtype=dtype, name="tok_embed")
        x = embed(input_ids)
        x = x + nn.Embed(cfg.max_len, cfg.hidden_dim, dtype=dtype, name="pos_embed")(
            jnp.arange(seq)[None, :]
        )
        if token_type_ids is not None:
            x = x + nn.Embed(cfg.num_types, cfg.hidden_dim, dtype=dtype, name="type_embed")(
                token_type_ids
            )
        x = nn.LayerNorm(dtype=dtype, name="ln_embed")(x)
        bias = None
        if attention_mask is not None:
            bias = jnp.where(attention_mask[:, None, None, :].astype(bool), 0.0, -1e30)
        for i in range(cfg.num_layers):
            x = BertBlock(cfg, name=f"block_{i}")(x, bias)
        return x


class BertClassifier(nn.Module):
    """[CLS]-pooled sequence classification (the fine-tune config)."""

    config: BertConfig = field(default_factory=BertConfig)

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, token_type_ids=None):
        x = BertEncoder(self.config, name="encoder")(
            input_ids, attention_mask=attention_mask, token_type_ids=token_type_ids
        )
        pooled = nn.tanh(nn.Dense(self.config.hidden_dim, dtype=jnp.float32, name="pooler")(
            x[:, 0].astype(jnp.float32)
        ))
        return nn.Dense(self.config.num_classes, dtype=jnp.float32, name="head")(pooled)


class BertMlm(nn.Module):
    """Masked-LM head over the encoder (pretraining parity)."""

    config: BertConfig = field(default_factory=BertConfig)

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None):
        cfg = self.config
        x = BertEncoder(cfg, name="encoder")(input_ids, attention_mask=attention_mask)
        x = nn.gelu(nn.Dense(cfg.hidden_dim, dtype=jnp.float32, name="mlm_dense")(
            x.astype(jnp.float32)
        ), approximate=True)
        x = nn.LayerNorm(name="mlm_ln")(x)
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32, name="mlm_head")(x)


def mlm_step(module, *, ignore_id: int = -100, accumulate_steps: int = 1):
    """Masked-LM training step over padded corpora.

    ``batch = (inputs, labels, attention_mask)``: unlike the bare
    ``lm_step(BertMlm(cfg))`` composition (fine for fixed-length
    batches), this passes the padding mask through to the encoder so
    real tokens never attend pad positions. ``accumulate_steps > 1``
    adds gradient accumulation over a leading microbatch axis.
    """
    import jax

    from unionml_tpu.models.train import (
        _bind_frozen,
        accumulated_value_and_grad,
        masked_cross_entropy,
    )

    def loss_fn(params, microbatch):
        inputs, labels, attention_mask = microbatch
        logits = module.apply(
            {"params": params}, inputs, attention_mask=attention_mask
        )
        loss = masked_cross_entropy(logits, labels, ignore_id=ignore_id)
        return loss, {"z": jnp.float32(0.0)}

    def step(state, batch):
        bound = _bind_frozen(loss_fn, state)
        if accumulate_steps > 1:
            (loss, _), grads = accumulated_value_and_grad(
                bound, state.params, batch
            )
        else:
            (loss, _), grads = jax.value_and_grad(bound, has_aux=True)(
                state.params, batch
            )
        state = state.apply_gradients(grads=grads)
        return state, {"loss": loss, "perplexity": jnp.exp(loss)}

    return step


def make_mlm_batch(
    tokens,
    *,
    mask_id: int,
    vocab_size: int,
    rng,
    mask_prob: float = 0.15,
    special_ids: tuple = (0,),
    ignore_id: int = -100,
):
    """BERT masking rule over a token batch: returns ``(inputs, labels)``.

    15% of non-special positions are selected; of those 80% become
    ``mask_id``, 10% a random token, 10% stay unchanged. ``labels``
    carry the original ids at selected positions and ``ignore_id``
    elsewhere — exactly the ``(inputs, labels)`` tuple contract of
    :func:`unionml_tpu.models.train.lm_step`, so MLM pretraining is
    ``lm_step(BertMlm(cfg))`` over these batches. Host-side numpy (runs
    in the data path, not the compiled step); ``rng`` is a
    ``numpy.random.Generator``.
    """
    import numpy as np

    # signed dtype: with uint token arrays (typical tokenized corpora),
    # ignore_id=-100 would wrap to a huge in-range positive and every
    # position would be supervised with a garbage label
    tokens = np.asarray(tokens).astype(np.int64)
    maskable = ~np.isin(tokens, np.asarray(special_ids))
    selected = (rng.random(tokens.shape) < mask_prob) & maskable
    labels = np.where(selected, tokens, ignore_id)
    roll = rng.random(tokens.shape)
    inputs = tokens.copy()
    inputs[selected & (roll < 0.8)] = mask_id
    random_slots = selected & (roll >= 0.8) & (roll < 0.9)
    inputs[random_slots] = rng.integers(0, vocab_size, size=int(random_slots.sum()))
    return inputs, labels


BERT_PARTITION_RULES = (
    PartitionRule(r"attn_(q|k|v)/kernel$", (None, "tensor", None)),
    PartitionRule(r"attn_o/kernel$", ("tensor", None, None)),
    PartitionRule(r"mlp/up/kernel$", (None, "tensor")),
    PartitionRule(r"mlp/down/kernel$", ("tensor", None)),
    PartitionRule(r"tok_embed/embedding$", (None, "tensor")),
    PartitionRule(r"mlm_head/kernel$", (None, "tensor")),
)
