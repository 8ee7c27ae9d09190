"""ViT (Dosovitskiy et al. 2020, "An Image is Worth 16x16 Words"), plain.

Pre-norm encoder: patch embedding, class token, learned positions, blocks of
LayerNorm -> multi-head attention -> residual, LayerNorm -> MLP(GELU) ->
residual, a final LayerNorm and a linear head on the class token.
Departures that the program makes and this follows: GELU in its tanh form,
no biases on q, k, v, o, LayerNorm epsilon 1e-6.
Parameters come as the nested dict of arrays the benchmark made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import make_mm, softmax_ce


def _layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def forward(params, images, cfg: dict, control=None):
    """images [B, H, W, C] -> logits [B, classes], float32."""
    mm = make_mm(control)
    p, d, heads = cfg["patch_size"], cfg["hidden_size"], cfg["num_attention_heads"]
    b, h, w, c = images.shape
    x = images.astype(jnp.float32).reshape(b, h // p, p, w // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p), p * p * c)
    pe = params["patch_embed"]
    x = mm(x, pe["kernel"].reshape(p * p * c, d)) + pe["bias"]
    cls = jnp.broadcast_to(params["cls"].astype(jnp.float32), (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"]
    hd = d // heads

    # rematerialised per block: the float32 activations of a full batch
    # would not fit beside the program's memory otherwise; same numbers
    @jax.checkpoint
    def block(x, blk):
        y = _layer_norm(x, blk["ln1"])
        a = blk["attn"]
        q = mm(y, a["q"]["kernel"].reshape(d, d)).reshape(b, -1, heads, hd)
        k = mm(y, a["k"]["kernel"].reshape(d, d)).reshape(b, -1, heads, hd)
        v = mm(y, a["v"]["kernel"].reshape(d, d)).reshape(b, -1, heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / jnp.sqrt(float(hd))
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision="highest")
        x = x + mm(o.reshape(b, -1, d), a["o"]["kernel"].reshape(d, d))
        y = _layer_norm(x, blk["ln2"])
        m = blk["mlp"]
        y = jax.nn.gelu(mm(y, m["up"]["kernel"]) + m["up"]["bias"], approximate=True)
        return x + mm(y, m["down"]["kernel"]) + m["down"]["bias"]

    for i in range(cfg["num_hidden_layers"]):
        x = block(x, params[f"block_{i}"])
    x = _layer_norm(x, params["ln_final"])
    return mm(x[:, 0], params["head"]["kernel"]) + params["head"]["bias"]


def loss(params, batch, cfg: dict, control=None):
    """(objective, reported loss): the same number for a classifier."""
    images, labels = batch
    ce = softmax_ce(forward(params, images, cfg, control), labels)
    return ce, ce
