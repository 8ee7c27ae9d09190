"""Decoder-only transformer of the Mistral / Mixtral kind, plain.

Mistral 7B (Jiang et al. 2023) and Mixtral of Experts (Jiang et al. 2024):
RMSNorm, rotary positions in the half-split ("rotate_half") layout of the
published checkpoints, grouped-query causal attention, SwiGLU MLP or a
top-k mixture of SwiGLU experts (softmax over all experts, top-k, weights
renormalised to sum to one), untied head. No sliding window: both configs
publish ``sliding_window: null``.
Parameters come as the nested dict of arrays the benchmark made, float or
int8 with per-channel scales. int8 weights are dequantised here one matrix
at a time, and the experts are visited by a scan, so the float32 copy of a
12 GB tree never exists at once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import make_mm, softmax_ce


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [B, S, H, D]; the pairs are (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _weight(p: dict, shape=None):
    """A dense weight as float32 [in, out]: ``kernel`` or ``kernel_q * scale``."""
    if "kernel_q" in p:
        return p["kernel_q"].astype(jnp.float32) * p["scale"]
    w = p["kernel"].astype(jnp.float32)
    return w.reshape(shape) if shape is not None else w


def _experts(moe: dict):
    """The stacked expert weights as scan inputs: (gate, up, down) triples of
    (values [E, in, out], scale [E, out] or None)."""
    out = []
    for name in ("w_gate", "w_up", "w_down"):
        if f"{name}_q" in moe:
            out.append((moe[f"{name}_q"], moe[f"{name}_scale"]))
        else:
            out.append((moe[name], jnp.ones((moe[name].shape[0], moe[name].shape[2]), jnp.float32)))
    return tuple(out)


def embed(params, tokens):
    return params["embed"]["embedding"].astype(jnp.float32)[tokens]


def layer(x, blk: dict, cfg: dict, control=None):
    """One block on x [B, S, D] -> (x, load-balancing term)."""
    mm = make_mm(control)
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    experts, topk = cfg.get("num_local_experts", 0), cfg.get("num_experts_per_tok", 0)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    a = blk["attn"]
    y = _rms_norm(x, blk["attn_norm"]["scale"], eps)
    q = mm(y, _weight(a["q"], (d, heads * hd))).reshape(b, s, heads, hd)
    k = mm(y, _weight(a["k"], (d, kv * hd))).reshape(b, s, kv, hd)
    v = mm(y, _weight(a["v"], (d, kv * hd))).reshape(b, s, kv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / jnp.sqrt(float(hd))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, precision="highest")
    x = x + mm(o.reshape(b, s, heads * hd), _weight(a["o"], (heads * hd, d)))
    y = _rms_norm(x, blk["mlp_norm"]["scale"], eps)
    if not experts:
        m = blk["mlp"]
        h = jax.nn.silu(mm(y, _weight(m["gate"]))) * mm(y, _weight(m["up"]))
        return x + mm(h, _weight(m["down"])), jnp.float32(0.0)
    moe = blk["moe"]
    flat = y.reshape(b * s, d)
    probs = jax.nn.softmax(
        jnp.matmul(flat, moe["router_kernel"].astype(jnp.float32), precision="highest"), axis=-1
    )
    top_w, top_i = jax.lax.top_k(probs, topk)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(b * s)[:, None], top_i].set(top_w)

    def one_expert(acc, xs):
        (gq, gs), (uq, us), (dq, ds), g = xs
        h = jax.nn.silu(mm(flat, gq.astype(jnp.float32) * gs)) * mm(flat, uq.astype(jnp.float32) * us)
        return acc + g[:, None] * mm(h, dq.astype(jnp.float32) * ds), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(flat), _experts(moe) + (gate.T,))
    first = jax.nn.one_hot(top_i[:, 0], experts, dtype=jnp.float32)
    aux = experts * jnp.sum(jnp.mean(first, axis=0) * jnp.mean(probs, axis=0))
    return x + out.reshape(b, s, d), aux


def head(params, x, cfg: dict, control=None):
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return make_mm(control)(x, _weight(params["lm_head"]))


def forward(params, tokens, cfg: dict, control=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, mean load-balancing term)."""
    x = embed(params, tokens)
    aux = []
    # rematerialised per block, for the float32 activations' sake; same numbers
    block = jax.checkpoint(lambda x, blk: layer(x, blk, cfg, control))
    for i in range(cfg["num_hidden_layers"]):
        x, a = block(x, params[f"block_{i}"])
        aux.append(a)
    return head(params, x, cfg, control), sum(aux) / len(aux)


def forward_layerwise(params, tokens, cfg: dict, control=None):
    """The same logits, one jitted program per kind of piece: every layer has
    the same shapes, so a deep model compiles once and holds one layer's
    float32 weights at a time. For checks after a served window."""
    lay = jax.jit(lambda x, blk: layer(x, blk, cfg, control)[0])
    x = jax.jit(embed)(params, tokens)
    for i in range(cfg["num_hidden_layers"]):
        x = lay(x, params[f"block_{i}"])
    rest = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    return jax.jit(lambda p, x: head(p, x, cfg, control))(rest, x)


def loss(params, batch, cfg: dict, control=None):
    """(objective, reported loss) of next-token prediction over [B, S] token
    ids: the program's ``lm_step`` differentiates cross entropy plus 0.01
    times the load-balancing term, and reports the cross entropy."""
    logits, aux = forward(params, batch[:, :-1], cfg, control)
    ce = softmax_ce(logits, batch[:, 1:])
    return ce + 0.01 * aux, ce
