"""GLM-4.7-Flash (``glm4_moe_lite``), plain: the whole forward pass in
``jax.numpy`` and float32.

No cache, no kernels, no absorbed form: every position's keys and values
are expanded from its latent, full causal softmax attention, the experts
in a Python loop with every expert run on every token and the unrouted
products weighted zero. It follows the published config
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json) and
DeepSeek-V3's ``noaux_tc`` router with one group; assumed: rotary
dimensions pair ``(i, i + rope / 2)``; the multi-token-prediction layer is
left out.

Parameters come as the nested dict :class:`~unionml_tpu.models.glm_moe_lite.GlmMoeLite`
uses, by leaf name; int8 leaves (``kernel_q`` with ``scale``, ``w_*_q``
with ``w_*_scale``) are dequantised here. ``cfg`` is the dict of
published keys (``GlmMoeLiteConfig.to_hf()``). Callers set
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [S, ..., D] rotated by ``positions`` [S]; the pairs are (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _weight(p: dict):
    if "kernel_q" in p:
        return p["kernel_q"].astype(jnp.float32) * p["scale"]
    return p["kernel"].astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def attention(x, p: dict, cfg: dict):
    """x [S, D] -> [S, D]: one sequence, expanded latent attention."""
    heads, nope, rope, vd = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
    )
    rank, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], cfg["rope_theta"]
    s = x.shape[0]
    pos = jnp.arange(s)
    q = _mm(_rms_norm(_mm(x, _weight(p["q_a"])), p["q_a_norm"]["scale"], eps), _weight(p["q_b"]))
    q = q.reshape(s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], axis=-1)
    kv = _mm(x, _weight(p["kv_a"]))
    c_kv = _rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_rope = _rope(kv[:, rank:], pos, theta)
    up = _mm(c_kv, _weight(p["kv_b"])).reshape(s, heads, nope + vd)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (s, heads, rope))], axis=-1)
    v = up[..., nope:]
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / jnp.sqrt(float(nope + rope))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -1e30)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision="highest")
    return _mm(o.reshape(s, heads * vd), _weight(p["o"]))


def route(x, moe: dict, cfg: dict):
    """x [S, D] -> gate [S, E]: the routing weight of every expert for every
    token, zero where the expert was not chosen."""
    scores = jax.nn.sigmoid(_mm(x, moe["router_kernel"].astype(jnp.float32)))
    bias = moe["e_score_correction_bias"].astype(jnp.float32).reshape(-1)
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def _expert(moe: dict, name: str, e: int):
    if f"{name}_q" in moe:
        return moe[f"{name}_q"][e].astype(jnp.float32) * moe[f"{name}_scale"][e]
    return moe[name][e].astype(jnp.float32)


def mixture(x, blk: dict, cfg: dict):
    moe, shared = blk["moe"], blk["shared_expert"]
    gate = route(x, moe, cfg)
    out = _swiglu(x, _weight(shared["gate"]), _weight(shared["up"]), _weight(shared["down"]))
    for e in range(cfg["n_routed_experts"]):
        y = _swiglu(x, _expert(moe, "w_gate", e), _expert(moe, "w_up", e), _expert(moe, "w_down", e))
        out = out + gate[:, e:e + 1] * y
    return out


def layer(x, blk: dict, index: int, cfg: dict):
    """One block on x [S, D]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, blk["attn_norm"]["scale"], eps), blk["attn"], cfg)
    h = _rms_norm(x, blk["mlp_norm"]["scale"], eps)
    if index < cfg["first_k_dense_replace"]:
        m = blk["mlp"]
        return x + _swiglu(h, _weight(m["gate"]), _weight(m["up"]), _weight(m["down"]))
    return x + mixture(h, blk, cfg)


def forward(params, tokens, cfg: dict):
    """tokens [B, S] -> logits [B, S, vocab] float32."""

    def one(seq):
        x = params["embed"]["embedding"].astype(jnp.float32)[seq]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, params[f"block_{i}"], i, cfg)
        x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
        return _mm(x, _weight(params["lm_head"]))

    return jnp.stack([one(seq) for seq in tokens])
