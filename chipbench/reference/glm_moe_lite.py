"""GLM-4.7-Flash (``glm4_moe_lite``), plain: the whole forward pass in
``jax.numpy`` and float32.

The benchmark's copy of ``unionml_tpu/models/glm_moe_lite_reference.py`` (it
imports nothing of the program; ``chipbench/tests`` hold the two to the same
numbers), shaped for a check after a served window at the published widths:
``forward_layerwise`` runs one jitted program per kind of layer, visits the
experts by a scan (one expert's float32 weights at a time), takes the
softmax in blocks of queries and the head in blocks of rows, and hands back
a host array, so that beside 9.3 GB of int8 weights the chip never holds
the ``[positions, vocabulary]`` logits (2.85 GB at 4,608 positions).

No cache, no kernels, no absorbed form: every position's keys and values
are expanded from its latent (``[k_nope ; v]_h = RMSNorm(c_kv) W_kvb``, the
rotated ``k_rope`` shared by all heads), full causal softmax attention at
scale ``(qk_nope + qk_rope) ** -0.5``; the router is DeepSeek-V3's
``noaux_tc`` with one group (top-k of ``sigmoid + bias``, weights the
sigmoids normalised and times ``routed_scaling_factor``), every routed row
computed, a shared expert added. It follows the published config
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json);
assumed: rotary dimensions pair ``(i, i + rope / 2)``; the
multi-token-prediction layer is left out.

Parameters come as the nested dict the program's module uses, by leaf name;
int8 leaves are dequantised here, one matrix at a time. ``cfg`` is the dict
of published keys. ``control="int4"`` rounds every wide weight (not the
router, the norms or the embedding) to int4 first. Callers set
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import fake_int4 as _fake_int4

_Q_BLOCK = 512     # queries a softmax block holds: [heads, 512, S] scores
_ROW_BLOCK = 512   # rows a block of the head holds: [512, vocabulary] logits


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


def _matrix(values, scale, control=None):
    """A weight as float32 [in, out]: ``values`` alone, or int8 ``values``
    times the per-channel ``scale``."""
    w = values.astype(jnp.float32)
    if scale is not None:
        w = w * scale
    return _fake_int4(w) if control == "int4" else w


def _weight(p: dict, control=None):
    if "kernel_q" in p:
        return _matrix(p["kernel_q"], p["scale"], control)
    return _matrix(p["kernel"], None, control)


def _mm(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def attention(x, p: dict, cfg: dict, control=None):
    """x [S, D] -> [S, D]: one sequence, expanded latent attention."""
    heads, nope, rope, vd = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
    )
    rank, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = x.shape[0]
    pos = jnp.arange(s)
    q = _mm(_rms_norm(_mm(x, _weight(p["q_a"], control)), p["q_a_norm"]["scale"], eps), _weight(p["q_b"], control))
    q = q.reshape(s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], axis=-1)
    kv = _mm(x, _weight(p["kv_a"], control))
    c_kv = _rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_rope = _rope(kv[:, rank:], pos, theta)
    up = _mm(c_kv, _weight(p["kv_b"], control)).reshape(s, heads, nope + vd)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (s, heads, rope))], axis=-1)
    v = up[..., nope:]
    out = []
    for start in range(0, s, _Q_BLOCK):
        qb = q[start:start + _Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest") / jnp.sqrt(float(nope + rope))
        visible = jnp.arange(s)[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(visible[None], sc, -1e30)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision="highest"))
    o = jnp.concatenate(out, axis=0).reshape(s, heads * vd)
    return _mm(o, _weight(p["o"], control))


def route(x, moe: dict, cfg: dict):
    """x [S, D] -> gate [S, E]: every expert's routing weight for every
    token, zero where the expert was not chosen. Float32 throughout, and
    no control touches it."""
    scores = jax.nn.sigmoid(_mm(x, moe["router_kernel"].astype(jnp.float32)))
    bias = moe["e_score_correction_bias"].astype(jnp.float32).reshape(-1)
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def _experts(moe: dict):
    """The stacked expert weights as scan inputs: (values [E, in, out],
    scale [E, out]) for gate, up and down."""
    out = []
    for name in ("w_gate", "w_up", "w_down"):
        if f"{name}_q" in moe:
            out.append((moe[f"{name}_q"], moe[f"{name}_scale"]))
        else:
            out.append((moe[name], jnp.ones((moe[name].shape[0], moe[name].shape[2]), jnp.float32)))
    return tuple(out)


def mixture(x, blk: dict, cfg: dict, control=None):
    shared = blk["shared_expert"]
    gate = route(x, blk["moe"], cfg)

    def one_expert(acc, xs):
        (gq, gs), (uq, us), (dq, ds), g = xs
        y = _swiglu(x, _matrix(gq, gs, control), _matrix(uq, us, control), _matrix(dq, ds, control))
        return acc + g[:, None] * y, None

    first = _swiglu(x, _weight(shared["gate"], control), _weight(shared["up"], control), _weight(shared["down"], control))
    out, _ = jax.lax.scan(one_expert, first, _experts(blk["moe"]) + (gate.T,))
    return out


def layer(x, blk: dict, dense: bool, cfg: dict, control=None):
    """One block on x [S, D]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, blk["attn_norm"]["scale"], eps), blk["attn"], cfg, control)
    h = _rms_norm(x, blk["mlp_norm"]["scale"], eps)
    if dense:
        m = blk["mlp"]
        return x + _swiglu(h, _weight(m["gate"], control), _weight(m["up"], control), _weight(m["down"], control))
    return x + mixture(h, blk, cfg, control)


def head(params, x, cfg: dict, control=None):
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(x, _weight(params["lm_head"], control))


def forward_layerwise(params, tokens, cfg: dict, control=None):
    """tokens [B, S] -> logits [B, S, vocab] float32, a host array."""
    kinds = {d: jax.jit(lambda x, blk, d=d: layer(x, blk, d, cfg, control)) for d in (True, False)}
    embed = jax.jit(lambda table, seq: table.astype(jnp.float32)[seq])
    last = jax.jit(lambda p, x: head(p, x, cfg, control))
    rest = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    out = []
    for seq in np.asarray(tokens):
        x = embed(params["embed"]["embedding"], jnp.asarray(seq))
        for i in range(cfg["num_hidden_layers"]):
            x = kinds[i < cfg["first_k_dense_replace"]](x, params[f"block_{i}"])
        rows = [np.asarray(last(rest, x[r:r + _ROW_BLOCK])) for r in range(0, x.shape[0], _ROW_BLOCK)]
        out.append(np.concatenate(rows, axis=0))
    return np.stack(out)
