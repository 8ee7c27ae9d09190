"""Keye-VL-2.0's language model (``KeyeVL2``), plain: the whole forward
pass in ``jax.numpy`` and float32.

No cache, no kernels, no blocks: dense ``[heads, S, S]`` index scores,
``jax.lax.top_k`` per query over the positions ``s <= t``, softmax over the
selected set, the experts in a Python loop with every expert run on every
token and the unrouted products weighted zero. It follows the published
config (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json)
and DeepSeek-V3.2's indexer. Assumed, as the module's docstring lists:
per-head RMSNorm of queries and keys; the indexer reads the block's normed
hidden state; LayerNorm on the indexer's key; plain rotary over the
indexer's pairs on the temporal position; ReLU in the index score; the
index score's two constant scales dropped (positive: they change no
selection); exact top-k, ties towards the lower position. Departures from
the equations as written: none; the selection is held as a mask
(``top_k``'s positions scattered into it), not as a gathered set.

Parameters come as the nested dict :class:`~unionml_tpu.models.keye_vl_moe.KeyeVLMoe`
uses, by leaf name; int8 leaves (``kernel_q`` with ``scale``, ``w_*_q``
with ``w_*_scale``) are dequantised here. ``cfg`` is the dict of published
keys (``KeyeVLMoeConfig.to_hf()``). Callers set
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, positions, theta, sections=None):
    """x [S, H, D] rotated; the pairs are (i, i + D/2). ``positions`` [S], or
    [A, S] with frequency pair ``i`` turning by the axis ``sections`` gives it."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 2:
        axis_of = jnp.concatenate([jnp.full((n,), a) for a, n in enumerate(sections)])
        pos = positions.astype(jnp.float32)[axis_of, :].T          # [S, half]
    else:
        pos = positions.astype(jnp.float32)[:, None]
    ang = (pos * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _weight(p: dict, fan_in: int):
    """A projection's weight as float32 [fan_in, out]."""
    if "kernel_q" in p:
        return (p["kernel_q"].astype(jnp.float32) * p["scale"]).reshape(fan_in, -1)
    return p["kernel"].astype(jnp.float32).reshape(fan_in, -1)


def _mm(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def index_scores(x, p: dict, cfg: dict, temporal):
    """x [S, D] -> I [S, S]: ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``."""
    sa, d = cfg["sa_config"], x.shape[-1]
    heads, width, theta = sa["indexer_num_heads"], sa["indexer_head_dim"], float(cfg["rope_theta"])
    s = x.shape[0]
    q = _rope(_mm(x, _weight(p["index_q"], d)).reshape(s, heads, width), temporal, theta)
    k = _layer_norm(_mm(x, _weight(p["index_k"], d)), p["index_k_norm"], cfg["rms_norm_eps"])
    k = _rope(k[:, None, :], temporal, theta)[:, 0]
    w = _mm(x, _weight(p["index_w"], d))                            # [S, heads]
    dots = jnp.einsum("qhd,kd->hqk", q, k, precision="highest")
    return jnp.einsum("hqk,qh->qk", jax.nn.relu(dots), w, precision="highest")


def selected(scores, topk: int):
    """bool [S, S]: for each query ``t`` the ``topk`` positions ``s <= t`` of
    largest score (``jax.lax.top_k``: ties towards the lower position), all
    of them while ``t < topk``."""
    s = scores.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    if s <= topk:
        return causal
    _, picked = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    mask = jnp.zeros((s, s), bool).at[jnp.arange(s)[:, None], picked].set(True)
    return mask & causal


def attention(x, p: dict, cfg: dict, positions, *, select: bool = True):
    """x [S, D] -> ([S, D], selected [S, S]): one sequence. ``positions`` is
    [S] or [3, S]. ``select=False`` attends every ``s <= t`` (what the model
    is not)."""
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sections = cfg["rope_scaling"]["mrope_section"]
    s, d = x.shape
    q = _rms_norm(_mm(x, _weight(p["q"], d)).reshape(s, heads, hd), p["q_norm"]["scale"], eps)
    k = _rms_norm(_mm(x, _weight(p["k"], d)).reshape(s, kv_heads, hd), p["k_norm"]["scale"], eps)
    v = _mm(x, _weight(p["v"], d)).reshape(s, kv_heads, hd)
    q, k = _rope(q, positions, theta, sections), _rope(k, positions, theta, sections)
    temporal = positions if positions.ndim == 1 else positions[0]
    mask = jnp.tril(jnp.ones((s, s), bool))
    if select:
        mask = selected(index_scores(x, p, cfg, temporal), cfg["sa_config"]["topk"])
    k = jnp.repeat(k, heads // kv_heads, axis=1)     # head h reads key head h // group
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / jnp.sqrt(float(hd))
    sc = jnp.where(mask[None], sc, -1e30)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision="highest")
    return _mm(o.reshape(s, heads * hd), _weight(p["o"], heads * hd)), mask


def route(x, moe: dict, cfg: dict):
    """x [S, D] -> gate [S, E]: softmax over the experts in float32, the
    ``num_experts_per_tok`` largest renormalised to sum 1, zero elsewhere."""
    probs = jax.nn.softmax(_mm(x, moe["router_kernel"].astype(jnp.float32)), axis=-1)
    picked, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    picked = picked / jnp.maximum(picked.sum(-1, keepdims=True), 1e-9)
    return jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def _expert(moe: dict, name: str, e: int):
    if f"{name}_q" in moe:
        return moe[f"{name}_q"][e].astype(jnp.float32) * moe[f"{name}_scale"][e]
    return moe[name][e].astype(jnp.float32)


def mixture(x, moe: dict, cfg: dict):
    gate = route(x, moe, cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        y = _swiglu(x, _expert(moe, "w_gate", e), _expert(moe, "w_up", e), _expert(moe, "w_down", e))
        out = out + gate[:, e:e + 1] * y
    return out


def layer(x, blk: dict, cfg: dict, positions, *, select: bool = True):
    """One block on x [S, D] -> (x, selected [S, S])."""
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, blk["attn_norm"]["scale"], eps)
    a, mask = attention(h, blk["attn"], cfg, positions, select=select)
    x = x + a
    return x + mixture(_rms_norm(x, blk["mlp_norm"]["scale"], eps), blk["moe"], cfg), mask


def forward(params, tokens, cfg: dict, positions=None, *, select: bool = True, return_selected: bool = False):
    """tokens [B, S] -> logits [B, S, vocab] float32; ``positions`` [B, S] or
    [3, B, S] (default ``arange``). With ``return_selected`` also the
    selection of every layer, bool [B, layers, S, S]."""

    def one(seq, pos):
        x = params["embed"]["embedding"].astype(jnp.float32)[seq]
        masks = []
        for i in range(cfg["num_hidden_layers"]):
            x, mask = layer(x, params[f"block_{i}"], cfg, pos, select=select)
            masks.append(mask)
        x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
        return _mm(x, _weight(params["lm_head"], x.shape[-1])), jnp.stack(masks)

    tokens = jnp.asarray(tokens)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None, :], tokens.shape)
    positions = jnp.asarray(positions)
    per_seq = [positions[b] if positions.ndim == 2 else positions[:, b] for b in range(tokens.shape[0])]
    outs = [one(seq, pos) for seq, pos in zip(tokens, per_seq)]
    logits = jnp.stack([o[0] for o in outs])
    return (logits, jnp.stack([o[1] for o in outs])) if return_selected else logits
