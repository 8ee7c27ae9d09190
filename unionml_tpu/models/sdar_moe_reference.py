"""SDAR's mixture-of-experts decoder (``sdar_moe``), plain: the forward pass
and the generation loop in ``jax.numpy`` and float32.

No cache, no kernels: dense ``[heads, S, S]`` scores under the
**block-causal mask** (position ``s`` is visible to ``t`` iff ``s // Bk <=
t // Bk``: causal across blocks, bidirectional inside one), the experts in
a Python loop with every expert run on every token and the unrouted
products weighted zero. It follows the published config
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json).
Assumed, as the module's docstring lists: per-head RMSNorm of queries and
keys; rotary with half-split pairs on plain positions; position ``t``'s
logits predict the token *at* ``t`` (no shift by one); the generation
settings (the config gives none).

:func:`generate` is the generation loop as it reads: block after block,
forward after forward, every forward over the whole sequence so far. The
``floor(P / Bk)`` whole blocks of a prompt are final; the ``P mod Bk``
tokens of a trailing partial block open the first generated block as
decided entries. While an asked entry of the open block is undecided, one
forward runs the sequence with the mask token at the undecided entries;
each undecided asked entry's candidate is the argmax of its logits and its
confidence the candidate's softmax probability; the ``Bk / T`` most
confident are decided (ties towards the lower position; under
``low_confidence_dynamic`` every one over the threshold where at least
that many are). Entries past the asked length are never decided: they
stay the mask token.

Parameters come as the nested dict :class:`~unionml_tpu.models.sdar_moe.SdarMoe`
uses, by leaf name; int8 leaves (``kernel_q`` with ``scale``, ``w_*_q``
with ``w_*_scale``) are dequantised here. ``cfg`` is the dict of published
keys with the ``generation`` group (``SdarMoeConfig.to_hf()``). Callers set
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [S, H, D] rotated by ``positions`` [S]; the pairs are (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
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


def visible(positions, block_length: int, mask: str = "block_causal"):
    """bool [S, S]: whether the query at ``positions[t]`` sees the key at
    ``positions[s]``. ``"causal"`` is what the model is not."""
    q, k = positions[:, None], positions[None, :]
    if mask == "causal":
        return k <= q
    return k // block_length <= q // block_length


def attention(x, p: dict, cfg: dict, positions, mask: str = "block_causal"):
    """x [S, D] -> [S, D]: one sequence."""
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s, d = x.shape
    q = _rms_norm(_mm(x, _weight(p["q"], d)).reshape(s, heads, hd), p["q_norm"]["scale"], eps)
    k = _rms_norm(_mm(x, _weight(p["k"], d)).reshape(s, kv_heads, hd), p["k_norm"]["scale"], eps)
    v = _mm(x, _weight(p["v"], d)).reshape(s, kv_heads, hd)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)     # head h reads key head h // group
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / jnp.sqrt(float(hd))
    sc = jnp.where(visible(positions, cfg["generation"]["block_length"], mask)[None], sc, -1e30)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision="highest")
    return _mm(o.reshape(s, heads * hd), _weight(p["o"], heads * hd))


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


def layer(x, blk: dict, cfg: dict, positions, mask: str = "block_causal"):
    """One block on x [S, D]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, blk["attn_norm"]["scale"], eps), blk["attn"], cfg, positions, mask)
    return x + mixture(_rms_norm(x, blk["mlp_norm"]["scale"], eps), blk["moe"], cfg)


def forward(params, tokens, cfg: dict, *, mask: str = "block_causal"):
    """tokens [B, S] -> logits [B, S, vocab] float32 at positions
    ``arange(S)``: row ``t`` predicts the token at ``t``."""

    def one(seq):
        pos = jnp.arange(seq.shape[0])
        x = params["embed"]["embedding"].astype(jnp.float32)[seq]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, params[f"block_{i}"], cfg, pos, mask)
        x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
        return _mm(x, _weight(params["lm_head"], x.shape[-1]))

    return jnp.stack([one(seq) for seq in jnp.asarray(tokens)])


def decide(confidence, candidates, gen: dict):
    """bool [Bk]: the candidate entries one forward decides."""
    n = gen["block_length"] // gen["denoising_steps"]
    conf = np.where(candidates, np.asarray(confidence, np.float32), -1.0)
    if gen["remasking_strategy"] == "low_confidence_dynamic":
        over = candidates & (conf > np.float32(gen["confidence_threshold"]))
        if over.sum() >= n:
            return over
    # the n largest, ties towards the lower position
    order = sorted(np.flatnonzero(candidates), key=lambda j: (-conf[j], j))
    chosen = np.zeros_like(candidates)
    chosen[order[:n]] = True
    return chosen


def generate(params, prompt, cfg: dict, max_new_tokens: int, *, return_logits: bool = False):
    """The generation loop for one ``prompt`` (a list of ids). Returns
    ``(tokens, decided_at)``: the ``max_new_tokens`` generated ids and, for
    each, the forward of its block (0, 1, ...) that decided it; with
    ``return_logits`` also a list with one entry a forward, ``(block start,
    undecided asked entries, their logits [n, vocab])``."""
    gen = cfg["generation"]
    bk, mask_id = gen["block_length"], gen["mask_token_id"]
    prompt = [int(t) for t in prompt]
    stop = len(prompt) + max_new_tokens
    final = prompt[: len(prompt) // bk * bk]      # the whole blocks so far: their tokens are final
    held = prompt[len(final):]                    # a partial block's tokens open the next block
    tokens, decided_at, trace = [], [], []
    while len(final) < stop:
        start = len(final)
        block = np.asarray(held + [mask_id] * (bk - len(held)), np.int64)
        undecided = np.arange(bk) >= len(held)
        asked = start + np.arange(bk) < stop
        at = np.zeros(bk, np.int64)
        forwards = 0
        while (undecided & asked).any():
            seq = jnp.asarray([final + [int(t) for t in np.where(undecided, mask_id, block)]])
            logits = np.asarray(forward(params, seq, cfg)[0, start:])
            cand = undecided & asked
            if return_logits:
                trace.append((start, np.flatnonzero(cand), logits[cand]))
            choice = logits.argmax(-1)
            z = logits - logits.max(-1, keepdims=True)
            conf = np.exp(z[np.arange(bk), choice]) / np.exp(z).sum(-1)
            now = decide(conf, cand, gen)
            block = np.where(now, choice, block)
            at = np.where(now, forwards, at)
            undecided = undecided & ~now
            forwards += 1
        made = (np.arange(bk) >= len(held)) & asked
        tokens += [int(t) for t in block[made]]
        decided_at += [int(f) for f in at[made]]
        # the block is final: its rows are those of its final tokens
        final, held = final + [int(t) for t in block], []
    if return_logits:
        return tokens, decided_at, trace
    return tokens, decided_at
