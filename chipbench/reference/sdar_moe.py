"""SDAR's mixture-of-experts decoder (``sdar_moe``), plain: the forward pass
in ``jax.numpy`` and float32, shaped for a check after a served window.

The benchmark's copy of ``unionml_tpu/models/sdar_moe_reference.py`` (it
imports nothing of the program; ``chipbench/tests`` hold the two to the same
numbers). The model generates by diffusion over blocks of ``block_length``
positions: attention is **block-causal** (position ``s`` is visible to ``t``
iff ``s // Bk <= t // Bk``), and position ``t``'s logits predict the token
*at* ``t``. A block is decided over several forwards, each over the block's
current state (decided entries as they are, the mask token elsewhere)
against the *final* tokens of every earlier block. :func:`forward_states`
gives every such state's logits at once: one pass over the **clean**
sequence followed by ``T`` **noisy copies** of the generated span, copy ``f``
holding every block as it stood before its forward ``f``; a copy's block
sees the clean blocks before it and itself. At the published widths that is
2,304 + 4 x 260 positions at the most: one jitted program a layer, the
scores and the softmax in blocks of 256 queries, the experts by a scan (one
expert's float32 weights at a time), the head for the copies' rows only, in
blocks of rows, into one host array.

It follows the published config
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json); what
that does not say is listed under ``assumed`` in the configuration's file.
``mask="causal"`` is what the model is not (plain causal masking over each
state): the mechanism's control. ``control="int4"`` rounds every wide weight
(q, k, v, o, the experts, the head; not the router, the norms or the
embedding) to int4 first. Parameters come as the nested dict the program's
module uses, by leaf name; int8 leaves are dequantised here, one matrix at a
time. Callers set ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import fake_int4 as _fake_int4

_Q_BLOCK = 256     # queries a block of scores holds
_ROW_BLOCK = 512   # rows a block of the head holds: [512, vocabulary] logits


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


def _matrix(values, scale, control=None):
    """A weight as float32 [..., in, out]: ``values`` alone, or int8
    ``values`` times the per-channel ``scale``."""
    w = values.astype(jnp.float32)
    if scale is not None:
        w = w * scale
    return _fake_int4(w) if control == "int4" else w


def _weight(p: dict, fan_in: int, control=None):
    if "kernel_q" in p:
        return _matrix(p["kernel_q"].reshape(fan_in, -1), p["scale"].reshape(-1), control)
    return _matrix(p["kernel"].reshape(fan_in, -1), None, control)


def _mm(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def visible(q_pos, q_copy, k_pos, k_copy, block_length: int, mask: str = "block_causal"):
    """bool [Q, K]: whether a query row sees a key row. ``*_copy`` is 0 for
    the clean sequence and ``f + 1`` for noisy copy ``f`` (negative: a
    padding row, which sees itself alone). A clean query sees the clean keys
    of its own and earlier blocks; a copy's query the clean keys of earlier
    blocks and its own copy's keys of its own block. Under ``"causal"``
    (what the model is not) a key after the query's position is hidden too."""
    qb, kb = q_pos[:, None] // block_length, k_pos[None, :] // block_length
    qc, kc = q_copy[:, None], k_copy[None, :]
    clean_keys = (kc == 0) & jnp.where(qc == 0, kb <= qb, kb < qb)
    own_block = (kc == qc) & (qc > 0) & (kb == qb)
    out = clean_keys | own_block
    if mask == "causal":
        out = out & (k_pos[None, :] <= q_pos[:, None])
    padding = (qc < 0) & (k_pos[None, :] == q_pos[:, None]) & (kc == qc)
    return (out & (qc >= 0)) | padding


def attention(x, p: dict, cfg: dict, pos, copy, control=None, mask: str = "block_causal"):
    """x [S, D] -> [S, D]: rows at positions ``pos`` of copies ``copy``."""
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta, bk = cfg["rms_norm_eps"], float(cfg["rope_theta"]), cfg["generation"]["block_length"]
    s, d = x.shape
    q = _rms_norm(_mm(x, _weight(p["q"], d, control)).reshape(s, heads, hd), p["q_norm"]["scale"], eps)
    k = _rms_norm(_mm(x, _weight(p["k"], d, control)).reshape(s, kv_heads, hd), p["k_norm"]["scale"], eps)
    v = _mm(x, _weight(p["v"], d, control)).reshape(s, kv_heads, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(s, kv_heads, heads // kv_heads, hd)     # head h reads key head h // group

    def block(rows):
        """The query rows ``rows`` [Q] against every row."""
        sees = visible(pos[rows], copy[rows], pos, copy, bk, mask)
        sc = jnp.einsum("qhgd,khd->hgqk", q[rows], k, precision="highest") / jnp.sqrt(float(hd))
        sc = jnp.where(sees[None, None], sc, -1e30)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v, precision="highest")

    # one compiled block, visited in turn (rows that are not whole blocks go as one)
    size = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    o = jax.lax.map(block, jnp.arange(s).reshape(s // size, size)).reshape(s, heads * hd)
    return _mm(o, _weight(p["o"], heads * hd, control))


def route(x, moe: dict, cfg: dict):
    """x [S, D] -> gate [S, E]: every expert's routing weight for every
    token, zero where the expert was not chosen. Float32 throughout, and
    no control touches it."""
    probs = jax.nn.softmax(_mm(x, moe["router_kernel"].astype(jnp.float32)), axis=-1)
    picked, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    picked = picked / jnp.maximum(picked.sum(-1, keepdims=True), 1e-9)
    return jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], chosen].set(picked)


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


def mixture(x, moe: dict, cfg: dict, control=None):
    gate = route(x, moe, cfg)

    def one_expert(acc, xs):
        (gq, gs), (uq, us), (dq, ds), g = xs
        y = _swiglu(x, _matrix(gq, gs[None, :], control), _matrix(uq, us[None, :], control),
                    _matrix(dq, ds[None, :], control))
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), _experts(moe) + (gate.T,))
    return out


def layer(x, blk: dict, cfg: dict, pos, copy, control=None, mask: str = "block_causal"):
    """One block on x [S, D]."""
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, blk["attn_norm"]["scale"], eps)
    x = x + attention(h, blk["attn"], cfg, pos, copy, control, mask)
    return x + mixture(_rms_norm(x, blk["mlp_norm"]["scale"], eps), blk["moe"], cfg, control)


def head(params, x, cfg: dict, control=None):
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(x, _weight(params["lm_head"], x.shape[-1], control))


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, control, mask: str):
    """The jitted programs of one (configuration, control, mask): made once,
    so that every request of a check runs the programs the first compiled."""
    cfg = json.loads(cfg_json)
    return (
        jax.jit(lambda table, seq: table.astype(jnp.float32)[seq]),
        jax.jit(lambda x, blk, pos, copy: layer(x, blk, cfg, pos, copy, control, mask)),
        jax.jit(lambda p, x: head(p, x, cfg, control)),
    )


def forward_states(params, clean, copies, start: int, cfg: dict, control=None, *, mask: str = "block_causal",
                   pad_to: int = 0):
    """Logits of every state at once. ``clean`` [L]: the final tokens at
    positions ``0 .. L - 1``; ``copies`` [T, G]: the tokens of noisy copy
    ``f`` at positions ``start .. start + G - 1`` (``start`` and ``G``
    multiples of the block length). Returns float32 ``[T, G, vocab]``, a
    host array: row ``[f, j]`` predicts the token at ``start + j`` from copy
    ``f``'s state of that block, the clean blocks before it and nothing
    else. ``pad_to`` pads the rows (with rows that see themselves alone) to
    that many: with ``clean`` and ``copies`` of one shape too, every request
    of a run takes one compiled program."""
    clean, copies = np.asarray(clean, np.int32), np.asarray(copies, np.int32)
    t, g = copies.shape
    rows = len(clean) + t * g
    total = max(pad_to, rows)
    tokens = np.zeros(total, np.int32)
    pos, copy = np.arange(total, dtype=np.int32), np.full(total, -1, np.int32)
    tokens[:len(clean)], copy[:len(clean)] = clean, 0
    tokens[len(clean):rows] = copies.reshape(-1)
    pos[len(clean):rows] = np.tile(start + np.arange(g, dtype=np.int32), t)
    copy[len(clean):rows] = np.repeat(1 + np.arange(t, dtype=np.int32), g)
    embed, one_layer, last = _programs(json.dumps(cfg, sort_keys=True), control, mask)
    pos_d, copy_d = jnp.asarray(pos), jnp.asarray(copy)
    rest = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    x = embed(params["embed"]["embedding"], jnp.asarray(tokens))
    for i in range(cfg["num_hidden_layers"]):
        x = one_layer(x, params[f"block_{i}"], pos_d, copy_d)
    x = x[len(clean):rows]
    out = np.zeros((t * g, cfg["vocab_size"]), np.float32)
    for r in range(0, t * g, _ROW_BLOCK):
        out[r:r + _ROW_BLOCK] = np.asarray(last(rest, x[r:r + _ROW_BLOCK]))
    return out.reshape(t, g, -1)


def forward(params, tokens, cfg: dict, control=None, *, mask: str = "block_causal"):
    """tokens [S] -> logits [S, vocab]: the clean pass alone (what the tests
    compare with the repo's reference)."""
    tokens = np.asarray(tokens, np.int32)
    pos, copy = jnp.arange(len(tokens)), jnp.zeros(len(tokens), jnp.int32)
    x = params["embed"]["embedding"].astype(jnp.float32)[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, params[f"block_{i}"], cfg, pos, copy, control, mask)
    return np.asarray(head(params, x, cfg, control))
