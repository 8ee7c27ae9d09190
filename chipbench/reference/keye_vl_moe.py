"""Keye-VL-2.0's language model (``KeyeVL2``), plain: the whole forward pass
in ``jax.numpy`` and float32.

The benchmark's copy of ``unionml_tpu/models/keye_vl_moe_reference.py`` (it
imports nothing of the program; ``chipbench/tests`` hold the two to the same
numbers), shaped for a check after a served window at the published widths:
``forward_layerwise`` runs one jitted program a layer, takes the index
scores, the selection and the softmax in blocks of 256 queries (the
``[16, 256, S]`` and ``[32, 256, S]`` float32 arrays of a block are 0.3 and
0.6 GB at 17,408 positions; whole they would be 19 and 39: ``calibrate.py``
runs this beside the engine's 13.1 GB), visits the
experts by a scan (one expert's float32 weights at a time), takes the head
in blocks of rows, and writes the logits into one host array (10.6 GB at
17,408 positions: never on the chip). Where the configuration's ``correct``
group says ``"reference_logits": "served_tail"``, the head is taken only for
the rows a served stream can be read from, ``serving.max_new_tokens + 1``
positions before the last token that is not zero and ``max_new_tokens``
behind it (a prompt holds no token 0, ``chipbench/traffic.py``; a stream may
hold any number of them, at its end too: its rows lie in that span whatever
it holds), and the other rows stay the zeros the array was made of, which
the host never backs with memory: the judge reads a request's served rows, and three such arrays of a
control's check (the run's, the control's, the next request's) are 32 GB of
a 40 GiB host otherwise (my chip run, PR 40: the check was ended there).

No cache, no kernels: every query scores every position ``s <= t`` with the
indexer (``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``), attends the
``sa_config.topk`` of largest score (all while fewer are visible) by
softmax at scale ``head_dim ** -0.5``; the router is a float32 softmax whose
``num_experts_per_tok`` largest are renormalised, every routed row computed,
no shared expert. The selection is exact and cut as ``jax.lax.top_k`` cuts
it (ties towards the lower position): the ``topk``-th largest score of a
query is read from ``top_k``'s values, positions above it are in, and of the
positions equal to it the first few. It follows the published config
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json);
what that does not say is listed under ``assumed`` in the configuration's
file. Text positions only (one axis): the cell's traffic is text, and with
three equal axes the multi-axis rotary is the plain one.

Parameters come as the nested dict the program's module uses, by leaf name;
int8 leaves are dequantised here, one matrix at a time. ``cfg`` is the dict
of published keys. ``control="int4"`` rounds every wide weight (q, k, v, o,
the indexer's query projection, the experts, the head; not the router, the
indexer's key and weight projections, the norms or the embedding) to int4
first. Callers set ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import fake_int4 as _fake_int4

_Q_BLOCK = 256     # queries a block of scores holds
_ROW_BLOCK = 512   # rows a block of the head holds: [512, vocabulary] logits


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


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
    return p["kernel"].astype(jnp.float32).reshape(fan_in, -1)   # a float projection: no control rounds it


def _mm(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def selected(scores, visible, topk: int):
    """bool like ``scores`` [Q, S]: per row the ``topk`` visible entries of
    largest score, ties towards the lower position."""
    masked = jnp.where(visible, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, topk)[0][:, -1:]
    above, ties = masked > kth, masked == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))) & visible


def attention(x, p: dict, cfg: dict, control=None, *, select: bool = True):
    """x [S, D] -> [S, D]: one sequence at positions ``arange(S)``."""
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa = cfg["sa_config"]
    ih, iw, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s, d = x.shape
    pos = jnp.arange(s)
    q = _rms_norm(_mm(x, _weight(p["q"], d, control)).reshape(s, heads, hd), p["q_norm"]["scale"], eps)
    k = _rms_norm(_mm(x, _weight(p["k"], d, control)).reshape(s, kv_heads, hd), p["k_norm"]["scale"], eps)
    v = _mm(x, _weight(p["v"], d, control)).reshape(s, kv_heads, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(s, kv_heads, heads // kv_heads, hd)     # head h reads key head h // group
    sparse = select and s > topk
    if sparse:
        iq = _rope(_mm(x, _weight(p["index_q"], d, control)).reshape(s, ih, iw), pos, theta)
        ik = _layer_norm(_mm(x, _weight(p["index_k"], d)), p["index_k_norm"], eps)
        ik = _rope(ik[:, None, :], pos, theta)[:, 0]
        weight = _mm(x, _weight(p["index_w"], d))

    def block(rows):
        """The queries ``rows`` [Q] (their positions) against every position."""
        visible = jnp.arange(s)[None, :] <= rows[:, None]
        if sparse:
            dots = jnp.einsum("qhd,kd->hqk", iq[rows], ik, precision="highest")
            scores = jnp.einsum("hqk,qh->qk", jax.nn.relu(dots), weight[rows], precision="highest")
            visible = selected(scores, visible, topk)
        sc = jnp.einsum("qhgd,khd->hgqk", q[rows], k, precision="highest") / jnp.sqrt(float(hd))
        sc = jnp.where(visible[None, None], sc, -1e30)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v, precision="highest")

    # one compiled block, visited in turn (a sequence that is not whole
    # blocks goes as one)
    size = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    o = jax.lax.map(block, pos.reshape(s // size, size)).reshape(s, heads * hd)
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


def layer(x, blk: dict, cfg: dict, control=None, *, select: bool = True):
    """One block on x [S, D]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, blk["attn_norm"]["scale"], eps), blk["attn"], cfg, control, select=select)
    return x + mixture(_rms_norm(x, blk["mlp_norm"]["scale"], eps), blk["moe"], cfg, control)


def head(params, x, cfg: dict, control=None):
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(x, _weight(params["lm_head"], x.shape[-1], control))


def forward_layerwise(params, tokens, cfg: dict, control=None, *, select: bool = True):
    """tokens [B, S] -> logits [B, S, vocab] float32, a host array.
    ``select=False`` attends every ``s <= t`` (what the model is not: the
    tests' control)."""
    one_layer = jax.jit(lambda x, blk: layer(x, blk, cfg, control, select=select))
    embed = jax.jit(lambda table, seq: table.astype(jnp.float32)[seq])
    last = jax.jit(lambda p, x: head(p, x, cfg, control))
    rest = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    tokens = np.asarray(tokens)
    asked = None
    if cfg.get("correct", {}).get("reference_logits") == "served_tail":
        asked = int(cfg["serving"]["max_new_tokens"])
    out = np.zeros(tokens.shape + (cfg["vocab_size"],), np.float32)   # pages exist once written
    for b, seq in enumerate(tokens):
        x = embed(params["embed"]["embedding"], jnp.asarray(seq))
        for i in range(cfg["num_hidden_layers"]):
            x = one_layer(x, params[f"block_{i}"])
        lo, hi = 0, x.shape[0]
        if asked is not None and seq.any():
            # prompt + stream = [0, n), the padding zeros behind it. The prompt
            # holds no zero, so its last token lies before ``end`` and at most
            # ``asked`` tokens behind: the stream's rows [prompt - 1, n - 1)
            # lie in [end - asked - 1, end + asked) whatever tokens it holds
            end = int(np.flatnonzero(seq)[-1]) + 1
            lo, hi = max(0, end - asked - 1) // _ROW_BLOCK * _ROW_BLOCK, min(hi, end + asked)
        for r in range(lo, hi, _ROW_BLOCK):
            out[b, r:r + _ROW_BLOCK] = np.asarray(last(rest, x[r:r + _ROW_BLOCK]))
    return out
