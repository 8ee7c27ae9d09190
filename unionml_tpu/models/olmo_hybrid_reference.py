"""Olmo-Hybrid, plain: the whole forward pass in ``jax.numpy`` and float32.

No cache, no chunks, no kernels: the gated delta rule token by token in a
``lax.scan``, the causal depthwise convolution as an explicit sum of
shifted rows, full softmax attention (in blocks of queries, which changes
no number). It follows the published config
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json);
what the config does not state is assumed:

- the block is ``h = x + RMSNorm(Mixer(x)); out = h + RMSNorm(MLP(h))``
  (the Olmo 2/3 family's norm-after-sublayer order);
- q and k of a full-attention layer are each RMS-normalised over their
  full width before the heads are split (the family's q/k norm);
- ``rope_theta: null`` is read as "no rotary embedding".

Parameters come as the nested dict the program's module uses, by leaf
name; int8 leaves (``kernel_q`` with ``scale``) are dequantised here, one
matrix at a time. ``cfg`` is the dict of published keys. ``control`` is a
lower precision for control runs: ``"int4"`` rounds every wide weight to
int4 first; ``"bf16_state"`` keeps the rule's state in bfloat16.

Callers set ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_Q_BLOCK = 512  # queries a softmax block holds: [heads, 512, S] scores


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _fake_int4(w):
    """Symmetric int4 per output channel (last axis)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 7.0
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def _weight(p: dict, control=None, heads_out: bool = False):
    """A dense weight as float32 [in, out]: ``kernel`` or ``kernel_q *
    scale``. A float q/k/v kernel is [in, heads, head_dim] (``heads_out``)
    and a float o kernel [heads, head_dim, out]: the heads are flattened."""
    if "kernel_q" in p:
        w = p["kernel_q"].astype(jnp.float32) * p["scale"]
    else:
        w = p["kernel"].astype(jnp.float32)
    w = w.reshape(w.shape[0], -1) if heads_out else w.reshape(-1, w.shape[-1])
    return _fake_int4(w) if control == "int4" else w


def _mm(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def linear_attention(x, p: dict, cfg: dict, control=None):
    """x [S, D] -> [S, D]: one sequence through a gated-delta-rule layer."""
    heads, dk, dv = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    s = x.shape[0]
    qkv = jnp.concatenate([_mm(x, _weight(p[n], control)) for n in ("q", "k", "v")], axis=-1)
    padded = jnp.concatenate([jnp.zeros((width - 1, qkv.shape[1]), jnp.float32), qkv], axis=0)
    conv = p["conv_kernel"].astype(jnp.float32)
    mixed = jax.nn.silu(sum(conv[j] * padded[j:j + s] for j in range(width)))
    q, k, v = jnp.split(mixed, [heads * dk, 2 * heads * dk], axis=-1)
    q = _l2norm(q.reshape(s, heads, dk)) / jnp.sqrt(float(dk))
    k = _l2norm(k.reshape(s, heads, dk))
    v = v.reshape(s, heads, dv)
    beta = jax.nn.sigmoid(_mm(x, p["b"]["kernel"].astype(jnp.float32)))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    a = _mm(x, p["a"]["kernel"].astype(jnp.float32))
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(a + p["dt_bias"]))
    state_dtype = jnp.bfloat16 if control == "bf16_state" else jnp.float32

    def token(state, xs):
        q, k, v, alpha, beta = xs                       # [H, dk], [H, dk], [H, dv], [H], [H]
        state = state.astype(jnp.float32) * alpha[:, None, None]
        u = beta[:, None] * (v - jnp.sum(state * k[:, :, None], axis=1))
        state = state + k[:, :, None] * u[:, None, :]
        return state.astype(state_dtype), jnp.sum(state * q[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), state_dtype), (q, k, v, alpha, beta))
    o = _rms_norm(o, p["o_norm"]["scale"], cfg["rms_norm_eps"])
    o = o * jax.nn.silu(_mm(x, _weight(p["g"], control))).reshape(s, heads, dv)
    return _mm(o.reshape(s, heads * dv), _weight(p["o"], control))


def full_attention(x, p: dict, cfg: dict, control=None):
    """x [S, D] -> [S, D]: causal softmax attention, q/k-normed, no rotary."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    eps = cfg["rms_norm_eps"]
    s = x.shape[0]
    q = _rms_norm(_mm(x, _weight(p["q"], control, True)), p["q_norm"]["scale"], eps).reshape(s, heads, hd)
    k = _rms_norm(_mm(x, _weight(p["k"], control, True)), p["k_norm"]["scale"], eps).reshape(s, kv, hd)
    v = _mm(x, _weight(p["v"], control, True)).reshape(s, kv, hd)
    k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
    out = []
    for start in range(0, s, _Q_BLOCK):
        qb = q[start:start + _Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest") / jnp.sqrt(float(hd))
        visible = jnp.arange(s)[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(visible[None], sc, -1e30)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision="highest"))
    o = jnp.concatenate(out, axis=0).reshape(s, heads * hd)
    return _mm(o, _weight(p["o"], control))


def layer(x, blk: dict, kind: str, cfg: dict, control=None):
    """One block on x [S, D]."""
    eps = cfg["rms_norm_eps"]
    if kind == "linear_attention":
        mixed = linear_attention(x, blk["gdn"], cfg, control)
    else:
        mixed = full_attention(x, blk["attn"], cfg, control)
    h = x + _rms_norm(mixed, blk["mixer_norm"]["scale"], eps)
    m = blk["mlp"]
    y = _mm(jax.nn.silu(_mm(h, _weight(m["gate"], control))) * _mm(h, _weight(m["up"], control)),
            _weight(m["down"], control))
    return h + _rms_norm(y, blk["mlp_norm"]["scale"], eps)


def head(params, x, cfg: dict, control=None):
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(x, _weight(params["lm_head"], control))


def forward(params, tokens, cfg: dict, control=None):
    """tokens [B, S] -> logits [B, S, vocab] float32."""

    def one(seq):
        x = params["embed"]["embedding"].astype(jnp.float32)[seq]
        for i, kind in enumerate(cfg["layer_types"]):
            x = layer(x, params[f"block_{i}"], kind, cfg, control)
        return head(params, x, cfg, control)

    return jnp.stack([one(seq) for seq in tokens])
