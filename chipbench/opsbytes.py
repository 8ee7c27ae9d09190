"""Operations and bytes that the algorithm needs, computed from shapes.

A share of a peak divides one of these by a measured time, so each function
counts what the chip *must* do for the call and nothing it merely happens to
do: top-k experts, not the dense dispatch's all experts; causal attention's
half square; weights read once per step. Counting less than the chip must
move could push a share past 100 %, so every term that a step cannot avoid
is in. Configs are the JSON objects under ``chipbench/configs`` (Hugging Face
key names for decoders).
"""

from __future__ import annotations


# ------------------------------------------------------------- decoders


def _dec(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return dict(
        d=cfg["hidden_size"], heads=heads, kv=cfg["num_key_value_heads"], hd=head_dim,
        ff=cfg["intermediate_size"], layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        experts=cfg.get("num_local_experts", 0), topk=cfg.get("num_experts_per_tok", 0),
    )


def decoder_attn_params(cfg: dict) -> int:
    """q, k, v and o projection weights of one layer."""
    c = _dec(cfg)
    return c["d"] * c["heads"] * c["hd"] * 2 + c["d"] * c["kv"] * c["hd"] * 2


def decoder_mlp_params(cfg: dict) -> int:
    """One SwiGLU MLP (one expert, for a mixture): gate, up, down."""
    c = _dec(cfg)
    return 3 * c["d"] * c["ff"]


def decoder_layer_params(cfg: dict) -> int:
    """All weights of one layer (every expert and the router, for a mixture)."""
    c = _dec(cfg)
    mlp = decoder_mlp_params(cfg)
    if c["experts"]:
        mlp = mlp * c["experts"] + c["d"] * c["experts"]
    return decoder_attn_params(cfg) + mlp


def experts_touched(experts: int, topk: int, tokens: float) -> float:
    """Expected distinct experts that ``tokens`` tokens route to, under
    uniform routing (seeded random weights route near uniformly)."""
    if not experts:
        return 0.0
    return experts * (1.0 - (1.0 - topk / experts) ** max(tokens, 0.0))


def decode_step_cost(cfg: dict, tokens: float, kv_tokens: float, *,
                     weight_bytes: float = 1.0, kv_bytes: float = 2.0):
    """(flops, bytes) of one decode step over ``tokens`` live sequences that
    hold ``kv_tokens`` cached positions in total.

    Bytes: every matmul weight the batch touches once (attention, the
    experts routed to, the head), the cached keys and values read, the new
    rows written, the embedding rows gathered (fp32). FLOPs: two per weight
    per token for the top-k experts only, plus scores and values over the
    cache."""
    c = _dec(cfg)
    attn, mlp = decoder_attn_params(cfg), decoder_mlp_params(cfg)
    if c["experts"]:
        mlp_read = mlp * experts_touched(c["experts"], c["topk"], tokens) + c["d"] * c["experts"] * 4
        mlp_flop_params = mlp * c["topk"] + c["d"] * c["experts"]
    else:
        mlp_read, mlp_flop_params = mlp, mlp
    head = c["d"] * c["vocab"]
    weight_read = (c["layers"] * (attn + mlp_read) + head) * weight_bytes
    kv_row = 2 * c["kv"] * c["hd"] * kv_bytes  # keys and values of one position, one layer
    kv_traffic = c["layers"] * kv_row * (kv_tokens + tokens)
    embed = tokens * c["d"] * 4
    flops = 2 * tokens * (c["layers"] * (attn + mlp_flop_params) + head)
    flops += 4 * c["layers"] * c["heads"] * c["hd"] * kv_tokens
    return flops, weight_read + kv_traffic + embed


def prefill_cost(cfg: dict, prompt_tokens: int, *, weight_bytes: float = 1.0, kv_bytes: float = 2.0):
    """(flops, bytes) of one prompt of ``prompt_tokens`` true tokens: the
    padding of its bucket is the program's cost, not the algorithm's. The
    head runs for the last position only."""
    c = _dec(cfg)
    n = prompt_tokens
    attn, mlp = decoder_attn_params(cfg), decoder_mlp_params(cfg)
    if c["experts"]:
        mlp_read = mlp * experts_touched(c["experts"], c["topk"], n) + c["d"] * c["experts"] * 4
        mlp_flop_params = mlp * c["topk"] + c["d"] * c["experts"]
    else:
        mlp_read, mlp_flop_params = mlp, mlp
    head = c["d"] * c["vocab"]
    flops = 2 * n * c["layers"] * (attn + mlp_flop_params) + 2 * head
    flops += 2 * c["layers"] * c["heads"] * c["hd"] * n * n  # causal: half of 4 n^2
    kv_row = 2 * c["kv"] * c["hd"] * kv_bytes
    bytes_moved = (c["layers"] * (attn + mlp_read) + head) * weight_bytes
    bytes_moved += c["layers"] * kv_row * n + n * c["d"] * 4
    return flops, bytes_moved


def decoder_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token of a causal LM step: six
    per matmul weight (top-k experts only), plus causal attention (half the
    square), recomputation not counted. The embedding gather is free."""
    c = _dec(cfg)
    mlp = decoder_mlp_params(cfg)
    if c["experts"]:
        mlp = mlp * c["topk"] + c["d"] * c["experts"]
    weights = c["layers"] * (decoder_attn_params(cfg) + mlp) + c["d"] * c["vocab"]
    attention = c["layers"] * 2 * seq * c["heads"] * c["hd"]  # forward, causal
    return 6.0 * weights + 3.0 * attention


# ------------------------------------------------------------------ ViT


def vit_tokens(cfg: dict) -> int:
    return (cfg["image_size"] // cfg["patch_size"]) ** 2 + 1


def vit_block_params(cfg: dict) -> int:
    """Matmul weights of one encoder block: q, k, v, o and the two MLP maps."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 2 * d * ff


def vit_train_flops_per_sample(cfg: dict) -> float:
    """Forward + backward FLOPs of one image: three times the forward's."""
    d, s = cfg["hidden_size"], vit_tokens(cfg)
    patches = s - 1
    fwd = 2 * s * cfg["num_hidden_layers"] * vit_block_params(cfg)
    fwd += cfg["num_hidden_layers"] * 4 * s * s * d          # scores and values, full square
    fwd += 2 * patches * (cfg["patch_size"] ** 2 * cfg["num_channels"]) * d
    fwd += 2 * d * cfg["num_labels"]
    return 3.0 * fwd


def vit_attention_cost(cfg: dict, batch: int, *, act_bytes: float = 2.0):
    """(flops, bytes) of the attention kernel's forward + backward over one
    batch, all layers: forward scores and values (4 s^2 d), backward dq, dk,
    dv and the score gradient (8 s^2 d), recomputed scores not counted;
    q, k, v, out read or written once each way, and their gradients."""
    d, s, layers = cfg["hidden_size"], vit_tokens(cfg), cfg["num_hidden_layers"]
    flops = layers * batch * 12 * s * s * d
    # forward reads q, k, v and writes out; backward reads q, k, v, dout and
    # writes dq, dk, dv: the least, each tensor once each way
    return flops, layers * batch * (4 + 7) * s * d * act_bytes


def train_flops_per_sample(cfg: dict) -> float:
    """Forward + backward FLOPs of one training sample of ``cfg``'s family."""
    if cfg["family"] == "vit":
        return vit_train_flops_per_sample(cfg)
    seq = cfg["training"]["sequence_length"]
    return decoder_train_flops_per_token(cfg, seq - 1) * (seq - 1)
