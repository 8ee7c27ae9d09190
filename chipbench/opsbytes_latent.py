"""Operations and bytes of a latent-attention mixture decoder (the
``glm_moe_lite`` family: MLA, a sigmoid-routed mixture beside a shared
expert), computed from shapes.

As ``opsbytes.py``: each function counts what the chip *must* do for the
call at the published sizes, so that a share of a peak cannot pass 100 %.
A cached position is one latent row of ``kv_lora_rank + qk_rope_head_dim``
bfloat16 values a layer (1,152 B at the published widths: the 64 lanes that
pad it to 640 on the chip are the program's cost, not the algorithm's),
read once a step by all heads. Weights are read once a step; of the routed
experts only those that hold a row need be read, counted as the number that
``tokens`` rows are expected to touch under even routing (a dense dispatch
reads them all: its cost too). Configs are the JSON objects under
``chipbench/configs``.
"""

from __future__ import annotations

LATENT_KERNEL = r"^%?paged_latent_attention[.\s=]"  # the HLO instruction the pallas_call's name gives


def _sizes(cfg: dict) -> dict:
    dense = cfg["first_k_dense_replace"]
    return dict(
        d=cfg["hidden_size"], ff=cfg["intermediate_size"], eff=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], experts=cfg["n_routed_experts"], shared=cfg["n_shared_experts"],
        topk=cfg["num_experts_per_tok"], layers=cfg["num_hidden_layers"], dense=dense,
        mixture=cfg["num_hidden_layers"] - dense,
    )


def attention_params(cfg: dict) -> int:
    """q_a, q_b, kv_a, kv_b and o of one layer."""
    c = _sizes(cfg)
    qk = c["nope"] + c["rope"]
    return (
        c["d"] * c["q_rank"] + c["q_rank"] * c["heads"] * qk + c["d"] * (c["rank"] + c["rope"])
        + c["rank"] * c["heads"] * (c["nope"] + c["vd"]) + c["heads"] * c["vd"] * c["d"]
    )


def expert_params(cfg: dict) -> int:
    c = _sizes(cfg)
    return 3 * c["d"] * c["eff"]


def dense_mlp_params(cfg: dict) -> int:
    c = _sizes(cfg)
    return 3 * c["d"] * c["ff"]


def experts_touched(cfg: dict, tokens: float) -> float:
    """Routed experts that hold a row of ``tokens`` rows, expected under
    even routing: each row draws ``num_experts_per_tok`` distinct ones."""
    c = _sizes(cfg)
    return c["experts"] * (1.0 - (1.0 - c["topk"] / c["experts"]) ** tokens)


def matmul_params_a_token(cfg: dict) -> int:
    """Every matmul weight one token passes: its top-k experts and the
    shared one, the router, the head."""
    c = _sizes(cfg)
    mixture = (c["topk"] + c["shared"]) * expert_params(cfg) + c["d"] * c["experts"]
    return (
        c["layers"] * attention_params(cfg) + c["dense"] * dense_mlp_params(cfg)
        + c["mixture"] * mixture + c["d"] * c["vocab"]
    )


def weight_bytes(cfg: dict, tokens: float, *, weight_bytes_each: float = 1.0) -> float:
    """Bytes of the weights one program over ``tokens`` rows must read:
    int8 for the wide ones, float32 for the router and its bias; of the
    routed experts those the rows touch; the embedding is gathered."""
    c = _sizes(cfg)
    wide = (
        c["layers"] * attention_params(cfg) + c["dense"] * dense_mlp_params(cfg)
        + c["mixture"] * (experts_touched(cfg, tokens) + c["shared"]) * expert_params(cfg)
        + c["d"] * c["vocab"]
    )
    return wide * weight_bytes_each + c["mixture"] * (c["d"] + 1) * c["experts"] * 4.0


def latent_row_bytes(cfg: dict, *, kv_bytes: float = 2.0) -> float:
    """Bytes of one cached position in one layer."""
    c = _sizes(cfg)
    return (c["rank"] + c["rope"]) * kv_bytes


def latent_attention_cost(cfg: dict, kv_tokens: float):
    """(flops, bytes) of the absorbed attention of one decode step over
    ``kv_tokens`` cached positions in all, every layer: each row read once
    for all heads; a head scores a position over the row's whole width and
    weighs its ``kv_lora_rank`` values."""
    c = _sizes(cfg)
    width = c["rank"] + c["rope"]
    flops = 2.0 * c["layers"] * c["heads"] * (width + c["rank"]) * kv_tokens
    return flops, c["layers"] * latent_row_bytes(cfg) * kv_tokens


def decode_step_cost(cfg: dict, tokens: float, kv_tokens: float, *, weight_bytes_each: float = 1.0):
    """(flops, bytes) of one decode step over ``tokens`` live sequences that
    hold ``kv_tokens`` cached positions in all: the weights once (the
    experts the live rows touch), the latent rows read for the cached
    positions and written for the new ones, the embedding rows gathered."""
    c = _sizes(cfg)
    attn_flops, attn_bytes = latent_attention_cost(cfg, kv_tokens)
    moved = weight_bytes(cfg, tokens, weight_bytes_each=weight_bytes_each) + attn_bytes
    moved += tokens * c["layers"] * latent_row_bytes(cfg) + tokens * c["d"] * 4
    return 2.0 * tokens * matmul_params_a_token(cfg) + attn_flops, moved


def prefill_cost(cfg: dict, prompt_tokens: int, *, weight_bytes_each: float = 1.0):
    """(flops, bytes) of one prompt of ``prompt_tokens`` true tokens (the
    bucket's padding is the program's cost): every matmul over the tokens,
    the head for the last position only, the expanded attention's causal
    half square at head widths ``qk`` and ``v``; the weights once (the
    experts the prompt's rows touch), a latent row a token and layer
    written, the embedding rows gathered."""
    c = _sizes(cfg)
    n = prompt_tokens
    head = c["d"] * c["vocab"]
    flops = 2.0 * n * (matmul_params_a_token(cfg) - head) + 2.0 * head
    flops += c["layers"] * c["heads"] * (c["nope"] + c["rope"] + c["vd"]) * float(n) * n
    moved = weight_bytes(cfg, n, weight_bytes_each=weight_bytes_each)
    moved += n * c["layers"] * latent_row_bytes(cfg) + n * c["d"] * 4
    return flops, moved
