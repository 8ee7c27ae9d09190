"""Operations and bytes of a decoder that generates by diffusion over blocks
(the ``sdar_moe`` family: grouped-query attention under a block-causal mask,
a softmax-routed mixture in every layer), computed from shapes; and the trace
reductions its readers share.

As ``opsbytes.py``: each function counts what the chip *must* do for the call
at the published sizes, so that a share of a peak cannot pass 100 %. The unit
of decoding is one **forward** over every live sequence's open block:
``block_length`` rows a sequence. A cached position is, a layer,
``num_key_value_heads x head_dim`` keys and as many values (2,048 B in
bfloat16 at the published widths); a forward reads a live sequence's visible
positions **once**, for all ``block_length`` queries of its block, not once a
token. Weights are read once a forward; of the routed experts only those that
hold a row need be read, counted as the number that ``block_length x live``
rows are expected to touch under even routing. The head runs over every row of
the forward (each undecided entry is asked what stands there); the choice of
the entries a forward decides is counted as nothing. A prefill yields no
token: no head. Configs are the JSON objects under ``chipbench/configs``.
"""

from __future__ import annotations

from typing import Optional, Tuple


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], eff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        experts=cfg["num_experts"], topk=cfg["num_experts_per_tok"], layers=cfg["num_hidden_layers"],
        bk=cfg["generation"]["block_length"],
    )


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer."""
    c = _sizes(cfg)
    return c["d"] * c["hd"] * (2 * c["heads"] + 2 * c["kv_heads"])


def expert_params(cfg: dict) -> int:
    c = _sizes(cfg)
    return 3 * c["d"] * c["eff"]


def experts_touched(cfg: dict, rows: float) -> float:
    """Routed experts that hold one of ``rows`` rows, expected under even
    routing: each row draws ``num_experts_per_tok`` distinct ones."""
    c = _sizes(cfg)
    return c["experts"] * (1.0 - (1.0 - c["topk"] / c["experts"]) ** rows)


def layer_params_a_row(cfg: dict) -> int:
    """Every matmul weight of one layer that one row passes: the attention
    projections, the router and its top-k experts."""
    c = _sizes(cfg)
    return attention_params(cfg) + c["d"] * c["experts"] + c["topk"] * expert_params(cfg)


def weight_bytes(cfg: dict, rows: float, *, head: bool = True, weight_bytes_each: float = 1.0) -> float:
    """Bytes of the weights one program over ``rows`` rows must read: int8
    for the wide ones, float32 for the router; of the routed experts those
    the rows touch; the embedding is gathered."""
    c = _sizes(cfg)
    wide = c["layers"] * (attention_params(cfg) + experts_touched(cfg, rows) * expert_params(cfg))
    if head:
        wide += c["d"] * c["vocab"]
    return wide * weight_bytes_each + c["layers"] * c["d"] * c["experts"] * 4.0


def kv_row_bytes(cfg: dict, *, kv_bytes: float = 2.0) -> float:
    """Bytes of one cached position's keys and values in one layer."""
    c = _sizes(cfg)
    return 2 * c["kv_heads"] * c["hd"] * kv_bytes


def block_attention_cost(cfg: dict, kv_tokens: float):
    """(flops, bytes) of one forward's attention over ``kv_tokens`` visible
    positions in all (each live sequence's committed rows and its open
    block), every layer: their keys and values read once a forward; every
    query head of each of the block's ``block_length`` queries scores and
    weighs each."""
    c = _sizes(cfg)
    return (
        2.0 * c["layers"] * c["bk"] * c["heads"] * 2 * c["hd"] * kv_tokens,
        c["layers"] * kv_row_bytes(cfg) * kv_tokens,
    )


def forward_cost(cfg: dict, live: float, kv_tokens: float, *, weight_bytes_each: float = 1.0):
    """(flops, bytes) of one forward over ``live`` sequences' open blocks
    that see ``kv_tokens`` positions in all: ``block_length x live`` rows
    through every matmul and the head; the weights once (the experts the
    rows touch); the visible keys and values once; a row written a block
    entry and layer; the embedding rows gathered."""
    c = _sizes(cfg)
    rows = c["bk"] * live
    attn_flops, attn_bytes = block_attention_cost(cfg, kv_tokens)
    flops = 2.0 * rows * (c["layers"] * layer_params_a_row(cfg) + c["d"] * c["vocab"]) + attn_flops
    moved = weight_bytes(cfg, rows, weight_bytes_each=weight_bytes_each) + attn_bytes
    moved += rows * c["layers"] * kv_row_bytes(cfg) + rows * c["d"] * 4
    return flops, moved


def block_causal_pairs(n: int, bk: int) -> float:
    """(query, key) pairs of ``n`` positions under the block-causal mask: a
    query sees every position up to the end of its own block (the half
    square, and the rest of each block)."""
    whole, rest = divmod(int(n), bk)
    return float(sum((b + 1) * bk * bk for b in range(whole)) + rest * (whole * bk + rest))


def prefill_cost(cfg: dict, prompt_tokens: int, *, weight_bytes_each: float = 1.0):
    """(flops, bytes) of one prompt of ``prompt_tokens`` true tokens (the
    bucket's padding is the program's cost): every layer's matmuls over the
    tokens and **no head** (a prefill yields no token); attention over the
    block-causal half square; the weights once (the experts the prompt's
    rows touch), a row a token and layer written, the embedding rows
    gathered."""
    c = _sizes(cfg)
    n = int(prompt_tokens)
    flops = 2.0 * n * c["layers"] * layer_params_a_row(cfg)
    flops += 2.0 * c["layers"] * c["heads"] * 2 * c["hd"] * block_causal_pairs(n, c["bk"])
    moved = weight_bytes(cfg, n, head=False, weight_bytes_each=weight_bytes_each)
    moved += n * c["layers"] * kv_row_bytes(cfg) + n * c["d"] * 4
    return flops, moved


# ---- what the readers share: the traced load

# [(share of the traced seconds it was live, cached positions)] of the
# sequences live in the traced seconds, from the client's records: a request
# counts as live from its first token to its last (its first block's forwards
# before that are left out, which counts the load low and a share of a peak
# low with it) and holds its prompt and the tokens received by then
from chipbench.opsbytes_sparse import traced_rows  # noqa: E402


def traced_load(run) -> Optional[Tuple[float, float]]:
    """(live sequences, visible positions) resident on average over the
    traced seconds."""
    rows = traced_rows(run)
    if rows is None:
        return None
    return sum(share for share, _ in rows), sum(share * n for share, n in rows)
