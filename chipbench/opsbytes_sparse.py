"""Operations and bytes of a decoder whose attention reads the positions a
learned indexer selects (the ``keye_vl_moe`` family: grouped-query attention
behind ``sa_config``'s indexer, a softmax-routed mixture in every layer),
computed from shapes; and the trace reductions its readers share.

As ``opsbytes.py``: each function counts what the chip *must* do for the
call at the published sizes, so that a share of a peak cannot pass 100 %. A
cached position is, a layer, ``num_key_value_heads x head_dim`` keys and as
many values (2,048 B in bfloat16 at the published widths) and one indexer key
of ``indexer_head_dim`` values (128 B: the 64 lanes that pad it to a whole
tile on the chip are the program's cost, not the algorithm's). A decode step
reads the indexer key of every visible position, and the keys and values of
the positions it selects only: ``min(length, topk)`` a sequence. The
selection itself (the top-k over the scores) is counted as nothing: its
least time is a pass over scores that never need leave the chip's fast
memory, and what the program spends on it lowers the share. Weights are read
once a step; of the routed experts only those that hold a row need be read,
counted as the number that ``tokens`` rows are expected to touch under even
routing. Configs are the JSON objects under ``chipbench/configs``.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple


def _sizes(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return dict(
        d=cfg["hidden_size"], eff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        experts=cfg["num_experts"], topk_experts=cfg["num_experts_per_tok"], layers=cfg["num_hidden_layers"],
        ih=sa["indexer_num_heads"], idim=sa["indexer_head_dim"], topk=sa["topk"],
    )


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer."""
    c = _sizes(cfg)
    return c["d"] * c["hd"] * (2 * c["heads"] + 2 * c["kv_heads"])


def indexer_params(cfg: dict) -> int:
    """The indexer's query, key and weight projections of one layer."""
    c = _sizes(cfg)
    return c["d"] * (c["ih"] * c["idim"] + c["idim"] + c["ih"])


def expert_params(cfg: dict) -> int:
    c = _sizes(cfg)
    return 3 * c["d"] * c["eff"]


def experts_touched(cfg: dict, tokens: float) -> float:
    """Routed experts that hold a row of ``tokens`` rows, expected under
    even routing: each row draws ``num_experts_per_tok`` distinct ones."""
    c = _sizes(cfg)
    return c["experts"] * (1.0 - (1.0 - c["topk_experts"] / c["experts"]) ** tokens)


def matmul_params_a_token(cfg: dict) -> int:
    """Every matmul weight one token passes: attention and indexer
    projections, the router and its top-k experts, the head."""
    c = _sizes(cfg)
    layer = attention_params(cfg) + indexer_params(cfg) + c["d"] * c["experts"] + c["topk_experts"] * expert_params(cfg)
    return c["layers"] * layer + c["d"] * c["vocab"]


def weight_bytes(cfg: dict, tokens: float, *, weight_bytes_each: float = 1.0) -> float:
    """Bytes of the weights one program over ``tokens`` rows must read: int8
    for the wide ones, float32 for the router and the indexer's two narrow
    projections; of the routed experts those the rows touch; the embedding
    is gathered."""
    c = _sizes(cfg)
    wide = c["layers"] * (
        attention_params(cfg) + c["d"] * c["ih"] * c["idim"] + experts_touched(cfg, tokens) * expert_params(cfg)
    ) + c["d"] * c["vocab"]
    narrow = c["layers"] * c["d"] * (c["experts"] + c["idim"] + c["ih"])
    return wide * weight_bytes_each + narrow * 4.0


def kv_row_bytes(cfg: dict, *, kv_bytes: float = 2.0) -> float:
    """Bytes of one cached position's keys and values in one layer."""
    c = _sizes(cfg)
    return 2 * c["kv_heads"] * c["hd"] * kv_bytes


def index_key_bytes(cfg: dict, *, kv_bytes: float = 2.0) -> float:
    """Bytes of one cached position's indexer key in one layer."""
    return _sizes(cfg)["idim"] * kv_bytes


def picked_positions(cfg: dict, lengths) -> float:
    """Positions a decode step's attention reads for sequences of
    ``lengths`` cached positions: ``min(length, topk)`` each."""
    topk = _sizes(cfg)["topk"]
    return float(sum(min(float(n), topk) for n in lengths))


def index_scores_cost(cfg: dict, kv_tokens: float):
    """(flops, bytes) of one decode step's index scores over ``kv_tokens``
    visible positions in all, every layer: each indexer key read once;
    every indexer head's product with it."""
    c = _sizes(cfg)
    return 2.0 * c["layers"] * c["ih"] * c["idim"] * kv_tokens, c["layers"] * index_key_bytes(cfg) * kv_tokens


def sparse_attention_cost(cfg: dict, picked_tokens: float):
    """(flops, bytes) of one decode step's attention over ``picked_tokens``
    selected positions in all, every layer: their keys and values read
    once for all heads; every query head scores and weighs each."""
    c = _sizes(cfg)
    return 2.0 * c["layers"] * c["heads"] * 2 * c["hd"] * picked_tokens, c["layers"] * kv_row_bytes(cfg) * picked_tokens


def decode_step_cost(cfg: dict, tokens: float, kv_tokens: float, picked_tokens: float, *,
                     weight_bytes_each: float = 1.0):
    """(flops, bytes) of one decode step over ``tokens`` live sequences that
    hold ``kv_tokens`` cached positions in all, of which their attention
    selects ``picked_tokens``: the weights once (the experts the live rows
    touch), the indexer key of every visible position, the keys and values
    of the selected ones, a row written a sequence and layer, the embedding
    rows gathered. The selection's own time is counted as zero."""
    c = _sizes(cfg)
    index_flops, index_bytes = index_scores_cost(cfg, kv_tokens)
    attn_flops, attn_bytes = sparse_attention_cost(cfg, picked_tokens)
    moved = weight_bytes(cfg, tokens, weight_bytes_each=weight_bytes_each) + index_bytes + attn_bytes
    moved += tokens * c["layers"] * (kv_row_bytes(cfg) + index_key_bytes(cfg)) + tokens * c["d"] * 4
    return 2.0 * tokens * matmul_params_a_token(cfg) + index_flops + attn_flops, moved


def prefill_cost(cfg: dict, prompt_tokens: int, *, weight_bytes_each: float = 1.0):
    """(flops, bytes) of one prompt of ``prompt_tokens`` true tokens (the
    bucket's padding is the program's cost): every matmul over the tokens,
    the head for the last position only; the index scores of every query for
    every position before it (none for a prompt no longer than ``topk``,
    which selects everything); attention over ``min(t + 1, topk)`` positions
    a query; the weights once (the experts the prompt's rows touch), a row a
    token and layer written, the embedding rows gathered. The selection's
    own time is counted as zero."""
    c = _sizes(cfg)
    n = int(prompt_tokens)
    head = c["d"] * c["vocab"]
    flops = 2.0 * n * (matmul_params_a_token(cfg) - head) + 2.0 * head
    if n > c["topk"]:
        flops += 2.0 * c["layers"] * c["ih"] * c["idim"] * n * (n + 1) / 2.0
    full = min(n, c["topk"])                      # queries that see no more than topk positions
    attended = full * (full + 1) / 2.0 + (n - full) * c["topk"]
    flops += 2.0 * c["layers"] * c["heads"] * 2 * c["hd"] * attended
    moved = weight_bytes(cfg, n, weight_bytes_each=weight_bytes_each)
    moved += n * c["layers"] * (kv_row_bytes(cfg) + index_key_bytes(cfg)) + n * c["d"] * 4
    return flops, moved


# ---- what the readers share: the traced load and the scoped operations

# scope components (``jax.named_scope`` names and a Pallas kernel's name) of
# the operations each time is the sum of
INDEX_SELECT_SCOPES = frozenset({"indexer", "select", "paged_index_scores"})
SPARSE_ATTENTION_SCOPES = frozenset({"paged_sparse_attention"})


def traced_rows(run) -> Optional[List[Tuple[float, float]]]:
    """[(share of the traced seconds it was live, cached positions)] of the
    sequences live in the traced seconds, from the client's records (as
    ``opsbytes_hybrid.traced_load`` reads them: a request is live from its
    first token to its last and holds its prompt and the tokens received by
    the middle of the overlap). ``None`` where nothing was traced."""
    if run.record.get("trace_dir") is None:
        return None
    lo = run.record["t_zero"] + float(run.traffic["trace_from_s"])
    hi = lo + float(run.traffic["trace_seconds"])
    out = []
    for r in run.record["records"]:
        if r["error"] or len(r["t_tokens"]) < 2:
            continue
        a, b = max(r["t_tokens"][0], lo), min(r["t_tokens"][-1], hi)
        if b <= a:
            continue
        got = sum(1 for t in r["t_tokens"] if t <= (a + b) / 2.0)
        out.append(((b - a) / (hi - lo), float(r["n_prompt"] + got)))
    return out


def traced_load(run, cfg: dict) -> Optional[Tuple[float, float, float]]:
    """(live sequences, visible positions, selected positions) resident on
    average over the traced seconds."""
    rows = traced_rows(run)
    if rows is None:
        return None
    topk = _sizes(cfg)["topk"]
    return (
        sum(share for share, _ in rows), sum(share * n for share, n in rows),
        sum(share * min(n, topk) for share, n in rows),
    )


def scoped_ms_per_step(run, scopes: frozenset, program: str = r"^jit_decode_chunk\(") -> Optional[float]:
    """Device milliseconds a decode step spends in the operations whose
    ``tf_op`` holds one of ``scopes`` as a component, all layers together:
    over the operations inside whole traced runs of ``program``, per run and
    ``chunk_steps`` (``opscopes.whole_runs``, as the ``decode_step_*_ms``
    readers). ``None`` where nothing was traced or no operation carries such
    a scope (another program, or the parent's)."""
    from chipbench import opscopes

    ops = opscopes.for_run(run)
    if not ops:
        return None
    runs = opscopes.whole_runs(ops, program)
    seconds, found = 0.0, False
    for one in runs:
        for op in one:
            if scopes & set(opscopes.scope_path(op.tf_op)):
                seconds += op.end_s - op.start_s
                found = True
    if not found:
        return None
    return 1e3 * seconds / (len(runs) * (run.record.get("chunk_steps") or 1))


def kernel_roofline(run, scopes: frozenset, cost: Callable, what: str) -> Optional[float]:
    """100 x the least time ``cost(cfg, load) -> (flops, bytes)`` allows over
    the traced time of the operations under ``scopes``."""
    from chipbench.yardstick import roofline_s, say

    if "sa_config" not in run.config:
        return None
    ms = scoped_ms_per_step(run, scopes)
    load = traced_load(run, run.config)
    if not ms or load is None:
        return None
    flops, moved = cost(run.config, load)
    least, bound = roofline_s(flops, moved, run.peaks)
    say(f"{what}: {moved / 1e9:.3f} GB, {flops / 1e9:.1f} GFLOP for {load[0]:.1f} live sequences seeing "
        f"{load[1]:.0f} positions and selecting {load[2]:.0f}; {bound}-bound, least {least * 1e3:.3f} ms, "
        f"traced {ms:.3f} ms a step")
    return 100.0 * least * 1e3 / ms
