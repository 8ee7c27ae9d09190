"""Operations and bytes of a hybrid decoder: gated-delta-rule layers beside
full attention (the ``olmo_hybrid`` family), computed from shapes.

As ``opsbytes.py``: each function counts what the chip *must* do for the
call at the published sizes, so that a share of a peak cannot pass 100 %.
Weights are read once a step; every live sequence's state is read and
written once in every linear layer; keys and values of the full-attention
layers are read for the cached positions (30 heads: the two that pad the
pool's rows to 32 are the program's cost, not the algorithm's). Configs are
the JSON objects under ``chipbench/configs``.
"""

from __future__ import annotations

CHUNK = 64  # tokens a chunk of the prefill's scan holds


def _sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    kinds = cfg["layer_types"]
    return dict(
        d=cfg["hidden_size"], ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        heads=heads, kv=cfg["num_key_value_heads"], hd=cfg["hidden_size"] // heads,
        lh=cfg["linear_num_value_heads"], dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        width=cfg["linear_conv_kernel_dim"],
        linear=sum(k == "linear_attention" for k in kinds), full=sum(k == "full_attention" for k in kinds),
    )


def conv_channels(cfg: dict) -> int:
    c = _sizes(cfg)
    return c["lh"] * (2 * c["dk"] + c["dv"])


def mlp_params(cfg: dict) -> int:
    c = _sizes(cfg)
    return 3 * c["d"] * c["ff"]


def linear_mixer_params(cfg: dict) -> int:
    """int8 weights of a linear layer's mixer: q, k, v, the output gate, o."""
    c = _sizes(cfg)
    return c["d"] * conv_channels(cfg) + 2 * c["d"] * c["lh"] * c["dv"]


def linear_mixer_small_params(cfg: dict) -> int:
    """float32 weights of a linear layer's mixer: the two decay/beta
    projections and the convolution."""
    c = _sizes(cfg)
    return 2 * c["d"] * c["lh"] + c["width"] * conv_channels(cfg)


def full_mixer_params(cfg: dict) -> int:
    c = _sizes(cfg)
    return 2 * c["d"] * c["heads"] * c["hd"] + 2 * c["d"] * c["kv"] * c["hd"]


def matmul_params(cfg: dict) -> int:
    """Every matmul weight a token passes, head included."""
    c = _sizes(cfg)
    per_linear = linear_mixer_params(cfg) + 2 * c["d"] * c["lh"] + mlp_params(cfg)
    per_full = full_mixer_params(cfg) + mlp_params(cfg)
    return c["linear"] * per_linear + c["full"] * per_full + c["d"] * c["vocab"]


def weight_bytes(cfg: dict, *, weight_bytes: float = 1.0) -> float:
    """Bytes of the weights one step reads: int8 for the wide ones, float32
    for the small ones; the embedding is gathered, not read."""
    c = _sizes(cfg)
    wide = (
        c["linear"] * (linear_mixer_params(cfg) + mlp_params(cfg))
        + c["full"] * (full_mixer_params(cfg) + mlp_params(cfg)) + c["d"] * c["vocab"]
    )
    return wide * weight_bytes + c["linear"] * linear_mixer_small_params(cfg) * 4.0


def state_bytes(cfg: dict) -> int:
    """Bytes of one sequence's rule state ``S`` in one linear layer."""
    c = _sizes(cfg)
    return c["lh"] * c["dk"] * c["dv"] * 4


def state_step_cost(cfg: dict, tokens: float):
    """(flops, bytes) of the ``gated_delta_step`` kernel over ``tokens`` live
    sequences, all linear layers of one decode step: each state read and
    written once; q, k, v, decay and beta read and the output written;
    seven operations an element of S (decay, S^T k, the rank-1 update, S^T q)."""
    c = _sizes(cfg)
    small = c["lh"] * (2 * c["dk"] + 2 * c["dv"] + 2) * 4
    moved = tokens * c["linear"] * (2 * state_bytes(cfg) + small)
    return 7.0 * tokens * c["linear"] * c["lh"] * c["dk"] * c["dv"], moved


def decode_step_cost(cfg: dict, tokens: float, kv_tokens: float, *, weight_bytes_each: float = 1.0,
                     kv_bytes: float = 2.0):
    """(flops, bytes) of one decode step over ``tokens`` live sequences that
    hold ``kv_tokens`` cached positions in total: the weights once, the
    states (``state_step_cost``), the convolution's tail read and written,
    keys and values of the full layers read for the cached positions and
    written for the new ones, the embedding rows gathered (float32)."""
    c = _sizes(cfg)
    state_flops, state_moved = state_step_cost(cfg, tokens)
    tail = tokens * c["linear"] * 2 * (c["width"] - 1) * conv_channels(cfg) * 2.0
    kv_row = 2 * c["kv"] * c["hd"] * kv_bytes
    moved = weight_bytes(cfg, weight_bytes=weight_bytes_each) + state_moved + tail
    moved += c["full"] * kv_row * (kv_tokens + tokens) + tokens * c["d"] * 4
    flops = 2.0 * tokens * matmul_params(cfg) + state_flops
    flops += 4.0 * c["full"] * c["heads"] * c["hd"] * kv_tokens
    flops += 2.0 * tokens * c["linear"] * c["width"] * conv_channels(cfg)
    return flops, moved


def chunk_scan_flops(cfg: dict, prompt_tokens: int) -> float:
    """Operations of the chunked gated delta rule over one prompt, all
    linear layers: per chunk of C tokens and head, K K^T and Q K^T
    (2 C^2 dk each), the triangular solve against [V | K] (C^2 (dk + dv)),
    the three products with the state (2 C dk dv each) and the two with the
    solved updates (2 C^2 dv, 2 C dk dv)."""
    c = _sizes(cfg)
    chunks = -(-prompt_tokens // CHUNK)
    per = 4 * CHUNK ** 2 * c["dk"] + CHUNK ** 2 * (c["dk"] + c["dv"]) + 2 * CHUNK ** 2 * c["dv"]
    per += 8 * CHUNK * c["dk"] * c["dv"]
    return float(c["linear"] * c["lh"] * chunks * per)


def prefill_cost(cfg: dict, prompt_tokens: int, *, weight_bytes_each: float = 1.0, kv_bytes: float = 2.0):
    """(flops, bytes) of one prompt of ``prompt_tokens`` true tokens (the
    bucket's padding is the program's cost): every matmul over the tokens,
    the head for the last position only, causal attention's half square in
    the full layers, the chunked scan in the linear ones; the weights once,
    keys and values written, each linear layer's state written once."""
    c = _sizes(cfg)
    n = prompt_tokens
    head = c["d"] * c["vocab"]
    flops = 2.0 * n * (matmul_params(cfg) - head) + 2.0 * head
    flops += 2.0 * c["full"] * c["heads"] * c["hd"] * n * n
    flops += chunk_scan_flops(cfg, n) + 2.0 * n * c["linear"] * c["width"] * conv_channels(cfg)
    moved = weight_bytes(cfg, weight_bytes=weight_bytes_each)
    moved += c["full"] * 2 * c["kv"] * c["hd"] * kv_bytes * n
    moved += c["linear"] * state_bytes(cfg) + n * c["d"] * 4
    return flops, moved


# ---- what the traced seconds held ----


def kernel_ms_per_step(run, pattern: str):
    """Device milliseconds the operations matching ``pattern`` take of one
    decode step, all layers together: their durations inside whole traced
    ``jit_decode_chunk`` runs, over those runs x ``chunk_steps`` (the
    reduction of ``paged_attn_ms_per_step``). A run is whole if it holds as
    many such calls as the fullest run traced. ``None`` where nothing was
    traced or the program has no such operation."""
    if run.trace is None:
        return None
    kernel = run.trace.ops_matching(pattern)
    per_run = []
    for start, end in run.trace.module_runs(r"^jit_decode_chunk\("):
        inside = [e - s for s, e in kernel if start <= s and e <= end]
        per_run.append((len(inside), sum(inside)))
    calls = max((n for n, _ in per_run), default=0)
    if not calls:
        return None
    whole = [t for n, t in per_run if n == calls]
    return 1e3 * sum(whole) / (len(whole) * run.record["chunk_steps"])


GDN_STEP_KERNEL = r"^%?gated_delta_step[.\s=]"  # the HLO instruction the pallas_call's name gives



def traced_load(run):
    """(live sequences, cached positions) resident on average over the
    traced seconds, from the client's records: a request is live from its
    first token to its last and holds its prompt and the tokens received so
    far. The engine frees a slot a few chunks after the client's last token,
    so this counts no more than the device served: a share computed from it
    cannot pass 100 % on that account. ``None`` where nothing was traced."""
    if run.record.get("trace_dir") is None:
        return None
    lo = run.record["t_zero"] + float(run.traffic["trace_from_s"])
    hi = lo + float(run.traffic["trace_seconds"])
    live = cached = 0.0
    for r in run.record["records"]:
        if r["error"] or len(r["t_tokens"]) < 2:
            continue
        a, b = max(r["t_tokens"][0], lo), min(r["t_tokens"][-1], hi)
        if b <= a:
            continue
        share = (b - a) / (hi - lo)
        # tokens received by the middle of the overlap
        got = sum(1 for t in r["t_tokens"] if t <= (a + b) / 2.0)
        live += share
        cached += share * (r["n_prompt"] + got)
    return live, cached
