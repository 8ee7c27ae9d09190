"""SDAR's mixture-of-experts decoder (``sdar_moe``): a decoder that generates
by diffusion over blocks, and the engine serving it from the block pool.

Float32 on the CPU at a tiny size (hidden 64, 4 query heads over 2 key /
value heads of 16, three layers of 8 experts top-2, blocks of 4) on seeded
random weights, against the plain reference
(``unionml_tpu/models/sdar_moe_reference.py``: dense scores under the
block-causal mask, a loop over experts, no cache, and the generation loop
written block after block, forward after forward).

Tolerances. Program and reference compute the same float32 numbers in
another order (a pool's blocks walked group by group against a full pass,
grouped against looped experts), which moves logits of size ~3 by a few
1e-6: ``LOGIT_TOL`` is 1e-4. Which entry a forward decides hangs on
confidences that, on random weights, lie within a part in a thousand of one
another: token identity with the reference's loop is held where it holds
(most prompts), and every forward's logits are held always, against the
reference's forward of the state the engine itself was in.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu import telemetry
from unionml_tpu.models import generate as generate_mod
from unionml_tpu.models import sdar_moe_reference as reference
from unionml_tpu.models.layers import BlockDiffusion, KVRows
from unionml_tpu.models.quantization import quantize_params
from unionml_tpu.models.sdar_moe import SDAR_MOE_QUANT_PATTERNS, SdarMoe, SdarMoeConfig
from unionml_tpu.ops.flash_attention import flash_attention
from unionml_tpu.serving.engine import DecodeEngine
from unionml_tpu.serving.scheduler import SchedulerConfig

LOGIT_TOL = 1e-4
VOCAB = 211
BK = 4


def _tiny(**over):
    kw = dict(
        vocab_size=VOCAB, dtype="float32", cache_dtype="float32", remasking_strategy="low_confidence_static",
    )
    kw.update(over)
    return SdarMoeConfig.tiny(**kw)


def _params(module, seed=3):
    return module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def served():
    module = SdarMoe(_tiny())
    return module, _params(module)


def _reference_logits(params, tokens, cfg, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, jnp.asarray([tokens]), cfg.to_hf(), **kw))[0]


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).tolist() for n in lengths]


# ----------------------------------------------------- (a) the model's logits


@pytest.mark.parametrize("length", [24, 7, 4], ids=["six-blocks", "a-partial-block", "one-block"])
def test_model_forward_matches_reference(served, length):
    module, params = served
    tokens = _prompts(length, seed=length)[0]
    got = np.asarray(module.apply({"params": params}, jnp.asarray([tokens])))[0]
    assert np.abs(got - _reference_logits(params, tokens, module.config)).max() < LOGIT_TOL


def test_a_plainly_causal_mask_fails_the_tolerance(served):
    """What the comparison is worth: the reference with a causal mask in
    the block-causal one's place lies far outside it."""
    module, params = served
    tokens = _prompts(24, seed=1)[0]
    got = np.asarray(module.apply({"params": params}, jnp.asarray([tokens])))[0]
    want = _reference_logits(params, tokens, module.config, mask="causal")
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_int8_weights_are_read_as_the_reference_reads_them(served):
    module, params = served
    qparams = quantize_params(params, patterns=SDAR_MOE_QUANT_PATTERNS)
    qmodule = SdarMoe(_tiny(quantized=True))
    tokens = _prompts(18, seed=2)[0]
    got = np.asarray(qmodule.apply({"params": qparams}, jnp.asarray([tokens])))[0]
    assert np.abs(got - _reference_logits(qparams, tokens, qmodule.config)).max() < LOGIT_TOL


def test_config_reads_the_published_keys_and_refuses_what_it_cannot_run():
    hf = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048,
        "max_position_embeddings": 32768, "mlp_only_layers": [], "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-6, "rope_scaling": None,
        "rope_theta": 1000000, "tie_word_embeddings": False, "vocab_size": 151936,
        "generation": {"block_length": 4, "denoising_steps": 4, "remasking_strategy": "low_confidence_static",
                       "mask_token_id": 151669},
    }
    cfg = SdarMoeConfig.from_hf(hf, quantized=True)
    assert (cfg.hidden_size, cfg.num_experts, cfg.head_dim, cfg.rope_theta) == (2048, 128, 128, 1e6)
    scheme = SdarMoe(cfg).generation_scheme()
    assert scheme == BlockDiffusion(4, 4, "low_confidence_static", 0.9, 151669)
    assert scheme.per_forward == 1 and scheme.forwards_per_block == 4
    assert cfg.to_hf()["generation"]["mask_token_id"] == 151669
    # bfloat16 rows of 4 key and 4 value heads, one whole 8 x 128 tile: 2,048 B a position
    assert SdarMoe(cfg).cache_layout() == (KVRows(4, 128, fused=True),) * 48
    assert KVRows(4, 128, fused=True).row_nbytes() == 2048
    assert [b.shape for b in KVRows(4, 128, fused=True).init(2, 16)] == [(2, 16, 8, 128)]
    assert KVRows(4, 128, dtype="float32").pool_row == (4, 128, 4)
    for key, bad in (
        ("norm_topk_prob", False), ("tie_word_embeddings", True), ("rope_scaling", {"type": "yarn"}),
    ):
        with pytest.raises(ValueError, match=key):
            SdarMoeConfig.from_hf({**hf, key: bad})
    with pytest.raises(ValueError, match="power of two"):
        BlockDiffusion(6, 3)
    with pytest.raises(ValueError, match="divide"):
        BlockDiffusion(8, 3)
    with pytest.raises(ValueError, match="remasking"):
        BlockDiffusion(4, 4, "random")


# ------------------------------------------- (d) the flash prefill's mask


@pytest.mark.parametrize("length,bucket", [(24, 32), (21, 32), (32, 32), (130, 256)],
                         ids=["whole-blocks", "partial-block", "full-bucket", "two-query-tiles"])
@pytest.mark.parametrize("bk", [4, 8])
def test_flash_block_causal_mask_matches_the_plain_one(length, bucket, bk):
    """A right-padded prompt through the flash kernel under the
    block-causal mask: the rows of its whole blocks are those of masked
    plain attention over the prompt alone (the padding and a trailing
    partial block are seen by no committed row)."""
    rng = np.random.default_rng(length + bk)
    q = jnp.asarray(rng.standard_normal((1, bucket, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, bucket, 2, 16)), jnp.float32) for _ in range(2))
    got = flash_attention(q, k, v, causal=True, kv_valid_start=jnp.zeros((1,), jnp.int32),
                          causal_block=bk, block_q=128, block_kv=128)[0]
    whole = length // bk * bk
    pos = np.arange(length)
    vis = (pos[None, :] // bk) <= (pos[:, None] // bk)
    kk, vv = (np.repeat(np.asarray(x)[0, :length], 2, axis=1) for x in (k, v))
    sc = np.einsum("qhd,khd->hqk", np.asarray(q)[0, :length], kk) / 4.0
    sc = np.where(vis[None], sc, -1e30)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), vv)
    assert np.abs(np.asarray(got)[:whole] - want[:whole]).max() < 1e-5


def test_flash_refuses_the_block_mask_outside_the_forward_only_path():
    x = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError, match="forward-only"):
        flash_attention(x, x, x, causal=True, causal_block=4)
    with pytest.raises(ValueError, match="power of two"):
        flash_attention(x, x, x, causal=True, causal_block=3, kv_valid_start=jnp.zeros((1,), jnp.int32))


# -------------------------------------------------- the rule, as a function


@pytest.mark.parametrize("rule,conf,cand,want", [
    ("low_confidence_static", [.1, .4, .3, .2], [1, 1, 1, 1], [0, 1, 0, 0]),
    ("low_confidence_static", [.1, .4, .4, .2], [1, 1, 1, 1], [0, 1, 0, 0]),      # a tie: the lower
    ("low_confidence_static", [.9, .4, .3, .2], [0, 0, 1, 1], [0, 0, 1, 0]),       # decided: no candidate
    ("low_confidence_dynamic", [.95, .4, .93, .2], [1, 1, 1, 1], [1, 0, 1, 0]),    # all that pass
    ("low_confidence_dynamic", [.5, .4, .3, .2], [1, 1, 1, 1], [1, 0, 0, 0]),      # none passes: the floor
    ("low_confidence_dynamic", [.95, .4, .93, .2], [0, 1, 0, 1], [0, 1, 0, 0]),
])
def test_the_rule_decides_what_the_references_rule_decides(rule, conf, cand, want):
    scheme = BlockDiffusion(4, 4, rule, 0.9, 0)
    cand = np.asarray(cand, bool)
    got = np.asarray(scheme.choose(jnp.asarray([conf], jnp.float32), jnp.asarray([cand])))[0]
    gen = {"block_length": 4, "denoising_steps": 4, "remasking_strategy": rule, "confidence_threshold": 0.9}
    assert got.tolist() == reference.decide(np.asarray(conf), cand, gen).tolist() == [bool(x) for x in want]


def test_two_entries_a_forward_where_the_steps_are_half_the_block():
    scheme = BlockDiffusion(4, 2)
    got = scheme.choose(jnp.asarray([[.1, .4, .3, .2]]), jnp.ones((1, 4), bool))
    assert np.asarray(got)[0].tolist() == [False, True, True, False]


# ------------------------------------------------------------ through the engine


def _serve(monkeypatch, module, params, prompts, *, asked=None, slots=2, new_tokens=24, buckets=(16, 64),
           together=False, **engine_kw):
    """Serve ``prompts`` through a new engine (one at a time, or all at
    once) and return for each its tokens, the forward that decided each,
    and (one at a time) the logits of every forward the engine ran while it
    was served, ``[forwards, slots, Bk, vocab]``; and the engine's stats."""
    seen, done = [], {}
    bk = module.config.block_length

    def make_sampler(**_):
        def sample(logits, key):
            jax.debug.callback(lambda rows: seen.append(np.asarray(rows)), logits, ordered=True)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return sample

    monkeypatch.setattr(generate_mod, "make_sampler", make_sampler)
    tracer = telemetry.TraceRecorder()
    tracer.add_listener(lambda rid, meta, spans: done.update({rid: (meta, spans)}))
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens, prompt_buckets=buckets, paged=True,
        kv_block_size=8, chunk_steps=4, pipeline_depth=2, registry=telemetry.MetricsRegistry(),
        tracer=tracer, **engine_kw,
    )
    asked = asked or [new_tokens] * len(prompts)
    out = []
    try:
        if together:
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(len(prompts)) as pool:
                futures = [
                    pool.submit(engine.generate, params, [p], max_new_tokens=n)
                    for p, n in zip(prompts, asked)
                ]
                out = [(f.result()[0], None, None) for f in futures]
        else:
            for prompt, n in zip(prompts, asked):
                _settle(engine)
                del seen[:]
                done.clear()
                tokens = engine.generate(params, [prompt], max_new_tokens=n)[0]
                _settle(engine)
                (meta, _), = [v for v in done.values() if v[0].get("events")]
                (event,) = [e for e in meta["events"] if e["name"] == "decided_at"]
                rows = list(seen)
                logits = np.stack(rows).reshape(len(rows), slots, bk, -1)
                out.append((tokens, event["args"]["forwards"], logits))
        deadline = time.monotonic() + 30
        while engine.stats()["kv_pool"]["blocks_in_use"] and time.monotonic() < deadline:
            time.sleep(0.02)
        stats = engine.stats()
        spans = [s for _, spans in done.values() for s in spans]
    finally:
        engine.close()
    return out, stats, spans


def _states(prompt, tokens, decided_at, cfg):
    """The states the reference's loop passes through for what was served:
    for every denoising forward, in order, ``(sequence, block start, the
    undecided asked entries, the entries it decided)``."""
    bk, mask_id = cfg.block_length, cfg.mask_token_id
    start, stop = len(prompt) // bk * bk, len(prompt) + len(tokens)
    seq = list(prompt) + list(tokens)
    at = [-1] * len(prompt) + list(decided_at)
    out = []
    while start < stop:
        idx = [i for i in range(start, start + bk)]
        forwards = 1 + max(at[i] for i in idx if i < stop)
        for f in range(forwards):
            state = [seq[i] if i < stop and at[i] < f else mask_id for i in idx]
            und = [j for j, i in enumerate(idx) if i < stop and at[i] >= f]
            now = [j for j, i in enumerate(idx) if i < stop and at[i] == f]
            out.append((seq[:start] + state, start, und, now))
        start += bk
    return out


def _check_every_forward(params, cfg, prompt, tokens, decided_at, logits, slot=0):
    """Every denoising forward's logits at its undecided entries are the
    reference's forward of the same state, and what it decided is what the
    rule decides from the reference's logits, unless a near-tie decides."""
    bk = cfg.block_length
    states = _states(prompt, tokens, decided_at, cfg)
    gen = cfg.to_hf()["generation"]
    # a chunk dispatched while the request before this one retired may have
    # run first (its slots dead): this request's forwards start where its
    # first state's logits are met, and follow one another from there
    seq, start, und, _ = states[0]
    want = _reference_logits(params, seq, cfg)[start:start + bk]
    i = next(k for k in range(len(logits)) if np.abs(logits[k, slot][und] - want[und]).max() < LOGIT_TOL)
    blocks = sorted({s[1] for s in states})
    for b, start in enumerate(blocks):
        mine = [s for s in states if s[1] == start]
        for seq, _, und, now in mine:
            want = _reference_logits(params, seq, cfg)[start:start + bk]
            got = logits[i, slot]
            assert np.abs(got[und] - want[und]).max() < LOGIT_TOL
            assert all(int(got[j].argmax()) == seq_final for j, seq_final in
                       zip(now, [(list(prompt) + list(tokens))[start + j] for j in now]))
            z = want - want.max(-1, keepdims=True)
            conf = (np.exp(z) / np.exp(z).sum(-1, keepdims=True)).max(-1)
            cand = np.zeros(bk, bool)
            cand[und] = True
            ref_now = np.flatnonzero(reference.decide(conf, cand, gen)).tolist()
            ranked = sorted(conf[und], reverse=True)
            near_tie = len(ranked) > len(now) and ranked[len(now) - 1] - ranked[len(now)] < 1e-4 * ranked[0]
            assert ref_now == now or near_tie
            i += 1      # (a block's commit rides with the next block's first forward)
    return i


@pytest.mark.parametrize("paged_impl,prefill_impl", [("reference", "cached"), ("pallas", "flash")],
                         ids=["plain", "kernels"])
def test_engine_serves_the_references_forwards(monkeypatch, served, paged_impl, prefill_impl):
    """(b) Right-padded in its bucket, prefilled under the block-causal
    mask, its whole blocks committed by block scatter, then decoded by
    blocks through the pool (``paged_attention`` with four queries a row;
    the kernel in interpret mode under ``paged_impl="pallas"``): every
    denoising forward's logits at the undecided entries are the
    reference's, for prompts of every residue mod 4."""
    _, params = served
    module = SdarMoe(_tiny(paged_impl=paged_impl, prefill_impl=prefill_impl))
    prompts = _prompts(20, 5, 10, 39, seed=5)
    results, stats, spans = _serve(monkeypatch, module, params, prompts, new_tokens=12)
    for prompt, (tokens, decided_at, logits) in zip(prompts, results):
        assert len(tokens) == len(decided_at) == 12 and set(decided_at) <= {0, 1, 2, 3}
        ran = _check_every_forward(params, module.config, prompt, tokens, decided_at, logits)
        assert ran <= len(logits)
    gen = stats["generation"]
    assert gen["scheme"] == "block_diffusion" and gen["block_length"] == 4 and gen["denoising_steps"] == 4
    assert gen["remasking"] == "low_confidence_static" and 0.75 <= gen["tokens_per_forward"] <= 1.0
    pool = stats["kv_pool"]
    assert pool["row_layout"] == "kv" and pool["blocks_in_use"] == 0
    assert pool["freed_blocks"] == pool["allocated_blocks"] > 0
    # fused rows: a forward's 8 queries x 4 heads against a group's positions, a key head at a time
    assert pool["score_tile"] == [8 * 4, pool["kernel_blocks_per_group"] * 8]
    assert stats["moe"]["decode_chunk"]["expert_rows_routed"] == 2 * 8 * 2     # slots x 2 Bk x top-2
    admits = [s for s in spans if s["name"] == "admit"]
    assert admits and admits[-1]["args"]["held_back"] == 39 % 4
    chunks = [s for s in spans if s["name"].startswith("decode-chunk")]
    assert chunks and all({"tokens", "forwards", "commits"} <= set(s["args"]) for s in chunks)
    assert sum(s["args"]["tokens"] for s in chunks) == 12


def test_tokens_are_the_references_loops_where_no_near_tie_decides(monkeypatch, served):
    module, params = served
    prompts = _prompts(8, 11, 3, 13, 6, seed=9)
    results, _, _ = _serve(monkeypatch, module, params, prompts, new_tokens=12)
    same = 0
    for prompt, (tokens, decided_at, _) in zip(prompts, results):
        with jax.default_matmul_precision("highest"):
            want, want_at = reference.generate(params, prompt, module.config.to_hf(), 12)
        same += tokens == want and decided_at == want_at
    assert same >= 3


# --------------------------------------------------------- (h) any length asked


@pytest.mark.parametrize("asked", [1, 3, 9, 8])
def test_any_number_of_tokens_for_prompts_of_every_residue(monkeypatch, served, asked):
    """The last block is computed whole and emitted cut; the entries past
    the asked length are never decided; ``n_emit`` sums to what was asked."""
    module, params = served
    prompts = _prompts(8, 9, 10, 11, seed=asked)
    results, stats, _ = _serve(monkeypatch, module, params, prompts, asked=[asked] * 4, new_tokens=12)
    for prompt, (tokens, decided_at, logits) in zip(prompts, results):
        assert len(tokens) == len(decided_at) == asked
        _check_every_forward(params, module.config, prompt, tokens, decided_at, logits)
    goodput = stats["goodput"]
    assert goodput["tokens_emitted"] == goodput["tokens_decided"] == 4 * asked
    # under the static rule a live forward decides one entry, and every block
    # but a request's last is committed by the next block's first forward:
    # no forward is left that decides nothing
    assert goodput["block_forwards"] == goodput["tokens_decided"]
    closed = sum(-(-(len(p) + asked) // BK) - len(p) // BK - 1 for p in prompts)
    assert goodput["block_commits"] == goodput["block_fused_commits"] == closed


# ------------------------------- (k) the commit rides with the next block's first forward


def _reference_rows(params, seq, cfg):
    """Every layer's cached rows of ``seq`` as the plain reference computes
    them: ``[layers, S, 2 Hk, D]``, a position's key heads (normalised,
    rotated) and its value heads behind them."""
    hf = cfg.to_hf()
    eps, theta = hf["rms_norm_eps"], float(cfg.rope_theta)
    kv_heads, hd, pos = cfg.num_key_value_heads, cfg.head_dim, jnp.arange(len(seq))
    out = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(jnp.float32)[jnp.asarray(seq)]
        for i in range(cfg.num_hidden_layers):
            blk = params[f"block_{i}"]
            h = reference._rms_norm(x, blk["attn_norm"]["scale"], eps)
            k, v = (
                reference._mm(h, reference._weight(blk["attn"][name], h.shape[-1]))
                .reshape(len(seq), kv_heads, hd)
                for name in ("k", "v")
            )
            k = reference._rope(reference._rms_norm(k, blk["attn"]["k_norm"]["scale"], eps), pos, theta)
            out.append(np.concatenate([np.asarray(k), np.asarray(v)], axis=1))
            x = reference.layer(x, blk, hf, pos)
    return np.stack(out)


def _engine_watched(monkeypatch, module, **kw):
    """An engine whose harvested chunks' ``info`` (``[forwards, slots, 4]``
    a chunk, with the slots it was dispatched for) and released requests'
    pool blocks are recorded."""
    infos, released = [], []
    process, release = DecodeEngine._process_block_chunk, DecodeEngine._release_blocks_locked

    def watch_chunk(self, mask, gens, outs, dispatched, seq):
        infos.append((np.asarray(mask).copy(), np.asarray(outs[2]).copy()))
        return process(self, mask, gens, outs, dispatched, seq)

    def watch_release(self, req, slot=None):
        if req._block_ids:
            released.append((list(req.prompt), list(req._block_ids)))
        return release(self, req, slot)

    monkeypatch.setattr(DecodeEngine, "_process_block_chunk", watch_chunk)
    monkeypatch.setattr(DecodeEngine, "_release_blocks_locked", watch_release)
    kw = {**dict(slots=2, max_new_tokens=24, prompt_buckets=(16, 64), paged=True, kv_block_size=8,
                 chunk_steps=4, pipeline_depth=2), **kw}
    engine = DecodeEngine(
        module, registry=telemetry.MetricsRegistry(), tracer=telemetry.TraceRecorder(), **kw,
    )
    return engine, infos, released


def _settle(engine):
    """Wait out a chunk dispatched while the last request retired."""
    deadline = time.monotonic() + 30
    while not engine._engine_empty() and time.monotonic() < deadline:
        time.sleep(0.01)
    jax.effects_barrier()


@pytest.mark.parametrize("paged_impl", ["reference", "pallas"], ids=["plain", "kernel"])
@pytest.mark.parametrize("length,asked", [(8, 12), (9, 11), (10, 5), (11, 9), (20, 4), (5, 16)])
def test_committed_rows_are_the_references_rows_of_the_final_tokens(
    monkeypatch, served, paged_impl, length, asked,
):
    """(a) Prompts of every residue, several asked lengths: what the pool
    holds for every block but the request's last (the prompt's whole blocks
    by the prefill, the generated ones each by the first half of the forward
    that opened the next) equals, row for row, the reference's keys and
    values of the final tokens; tokens and ``decided_at`` are the
    reference's loop's."""
    _, params = served
    module = SdarMoe(_tiny(paged_impl=paged_impl))
    cfg = module.config
    prompt = _prompts(length, seed=100 + length)[0]
    engine, infos, released = _engine_watched(monkeypatch, module)
    try:
        timeline = []
        engine._tracer.add_listener(lambda rid, meta, spans: timeline.append(meta))
        tokens = engine.generate(params, [prompt], max_new_tokens=asked)[0]
        _settle(engine)
        pool = [np.asarray(layer[0]) for layer in engine._state["pool"]]
    finally:
        engine.close()
    (decided_at,) = [
        e["args"]["forwards"] for m in timeline for e in m.get("events", []) if e["name"] == "decided_at"
    ]
    with jax.default_matmul_precision("highest"):
        want, want_at = reference.generate(params, prompt, cfg.to_hf(), asked)
    assert (tokens, decided_at) == (want, want_at)
    ((_, block_ids),) = released
    final = (length + asked - 1) // BK * BK          # the last block's first row: it is never committed
    want_rows = _reference_rows(params, prompt + tokens, cfg)[:, :final]
    got_rows = np.stack([layer[block_ids].reshape((-1,) + layer.shape[2:])[:final] for layer in pool])
    assert got_rows.shape == want_rows.shape and final >= 8
    assert np.abs(got_rows - want_rows).max() < LOGIT_TOL
    # every block that was closed was closed by a forward that denoised the next
    kinds = np.concatenate([info[:, :, 3][:, mask].reshape(-1) for mask, info in infos])
    assert set(kinds.tolist()) <= {0, 1, 3}
    assert (kinds == 3).sum() == final // BK - length // BK


def test_a_block_decided_in_one_forward_is_closed_by_the_very_next(monkeypatch, served):
    """(b) The dynamic rule with a threshold that every confidence passes:
    each forward decides a whole block, and from the second on each also
    closes the block before it, so a chunk of four forwards moves ``fill``
    on by four blocks, which the host's bound (``_chunk_advance``) allows
    for: every block's rows land in the request's own pool blocks and
    every forward's logits are the reference's."""
    _, params = served
    module = SdarMoe(_tiny(remasking_strategy="low_confidence_dynamic", confidence_threshold=0.0))
    prompts = _prompts(8, 13, seed=51)
    results, stats, _ = _serve(monkeypatch, module, params, prompts, new_tokens=24)
    for prompt, (tokens, decided_at, logits) in zip(prompts, results):
        assert len(tokens) == 24 and set(decided_at) == {0}
        _check_every_forward(params, module.config, prompt, tokens, decided_at, logits)
        with jax.default_matmul_precision("highest"):
            assert (tokens, decided_at) == reference.generate(params, prompt, module.config.to_hf(), 24)
    goodput = stats["goodput"]
    # 6 and 7 blocks, a forward each; all but each request's last closed, by the next one's forward
    sums = (goodput["block_forwards"], goodput["block_commits"], goodput["block_fused_commits"])
    assert sums == (13, 11, 11)
    assert stats["generation"]["tokens_per_forward"] > 3.5

    engine, infos, _ = _engine_watched(monkeypatch, module)
    try:
        assert engine._chunk_advance == BK * engine.chunk_steps and engine._step_rows == 2 * BK
        engine.generate(params, [prompts[0]], max_new_tokens=24)
        _settle(engine)
    finally:
        engine.close()
    kinds = np.concatenate([info[:, 0, 3] for mask, info in infos if mask[0]])
    assert kinds[kinds > 0].tolist() == [1, 3, 3, 3, 3, 3]
    # a chunk whose forwards closed a block each but the first: past a bound of one every second forward
    assert max(int((info[:, 0, 3] == 3).sum()) for _, info in infos) > -(-engine.chunk_steps // 2)


def test_a_slot_in_the_middle_of_a_block_beside_slots_that_close_one(monkeypatch, served):
    """(c) Prompts of residue 0, 1 and 2 admitted together run out of step
    (their first blocks take 4, 3 and 2 forwards): forwards in which one slot
    denoises alone (its second half dead) while another closes a block and
    opens the next. Each request's tokens are those it is served alone."""
    module, params = served
    prompts = _prompts(8, 9, 10, seed=61)
    alone, _, _ = _serve(monkeypatch, module, params, prompts, new_tokens=12)
    engine, infos, _ = _engine_watched(monkeypatch, module, slots=3)
    try:
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(3) as pool:
            futures = [pool.submit(engine.generate, params, [p], max_new_tokens=12) for p in prompts]
            together = [f.result()[0] for f in futures]
        _settle(engine)
    finally:
        engine.close()
    assert together == [t for t, _, _ in alone]
    mixed = sum(
        1 for _, info in infos for kinds in info[:, :, 3].tolist() if 1 in kinds and 3 in kinds
    )
    assert mixed > 0


# ------------------------------------------- (e) committed rows are final rows


def test_a_chunk_that_keeps_the_last_denoising_forwards_rows_fails(monkeypatch, served):
    """The broken path as a test: a block's rows kept from the forward that
    decided its last entry hold the mask token's keys and values at that
    entry, and the next block's forwards read them."""
    module, params = served
    monkeypatch.setattr(DecodeEngine, "_block_stale_commit", True)
    prompts = _prompts(9, seed=21)
    (tokens, decided_at, logits), = _serve(monkeypatch, module, params, prompts, new_tokens=12)[0]
    states = _states(prompts[0], tokens, decided_at, module.config)
    first = [s for s in states if s[1] == 8]
    later = [s for s in states if s[1] > 8]
    worst = 0.0
    for i, (seq, start, und, _) in enumerate(first + later):      # no commit forwards on this path
        want = _reference_logits(params, seq, module.config)[start:start + BK]
        gap = np.abs(logits[i, 0][und] - want[und]).max()
        if start == 8:
            assert gap < LOGIT_TOL          # the first block reads the prompt's rows only
        worst = max(worst, gap)
    assert worst > 100 * LOGIT_TOL


# ------------------------------------------------ (f) the mask id as a token


def test_a_prompt_and_a_served_token_equal_to_the_mask_id_change_nothing(monkeypatch, served):
    """Undecided entries are flags, never found by comparing ids: a prompt
    full of the mask token is served as any other, and with a mask id that
    the model itself emits, a served token equal to it stays decided."""
    module, params = served
    mask_id = module.config.mask_token_id
    prompts = [[mask_id] * 9, [mask_id if i % 2 else 7 for i in range(14)]]
    results, _, _ = _serve(monkeypatch, module, params, prompts, new_tokens=8)
    for prompt, (tokens, decided_at, logits) in zip(prompts, results):
        assert len(tokens) == 8
        _check_every_forward(params, module.config, prompt, tokens, decided_at, logits)


def test_a_sampler_that_only_ever_returns_the_mask_id_is_served_like_any_other(monkeypatch, served):
    """Every candidate is the mask id itself: an engine that found the
    undecided entries by comparing ids would never see one decided. Each
    forward still decides one entry, a block takes its four forwards, and
    the request ends with twelve tokens, all the mask id."""
    module, params = served
    mask_id = module.config.mask_token_id
    monkeypatch.setattr(
        generate_mod, "make_sampler",
        lambda **_: lambda logits, key: jnp.full(logits.shape[:1], mask_id, jnp.int32),
    )
    tracer, done = telemetry.TraceRecorder(), []
    tracer.add_listener(lambda rid, meta, spans: done.append(meta))
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=12, prompt_buckets=(16,), paged=True, kv_block_size=8,
        chunk_steps=4, registry=telemetry.MetricsRegistry(), tracer=tracer,
    )
    try:
        assert engine.generate(params, [_prompts(8, seed=2)[0]])[0] == [mask_id] * 12
        report = engine.perf.report()
    finally:
        engine.close()
    (at,) = [e["args"]["forwards"] for m in done for e in m.get("events", []) if e["name"] == "decided_at"]
    assert [sorted(at[i:i + 4]) for i in (0, 4, 8)] == [[0, 1, 2, 3]] * 3
    assert (report["block_forwards"], report["block_commits"], report["tokens_decided"]) == (12, 2, 12)
    assert report["block_fused_commits"] == 2


# ---------------------------------------------------- (g) the dynamic rule


def test_the_dynamic_rule_decides_several_entries_where_confidences_pass(monkeypatch, served):
    """Weights scaled so that some confidences pass the threshold: more
    than one entry a forward, fewer forwards a block, the decided sets the
    reference's."""
    _, params = served
    sharp = jax.tree_util.tree_map(lambda x: x, params)
    sharp["lm_head"] = {"kernel": params["lm_head"]["kernel"] * 60.0}
    module = SdarMoe(_tiny(remasking_strategy="low_confidence_dynamic", confidence_threshold=0.5))
    prompts = _prompts(8, 13, seed=31)
    results, stats, _ = _serve(monkeypatch, module, sharp, prompts, new_tokens=16)
    several = 0
    for prompt, (tokens, decided_at, logits) in zip(prompts, results):
        assert len(tokens) == 16
        _check_every_forward(sharp, module.config, prompt, tokens, decided_at, logits)
        with jax.default_matmul_precision("highest"):
            want, want_at = reference.generate(sharp, prompt, module.config.to_hf(), 16)
        assert (tokens, decided_at) == (want, want_at)
        several += sum(decided_at.count(f) > 1 for f in (0, 1))
    assert several > 0
    gen = stats["generation"]
    assert gen["tokens_per_forward"] > 1.0 and gen["forwards_per_block"] < 4


# -------------------------------------------- (i) neighbours, joining and leaving


def test_slots_joining_and_leaving_leave_their_neighbours_tokens_unchanged(monkeypatch, served):
    module, params = served
    prompts = _prompts(9, 14, 5, 20, 11, seed=40)
    asked = [12, 3, 9, 5, 12]
    alone, _, _ = _serve(monkeypatch, module, params, prompts, asked=asked, new_tokens=12)
    together, stats, _ = _serve(monkeypatch, module, params, prompts, asked=asked, new_tokens=12,
                                slots=3, together=True)
    assert [t for t, _, _ in together] == [t for t, _, _ in alone]
    assert stats["kv_pool"]["blocks_in_use"] == 0


def test_a_stream_carries_whole_blocks_and_its_first_event_is_the_first_block(served):
    module, params = served
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=12, prompt_buckets=(16,), paged=True, kv_block_size=8,
        chunk_steps=4, registry=telemetry.MetricsRegistry(), tracer=telemetry.TraceRecorder(),
    )
    try:
        prompt = _prompts(9, seed=3)[0]
        events = list(engine.generate_stream(params, prompt, max_new_tokens=11))
        assert [t for e in events for t in e] == engine.generate(params, [prompt], max_new_tokens=11)[0]
        assert len(events[0]) == 3            # 9 % 4 = 1 entry held back: the first block makes three
        assert engine.stats()["ttft_ms"]["n"] == 2
    finally:
        engine.close()


def test_eos_inside_a_block_ends_the_request_there(monkeypatch, served):
    module, params = served
    prompt = _prompts(8, seed=6)[0]
    (tokens, _, _), = _serve(monkeypatch, module, params, [prompt], new_tokens=12)[0]
    eos = tokens[5]
    (cut, _, _), = _serve(monkeypatch, module, params, [prompt], new_tokens=12, eos_id=int(eos))[0]
    assert cut == tokens[:tokens.index(eos) + 1]


# --------------------------------------------------- (j) what is refused, by name


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache="),
    (dict(system_prefix=[1, 2, 3]), "system_prefix="),
    (dict(prefill_chunk=8), "prefill_chunk="),
    (dict(scheduler=SchedulerConfig(preempt=True)), "preempt=True"),
    (dict(draft_module=SdarMoe(_tiny())), "draft_module="),
])
def test_what_cuts_or_reuses_a_sequence_is_refused_by_name(served, kw, what):
    module, _ = served
    with pytest.raises(ValueError, match=f"{what}.*generates by blocks"):
        DecodeEngine(module, paged=True, prompt_buckets=(16,), **kw)


def test_handoff_and_an_engine_without_a_pool_are_refused_by_name(served):
    module, params = served
    with pytest.raises(ValueError, match="generates by blocks.*paged=True"):
        DecodeEngine(module, prompt_buckets=(16,))
    with pytest.raises(ValueError, match="kv_block_size 6 must be a multiple"):
        DecodeEngine(module, paged=True, prompt_buckets=(16,), kv_block_size=6)
    engine = DecodeEngine(module, paged=True, prompt_buckets=(16,), kv_block_size=8,
                          registry=telemetry.MetricsRegistry())
    try:
        for call in (lambda: engine.prefill_export(params, [1, 2, 3]), lambda: engine.kv_export([1, 2, 3]),
                     lambda: engine.kv_import([])):
            with pytest.raises(ValueError, match="generates by blocks"):
                call()
    finally:
        engine.close()


@pytest.mark.parametrize("running", [False, True], ids=["no-slot-runs", "a-slot-in-the-middle-of-a-block"])
def test_the_mixture_is_always_handed_a_row(served, running):
    """A forward over a pool hands the mixture the rows that run, per row;
    where no slot runs (the steps of a chunk after its last request ended)
    it still hands the first row: with none the grouped kernel's tile maps
    point before the first tile, which no CPU path notices and the chip
    halts on."""
    from flax import linen as nn

    from unionml_tpu.ops.moe import MoEMlp

    module, params = served
    cfg, slots, rows = module.config, 2, 2 * BK
    live = jnp.zeros((slots, rows), bool).at[1, :BK].set(running)
    handed = []

    def watch(next_fun, args, kwargs, context):
        if isinstance(context.module, MoEMlp) and context.method_name == "__call__":
            handed.append(np.asarray(args[1]))
        return next_fun(*args, **kwargs)

    cache = tuple(layer.init(6, 8) for layer in module.cache_layout())       # a pool of six blocks of 8
    with nn.intercept_methods(watch):
        module.apply(
            {"params": params}, jnp.ones((slots, rows), jnp.int32), cache=cache,
            cache_index=jnp.asarray([8, 16]), block_table=jnp.asarray([[1, 2, 3], [4, 5, 0]]), live=live,
        )
    want = np.asarray(live).copy()
    want[0, 0] = True
    assert len(handed) == cfg.num_hidden_layers and all((v == want).all() for v in handed)


def test_the_kernel_is_handed_a_length_a_slot_and_a_limit_a_query(monkeypatch, served):
    """A slot that closes a block reads ``fill + 2 Bk`` rows, one in the
    middle of a block ``fill + Bk``, one that runs nothing **none** (at a
    stale ``fill`` it would walk the trash block); a query sees to the end
    of its own block; a dead row's keys and values go to the trash block."""
    from unionml_tpu.models import sdar_moe

    module, params = served
    calls, plain = [], sdar_moe.paged_attention

    def watch(q, rows, v, table, lengths, **kw):
        calls.append((np.asarray(lengths), np.asarray(kw["limits"]), np.asarray(rows)))
        return plain(q, rows, v, table, lengths, **kw)

    monkeypatch.setattr(sdar_moe, "paged_attention", watch)
    # three slots: one closes a block, one is in the middle of one, one runs nothing
    live = jnp.zeros((3, 2 * BK), bool).at[0].set(True).at[1, :BK].set(True)
    cache = tuple(layer.init(8, 8) for layer in module.cache_layout())      # a pool of eight blocks of 8
    _, new_cache = module.apply(
        {"params": params}, jnp.ones((3, 2 * BK), jnp.int32), cache=cache,
        cache_index=jnp.asarray([8, 20, 16]), live=live,
        block_table=jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 6], [0, 0, 0, 0]]),
    )
    assert len(calls) == module.config.num_hidden_layers
    for lengths, limits, _ in calls:
        assert lengths.tolist() == [16, 24, 0]
        assert limits[0].tolist() == [12] * 4 + [16] * 4 and limits[1].tolist() == [24] * 4 + [28] * 4
    (pool,) = (np.asarray(layer[0]) for layer in new_cache[:1])
    assert np.abs(pool[2]).sum() > 0 and np.abs(pool[5, 4:]).sum() > 0  # slot 0 rows 8-15, slot 1 rows 20-23
    assert not np.abs(pool[6]).sum() and not np.abs(pool[7]).sum()         # slot 1's dead rows 24-27: nowhere
    assert np.abs(pool[0]).sum() > 0                                       # ... but in the trash block


def test_every_other_module_generates_a_token_a_step():
    from unionml_tpu.models import Llama, LlamaConfig

    module = Llama(LlamaConfig.tiny(vocab_size=64, num_layers=1, dtype="float32"))
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = DecodeEngine(module, paged=True, slots=2, max_new_tokens=4, prompt_buckets=(16,),
                          kv_block_size=8, chunk_steps=2, registry=telemetry.MetricsRegistry())
    try:
        engine.generate(params, [[3, 4, 5]])
        assert engine.stats()["generation"] == {"scheme": "next_token"}
        assert engine.perf.report()["block_forwards"] == 0
    finally:
        engine.close()
