"""Keye-VL-2.0's language model (``KeyeVL2``): attention over the positions a
learned indexer selects, on a pool whose rows hold keys, values and indexer
keys, a softmax-routed mixture, and the engine serving them.

Float32 on the CPU at a tiny size (hidden 64, 4 query heads over 2 key /
value heads of 16, an indexer of 4 heads of 8 that keeps ``topk`` 8
positions, three layers of 8 experts top-2) on seeded random weights,
against the plain reference (``unionml_tpu/models/keye_vl_moe_reference.py``:
dense index scores, ``top_k`` per query, softmax over the selected set, a
loop over experts, no cache).

Tolerances. Program and reference compute the same float32 numbers in
another order (blocks of queries and a threshold mask against a dense
``top_k``, a cache against a full pass, a pool's blocks walked group by
group, grouped against looped experts), which moves logits of size ~3 by a
few 1e-6: ``LOGIT_TOL`` is 1e-4. It holds only while both select the same
positions: with 8 picks of up to 124 a different pick moves a logit by
0.1-3 (a path without the selection lies 2-3.4 away), so the tolerance is
also the test that the sets are equal. A bfloat16 program is not held to
the reference here: at these widths its scores at the boundary pick another
position for a large share of tokens (``chipbench/tests/test_sparse_rehearsal.py``
holds the served form to limits read over seeds).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu import telemetry
from unionml_tpu.models import generate as generate_mod
from unionml_tpu.models import keye_vl_moe as keye_mod
from unionml_tpu.models import keye_vl_moe_reference as reference
from unionml_tpu.models.keye_vl_moe import KEYE_VL_MOE_QUANT_PATTERNS, KeyeVLMoe, KeyeVLMoeConfig
from unionml_tpu.models.layers import IndexedKVRows, KVRows
from unionml_tpu.models.quantization import quantize_params
from unionml_tpu.ops import paged_attention as paged
from unionml_tpu.ops import sparse_attention as sparse
from unionml_tpu.ops.attention import attention as xla_attention
from unionml_tpu.serving.engine import DecodeEngine
from unionml_tpu.serving.prefix_cache import RadixPrefixCache

LOGIT_TOL = 1e-4
VOCAB = 211


def _tiny(**over):
    return KeyeVLMoeConfig.tiny(vocab_size=VOCAB, dtype="float32", cache_dtype="float32", **over)


def _params(module, seed=3):
    return module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def served():
    module = KeyeVLMoe(_tiny())
    return module, _params(module)


def _reference_logits(params, tokens, cfg, positions=None, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, jnp.asarray([tokens]), cfg.to_hf(), positions, **kw))[0]


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).tolist() for n in lengths]


# ------------------------------------------------- (a) whole prompts, (e), (f)


@pytest.mark.parametrize("length", [40, 6, 8], ids=["over-topk", "under-topk", "at-topk"])
def test_model_forward_matches_reference(served, length):
    """Five times ``topk``, the selection active from the ninth query on;
    and no longer than ``topk``, where every query attends all it sees."""
    module, params = served
    (prompt,) = _prompts(length)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(module.apply({"params": params}, jnp.asarray([prompt])))[0]
        want, picked = reference.forward(
            params, jnp.asarray([prompt]), module.config.to_hf(), return_selected=True)
    assert np.abs(got - np.asarray(want)[0]).max() < LOGIT_TOL
    per_query = np.asarray(picked)[0, 0].sum(-1)
    assert per_query.tolist() == [min(t + 1, 8) for t in range(length)]


def test_a_path_that_skips_the_selection_fails_the_tolerance(served):
    """Dense attention over every position before a query is another model:
    the program with ``topk`` past the length lies as far from the
    reference as the reference's own dense form does."""
    _, params = served
    (prompt,) = _prompts(40)
    dense = KeyeVLMoe(_tiny(index_topk=64))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(dense.apply({"params": params}, jnp.asarray([prompt])))[0]
    want = _reference_logits(params, prompt, _tiny())
    assert np.abs(got - want).max() > 0.1
    assert np.abs(got - _reference_logits(params, prompt, _tiny(), select=False)).max() < LOGIT_TOL


def test_three_axis_positions_match_the_reference(served):
    """A frame's tokens share a temporal position and differ in height and
    width: each frequency pair turns by its section's axis, and the indexer
    by the temporal one."""
    module, params = served
    (prompt,) = _prompts(40, seed=1)
    t = np.arange(40)
    positions = np.stack([t // 4, (t // 2) % 5, t % 7])[:, None, :]           # [3, 1, 40], unequal axes
    def apply(**kw):
        return np.asarray(module.apply({"params": params}, jnp.asarray([prompt]), **kw))[0]

    with jax.default_matmul_precision("highest"):
        got = apply(positions=jnp.asarray(positions))
        plain = apply()
        equal = apply(positions=jnp.asarray(np.stack([t, t, t])[:, None, :]))
    want = _reference_logits(params, prompt, module.config, jnp.asarray(positions))
    assert np.abs(got - want).max() < LOGIT_TOL
    assert np.abs(got - plain).max() > 0.1              # the axes matter
    assert np.abs(equal - plain).max() < 1e-5           # three equal axes are plain rotary
    with pytest.raises(ValueError, match="mrope_section"):
        keye_mod.multi_axis_rotary(
            jnp.zeros((1, 4, 2, 16)), jnp.zeros((2, 1, 4), jnp.int32), (2, 3, 3), theta=1e4)


def test_int8_weights_are_read_as_the_reference_reads_them(served):
    module, params = served
    qparams = quantize_params(params, KEYE_VL_MOE_QUANT_PATTERNS)
    attn = qparams["block_0"]["attn"]
    assert all("kernel_q" in attn[name] for name in ("q", "k", "v", "o", "index_q"))
    assert "kernel" in attn["index_k"] and "kernel" in attn["index_w"]   # they decide a selection: float
    assert "w_gate_q" in qparams["block_0"]["moe"] and "kernel_q" in qparams["lm_head"]
    quantized = KeyeVLMoe(_tiny(quantized=True))
    (prompt,) = _prompts(40, seed=2)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(quantized.apply({"params": qparams}, jnp.asarray([prompt])))[0]
    assert np.abs(got - _reference_logits(qparams, prompt, module.config)).max() < LOGIT_TOL


def test_a_right_padded_prompts_padding_meets_no_expert(served, monkeypatch):
    """A whole prompt right-padded in its bucket, as the engine prefills it
    (contiguous rows, ``kv_mask`` over the real ones): with int8 experts
    (the grouped dispatch) the padding is sent to no expert, whatever it
    holds, and the last real position's logits are the unpadded prompt's."""
    from unionml_tpu.ops import moe

    module, params = served
    qparams = quantize_params(params, KEYE_VL_MOE_QUANT_PATTERNS)
    quantized = KeyeVLMoe(_tiny(quantized=True))
    (prompt,) = _prompts(40, seed=2)
    bucket = 64
    seen = []
    grouped = moe.grouped_expert_mlp
    monkeypatch.setattr(
        moe, "grouped_expert_mlp",
        lambda *a, valid=None, **kw: seen.append(valid) or grouped(*a, valid=valid, **kw),
    )
    cache = tuple(row.init(1, bucket) for row in quantized.cache_layout())
    tokens = jnp.asarray([prompt + [0] * (bucket - len(prompt))])
    with jax.default_matmul_precision("highest"):
        got, _ = quantized.apply(
            {"params": qparams}, tokens, cache=cache, cache_index=jnp.int32(0),
            kv_mask=(jnp.arange(bucket) < len(prompt))[None, :], logit_index=jnp.asarray([len(prompt) - 1]),
        )
        want = quantized.apply({"params": qparams}, jnp.asarray([prompt]))[0, -1]
    # three layers of the padded prompt, each with its mask, then three of the whole one without
    assert [None if v is None else int(v.sum()) for v in seen] == [len(prompt)] * 3 + [None] * 3
    assert np.abs(np.asarray(got)[0, 0] - np.asarray(want)).max() < LOGIT_TOL


def test_config_reads_the_published_keys_and_refuses_what_it_cannot_run():
    hf = {
        "vocab_size": 151936, "hidden_size": 2048, "num_hidden_layers": 48, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "moe_intermediate_size": 768, "num_experts": 128,
        "num_experts_per_tok": 8, "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "max_position_embeddings": 262144, "intermediate_size": 6144, "model_type": "KeyeVL2",
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    }
    cfg = KeyeVLMoeConfig.from_hf(hf, quantized=True)
    assert cfg == KeyeVLMoeConfig(quantized=True)        # the defaults are the published model
    assert (cfg.index_topk, cfg.indexer_num_heads, cfg.indexer_head_dim) == (2048, 16, 64)
    assert cfg.mrope_section == (16, 24, 24)
    back = cfg.to_hf()
    assert all(back[k] == hf[k] for k in ("num_experts", "head_dim", "num_key_value_heads", "rope_scaling"))
    assert back["sa_config"]["topk"] == 2048
    for key, value in (("norm_topk_prob", False), ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError, match=key):
            KeyeVLMoeConfig.from_hf(dict(hf, **{key: value}))
    with pytest.raises(ValueError, match="one key head"):
        KeyeVLMoeConfig.from_hf(dict(hf, sa_config=dict(hf["sa_config"], indexer_num_kv_heads=2)))
    with pytest.raises(ValueError, match="rope_scaling"):
        KeyeVLMoeConfig.from_hf(dict(hf, rope_scaling={"rope_type": "yarn", "factor": 4.0}))


# --------------------------------------------------- (c) the selection is exact


def _tied_scores(seed, rows=5, n=70):
    """Scores with many equal values, zeros, and a tail of ``-inf``."""
    rng = np.random.default_rng(seed)
    scores = rng.integers(-3, 4, (rows, n)).astype(np.float32) * 0.5
    scores[:, n - 9:] = -np.inf
    scores[1, 4:] = -np.inf                     # fewer visible than k
    scores[2] = np.where(np.arange(n) < n - 9, 0.0, -np.inf)    # all equal
    return scores


@pytest.mark.parametrize("scores", ["distinct", "tied-1", "tied-2", "tied-3"])
@pytest.mark.parametrize("k", [1, 8, 33])
def test_the_mask_selects_what_top_k_selects(k, scores):
    """Distinct scores and ties alike: the ``k`` largest, ties towards the
    lower position, never a ``-inf``; fewer than ``k`` visible selects them
    all. (``jax.lax.top_k``, a sort on the chip, is what no program runs any
    more: the mask is the selection's one form.)"""
    if scores == "distinct":
        scores = np.random.default_rng(9).standard_normal((5, 70)).astype(np.float32)
    else:
        scores = _tied_scores(int(scores[-1]))
    _, want = jax.lax.top_k(jnp.asarray(scores), k)
    mask = np.asarray(sparse.top_k_mask(jnp.asarray(scores), k))
    for r in range(scores.shape[0]):
        expect = {int(p) for p in np.asarray(want)[r] if np.isfinite(scores[r, p])}
        assert set(np.flatnonzero(mask[r]).tolist()) == expect


def test_kth_largest_key_is_the_kth_largest():
    rng = np.random.default_rng(4)
    parts = [rng.standard_normal((3, 50)) * 1e3, -rng.random((3, 14)) * 1e-3, np.zeros((3, 3))]
    scores = np.concatenate(parts, -1).astype(np.float32)
    keys = sparse.ordered_key(jnp.asarray(scores))
    order = np.argsort(np.asarray(keys).astype(np.int64), axis=-1)
    assert (np.take_along_axis(scores, order, -1) == np.sort(scores, -1)).all()   # the image keeps the order
    for k in (1, 5, 67):
        tau = np.asarray(sparse.kth_largest_key(keys, k))
        assert (tau == np.sort(np.asarray(keys), -1)[:, -k]).all()


def test_equal_scores_have_one_image_whatever_their_sign():
    """``w * relu(.)`` with a negative weight is ``-0.0``: the scores hold
    ``+0.0`` only, so that a tie of zeros is cut by position."""
    q = jnp.zeros((1, 3, 2, 4)).at[0, 0, 0, 0].set(1.0)
    k = jnp.ones((1, 5, 4))
    w = -jnp.ones((1, 3, 2))
    scores = np.asarray(sparse.index_scores(q, k, w))
    assert (scores[0, 0] == -1.0).all() and not np.signbit(scores[0, 1:]).any() and (scores[0, 1:] == 0).all()


def test_the_programs_selection_is_the_references(served):
    """A layer's own index scores: the threshold mask in blocks of queries
    against the reference's ``top_k``."""
    module, params = served
    cfg = module.config.to_hf()
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    p = params["block_1"]["attn"]
    with jax.default_matmul_precision("highest"):
        scores = reference.index_scores(x, p, cfg, jnp.arange(40))
        want = np.asarray(reference.selected(scores, 8))
        visible = np.tril(np.ones((40, 40), bool))
        got = np.asarray(sparse.top_k_mask(jnp.where(visible, scores, -jnp.inf), 8))
    assert (got == want).all() and want.sum(-1).tolist() == [min(t + 1, 8) for t in range(40)]


# ----------------------------------- (d) with topk >= length: ordinary attention


def _pools(rng, blocks=24, block=8, kv_heads=2, hd=16, index_dim=8):
    """(keys and values a row, indexer keys)."""
    kv = jnp.asarray(rng.standard_normal((blocks, block, 2 * kv_heads, hd)), jnp.float32)
    idx = jnp.asarray(rng.standard_normal((blocks, block, 128)), jnp.float32).at[..., index_dim:].set(0)
    return kv, idx


def _table(rng, lengths, block=8, width=6, blocks=24):
    table = np.zeros((len(lengths), width), np.int32)
    free = list(rng.permutation(blocks - 1) + 1)
    for r, n in enumerate(lengths):
        for j in range(-(-n // block)):
            table[r, j] = free.pop()
    return jnp.asarray(table)


def _selected_softmax(q, kv, table, selected, scale):
    """Grouped-query softmax attention over the selected positions of each
    row, one position at a time in float64: what the decode read must equal."""
    q, kv, table, selected = (np.asarray(x) for x in (q, kv, table, selected))
    block, kv_heads = kv.shape[1], kv.shape[2] // 2
    group = q.shape[1] // kv_heads
    out = np.zeros(q.shape, np.float64)
    for b in range(q.shape[0]):
        picks = np.flatnonzero(selected[b])
        if not picks.size:
            continue
        rows = kv[table[b, picks // block], picks % block].astype(np.float64)    # [K, 2 Hk, D]
        for h in range(q.shape[1]):
            s = rows[:, h // group] @ q[b, h].astype(np.float64) * scale
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ rows[:, kv_heads + h // group]
    return out


def _decode_read_case(rng, lengths, *, blocks=24, block=8, kv_heads=2, hd=16, q_heads=4, width=6):
    """(q, pool, table, lengths, scores): rows of ``lengths`` cached
    positions in shuffled pool blocks, their index scores ``-inf`` past the
    length as ``paged_index_scores`` leaves them."""
    kv, _ = _pools(rng, blocks=blocks, block=block, kv_heads=kv_heads, hd=hd)
    lengths = np.asarray(lengths)
    table = _table(rng, lengths, block=block, width=width, blocks=blocks)
    q = jnp.asarray(rng.standard_normal((len(lengths), q_heads, hd)), jnp.float32)
    scores = jnp.asarray(rng.standard_normal((len(lengths), width * block)), jnp.float32)
    scores = jnp.where(jnp.arange(width * block)[None, :] < jnp.asarray(lengths)[:, None], scores, -jnp.inf)
    return q, kv, table, jnp.asarray(lengths), scores


IMPLS = ["reference", "pallas"]


@pytest.mark.parametrize("impl", IMPLS)
def test_every_row_selected_is_paged_attention(impl, monkeypatch):
    """Rows no longer than ``topk`` select everything they see: the walk is
    ``paged_attention`` over the same pool, the last block partly filled, a
    retired slot among the live ones."""
    monkeypatch.setattr(paged, "_SPARSE_ROWS_PER_STEP", 16)      # three groups of two blocks a row
    q, kv, table, lengths, scores = _decode_read_case(np.random.default_rng(6), (40, 1, 17, 0))
    selected = sparse.top_k_mask(scores, 48)
    assert np.asarray(selected).sum(-1).tolist() == [40, 1, 17, 0]
    got = paged.paged_sparse_attention(q, kv, table, lengths, selected, impl=impl)
    want = paged.paged_attention(q, kv[:, :, :2], kv[:, :, 2:], table, lengths, impl="reference")
    assert np.abs(np.asarray(got)[:3] - np.asarray(want)[:3]).max() < 1e-5
    assert (np.asarray(got)[3] == 0).all()          # a retired slot selects nothing, walks nothing
    with pytest.raises(ValueError, match="2 \\* kv_heads, head_dim"):
        paged.paged_sparse_attention(q, kv.reshape(24, 8, 64), table, lengths, selected, impl=impl)
    with pytest.raises(ValueError, match="table_width \\* block_size"):
        paged.paged_sparse_attention(q, kv, table, lengths, selected[:, :40], impl=impl)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize(
    "lengths", [(40, 1, 17, 0), (9, 48, 0, 33), (0, 0, 48, 9)],
    ids=["mixed", "dead-between-live", "leading-empty"])
def test_the_decode_read_weighs_the_selected_rows_and_no_others(lengths, impl, monkeypatch):
    """The kernel (interpret mode, three groups of two blocks a row) and the
    plain form against the float64 softmax over the selected set, on a pool
    with shuffled block ids. What ``selected_rows_pct`` cannot show (the
    engine reckons it): every row outside the selection may hold anything
    *finite* (the walk fetches it and weighs it zero; the pool never holds
    anything else) and the output does not move."""
    monkeypatch.setattr(paged, "_SPARSE_ROWS_PER_STEP", 16)
    rng = np.random.default_rng(16)
    q, kv, table, lengths, scores = _decode_read_case(rng, lengths)
    selected = sparse.top_k_mask(scores, 8)
    assert np.asarray(selected).sum(-1).tolist() == np.minimum(np.asarray(lengths), 8).tolist()
    want = _selected_softmax(q, kv, table, selected, 0.25)
    clean = np.asarray(paged.paged_sparse_attention(q, kv, table, lengths, selected, impl=impl))
    assert np.abs(clean - want).max() < 1e-5
    assert not clean[np.asarray(lengths) == 0].any()
    block = kv.shape[1]
    picks = np.asarray(selected)
    keep = np.zeros(kv.shape[:2], bool)
    for r in range(picks.shape[0]):
        at = np.flatnonzero(picks[r])
        keep[np.asarray(table)[r, at // block], at % block] = True
    assert keep.sum() == np.minimum(np.asarray(lengths), 8).sum()
    poison = jnp.asarray(rng.choice([-1e4, 1e4], kv.shape), jnp.float32)
    poisoned = jnp.where(jnp.asarray(keep)[..., None, None], kv, poison)
    got = np.asarray(paged.paged_sparse_attention(q, poisoned, table, lengths, selected, impl=impl))
    assert np.isfinite(got).all() and np.abs(got - clean).max() < 1e-6
    # and a selected row does count: move one and its sequence's output moves
    live = int(np.flatnonzero(np.asarray(lengths))[0])
    at = int(np.flatnonzero(picks[live])[0])
    hit = kv.at[np.asarray(table)[live, at // block], at % block].add(3.0)
    moved = np.asarray(paged.paged_sparse_attention(q, hit, table, lengths, selected, impl=impl))
    assert np.abs(moved[live] - clean[live]).max() > 1e-3
    assert np.abs(np.delete(moved, live, 0) - np.delete(clean, live, 0)).max() < 1e-6


@pytest.mark.parametrize("impl", IMPLS)
def test_ties_at_the_last_selected_score_are_cut_towards_the_lower_position(impl, monkeypatch):
    """Equal index scores across the cut: the mask keeps the lower positions
    (what ``jax.lax.top_k`` keeps) and the read weighs exactly those."""
    monkeypatch.setattr(paged, "_SPARSE_ROWS_PER_STEP", 16)
    rng = np.random.default_rng(26)
    q, kv, table, lengths, _ = _decode_read_case(rng, (48, 30, 0, 11))
    scores = _tied_scores(2, rows=4, n=48)
    scores = np.where(np.arange(48)[None, :] < np.asarray(lengths)[:, None], scores, -np.inf)
    selected = sparse.top_k_mask(jnp.asarray(scores), 8)
    _, want = jax.lax.top_k(jnp.asarray(scores), 8)
    for r in range(4):
        expect = {int(p) for p in np.asarray(want)[r] if np.isfinite(scores[r, p])}
        assert set(np.flatnonzero(np.asarray(selected)[r]).tolist()) == expect
    first = np.asarray(selected)[0]
    assert set(scores[0, first].tolist()) & set(scores[0, ~first].tolist())       # the cut runs through a tie
    got = np.asarray(paged.paged_sparse_attention(q, kv, table, lengths, selected, impl=impl))
    assert np.abs(got - _selected_softmax(q, kv, table, selected, 0.25)).max() < 1e-5


def test_the_walk_at_the_cells_tile_shapes(monkeypatch):
    """Blocks of 64 positions of 4 + 4 heads of 128, 32 query heads, as the
    served cell's: a position's eight heads are one tile, a lane tile of
    score columns holds 16 positions, and the selection reaches the columns
    by the gather within a tile (the tiny pools above take ``jnp.repeat``).
    Two blocks a group; nine groups, so that the mask's rows span two
    sublane tiles; a row that ends in the middle of a block."""
    monkeypatch.setattr(paged, "_SPARSE_ROWS_PER_STEP", 128)
    rng = np.random.default_rng(36)
    q, kv, table, lengths, scores = _decode_read_case(
        rng, (1100, 0, 70, 1152), blocks=40, block=64, kv_heads=4, hd=128, q_heads=32, width=18)
    selected = sparse.top_k_mask(scores, 64)
    scale = 128 ** -0.5
    want = _selected_softmax(q, kv, table, selected, scale)
    got = np.asarray(paged.paged_sparse_attention(q, kv, table, lengths, selected, impl="pallas"))
    assert np.abs(got - want).max() < 1e-5 and not got[1].any()
    plain = np.asarray(paged.paged_sparse_attention(q, kv, table, lengths, selected, impl="reference"))
    assert np.abs(plain - want).max() < 1e-5


def test_a_cache_no_longer_than_topk_is_plain_causal_attention():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 12, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 12, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 12, 2, 16)), jnp.float32)
    iq = jnp.full((2, 12, 4, 8), jnp.nan)           # no index score is computed
    ik, iw = jnp.full((2, 12, 8), jnp.nan), jnp.full((2, 12, 4), jnp.nan)
    got = sparse.sparse_attention(q, k, v, iq, ik, iw, jnp.arange(12)[None, :], topk=12, scale=0.25)
    want = xla_attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True, scale=0.25)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_blocks_of_queries_change_nothing(monkeypatch):
    rng = np.random.default_rng(8)
    args = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in
            ((1, 64, 4, 16), (1, 64, 2, 16), (1, 64, 2, 16), (1, 64, 4, 8), (1, 64, 8), (1, 64, 4))]
    whole = sparse.sparse_attention(*args, jnp.arange(64)[None, :], topk=8, scale=0.25)
    monkeypatch.setattr(sparse, "_BLOCK_SCORE_BYTES", 4 * 16 * 64 * 4)     # blocks of 16 queries
    assert sparse._query_block(64, 64, 4) == 16
    blocked = sparse.sparse_attention(*args, jnp.arange(64)[None, :], topk=8, scale=0.25)
    assert np.abs(np.asarray(whole) - np.asarray(blocked)).max() < 1e-6


@pytest.mark.parametrize(
    "case", ["whole-prompt", "right-padded", "tiles-of-padding", "chunk-behind-cached-rows"])
def test_the_prefill_kernel_is_the_plain_softmax_over_the_selected_set(case, monkeypatch):
    """``sparse_prefill_select`` and ``sparse_prefill_attention`` in
    interpret mode at a head width of 128, two key heads of two query heads
    each, tiles of 128 queries x 512 keys with the tiles past a block's last
    visible position skipped: the plain form's numbers for every real
    query, zeros for a tile of padding."""
    rng = np.random.default_rng(11)
    seq, rows = (256, 1024) if case == "chunk-behind-cached-rows" else (1024, 1024)
    shapes = ((1, seq, 4, 128), (1, rows, 2, 128), (1, rows, 2, 128),
              (1, seq, 4, 16), (1, rows, 16), (1, seq, 4))
    args = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]
    q_pos = (512 if case == "chunk-behind-cached-rows" else 0) + jnp.arange(seq)[None, :]
    real = {"right-padded": 900, "tiles-of-padding": 600}.get(case, rows)
    kv_valid = (jnp.arange(rows) < real)[None, :] if real < rows else None
    assert not sparse._use_kernel(seq, rows, 128)                 # off the TPU: plain JAX
    plain = sparse.sparse_attention(*args, q_pos, kv_valid, topk=64, scale=0.1)
    monkeypatch.setattr(sparse, "_use_kernel", sparse._kernel_fits)   # whole tiles: the kernels, as on a TPU
    kernel = sparse.sparse_attention(*args, q_pos, kv_valid, topk=64, scale=0.1)
    assert np.abs(np.asarray(plain) - np.asarray(kernel))[:, :real].max() < 1e-5
    # a tile whose 128 queries are all padding is skipped: zeros
    assert not np.asarray(kernel)[:, -(-real // 128) * 128:].any()
    # shapes that are not whole tiles stay in plain JAX; the kernel itself refuses them
    short = [a[:, :100] for a in args]
    sparse.sparse_attention(*short, q_pos[:, :100], topk=64, scale=0.1)
    with pytest.raises(ValueError, match="whole tiles"):
        q, k, v = short[:3]
        sparse.masked_attention(q, k, v, jnp.ones((1, 100, 100), bool), jnp.zeros((1,), jnp.int32), scale=0.1)


@pytest.mark.parametrize(
    "seq,rows,topk,start,valid,ties",
    [
        (256, 1024, 64, 0, None, None), (256, 1024, 64, 512, None, None), (1024, 1024, 64, 0, 900, None),
        (1024, 1024, 64, 0, 600, None), (512, 1024, 64, 0, None, "every-key-twice"),
        (512, 1024, 64, 0, None, "all-scores-zero"), (512, 1536, 700, 1024, 1400, "every-key-twice"),
    ],
    ids=["whole-prompt", "chunk-behind-cached-rows", "right-padded", "tiles-of-padding", "ties",
         "all-tied", "ties-behind-cached-rows-padded"],
)
def test_the_select_kernel_picks_what_the_threshold_mask_picks(
        seq, rows, topk, start, valid, ties):
    """``sparse_prefill_select`` in interpret mode against ``top_k_mask``
    over ``index_scores``: the same set for every real query, ties cut
    towards the lower position, nothing past a tile's last visible key, a
    tile of padding left alone."""
    rng = np.random.default_rng(13)
    iq = jnp.asarray(rng.standard_normal((1, seq, 4, 16)), jnp.float32)
    ik = rng.standard_normal((1, rows, 16)).astype(np.float32)
    if ties == "every-key-twice":
        ik[:, 1::2] = ik[:, 0::2]
    iw = np.zeros((1, seq, 4)) if ties == "all-scores-zero" else rng.standard_normal((1, seq, 4))
    ik, iw = jnp.asarray(ik), jnp.asarray(iw, jnp.float32)
    pos = start + jnp.arange(seq)[None, :]
    kv_valid = None if valid is None else (jnp.arange(rows) < valid)[None, :]
    real = np.ones(seq, bool) if valid is None else np.asarray(pos)[0] < valid
    last = np.where(real, np.asarray(pos)[0], -1).reshape(-1, 128).max(axis=1)
    tiles = sparse.select_mask_tiles(iq, ik, iw, pos, kv_valid, jnp.asarray(last), topk=topk)
    assert tiles.shape == (1, seq // 128, rows // 512, 128, 512) and tiles.dtype == jnp.int8
    got = np.asarray(tiles).transpose(0, 1, 3, 2, 4).reshape(seq, rows) > 0
    visible = jnp.arange(rows)[None, None, :] <= pos[..., None]
    if kv_valid is not None:
        visible = visible & kv_valid[:, None, :]
    scores = jnp.where(visible, sparse.index_scores(iq, ik, iw), -jnp.inf)
    want = np.asarray(sparse.top_k_mask(scores, topk))[0]
    assert want[real].sum(axis=-1).max() == topk
    for i, limit in enumerate(last):
        tile, written = slice(128 * i, 128 * (i + 1)), (limit + 512) // 512 * 512
        assert (got[tile, :written][real[tile]] == want[tile, :written][real[tile]]).all()
        assert not want[tile, written:][real[tile]].any()


@pytest.mark.parametrize("lengths", [(40, 1, 17, 0), (0, 0, 48, 9)], ids=["mixed", "leading-empty"])
def test_paged_index_scores_kernel_matches_the_gather(lengths, monkeypatch):
    """The kernel in interpret mode, three groups of two blocks a row."""
    monkeypatch.setattr(paged, "_INDEX_ROWS_PER_STEP", 16)
    rng = np.random.default_rng(10)
    _, idx = _pools(rng)
    table = _table(rng, lengths)
    iq = jnp.asarray(rng.standard_normal((4, 4, 128)), jnp.float32).at[..., 8:].set(0)
    iw = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
    want = np.asarray(paged.paged_index_scores(iq, iw, idx, table, jnp.asarray(lengths), impl="reference"))
    got = np.asarray(paged.paged_index_scores(iq, iw, idx, table, jnp.asarray(lengths), impl="pallas"))
    assert got.shape == want.shape == (4, 48)
    for r, n in enumerate(lengths):
        assert np.isneginf(got[r, n:]).all() and np.isneginf(want[r, n:]).all()
        assert np.abs(got[r, :n] - want[r, :n]).max(initial=0.0) < 1e-4
    with pytest.raises(ValueError, match="index_w"):
        paged.paged_index_scores(iq, iw[:, :2], idx, table, jnp.asarray(lengths))


# ----------------------------------------------------------- (b) the engine


def _serve(monkeypatch, module, params, prompts, *, slots=2, new_tokens=24, paged=True, buckets=(32, 128),
           **engine_kw):
    """Serve ``prompts`` through a new engine and return, for each, its
    tokens and the logits the engine sampled them from, and the engine's
    stats at the end."""
    seen = []

    def make_sampler(**_):
        def sample(logits, key):
            jax.debug.callback(lambda rows: seen.append(np.asarray(rows)), logits, ordered=True)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return sample

    monkeypatch.setattr(generate_mod, "make_sampler", make_sampler)
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens, prompt_buckets=buckets, paged=paged,
        kv_block_size=16 if paged else None, chunk_steps=4, pipeline_depth=2,
        registry=telemetry.MetricsRegistry(), **engine_kw,
    )
    out = []
    try:
        for prompt in prompts:
            del seen[:]
            tokens = engine.generate(params, [prompt])[0]
            jax.effects_barrier()
            rows = [seen[0][0]] + [r[0] for r in seen[1:] if r.shape[0] == slots]
            out.append((tokens, np.stack(rows[:len(tokens)])))
        deadline = time.monotonic() + 30
        while paged and engine.stats()["kv_pool"]["blocks_in_use"] and time.monotonic() < deadline:
            time.sleep(0.02)
        stats = engine.stats()
    finally:
        engine.close()
    return out, stats


def _worst_gap(params, cfg, prompt, tokens, logits):
    want = _reference_logits(params, list(prompt) + list(tokens), cfg)
    return np.abs(logits - want[len(prompt) - 1:len(prompt) - 1 + len(tokens)]).max()


@pytest.mark.parametrize(
    "lengths,paged_impl,kw",
    [((40, 5), "reference", {}), ((100, 64), "pallas", {}),
     ((100, 70), "reference", {"prefill_chunk": 64}), ((20, 100), "reference", {"paged": False}),
     ((40, 5), "reference", {"prefill_impl": "flash"})],
    ids=["plain", "kernel", "chunked-prefill", "contiguous-cache", "prefill_impl-flash"],
)
def test_engine_serves_the_references_logits(monkeypatch, served, lengths, paged_impl, kw):
    """Right-padded in its bucket, prefilled (index scores, the selection as
    a mask and the softmax in blocks of queries; or in lead chunks that read
    the rows before them), committed to the pool by block scatter, keys,
    values and indexer keys alike, then 24 tokens decoded through the pool
    (``paged_index_scores``, the exact top-8 as a mask, the walk of
    ``paged_sparse_attention`` with it; the two kernels in interpret mode
    under ``paged_impl="pallas"``): every sampled row of logits is the
    reference's row of its full pass, with the selection active in both
    programs."""
    _, params = served
    kw = dict(kw)
    # the other decoders' value for whole prompts: the engine then says
    # ``full_prefill``, which changes nothing in this module
    module = KeyeVLMoe(_tiny(paged_impl=paged_impl, prefill_impl=kw.pop("prefill_impl", "cached")))
    prompts = _prompts(*lengths)
    results, stats = _serve(monkeypatch, module, params, prompts, **kw)
    for prompt, (tokens, logits) in zip(prompts, results):
        assert len(tokens) == 24
        assert _worst_gap(params, module.config, prompt, tokens, logits) < LOGIT_TOL
    if kw.get("paged", True):
        pool = stats["kv_pool"]
        # three layers of 2 x 32 + 128 float32 values as stored
        assert pool["row_layout"] == "kv+index" and pool["bytes_per_token"] == 3 * 4 * (64 + 128)
        assert pool["blocks_in_use"] == 0 and pool["freed_blocks"] == pool["allocated_blocks"] > 0
        assert stats["moe"]["decode_chunk"]["router"] == "softmax"
        attention = stats["attention"]
        assert attention["index_layers"] == 3 and attention["index_topk"] == 8
        assert attention["decode_read"] == "walk"       # a table of 160 positions over a topk of 8
        assert 0 < attention["selected_positions"] < attention["visible_positions"]


def test_a_short_table_takes_ordinary_paged_attention(monkeypatch, served):
    """A pool table no longer than ``topk`` cannot hold a position that is
    not selected: the decode step is ``paged_attention``, and serves the
    same logits."""
    _, params = served
    module = KeyeVLMoe(_tiny(index_topk=256))
    called = []
    real = keye_mod.paged_attention
    monkeypatch.setattr(keye_mod, "paged_attention", lambda *a, **kw: called.append(1) or real(*a, **kw))
    prompts = _prompts(20)
    results, stats = _serve(monkeypatch, module, params, prompts)
    assert called and stats["attention"]["decode_read"] == "dense"
    for prompt, (tokens, logits) in zip(prompts, results):
        want = _reference_logits(params, list(prompt) + list(tokens), module.config)
        assert np.abs(logits - want[len(prompt) - 1:len(prompt) - 1 + len(tokens)]).max() < LOGIT_TOL


def test_cache_layout_is_keys_values_and_an_indexer_key_a_layer():
    layout = KeyeVLMoe(KeyeVLMoeConfig(num_hidden_layers=12)).cache_layout()
    assert layout == (IndexedKVRows(4, 128, 64, "bfloat16"),) * 12
    row = layout[0]
    assert row.owns_rows and row.kind == "kv+index" and KVRows(4, 128).kind == "kv"
    assert row.row_nbytes() == 2048 + 128                      # what a position holds
    assert row.index_stored == 128 and row.pool_row_nbytes() == 2048 + 256   # and takes, in whole lane tiles
    assert row.pool_row == (4, 128, 2)
    kv, idx = row.init(3, 32)
    # a position's four key heads and four value heads: one bfloat16 tile of 8 x 128
    assert kv.shape == (3, 32, 8, 128) and idx.shape == (3, 32, 128) and idx.dtype == jnp.bfloat16
    # 12 layers: the issue's 27.6 KB a position
    assert 12 * row.pool_row_nbytes() == 27_648


# ------------------------------------- (g) a block prefix restores all three


def test_a_cached_prefix_admission_equals_a_cold_one(monkeypatch, served):
    """The prefix cache takes the three buffers' blocks as it takes K and V:
    the second admission of a prompt splices them, indexer keys included, and
    serves the cold one's logits with the selection reading restored rows; a
    prompt that shares 32 tokens prefills only its tail."""
    module, params = served
    shared = _prompts(32, seed=9)[0]
    first, second = shared + _prompts(9, seed=10)[0], shared + _prompts(14, seed=11)[0]
    cache = RadixPrefixCache(block_size=16, registry=telemetry.MetricsRegistry())
    results, stats = _serve(
        monkeypatch, module, params, [first, first, second], prefix_cache=cache, buckets=(64,),
    )
    (cold_t, cold_l), (warm_t, warm_l), (part_t, part_l) = results
    assert warm_t == cold_t and np.abs(warm_l - cold_l).max() < 1e-5
    assert _worst_gap(params, module.config, first, warm_t, warm_l) < LOGIT_TOL
    assert _worst_gap(params, module.config, second, part_t, part_l) < LOGIT_TOL
    pc = stats["prefix_cache"]
    assert pc["hits"] + pc["partial_hits"] >= 2 and pc["prefill_tokens_saved"] >= 64
    assert stats["kv_pool"]["blocks_in_use"] == 0


def test_a_preempted_stream_resumes_from_its_blocks(served):
    """Eviction extracts the victim's blocks (keys, values, indexer keys)
    into the host store and the resume splices them back: both streams end
    as their solo runs do."""
    module, params = served

    def engine(**kw):
        registry = telemetry.MetricsRegistry()
        return DecodeEngine(
            module, paged=True, registry=registry, slots=2, max_new_tokens=48, prompt_buckets=(64,),
            chunk_steps=2, pipeline_depth=2, kv_block_size=16,
            prefix_cache=RadixPrefixCache(block_size=16, registry=registry), **kw,
        )

    low_prompt, high_prompt = _prompts(20, 8, seed=12)
    solo = engine()
    try:
        want_low = solo.generate(params, [low_prompt])[0]
        want_high = solo.generate(params, [high_prompt], max_new_tokens=8)[0]
    finally:
        solo.close()
    eng = engine(kv_pool_blocks=6)  # capacity 5: one resident fits
    try:
        low_out, errors = [], []

        def low_client():
            try:
                for chunk in eng.generate_stream(params, low_prompt, priority="low"):
                    low_out.extend(chunk)
            except BaseException as exc:  # pragma: no cover - fails below
                errors.append(exc)

        t = threading.Thread(target=low_client)
        t.start()
        deadline = time.monotonic() + 60
        while not low_out and time.monotonic() < deadline:
            time.sleep(0.002)
        high_out = eng.generate(params, [high_prompt], max_new_tokens=8, priority="high")[0]
        t.join(timeout=120)
        assert not t.is_alive() and not errors
        assert high_out == want_high and low_out == want_low
        assert eng.stats()["scheduler"]["preemptions"] >= 1
    finally:
        eng.close()


def test_blocks_hand_off_between_engines(served):
    """``prefill_export`` on one engine, ``kv_export`` / ``kv_import`` to
    another's host store: the second engine splices the blocks and serves
    the first one's tokens."""
    module, params = served

    def engine():
        registry = telemetry.MetricsRegistry()
        return DecodeEngine(
            module, paged=True, registry=registry, slots=2, max_new_tokens=12, prompt_buckets=(64,),
            chunk_steps=2, kv_block_size=16, prefix_cache=RadixPrefixCache(block_size=16, registry=registry),
        )

    prompt = _prompts(40, seed=13)[0]
    donor, taker = engine(), engine()
    try:
        want = donor.generate(params, [prompt])[0]
        handle = donor.prefill_export(params, prompt)
        handle["lease"].release()
        assert handle["tokens"] == want[:1] and handle["cached_tokens"] >= 32
        entries = donor.kv_export(prompt)
        assert entries and taker.kv_import(entries) == len(entries)
        assert taker.generate(params, [prompt])[0] == want
        assert taker.stats()["prefix_cache"]["prefill_tokens_saved"] >= 32
    finally:
        donor.close()
        taker.close()


def test_speculation_over_the_paged_pool_is_refused_as_over_any_paged_pool(served):
    module, _ = served
    with pytest.raises(ValueError, match="speculative engine does not compose with the paged"):
        DecodeEngine(module, draft_module=module, speculate_k=2, paged=True, prompt_buckets=(32,))


def test_the_admit_span_and_the_perf_plane_carry_the_new_counters(served):
    module, params = served
    tracer = telemetry.get_tracer()
    seen = []
    tracer.add_listener(lambda rid, meta, spans: seen.append(spans))
    engine = DecodeEngine(
        module, paged=True, slots=2, max_new_tokens=8, prompt_buckets=(32,), kv_block_size=16, chunk_steps=2,
        registry=telemetry.MetricsRegistry(),
    )
    try:
        engine.generate(params, _prompts(20, seed=14))
        report = engine.perf.report()
        stats = engine.stats()
    finally:
        engine.close()
    admits = [s for spans in seen for s in spans if s["name"] == "admit"]
    assert admits and all(
        s["args"]["index_layers"] == 3 and s["args"]["latent_layers"] == 0 and s["args"]["state_layers"] == 0
        for s in admits
    )
    # 8 tokens in chunks of 2 steps from 20 cached rows: every step sees 21-29 rows and reads 8
    assert report["visible_positions"] >= sum(range(21, 28)) and report["selected_positions"] % 8 == 0
    assert report["selected_positions"] * 2 < report["visible_positions"]
    assert stats["attention"]["selected_positions"] == report["selected_positions"]


def test_an_engine_without_a_selection_counts_nothing():
    """Mixtral's family: nothing reads the counters there, so the
    dispatcher reckons nothing (``selected_rows_pct`` then reads nothing)
    and ``stats()`` has no ``attention`` entry."""
    from unionml_tpu.models import Llama, LlamaConfig

    module = Llama(LlamaConfig.tiny(vocab_size=64, num_layers=1, dtype="float32"))
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = DecodeEngine(
        module, paged=True, slots=2, max_new_tokens=6, prompt_buckets=(16,), kv_block_size=8, chunk_steps=2,
        registry=telemetry.MetricsRegistry(),
    )
    try:
        engine.generate(params, [[3, 4, 5, 6, 7]])
        report, stats = engine.perf.report(), engine.stats()
    finally:
        engine.close()
    assert report["visible_positions"] == report["selected_positions"] == 0 and "attention" not in stats
