"""Olmo-Hybrid: the gated delta rule, the model, and the engine serving it.

Everything here is float32 on the CPU at a tiny size, on seeded random
weights, against the plain reference
(``unionml_tpu/models/olmo_hybrid_reference.py``) and, for the rule
itself, against ``transformers``' ``torch_recurrent_gated_delta_rule``.

Tolerances. Program and reference compute the same float32 numbers in
another order (chunks of 64 against token by token; a cache against a
full pass), which moves logits of size ~4 by a few 1e-4: ``LOGIT_TOL`` is
5e-3. What the tests at the end break on purpose moves them by 3e-2 or
more: a bfloat16 state, beta without its factor 2, a padded position that
touches the state, a state that is not written when a prefill ends.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import generate as generate_mod
from unionml_tpu.models import olmo_hybrid as hybrid_mod
from unionml_tpu.models import olmo_hybrid_reference as reference
from unionml_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from unionml_tpu.ops import gated_delta as gd
from unionml_tpu.serving import programs as programs_mod
from unionml_tpu.serving.engine import DecodeEngine
from unionml_tpu.serving.scheduler import SchedulerConfig

RULE_TOL = 2e-5    # the rule alone, float32 both sides, values of size ~1
LOGIT_TOL = 5e-3   # see the module docstring
VOCAB = 211


# ------------------------------------------------------------ (a) the rule


def _l2(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _rule_inputs(batch=2, seq=150, heads=4, dk=16, dv=64, seed=0):
    rng = np.random.default_rng(seed)
    f = np.float32
    return dict(
        q=_l2(rng.normal(size=(batch, seq, heads, dk))).astype(f),
        k=_l2(rng.normal(size=(batch, seq, heads, dk))).astype(f),
        v=rng.normal(size=(batch, seq, heads, dv)).astype(f),
        g=(-0.3 * np.exp(rng.normal(size=(batch, seq, heads)))).astype(f),
        # the factor 2 of linear_allow_neg_eigval, applied outside the rule
        beta=(2.0 / (1.0 + np.exp(-rng.normal(size=(batch, seq, heads))))).astype(f),
        state=rng.normal(size=(batch, heads, dk, dv)).astype(f),
    )


def _torch_rule(x, upto=None, state=True):
    import torch

    from transformers.models.qwen3_next.modeling_qwen3_next import torch_recurrent_gated_delta_rule

    t = lambda a: torch.tensor(a[:, :upto] if upto is not None else a)  # noqa: E731
    o, s = torch_recurrent_gated_delta_rule(
        t(x["q"]), t(x["k"]), t(x["v"]), t(x["g"]), t(x["beta"]),
        torch.tensor(x["state"]) if state else None, True,
    )
    return o.numpy(), s.numpy()


@pytest.mark.parametrize("with_state", [False, True], ids=["zero-state", "given-state"])
def test_chunked_recurrent_and_torch_agree(with_state):
    x = _rule_inputs()
    heads = x["q"].shape[2]
    if not with_state:
        x["state"] = np.zeros_like(x["state"])
    want_o, want_s = _torch_rule(x)
    packed = gd.pack_state(jnp.asarray(x["state"]))
    o, s = gd.gated_delta_chunked(x["q"], x["k"], x["v"], x["g"], x["beta"], packed)
    assert np.abs(np.asarray(o) - want_o).max() < RULE_TOL
    assert np.abs(np.asarray(gd.unpack_state(s, heads)) - want_s).max() < RULE_TOL
    # token by token through the decode step
    s, outs = packed, []
    for t in range(x["q"].shape[1]):
        o_t, s = gd.gated_delta_step(
            x["q"][:, t], x["k"][:, t], x["v"][:, t], x["g"][:, t], x["beta"][:, t], s, impl="reference",
        )
        outs.append(np.asarray(o_t))
    assert np.abs(np.stack(outs, 1) - want_o).max() < RULE_TOL
    assert np.abs(np.asarray(gd.unpack_state(s, heads)) - want_s).max() < RULE_TOL


@pytest.mark.parametrize("valid_len", [1, 37, 64, 100], ids=lambda n: f"valid-{n}")
def test_positions_past_valid_len_leave_the_state(valid_len):
    """A right-padded bucket: the state after 150 positions of which
    ``valid_len`` are real is the state after ``valid_len`` tokens."""
    x = _rule_inputs(batch=1)
    want_o, want_s = _torch_rule(x, upto=valid_len)
    o, s = gd.gated_delta_chunked(
        x["q"], x["k"], x["v"], x["g"], x["beta"], gd.pack_state(jnp.asarray(x["state"])),
        valid_len=jnp.asarray([valid_len]),
    )
    assert np.abs(np.asarray(o)[:, :valid_len] - want_o).max() < RULE_TOL
    assert np.abs(np.asarray(gd.unpack_state(s, 4)) - want_s).max() < RULE_TOL


# ------------------------------------------------- (d) the kernel's math


@pytest.mark.parametrize(
    "live", [[True] * 5, [False, True, False, True, False], [False, False, False, False, True], [False] * 5],
    ids=["all", "alternate", "last", "none"],
)
@pytest.mark.parametrize(
    "heads,dk,dv", [(4, 16, 64), (6, 8, 128), (6, 96, 192)], ids=["two-a-row", "one-a-row", "cell-heads"],
)
def test_pallas_step_matches_xla_step(live, heads, dk, dv):
    """The ``gated_delta_step`` kernel in interpret mode against the XLA
    step: live rows agree, dead rows keep their state bit for bit.
    ``cell-heads`` is the served model's head (``d_k`` 96, no multiple of
    the 128 lanes the keys arrive on; two heads a row), fewer of them."""
    x = _rule_inputs(batch=5, seq=1, heads=heads, dk=dk, dv=dv, seed=3)
    args = [x[n][:, 0] for n in ("q", "k", "v", "g", "beta")]
    state = gd.pack_state(jnp.asarray(x["state"]))
    live = jnp.asarray(live)
    want_o, want_s = gd.gated_delta_step(*args, state, live, impl="reference")
    got_o, got_s = gd.gated_delta_step(*args, state, live, impl="pallas")
    mask = np.asarray(live)
    assert np.abs(np.asarray(got_o) - np.asarray(want_o))[mask].max(initial=0.0) < RULE_TOL
    assert np.abs(np.asarray(got_s) - np.asarray(want_s))[mask].max(initial=0.0) < RULE_TOL
    assert np.array_equal(np.asarray(got_s)[~mask], np.asarray(state)[~mask])


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_tokens_one_at_a_time_match_one_cached_call(monkeypatch, impl):
    """A ``GatedDeltaNet`` fed T tokens through its decode step (``seq == 1``,
    vector ``cache_index``) and through one multi-token cached call, from the
    same state and convolution tail: the step's convolution over the flat
    tail's slices against the prefill's over stacked rows, the step kernel
    against the chunked rule."""
    import functools

    cfg = _tiny(linear_num_key_heads=6, linear_num_value_heads=6, linear_key_head_dim=16,
                linear_value_head_dim=64)
    monkeypatch.setattr(hybrid_mod, "gated_delta_step", functools.partial(gd.gated_delta_step, impl=impl))
    layer = hybrid_mod.GatedDeltaNet(cfg)
    batch, seq = 3, 6
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(batch, seq, cfg.hidden_size)), jnp.float32)
    cache = (
        jnp.asarray(rng.normal(size=(batch,) + gd.state_shape(6, 16, 64)), jnp.float32),
        jnp.asarray(rng.normal(size=(batch, 3 * cfg.conv_channels)), jnp.float32),
    )
    params = layer.init(jax.random.PRNGKey(2), x, cache=cache, cache_index=0)
    want, (want_state, want_tail) = layer.apply(params, x, cache=cache, cache_index=0)
    got, live = [], jnp.ones((batch,), bool)
    for t in range(seq):
        index = jnp.full((batch,), t)
        out, cache = layer.apply(params, x[:, t:t + 1], cache=cache, cache_index=index, live=live)
        got.append(out)
    assert np.abs(np.asarray(jnp.concatenate(got, axis=1)) - np.asarray(want)).max() < RULE_TOL
    assert np.abs(np.asarray(cache[0]) - np.asarray(want_state)).max() < RULE_TOL
    assert np.array_equal(np.asarray(cache[1]), np.asarray(want_tail))


def test_state_packing_round_trips():
    s = jnp.asarray(np.random.default_rng(0).normal(size=(3, 30, 96, 192)).astype(np.float32))
    packed = gd.pack_state(s)
    assert packed.shape == (3,) + gd.state_shape(30, 96, 192) == (3, 15, 96, 384)
    assert np.array_equal(np.asarray(gd.unpack_state(packed, 30)), np.asarray(s))


# ------------------------------------------------------------ (b) the model


def _seeded_params(module, seed=3):
    """Random weights by leaf kind: int8 values with scales that give the
    lecun deviation, unit norm scales, standard-normal decay parameters."""
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rng = np.random.default_rng(seed)
    by_parent = {}
    for path, leaf in flat:
        by_parent.setdefault(tuple(p.key for p in path[:-1]), {})[path[-1].key] = leaf
    leaves = []
    for path, leaf in flat:
        name, parent = path[-1].key, tuple(p.key for p in path[:-1])
        if leaf.dtype == jnp.int8:
            value = rng.integers(-128, 128, leaf.shape, dtype=np.int8)
        elif name == "scale" and "kernel_q" in by_parent[parent]:
            fan_in = by_parent[parent]["kernel_q"].shape[0]
            value = np.full(leaf.shape, 1.0 / (74.0 * np.sqrt(fan_in)), np.float32)
        elif name == "scale":
            value = np.ones(leaf.shape, np.float32)
        elif name == "embedding":
            value = 0.02 * rng.normal(size=leaf.shape)
        else:
            fan_in = int(np.prod(leaf.shape[:-1])) if len(leaf.shape) >= 2 else 1
            if len(leaf.shape) == 3 and path[-2].key in ("q", "k", "v"):
                fan_in = leaf.shape[0]
            value = rng.normal(size=leaf.shape) / np.sqrt(fan_in)
        leaves.append(jnp.asarray(value, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _tiny(**over):
    return OlmoHybridConfig.tiny(vocab_size=VOCAB, dtype="float32", **over)


@pytest.fixture(scope="module")
def served():
    module = OlmoHybrid(_tiny())
    return module, _seeded_params(module)


def _reference_logits(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, jnp.asarray([tokens]), cfg.to_hf()))[0]


@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
def test_model_forward_matches_reference(quantized):
    module = OlmoHybrid(_tiny(quantized=quantized))
    params = _seeded_params(module)
    tokens = np.random.default_rng(1).integers(1, VOCAB, 150).tolist()
    got = np.asarray(module.apply({"params": params}, jnp.asarray([tokens])))[0]
    assert np.abs(got - _reference_logits(params, tokens, module.config)).max() < LOGIT_TOL


def test_cache_layout_names_each_layer():
    from unionml_tpu.models.layers import KVRows, SlotState

    layout = OlmoHybrid(OlmoHybridConfig()).cache_layout()
    assert [type(l) for l in layout] == [SlotState, SlotState, SlotState, KVRows] * 8
    assert layout[3] == KVRows(32, 128)  # 30 heads, padded as the chip pads them
    # S [30, 96, 192] float32, two heads a row, and three rows of q~, k~, v~
    assert layout[0].shapes == ((15, 96, 384), (3 * 11520,))
    assert layout[0].nbytes() == 30 * 96 * 192 * 4 + 3 * 11520 * 2
    assert layout[3].row_nbytes() == 2 * 32 * 128 * 2


# ----------------------------------------------------------- (c) the engine


def _serve(monkeypatch, module, params, prompts, *, slots=2, new_tokens=40, prefill_chunk=None, paged=True):
    """Serve ``prompts`` one after another through a new engine and return,
    for each, its tokens and the logits the engine sampled them from."""
    seen = []

    def make_sampler(**_):
        def sample(logits, key):
            jax.debug.callback(lambda rows: seen.append(np.asarray(rows)), logits, ordered=True)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return sample

    monkeypatch.setattr(generate_mod, "make_sampler", make_sampler)
    engine = DecodeEngine(
        module, slots=slots, max_new_tokens=new_tokens, prompt_buckets=(32, 128),
        prefill_chunk=prefill_chunk, paged=paged, kv_block_size=16 if paged else None,
        chunk_steps=4, pipeline_depth=2,
    )
    out = []
    try:
        for prompt in prompts:
            del seen[:]
            tokens = engine.generate(params, [prompt])[0]
            jax.effects_barrier()
            # the prefill's row, then one row a decode step of the request's
            # slot (the lowest free one: slot 0, the engine being idle)
            rows = [seen[0][0]] + [r[0] for r in seen[1:] if r.shape[0] == slots]
            out.append((tokens, np.stack(rows[:len(tokens)])))
    finally:
        engine.close()
    return out


def _worst_gap(params, cfg, prompt, tokens, logits):
    want = _reference_logits(params, list(prompt) + list(tokens), cfg)
    return np.abs(logits - want[len(prompt) - 1:len(prompt) - 1 + len(tokens)]).max()


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).tolist() for n in lengths]


@pytest.mark.parametrize(
    "lengths,prefill_chunk,paged",
    [((20, 5), None, True), ((64, 128), None, True), ((100, 70, 128), 64, True), ((20, 100), None, False)],
    ids=["shorter-than-a-chunk", "whole-chunks", "chunked-prefill", "contiguous-cache"],
)
def test_engine_serves_the_references_logits(monkeypatch, served, lengths, prefill_chunk, paged):
    """Right-padded in its bucket, prefilled (in one program or in lead
    chunks that carry the state), then 40 tokens decoded through the
    slot's state and the paged pool: every sampled row of logits is the
    reference's row of its full pass over prompt + tokens."""
    module, params = served
    prompts = _prompts(*lengths)
    results = _serve(monkeypatch, module, params, prompts, prefill_chunk=prefill_chunk, paged=paged)
    for prompt, (tokens, logits) in zip(prompts, results):
        assert len(tokens) == 40
        assert _worst_gap(params, module.config, prompt, tokens, logits) < LOGIT_TOL


def test_a_reused_slot_serves_what_a_fresh_engine_serves(monkeypatch, served):
    module, params = served
    first, second = _prompts(90, 23, seed=5)
    reused = _serve(monkeypatch, module, params, [first, second], slots=1)[1]
    fresh = _serve(monkeypatch, module, params, [second], slots=1)[0]
    assert reused[0] == fresh[0]
    assert np.abs(reused[1] - fresh[1]).max() < 1e-6
    assert _worst_gap(params, module.config, second, *reused) < LOGIT_TOL


def _broken_gap(monkeypatch, module, params, prompts=None, **kwargs):
    prompts = prompts or _prompts(100)
    results = _serve(monkeypatch, module, params, prompts, **kwargs)
    # against the reference of the model as published: unbroken
    return max(_worst_gap(params, _tiny(), p, *r) for p, r in zip(prompts, results))


def test_a_bfloat16_state_is_caught(monkeypatch, served):
    module = OlmoHybrid(_tiny(state_dtype="bfloat16"))
    assert _broken_gap(monkeypatch, module, served[1]) > 3 * LOGIT_TOL


def test_beta_without_its_factor_two_is_caught(monkeypatch, served):
    module = OlmoHybrid(_tiny(linear_allow_neg_eigval=False))
    assert _broken_gap(monkeypatch, module, served[1]) > 3 * LOGIT_TOL


def test_a_padded_position_that_touches_the_state_is_caught(monkeypatch, served):
    chunked = hybrid_mod.gated_delta_chunked
    monkeypatch.setattr(
        hybrid_mod, "gated_delta_chunked",
        lambda q, k, v, g, beta, state, valid_len: chunked(q, k, v, g, beta, state),
    )
    assert _broken_gap(monkeypatch, *served) > 3 * LOGIT_TOL


def test_a_state_left_stale_on_slot_reuse_is_caught(monkeypatch, served):
    splice = programs_mod._splice_rows

    def skip_states(dst, src, b_start, r_start):
        # the three linear layers' (S, tail) pairs: leave them as the slot's
        # last occupant left them
        states = len(dst) == 3 and all(len(layer) == 2 and layer[1].ndim == 2 for layer in dst)
        return dst if states else splice(dst, src, b_start, r_start)

    monkeypatch.setattr(programs_mod, "_splice_rows", skip_states)
    assert _broken_gap(monkeypatch, *served, prompts=_prompts(90, 23, seed=5), slots=1) > 3 * LOGIT_TOL


# ------------------------------------------------- (e) what is refused, seen


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(prefix_cache=True), dict(system_prefix=[1, 2, 3]),
        dict(draft_module=OlmoHybrid(_tiny())), dict(scheduler=SchedulerConfig(preempt=True)),
    ],
    ids=["prefix_cache", "system_prefix", "draft_module", "preemption"],
)
def test_what_restores_from_kv_blocks_alone_is_refused(served, kwargs):
    with pytest.raises(ValueError, match="recurrent state that a block prefix does not restore"):
        DecodeEngine(served[0], slots=2, prompt_buckets=(32,), paged=True, **kwargs)


def test_a_draft_with_recurrent_layers_is_refused():
    """A rejected proposal cannot be rolled back out of a recurrent state:
    the refusal reads the draft's own ``cache_layout()``, not the target's."""
    from unionml_tpu.models.llama import Llama, LlamaConfig

    target = Llama(LlamaConfig.tiny(vocab_size=VOCAB))
    with pytest.raises(ValueError, match="recurrent state that a block prefix does not restore"):
        DecodeEngine(target, slots=2, prompt_buckets=(32,), draft_module=OlmoHybrid(_tiny()))


@pytest.mark.parametrize("call", ["prefill_export", "kv_export", "kv_import"])
def test_kv_handoff_is_refused(served, call):
    module, params = served
    engine = DecodeEngine(module, slots=2, prompt_buckets=(32,), paged=True)
    try:
        args = {"prefill_export": (params, [1, 2, 3]), "kv_export": ([1, 2, 3],), "kv_import": ([],)}[call]
        with pytest.raises(ValueError, match="recurrent state that a block prefix does not restore"):
            getattr(engine, call)(*args)
    finally:
        engine.close()


def test_a_module_without_a_cache_layout_is_refused():
    class Bare:
        config = _tiny()

    with pytest.raises(TypeError, match="cache_layout"):
        DecodeEngine(Bare(), slots=2, prompt_buckets=(32,))


def test_the_state_is_counted_where_the_pool_is(served):
    module, params = served
    engine = DecodeEngine(
        module, slots=2, max_new_tokens=4, prompt_buckets=(32,), paged=True, kv_block_size=16,
    )
    try:
        engine.generate(params, _prompts(9))
        per_slot = 3 * (4 * 8 * 64 * 4 + 3 * 320 * 4)  # S float32 and the tail, three layers
        stats = engine.stats()
        assert stats["state"] == {
            "layers": 3, "bytes_per_slot": per_slot, "bytes_resident": 2 * per_slot,
            # keys and queries, values, the output: a tile a slot each; alpha and beta: a tile each
            "step_operand_bytes": gd.step_operand_bytes(2, 4, 8, 64),
        }
        assert stats["state"]["step_operand_bytes"] == 6 * 8 * 128 * 4 + 2 * 8 * 128 * 4
        assert stats["goodput"]["state_bytes_resident"] == 2 * per_slot
        assert stats["goodput"]["admissions_parked_on_pool"] == 0
        # only the full-attention layer owns pool rows (its 4 heads are 16
        # in the cache: kv_cache_heads)
        assert engine.kv_pool.block_nbytes == 16 * 2 * 16 * 16 * 2
        gauge = engine._registry.exposition()
        assert f'unionml_engine_recurrent_state_bytes{{engine="{engine.instance}"}} {2 * per_slot}' in gauge
    finally:
        engine.close()
