"""MoE dispatch + expert-parallel tests on the CPU-simulated mesh.

Strategy (SURVEY.md §4.3): the explicit all_to_all shard_map path is
checked numerically (values AND gradients) against a dense per-token
reference; the GSPMD path is checked by compiling a full MoE-Llama train
step with the `expert` mesh axis.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import (
    LLAMA_MOE_PARTITION_RULES,
    Llama,
    LlamaConfig,
    create_train_state,
    lm_step,
    make_generator,
)
from unionml_tpu.ops.moe import (
    MoEMlp,
    expert_parallel_moe,
    make_dispatch,
    top_k_routing,
)
from unionml_tpu.parallel import ShardingConfig, compile_step, make_mesh


def _dense_moe_reference(x, router_kernel, w_gate, w_up, w_down, num_selected):
    """Per-token loop-free dense reference: every routed token processed."""
    gate_logits = (x @ router_kernel).astype(jnp.float32)
    weights, indices, aux = top_k_routing(gate_logits, num_selected)
    num_experts = w_gate.shape[0]
    onehot = jax.nn.one_hot(indices, num_experts, dtype=x.dtype)  # [T,k,E]
    combine = jnp.einsum("tke,tk->te", onehot, weights.astype(x.dtype))
    gated = jax.nn.silu(jnp.einsum("td,edh->eth", x, w_gate))
    up = jnp.einsum("td,edh->eth", x, w_up)
    expert_out = jnp.einsum("eth,ehd->etd", gated * up, w_down)
    return jnp.einsum("etd,te->td", expert_out, combine), aux


def _moe_weights(tokens=32, d=16, hidden=32, experts=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (tokens, d))
    router = jax.random.normal(ks[1], (d, experts)) * 0.5
    w_gate = jax.random.normal(ks[2], (experts, d, hidden)) * (d**-0.5)
    w_up = jax.random.normal(ks[3], (experts, d, hidden)) * (d**-0.5)
    w_down = jax.random.normal(ks[4], (experts, hidden, d)) * (hidden**-0.5)
    return x, router, w_gate, w_up, w_down


def test_make_dispatch_respects_capacity():
    gate_logits = jax.random.normal(jax.random.PRNGKey(0), (64, 4))
    dispatch, combine, _ = make_dispatch(gate_logits, num_selected=2, capacity=5)
    # each expert bucket holds at most `capacity` tokens, one per slot
    assert float(dispatch.sum(axis=(0, 2)).max()) <= 5
    assert float(dispatch.max()) <= 1.0
    # every slot holds at most one token
    assert float(dispatch.sum(axis=0).max()) <= 1.0
    # combine weight lives exactly where dispatch does
    assert float(jnp.abs(combine * (1 - dispatch)).max()) == 0.0


def test_make_dispatch_first_choices_win_slots():
    # 3 tokens all routing expert 0 first; capacity 2 drops the last token's
    # first choice but keeps all second choices on expert 1
    gate_logits = jnp.array(
        [[5.0, 1.0, -5.0], [5.0, 1.0, -5.0], [5.0, 1.0, -5.0]], jnp.float32
    )
    dispatch, _, _ = make_dispatch(gate_logits, num_selected=2, capacity=2)
    per_expert = np.asarray(dispatch.sum(axis=2))  # [T, E]
    # tokens 0 and 1 won expert 0's two slots; token 2's 1st choice dropped
    np.testing.assert_array_equal(per_expert[:, 0], [1, 1, 0])
    # 2nd choices (expert 1) bucket after all 1st choices: tokens 0, 1 fit
    np.testing.assert_array_equal(per_expert[:, 1], [1, 1, 0])


@pytest.mark.parametrize("ep", [2, 4])
def test_expert_parallel_matches_dense(ep):
    x, router, w_gate, w_up, w_down = _moe_weights()
    mesh = make_mesh({"expert": ep}, devices=jax.devices()[:ep])
    ref, aux_ref = _dense_moe_reference(x, router, w_gate, w_up, w_down, 2)
    # capacity = local token count: nothing can overflow, outputs must match
    out, aux = expert_parallel_moe(
        x, router, w_gate, w_up, w_down, mesh,
        num_selected=2, capacity=x.shape[0] // ep,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # aux loss: per-shard mean of per-shard top-1 fractions != global aux in
    # general, but both are O(1) balance stats — just require finiteness
    assert np.isfinite(float(aux))


def test_expert_parallel_gradients_match_dense():
    x, router, w_gate, w_up, w_down = _moe_weights(tokens=16, experts=4)
    mesh = make_mesh({"expert": 2}, devices=jax.devices()[:2])

    def loss_ep(x, w_gate, w_down):
        out, _ = expert_parallel_moe(
            x, router, w_gate, w_up, w_down, mesh,
            num_selected=2, capacity=x.shape[0] // 2,
        )
        return jnp.sum(out**2)

    def loss_ref(x, w_gate, w_down):
        out, _ = _dense_moe_reference(x, router, w_gate, w_up, w_down, 2)
        return jnp.sum(out**2)

    g_ep = jax.grad(loss_ep, argnums=(0, 1, 2))(x, w_gate, w_down)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w_gate, w_down)
    for a, b in zip(g_ep, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_expert_parallel_capacity_drops_tokens():
    # capacity 1 per expert: overflow tokens lose (part of) their MLP
    # contribution, so the output must differ from the uncapped reference
    x, router, w_gate, w_up, w_down = _moe_weights(tokens=32, experts=2)
    mesh = make_mesh({"expert": 2}, devices=jax.devices()[:2])
    ref, _ = _dense_moe_reference(x, router, w_gate, w_up, w_down, 1)
    out, _ = expert_parallel_moe(
        x, router, w_gate, w_up, w_down, mesh, num_selected=1, capacity=1
    )
    assert not np.allclose(np.asarray(out), np.asarray(ref))


def test_moe_mlp_module_dense_path():
    module = MoEMlp(
        num_experts=4, num_selected=2, hidden_dim=32, model_dim=16,
        dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = module.init(jax.random.PRNGKey(1), x)["params"]
    out, aux = module.apply({"params": params}, x)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))
    # routed MLP must actually transform the input
    assert not np.allclose(np.asarray(out), np.asarray(x))


def test_moe_llama_train_step_loss_decreases():
    cfg = LlamaConfig.tiny(vocab_size=64, num_experts=4, num_selected=2)
    module = Llama(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    state = create_train_state(module, tokens[:1], learning_rate=1e-2)
    step = jax.jit(lm_step(module))
    _, first = step(state, tokens)
    for _ in range(10):
        state, metrics = step(state, tokens)
    assert float(metrics["loss"]) < float(first["loss"])
    assert np.isfinite(float(metrics["aux_loss"])) and float(metrics["aux_loss"]) > 0


def test_dense_llama_aux_loss_metric_is_zero():
    cfg = LlamaConfig.tiny(vocab_size=64)
    module = Llama(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, 64)
    state = create_train_state(module, tokens[:1])
    _, metrics = jax.jit(lm_step(module))(state, tokens)
    assert float(metrics["aux_loss"]) == 0.0


def test_moe_llama_expert_parallel_gspmd_step():
    # full train step over a data x expert x tensor mesh: expert weights
    # shard over `expert` per LLAMA_MOE_PARTITION_RULES, GSPMD inserts the
    # dispatch collectives
    cfg = LlamaConfig.tiny(vocab_size=64, num_experts=4, num_selected=2)
    module = Llama(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    state = create_train_state(module, tokens[:1], learning_rate=1e-2)
    sharding = ShardingConfig(
        data=-1, expert=2, tensor=2, rules=LLAMA_MOE_PARTITION_RULES
    )
    step, state = compile_step(lm_step(module), state, sharding=sharding)
    # expert dim actually sharded on the mesh
    moe_shard = state.params["block_0"]["moe"]["w_gate"].sharding
    assert "expert" in moe_shard.spec
    state, metrics = step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))


def test_moe_config_validation():
    with pytest.raises(ValueError, match="num_selected"):
        LlamaConfig.tiny(num_experts=1)  # default num_selected=2 > experts
    with pytest.raises(ValueError, match="num_selected"):
        LlamaConfig.tiny(num_experts=4, num_selected=0)


def test_quantized_moe_matches_fp_module():
    from unionml_tpu.models import LLAMA_QUANT_PATTERNS, quantize_params

    fp = MoEMlp(num_experts=4, num_selected=2, hidden_dim=32, model_dim=16,
                dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = fp.init(jax.random.PRNGKey(1), x)["params"]
    ref, aux_ref = fp.apply({"params": params}, x)

    qparams = quantize_params({"moe": params}, LLAMA_QUANT_PATTERNS)["moe"]
    assert qparams["w_gate_q"].dtype == jnp.int8
    assert qparams["w_gate_scale"].shape == (4, 32)
    qmod = MoEMlp(num_experts=4, num_selected=2, hidden_dim=32, model_dim=16,
                  dtype=jnp.float32, quantized=True)
    out, aux = qmod.apply({"params": qparams}, x)
    # int8 weight-only: a ~1% relative error bound on the block output
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel < 0.02, rel
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_quantized_moe_bf16_error_bounded():
    # production dtype: fp32 accumulate + fp32 scale before the single
    # bf16 cast must keep the int8 error near the fp32-path bound
    from unionml_tpu.models import LLAMA_QUANT_PATTERNS, quantize_params

    fp = MoEMlp(num_experts=4, num_selected=2, hidden_dim=64, model_dim=32,
                dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    params = fp.init(jax.random.PRNGKey(1), x)["params"]
    ref, _ = fp.apply({"params": params}, x)
    qparams = quantize_params({"moe": params}, LLAMA_QUANT_PATTERNS)["moe"]
    qmod = MoEMlp(num_experts=4, num_selected=2, hidden_dim=64, model_dim=32,
                  dtype=jnp.bfloat16, quantized=True)
    out, _ = qmod.apply({"params": qparams}, x.astype(jnp.bfloat16))
    rel = float(
        jnp.linalg.norm(out.astype(jnp.float32) - ref) / jnp.linalg.norm(ref)
    )
    assert rel < 0.05, rel


def test_quantized_moe_llama_generation():
    from unionml_tpu.models import LLAMA_QUANT_PATTERNS, quantize_params

    cfg = LlamaConfig.tiny(vocab_size=64, num_experts=4, num_selected=2)
    module = Llama(cfg)
    tokens = jnp.zeros((1, 4), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens)["params"]
    qcfg = LlamaConfig.tiny(vocab_size=64, num_experts=4, num_selected=2,
                            quantized=True)
    qparams = quantize_params(params, LLAMA_QUANT_PATTERNS)
    generate = make_generator(Llama(qcfg), max_new_tokens=4)
    out = generate(qparams, jnp.asarray([[1, 2, 3, 4]], jnp.int32))
    assert out.shape == (1, 4)
    assert np.isfinite(np.asarray(out)).all()


def test_moe_llama_generation():
    cfg = LlamaConfig.tiny(vocab_size=64, num_experts=4, num_selected=2)
    module = Llama(cfg)
    tokens = jnp.zeros((1, 4), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens)["params"]
    generate = make_generator(module, max_new_tokens=4)
    out = generate(params, jnp.asarray([[1, 2, 3, 4]], jnp.int32))
    assert out.shape == (1, 4)
    assert np.isfinite(np.asarray(out)).all()


def test_migrate_moe_router_params_old_layout_restores():
    """Old Dense-submodule router checkpoints rename to router_kernel.

    PARITY.md documents the layout break; the helper must produce a tree
    MoEMlp.apply accepts, keep the router fp32, and drop the old bias.
    """
    from unionml_tpu.ops import migrate_moe_router_params

    module = MoEMlp(num_experts=4, num_selected=2, hidden_dim=8, model_dim=8)
    x = jnp.ones((1, 3, 8), jnp.bfloat16)
    params = module.init(jax.random.PRNGKey(0), x)["params"]

    # reconstruct the pre-round-1 layout: router as a Dense submodule
    old = {k: v for k, v in params.items() if k != "router_kernel"}
    old["router"] = {
        "kernel": params["router_kernel"].astype(jnp.bfloat16),
        "bias": jnp.zeros((4,), jnp.bfloat16),
    }
    nested_old = {"block_0": {"moe": old}, "head": {"kernel": jnp.ones((8, 2))}}

    # old flax artifacts are often FrozenDicts — the helper must recurse
    # through any Mapping, not just plain dicts
    import flax.core

    migrated = migrate_moe_router_params(flax.core.freeze(nested_old))
    new_moe = migrated["block_0"]["moe"]
    assert "router" not in new_moe
    assert new_moe["router_kernel"].dtype == jnp.float32
    # untouched siblings survive
    np.testing.assert_array_equal(
        np.asarray(migrated["head"]["kernel"]), np.ones((8, 2))
    )
    out, aux = module.apply({"params": new_moe}, x)
    assert out.shape == x.shape and np.isfinite(float(aux))


# ------------------------------------------------ grouped dispatch (PR 34)
#
# The dense one-hot dispatch is the plain reference: every expert on
# every token. The grouped dispatch must give the same block output by
# both of its matmuls: `jax.lax.ragged_dot` (the CPU's path) and the
# Pallas kernel `moe_grouped_matmul` in interpret mode.


def _expert_weights(experts, d, hidden, quantized, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = ((experts, d, hidden), (experts, d, hidden), (experts, hidden, d))
    if not quantized:
        return tuple(jax.random.normal(k, s) * s[1] ** -0.5 for k, s in zip(ks, shapes)), None
    ws = tuple(jax.random.randint(k, s, -127, 128, jnp.int8) for k, s in zip(ks, shapes))
    scales = tuple(
        jax.random.uniform(k, (experts, s[2]), minval=0.5, maxval=1.5) / (73.0 * s[1] ** 0.5)
        for k, s in zip(ks, shapes)
    )
    return ws, scales


def _assert_grouped_matches_dense(x, weights, indices, ws, scales, impl):
    from unionml_tpu.ops.moe import dense_expert_mlp, grouped_expert_mlp

    want = dense_expert_mlp(x, weights, indices, *ws, scales=scales)
    got = grouped_expert_mlp(x, weights, indices, *ws, scales=scales, impl=impl)
    assert got.shape == want.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ragged_dot", "pallas"])
@pytest.mark.parametrize("experts,selected", [(8, 2), (4, 1)])
@pytest.mark.parametrize("tokens", [1, 7, 32, 256])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_grouped_dispatch_matches_dense(quantized, tokens, experts, selected, impl):
    d, hidden = 64, 128
    ws, scales = _expert_weights(experts, d, hidden, quantized, seed=tokens)
    kx, kr = jax.random.split(jax.random.PRNGKey(tokens + experts))
    x = jax.random.normal(kx, (tokens, d))
    weights, indices, _ = top_k_routing(jax.random.normal(kr, (tokens, experts)), selected)
    _assert_grouped_matches_dense(x, weights, indices, ws, scales, impl)


def _special_routing(kind):
    """(indices [T, k], experts): routings a random router rarely deals."""
    if kind == "empty_experts":  # experts 0, 3 and 7 get no row
        pairs = [(1, 2), (4, 5), (6, 1), (2, 4), (5, 6)] * 8
        return jnp.asarray(pairs, jnp.int32), 8
    if kind == "one_expert":  # every token to expert 2, alone
        return jnp.full((40, 1), 2, jnp.int32), 4
    # split_tile: 21 + 43 rows, so expert 1's rows start inside what would
    # be expert 0's second 16-row tile and end inside a tile of their own
    assert kind == "split_tile"
    return jnp.asarray([[0]] * 21 + [[1]] * 43, jnp.int32), 4


@pytest.mark.parametrize("impl", ["ragged_dot", "pallas"])
@pytest.mark.parametrize("kind", ["empty_experts", "one_expert", "split_tile"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_grouped_dispatch_special_routings(quantized, kind, impl):
    indices, experts = _special_routing(kind)
    tokens, selected = indices.shape
    d, hidden = 32, 64
    ws, scales = _expert_weights(experts, d, hidden, quantized, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, d))
    weights = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(6), (tokens, selected)), -1)
    _assert_grouped_matches_dense(x, weights, indices, ws, scales, impl)


@pytest.mark.parametrize("chunk", [1, 16, 128])
def test_group_rows_layout(chunk):
    from unionml_tpu.ops.moe import _padded_rows, group_rows

    experts, selected = 8, 2
    _, indices, _ = top_k_routing(jax.random.normal(jax.random.PRNGKey(0), (50, experts)), selected)
    indices = jnp.where(indices == 5, 6, indices)  # expert 5 stays empty
    source, slot, sizes, tile_expert, tile_rows = map(np.asarray, group_rows(indices, experts, chunk))
    flat = np.asarray(indices).reshape(-1)
    assert source.shape == (_padded_rows(flat.size, experts, chunk),)
    np.testing.assert_array_equal(sizes, np.bincount(flat, minlength=experts))
    assert sizes[5] == 0 and len(set(slot)) == flat.size  # one row a pair
    np.testing.assert_array_equal(source[slot], np.arange(flat.size) // selected)
    starts = np.cumsum(-(-sizes // chunk) * chunk) - (-(-sizes // chunk) * chunk)
    assert (starts % chunk == 0).all() and (tile_rows > 0).sum() == (-(-sizes // chunk)).sum()
    assert tile_rows.sum() == flat.size and (np.diff((tile_rows > 0).astype(int)) <= 0).all()
    for e in range(experts):
        rows = np.sort(slot[flat == e])
        # an expert's rows are contiguous from its start, in token order
        np.testing.assert_array_equal(rows, starts[e] + np.arange(sizes[e]))
        np.testing.assert_array_equal(slot[flat == e], rows)
        assert (tile_expert[rows // chunk] == e).all()
        used = -(-sizes[e] // chunk)  # the expert's tiles, and the routed rows each holds
        held = np.bincount(rows // chunk, minlength=tile_rows.size)[tile_expert == e]
        np.testing.assert_array_equal(held[:used], tile_rows[tile_expert == e][:used])


@pytest.mark.parametrize("chunk", [1, 16])
def test_group_rows_gives_a_token_left_out_no_row(chunk):
    """``valid`` takes a right-padded prompt's padding out of the layout:
    the real tokens' pairs lie as they would alone, fewer tiles hold rows,
    and the others' slots read row 0."""
    from unionml_tpu.ops.moe import group_rows

    experts, selected, tokens, real = 8, 2, 50, 31
    _, indices, _ = top_k_routing(jax.random.normal(jax.random.PRNGKey(0), (tokens, experts)), selected)
    valid = jnp.arange(tokens) < real
    source, slot, sizes, tile_expert, tile_rows = map(np.asarray, group_rows(indices, experts, chunk, valid))
    alone = [np.asarray(a) for a in group_rows(indices[:real], experts, chunk)]
    np.testing.assert_array_equal(sizes, alone[2])
    np.testing.assert_array_equal(slot[: real * selected], alone[1])
    assert (slot[real * selected:] == 0).all()
    np.testing.assert_array_equal(source[slot[: real * selected]], np.arange(real * selected) // selected)
    assert tile_rows.sum() == real * selected and (tile_rows > 0).sum() == (alone[4] > 0).sum()
    used = (tile_rows > 0).sum()
    np.testing.assert_array_equal(tile_expert[:used], alone[3][:used])


@pytest.mark.parametrize("impl", ["ragged_dot", "pallas"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_grouped_experts_send_a_token_left_out_nowhere(quantized, impl):
    """The real tokens' outputs are what they are without the mask, the
    others' are zero, whatever the others hold."""
    from unionml_tpu.ops.moe import grouped_expert_mlp

    experts, selected, tokens, real, d, hidden = 8, 2, 40, 23, 32, 64
    ws, scales = _expert_weights(experts, d, hidden, quantized, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, d))
    weights, indices, _ = top_k_routing(jax.random.normal(jax.random.PRNGKey(6), (tokens, experts)), selected)
    valid = jnp.arange(tokens) < real
    want = grouped_expert_mlp(x, weights, indices, *ws, scales=scales, impl=impl)
    got = grouped_expert_mlp(
        x.at[real:].set(jnp.nan), weights, indices, *ws, scales=scales, impl=impl, valid=valid)
    np.testing.assert_allclose(np.asarray(got[:real]), np.asarray(want[:real]), rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[real:]).any()


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("gated", [False, True], ids=["down", "gate_up"])
def test_grouped_matmul_kernel_matches_ragged_dot(gated, quantized):
    """The kernel alone in interpret mode, its k and n axes tiled (three k
    tiles: the accumulator is written, added to twice, scaled and stored),
    against `ragged_dot` on the same rows packed without padding."""
    from unionml_tpu.ops import moe

    experts, depth, width, chunk = 4, 384, 256, 16
    sizes = np.array([19, 0, 37, 8])
    indices = jnp.asarray(np.repeat(np.arange(experts), sizes)[:, None], jnp.int32)
    ws, scales = _expert_weights(experts, depth, width, quantized, seed=9)
    rhs, scales = (ws[:2], scales and scales[:2]) if gated else (ws[:1], scales and scales[:1])
    x = jax.random.normal(jax.random.PRNGKey(2), (int(sizes.sum()), depth))

    want = moe.grouped_matmul(x, rhs, jnp.asarray(sizes, jnp.int32), scales=scales, impl="ragged_dot")
    source, slot, _, tile_expert, tile_rows = moe.group_rows(indices, experts, chunk)
    got = moe._grouped_matmul_pallas(
        x[source], rhs, scales, tile_expert, tile_rows,
        chunk=chunk, gated=gated, interpret=True, tiles=(128, 128, 7),
    )
    assert got.shape == (moe._padded_rows(x.shape[0], experts, chunk), width)
    np.testing.assert_allclose(np.asarray(got[slot]), np.asarray(want), rtol=2e-5, atol=2e-5)


# --- several row blocks (PR 37): the kernel's sums cover a block of row
# tiles, and the grid passes the blocks outermost. Tiny shapes, the tiles
# given (`tiles=`: tk, tn, row tiles a block) so that the rows fall in
# several blocks; 16-row tiles.


def _block_routing(kind):
    """(rows of each expert, row tiles a block) for 16-row tiles."""
    if kind == "straddles_an_edge":
        # expert 1 holds tiles 1-4: blocks of three tiles cut it after its
        # second tile, and expert 3 (tiles 6-8) starts a block of its own
        return [16, 60, 5, 40], 3
    if kind == "last_blocks_empty":
        # 40 rows in 3 tiles used of the layout's 6: blocks 1 and 2 of two
        # tiles hold no routed row (and block 1 starts on the last tile used)
        return [33, 0, 7, 0], 2
    if kind == "one_pair":  # one routed pair in all: one tile of 4 used, two blocks
        return [0, 0, 1, 0], 2
    assert kind == "ragged_last_block"  # 9 tiles in blocks of four: the last holds one
    return [30, 31, 29, 30], 4


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("gated", [False, True], ids=["down", "gate_up"])
@pytest.mark.parametrize("kind", ["straddles_an_edge", "last_blocks_empty", "one_pair", "ragged_last_block"])
def test_grouped_matmul_kernel_in_row_blocks_matches_ragged_dot(kind, gated, quantized):
    """The kernel in interpret mode over several row blocks, three k tiles
    and two column tiles, against `ragged_dot` on the rows packed without
    padding."""
    from unionml_tpu.ops import moe

    sizes, block_tiles = _block_routing(kind)
    experts, depth, width, chunk = len(sizes), 384, 256, 16
    sizes = np.asarray(sizes)
    indices = jnp.asarray(np.repeat(np.arange(experts), sizes)[:, None], jnp.int32)
    ws, scales = _expert_weights(experts, depth, width, quantized, seed=11)
    rhs, scales = (ws[:2], scales and scales[:2]) if gated else (ws[:1], scales and scales[:1])
    x = jax.random.normal(jax.random.PRNGKey(4), (int(sizes.sum()), depth))

    want = moe.grouped_matmul(x, rhs, jnp.asarray(sizes, jnp.int32), scales=scales, impl="ragged_dot")
    source, slot, _, tile_expert, tile_rows = moe.group_rows(indices, experts, chunk)
    rows = moe._padded_rows(x.shape[0], experts, chunk)
    plan = moe._tile_plan(rows, depth, width, chunk, 128, 128, block_tiles)
    assert plan["row_blocks"] > 1 and plan["grid_steps"] == plan["row_blocks"] * 2 * 3 * block_tiles
    got = moe._grouped_matmul_pallas(
        x[source], rhs, scales, tile_expert, tile_rows,
        chunk=chunk, gated=gated, interpret=True, tiles=(128, 128, block_tiles),
    )
    assert got.shape == (rows, width)
    np.testing.assert_allclose(np.asarray(got[slot]), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize(
    "depth,weight_tile", [(384, 128), (192, 256)], ids=["three_k_tiles", "depth_192_in_one_pass"],
)
def test_grouped_dispatch_in_row_blocks_matches_dense(monkeypatch, depth, weight_tile, quantized):
    """Both products of the experts' SwiGLU through the kernel as
    `matmul_tiles` lays it out when the rows' sums outgrow the VMEM it may
    take (shrunk here, so that 8 experts' 11 row tiles go in blocks of one
    to four), against the dense dispatch: a depth in three k tiles, and
    one that no power of two divides, in one pass."""
    from unionml_tpu.ops import moe

    experts, selected, tokens, hidden = 8, 2, 120, 256
    monkeypatch.setattr(moe, "_WEIGHT_TILE", weight_tile)
    chunk = moe._row_chunk(tokens * selected, experts)
    monkeypatch.setattr(moe, "_ACC_BYTES", 4 * chunk * 128 * 4)  # four row tiles x 128 columns
    moe._grouped_matmul_pallas.clear_cache()
    try:
        rows = moe._padded_rows(tokens * selected, experts, chunk)
        gate_up = moe.matmul_tiles(rows, depth, hidden, 2, chunk)
        down = moe.matmul_tiles(rows, hidden, depth, 1, chunk)
        assert gate_up["tk"] == min(depth, weight_tile) and down["tk"] == weight_tile
        for plan in (gate_up, down):
            assert 1 <= plan["row_block_tiles"] <= 4 and plan["row_blocks"] >= 3
            assert plan["row_blocks"] * plan["row_block_tiles"] >= rows // chunk
        ws, scales = _expert_weights(experts, depth, hidden, quantized, seed=13)
        kx, kr = jax.random.split(jax.random.PRNGKey(17))
        x = jax.random.normal(kx, (tokens, depth))
        weights, indices, _ = top_k_routing(jax.random.normal(kr, (tokens, experts)), selected)
        indices = jnp.where(indices == 5, 6, indices)  # expert 5 stays empty
        _assert_grouped_matches_dense(x, weights, indices, ws, scales, "pallas")
    finally:
        moe._grouped_matmul_pallas.clear_cache()


_MIXTRAL_PRODUCTS = dict(experts=8, selected=2, d=4096, hidden=14336)
_GLM_PRODUCTS = dict(experts=64, selected=4, d=2048, hidden=1536)


def _layer_tiles(tokens, experts, selected, d, hidden):
    from unionml_tpu.ops import moe

    chunk = moe._row_chunk(tokens * selected, experts)
    rows = moe._padded_rows(tokens * selected, experts, chunk)
    gate_up, down = moe.matmul_tiles(rows, d, hidden, 2, chunk), moe.matmul_tiles(rows, hidden, d, 1, chunk)
    return chunk, rows, gate_up, down


@pytest.mark.parametrize("layer,tokens,gate_up,down", [
    # (tk, tn, row tiles a block, row blocks) of gate + up and of down, each
    # from a measured pair of `benchmarks/moe_dispatch.py` runs (PERF.md
    # section 6, PR 37) against the one block of narrower tiles before it
    (_MIXTRAL_PRODUCTS, 256, (2048, 2048, 6, 2), (2048, 2048, 11, 1)),  # was 1024 / 2048 wide: -2 %
    (_MIXTRAL_PRODUCTS, 512, (2048, 2048, 5, 3), (2048, 2048, 8, 2)),  # was 512 / 1024 wide: -10 %
    (_MIXTRAL_PRODUCTS, 1024, (2048, 2048, 6, 4), (2048, 2048, 12, 2)),  # was 512 / 1024 wide: -12 %
    # the decode chunk's 1,088 rows of 16: was 512 wide and 512 deep (-1 %; -3 % at 14 live rows)
    (_GLM_PRODUCTS, 32, (2048, 1536, 34, 2), (1536, 2048, 68, 1)),
    (_GLM_PRODUCTS, 512, (2048, 1536, 16, 6), (1536, 2048, 24, 4)),  # 64-row tiles
], ids=["mixtral_256", "mixtral_512", "mixtral_1024", "glm_chunk_32", "glm_512"])
def test_matmul_tiles_at_the_cells_shapes(layer, tokens, gate_up, down):
    chunk, rows, *plans = _layer_tiles(tokens, **layer)
    for plan, (tk, tn, block_tiles, blocks), (depth, width) in zip(
        plans, (gate_up, down), ((layer["d"], layer["hidden"]), (layer["hidden"], layer["d"]))
    ):
        assert plan == {
            "tk": tk, "tn": tn, "row_block_tiles": block_tiles, "row_blocks": blocks,
            "grid_steps": blocks * (width // tn) * (depth // tk) * block_tiles,
        }
        assert (blocks - 1) * block_tiles < rows // chunk <= blocks * block_tiles


@pytest.mark.parametrize("tokens", [1024, 2048, 4096])
def test_matmul_tiles_at_64_experts_top_4_go_in_row_blocks(tokens):
    """The buckets whose rows outgrew one block's sums: wide column tiles,
    the 1,536 depth in one pass, a few hundred grid steps a layer, and
    what a grid step holds in VMEM under the kernel's limit."""
    from unionml_tpu.ops import moe

    d, hidden = _GLM_PRODUCTS["d"], _GLM_PRODUCTS["hidden"]
    chunk, rows, gate_up, down = _layer_tiles(tokens, **_GLM_PRODUCTS)
    assert chunk == 128 and rows == {1024: 12160, 2048: 16256, 4096: 24448}[tokens]
    assert gate_up["tk"] == d and down["tk"] == hidden  # one k pass each
    assert gate_up["grid_steps"] + down["grid_steps"] <= 400  # 3,420 / 7,620 / 11,460 before
    for plan, n_rhs, depth in ((gate_up, 2, d), (down, 1, hidden)):
        assert plan["tn"] >= 512 and plan["row_blocks"] > 1
        assert plan["row_blocks"] * plan["row_block_tiles"] >= rows // chunk
        assert (plan["row_blocks"] - 1) * plan["row_block_tiles"] < rows // chunk  # no empty block
        block_rows = plan["row_block_tiles"] * chunk
        sums = n_rhs * block_rows * plan["tn"] * 4
        assert sums <= moe._ACC_BYTES
        # the sums, the output block and the operands in two buffers each,
        # and the weight tiles' bfloat16 copies the MXU sees
        held = sums + 2 * block_rows * plan["tn"] * 2 + 2 * chunk * plan["tk"] * 2
        held += n_rhs * plan["tk"] * plan["tn"] * (2 * 1 + 2)
        assert held <= moe._VMEM_LIMIT // 2


def test_dispatch_plan_counts_rows(monkeypatch):
    from unionml_tpu.ops import moe

    # float experts may be differentiated or sharded: the dense dispatch,
    # every expert on every token
    plan = moe.dispatch_plan(256, 8, 2, quantized=False)
    assert plan["dispatch"] == "dense" and plan["computed_over_routed"] == 4.0
    plan = moe.dispatch_plan(256, 8, 2, quantized=True)  # off the chip
    assert plan == {
        "dispatch": "grouped:ragged_dot", "expert_rows_routed": 512,
        "expert_rows_computed": 512, "computed_over_routed": 1.0,
    }
    # under a mesh the compiler partitions, int8 experts too: it cannot
    # partition a Pallas call (inside shard_map the axes are manual)
    mesh = make_mesh({"expert": 2, "tensor": 2}, devices=jax.devices()[:4])
    with jax.set_mesh(mesh):
        assert moe.dispatch_plan(256, 8, 2, quantized=True)["dispatch"] == "dense"
    seen = []
    jax.shard_map(
        lambda x: seen.append(moe.dispatch_plan(256, 8, 2, quantized=True)["dispatch"]) or x,
        mesh=mesh, in_specs=jax.sharding.PartitionSpec(), out_specs=jax.sharding.PartitionSpec(),
    )(jnp.zeros(4))
    assert seen == ["grouped:ragged_dot"]
    monkeypatch.setattr(moe, "_interpret", lambda: False)  # as on a TPU
    # up to the MXU's side in tokens (a decode chunk's slot rows) the dense
    # einsums are at the weight read's pace already
    for tokens in (32, 128):
        plan = moe.dispatch_plan(tokens, 8, 2, quantized=True)
        assert plan["dispatch"] == "dense" and plan["computed_over_routed"] == 4.0
    for tokens in (256, 512, 1024):
        plan = moe.dispatch_plan(tokens, 8, 2, quantized=True)
        chunk = moe._row_chunk(2 * tokens, 8)
        assert plan["dispatch"] == "grouped:moe_grouped_matmul"
        assert plan["expert_rows_routed"] == 2 * tokens
        assert plan["expert_rows_computed"] == moe._padded_rows(2 * tokens, 8, chunk)
        assert 1.0 <= plan["computed_over_routed"] < 4.0  # under the dense dispatch's


def test_quantized_moe_module_takes_the_grouped_dispatch(monkeypatch):
    """`MoEMlp(quantized=True)` routes through `grouped_expert_mlp`, float
    experts through the dense dispatch, and both agree with each other on
    the same (dequantized) weights."""
    from unionml_tpu.models import LLAMA_QUANT_PATTERNS, quantize_params
    from unionml_tpu.ops import moe

    calls = []
    for name in ("grouped_expert_mlp", "dense_expert_mlp"):
        fn = getattr(moe, name)
        monkeypatch.setattr(
            moe, name, lambda *a, _fn=fn, _name=name, **kw: calls.append(_name) or _fn(*a, **kw)
        )
    kw = dict(num_experts=4, num_selected=2, hidden_dim=32, model_dim=16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = MoEMlp(**kw).init(jax.random.PRNGKey(1), x)["params"]
    qparams = quantize_params({"moe": params}, LLAMA_QUANT_PATTERNS)["moe"]
    dequantized = {"router_kernel": params["router_kernel"], **{
        name: qparams[f"{name}_q"] * qparams[f"{name}_scale"][:, None, :]
        for name in ("w_gate", "w_up", "w_down")
    }}
    calls.clear()
    want, _ = MoEMlp(**kw).apply({"params": dequantized}, x)
    got, _ = MoEMlp(**kw, quantized=True).apply({"params": qparams}, x)
    assert calls == ["dense_expert_mlp", "grouped_expert_mlp"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
