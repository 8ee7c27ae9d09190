"""The main path's Pallas kernels compile for the chip, at real widths.

Interpret mode on the CPU accepts kernels Mosaic refuses (the paged
decode kernel passed every interpret test and compiled at no shape), so
these tests hand the installed TPU compiler a DESCRIBED ``v5e:2x2`` chip
and shapes — nothing runs, no chip is needed — and assert the compiled
program holds a ``tpu_custom_call``. A compile that passes is not a chip
run: ``chip_smoke.py`` is what runs them.
"""

import importlib.util
import json
import os
import re
from pathlib import Path

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# libtpu lets one process at a time load it, to guard a chip's owner. No
# chip is attached here, and under pytest-xdist several workers each
# load the compiler: without this all but the first would skip.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from unionml_tpu.ops import (
    flash_attention,
    fused_attention,
    fused_norm,
    gated_delta,
    int4_matmul,
    moe,
    paged_attention,
    sparse_attention,
)


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compile_for_the_chip(monkeypatch):
    """Steer the kernels off their CPU branch, and keep the persistent
    compile cache out of it: an executable for a described chip is
    written there but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    for module in (
        flash_attention, fused_attention, fused_norm, gated_delta, moe, paged_attention, sparse_attention,
    ):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _assert_mosaic(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# Llama-3-8B decode geometry (32 q / 8 kv heads, head_dim 128) at the
# three pool block sizes and the OLMoE 16/16 MHA shape, on a short table
# (one group of pool blocks a row); then the shape the benchmark's
# mixtral_chat_decode cell runs: 32 slots, a table 101 blocks wide (four
# groups, the last one partial) over its 1.5 GB pool; then the
# olmo_hybrid_longgen_decode cell's: 30 x 128 MHA held as 32 heads
# (OlmoHybridConfig.kv_cache_heads), 32 slots, a table 261 wide
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "q_heads,kv_heads,block,batch,width,n_blocks",
    [
        (32, 8, 16, 8, 11, 512), (32, 8, 32, 8, 11, 512),
        (32, 8, 64, 8, 11, 512), (16, 16, 16, 8, 11, 512),
        (32, 8, 16, 32, 101, 2861), (32, 32, 16, 32, 261, 1621),
    ],
    ids=["32-8-16", "32-8-32", "32-8-64", "16-16-16", "cell-32x101", "hybrid-cell-32x261"],
)
def test_paged_attention_compiles(
    chip, q_heads, kv_heads, block, batch, width, n_blocks, quantized
):
    hd = 128
    pool = ((n_blocks, block, kv_heads, hd), jnp.int8 if quantized else jnp.bfloat16)
    shapes = [
        ((batch, q_heads, hd), jnp.bfloat16), pool, pool,
        ((batch, width), jnp.int32), ((batch,), jnp.int32),
    ]
    if quantized:
        shapes += [((n_blocks, block, kv_heads), jnp.float32)] * 2

    def fn(q, k, v, table, lengths, k_scale=None, v_scale=None):
        return paged_attention.paged_attention(
            q, k, v, table, lengths, k_scale=k_scale, v_scale=v_scale,
            impl="pallas",
        )

    text = _assert_mosaic(chip, fn, *shapes)
    # chipbench's paged_attn_ms_per_step finds the kernel's device time
    # by this instruction name
    assert re.search(r"%paged_attention(\.\d+)* = ", text)
    # the kernel's [blocks, block * heads, head_dim] view of the pool is a
    # bitcast, never a copy of the pool (at 30 heads, which a bfloat16 array
    # pads to 32, it was a copy of every layer's pool every step)
    assert not re.search(rf"\[{n_blocks},{block * kv_heads},{hd}\]\S* copy\(", text)


# the sdar_chat_fixed_length_decode cell's kernel: 32 rows x 4 queries x 32
# heads over one pool of fused rows (4 key + 4 value heads of 128 a
# position), blocks of 16, a table 163 wide over 3,814 pool blocks: a forward's
# two blocks of 4 queries a row, each query under a limit of its own; four
# queries that share the row's length; and the same rows with one query
@pytest.mark.parametrize("queries,limited", [(8, True), (4, False), (1, False)],
                         ids=["cell-8-queries-a-limit-each", "4-queries", "one-query"])
def test_paged_attention_over_fused_rows_compiles(chip, queries, limited):
    """A key head's rows are read out of the gathered buffer by strided
    32-bit loads (two bfloat16 heads a word) and scored against that head's
    own query rows: Mosaic takes it, the kernel keeps the name the
    benchmark's readers find it by, and the pool reaches it as it lies."""
    batch, q_heads, kv_heads, hd, block, width, n_blocks = 32, 32, 4, 128, 16, 163, 3814
    q = (batch, queries, q_heads, hd) if queries > 1 else (batch, q_heads, hd)

    def fn(q, pool, table, lengths, limits=None):
        return paged_attention.paged_attention(q, pool, None, table, lengths, impl="pallas", limits=limits)

    text = _assert_mosaic(
        chip, fn, (q, jnp.bfloat16), ((n_blocks, block, 2 * kv_heads, hd), jnp.bfloat16),
        ((batch, width), jnp.int32), ((batch,), jnp.int32),
        *([((batch, queries), jnp.int32)] if limited else []),
    )
    calls = re.findall(r"%paged_attention(?:\.\d+)* = (\S+) custom-call\(", text)
    assert len(calls) == 1 and calls[0].startswith(f"bf16[{batch},{queries * q_heads},{hd}]")
    pool = rf"bf16\[{n_blocks},(?:{block},{2 * kv_heads}|{block * 2 * kv_heads}),{hd}\]"
    assert not re.search(rf"{pool}\S* (?:copy|transpose|gather|fusion)\(", text)


REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def hlo():
    """``scripts/compiled_chunk.py``: its reading of an array's type and
    layout in a compiled module's text."""
    spec = importlib.util.spec_from_file_location("compiled_chunk", REPO / "scripts" / "compiled_chunk.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize(
    "array,want",
    [
        # the minor axis pads to 128 lanes, the next one to 8 sublanes
        ("f32[32,15,96,4]{3,2,1,0:T(8,128)S(1)}", 32 * 15 * 96 * 128 * 4),
        ("f32[32,15,2,96]{2,3,1,0:T(8,128)}", 32 * 15 * 96 * 128 * 4),
        ("f32[32,3,20,96]{3,2,1,0:T(8,128)}", 32 * 3 * 24 * 128 * 4),
        # two bfloat16 rows, four int8 rows to a word
        ("bf16[32,34560]{1,0:T(8,128)(2,1)}", 32 * 34560 * 2),
        ("bf16[5,100]{1,0:T(8,128)(2,1)}", 16 * 128 * 2),
        ("s8[960,11008]{1,0:T(8,128)(4,1)S(1)}", 960 * 11008),
        ("s32[32]{0:T(128)}", 128 * 4),
        ("f32[32,30]", 32 * 30 * 4),
    ],
)
def test_an_arrays_bytes_in_tiles(hlo, array, want):
    assert hlo.result_bytes(array) == want


def _step_kernel_call(text):
    """The ``gated_delta_step`` custom call's result and operand types."""
    line = next(l for l in text.splitlines() if re.search(r"%gated_delta_step(\.\d+)* = ", l))
    result = line.split(" = ", 1)[1].split(" custom-call(")[0]
    operands = re.search(r"operand_layout_constraints=\{(.*?\})\}", line).group(1)
    return result, operands


def test_gated_delta_step_compiles(chip, hlo):
    """The decode kernel of the gated delta rule at the hybrid cell's shape:
    32 slots, 30 heads of 96 x 192, two heads a state row. What it takes
    besides the state is lane-dense: a few MB in the chip's tiles, as
    ``step_operand_bytes`` counts them (26 MB when keys and queries came as
    columns, 4 of 128 lanes used)."""
    batch, heads, dk, dv = 32, 30, 96, 192
    state = ((batch,) + gated_delta.state_shape(heads, dk, dv), jnp.float32)
    assert state[0] == (32, 15, 96, 384)
    text = _assert_mosaic(
        chip,
        lambda *args: gated_delta.gated_delta_step(*args, impl="pallas"),
        ((batch, heads, dk), jnp.bfloat16), ((batch, heads, dk), jnp.bfloat16),
        ((batch, heads, dv), jnp.bfloat16), ((batch, heads), jnp.float32), ((batch, heads), jnp.float32),
        state, ((batch,), jnp.bool_),
    )
    # chipbench's gdn_state_ms_per_step finds the kernel by this name
    assert re.search(r"%gated_delta_step(\.\d+)* = ", text)
    result, operands = _step_kernel_call(text)

    def tiles(types):
        """float32 arrays in (8, 128) tiles: the state, and the two [batch]
        int32 vectors of the grid's bookkeeping, aside."""
        return [
            hlo.tiled_bytes(dtype, dims, order, "T(8,128)")
            for dtype, dims, order, _ in hlo._ARRAY.findall(types)
            if dtype == "f32" and dims != "32,15,96,384"
        ]

    taken, given = tiles(operands), tiles(result)
    assert len(taken) == 4 and sum(taken) <= 4e6
    assert sum(taken) + sum(given) == gated_delta.step_operand_bytes(batch, heads, dk, dv) <= 4e6


def test_gated_delta_layer_step_compiles_lane_dense(chip, hlo):
    """One ``GatedDeltaNet`` decode step at the hybrid cell's widths (32 x
    3840 in, int8 weights): XLA hands the kernel and the convolution their
    operands without turning them. A layer used to take four ``copy`` and
    three 23.6 MB arrays ([32,15,96,4], [32,15,96,2], [32,15,2,96]: 2 or 4
    of 128 lanes used) to pass 0.7 MB of keys and queries."""
    from unionml_tpu.models.olmo_hybrid import GatedDeltaNet, OlmoHybridConfig

    config = json.loads((REPO / "chipbench" / "configs" / "olmo-hybrid-7b-int8.json").read_text())
    cfg = OlmoHybridConfig.from_hf(config, quantized=True)
    layer = GatedDeltaNet(cfg, name="gdn")
    batch = 32
    x = jax.ShapeDtypeStruct((batch, 1, cfg.hidden_size), jnp.bfloat16, sharding=chip)
    cache = (
        jax.ShapeDtypeStruct((batch,) + gated_delta.state_shape(30, 96, 192), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((batch, 3 * cfg.conv_channels), jnp.bfloat16, sharding=chip),
    )
    index = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip)
    live = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=chip)

    def step(params, x, cache, index, live):
        return layer.apply(params, x, cache=cache, cache_index=index, live=live)

    params = jax.eval_shape(
        lambda x, cache, index, live: layer.init(
            jax.random.PRNGKey(0), x, cache=cache, cache_index=index, live=live,
        ),
        x, cache, index, live,
    )
    params = jax.tree_util.tree_map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=chip), params)
    text = jax.jit(step, donate_argnums=(2,)).lower(params, x, cache, index, live).compile().as_text()
    assert re.search(r"%gated_delta_step(\.\d+)* = ", text)
    copies = [l for l in text.splitlines() if " copy(" in l and re.search(r'op_name="[^"]*gdn', l)]
    assert len(copies) <= 1, copies
    thin = set()  # a minor axis of under 8 on the 128 lanes, 1 MB or more of it
    for m in hlo._ARRAY.finditer(text):
        _, dims, order, _ = m.groups()
        minor = order and int(dims.split(",")[int(order.split(",")[0])])
        if minor and minor < 8 and hlo.tiled_bytes(*m.groups()) >= 1e6:
            thin.add(m.group(0))
    assert not thin, sorted(thin)


QKV = ((1, 2048, 32, 128), jnp.bfloat16)


def test_flash_attention_forward_compiles(chip):
    _assert_mosaic(
        chip, lambda q, k, v: flash_attention.flash_attention(q, k, v, causal=True),
        QKV, QKV, QKV,
    )


def test_flash_attention_backward_compiles(chip):
    def loss(q, k, v):
        out = flash_attention.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    _assert_mosaic(chip, jax.grad(loss, argnums=(0, 1, 2)), QKV, QKV, QKV)


def test_flash_attention_padded_gqa_forward_compiles(chip):
    kv = ((1, 2048, 8, 128), jnp.bfloat16)
    _assert_mosaic(
        chip,
        lambda q, k, v, pads: flash_attention.flash_attention(
            q, k, v, causal=True, kv_valid_start=pads
        ),
        QKV, kv, kv, ((1,), jnp.int32),
    )


VIT_QKV = ((64, 197, 12, 64), jnp.bfloat16)  # ViT-B/16 at batch 64
# a decoder's short-sequence training under attn_impl="auto": 128-wide heads
DECODER_QKV = ((4, 512, 8, 128), jnp.bfloat16)


def _fused_grads(causal):
    def loss(q, k, v):
        return fused_attention.fused_attention(q, k, v, causal=causal).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def test_fused_attention_forward_compiles(chip):
    text = _assert_mosaic(chip, fused_attention.fused_attention, VIT_QKV, VIT_QKV, VIT_QKV)
    # two 64-wide heads a lane tile: the kernel takes the projections' own
    # [B, S, H*D], not [B, H, S, D] blocks whose every tile is half padding
    assert re.search(r"bf16\[64,197,768\]\S* custom-call\(.*tpu_custom_call", text)


def test_fused_attention_backward_compiles(chip):
    text = _assert_mosaic(chip, _fused_grads(False), VIT_QKV, VIT_QKV, VIT_QKV)
    assert "bf16[64,12,197,64]" not in "".join(
        line for line in text.splitlines() if "tpu_custom_call" in line
    )


def test_fused_attention_wide_heads_causal_compile(chip):
    text = _assert_mosaic(chip, _fused_grads(True), DECODER_QKV, DECODER_QKV, DECODER_QKV)
    assert re.search(r"bf16\[4,512,1024\]\S* custom-call\(.*tpu_custom_call", text)


def test_fused_attention_heads_major_width_compiles(chip):
    # 80 lanes fit no tile: a head a block row, as every width was before
    qkv = ((8, 256, 8, 80), jnp.bfloat16)
    text = _assert_mosaic(chip, _fused_grads(False), qkv, qkv, qkv)
    assert re.search(r"bf16\[8,8,256,80\]\S* custom-call\(.*tpu_custom_call", text)


def test_attention_layer_keeps_lane_dense_rows_through_the_kernel(chip):
    """ViT-B's attention layer, forward and backward: the projections make
    and take ``[B, S, 768]`` (``layers.merged_dot_general``), so the reshape
    round the kernel cancels and nothing is laid out with a 64-wide minor
    axis, nor copied into what the kernel takes."""
    from unionml_tpu.models.layers import Attention

    layer = Attention(num_heads=12, attn_impl="fused")
    x = jax.ShapeDtypeStruct((64, 197, 768), jnp.bfloat16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x),
    )
    assert params["params"]["q"]["kernel"].shape == (768, 12, 64)  # the tree is as it was
    assert params["params"]["o"]["kernel"].shape == (12, 64, 768)

    def loss(params, x):
        return layer.apply(params, x).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2
    for line in calls:
        shapes = line.split("custom_call_target")[0]
        assert "bf16[64,197,768]{2,1,0" in shapes and ",64]{" not in shapes
    assert not re.search(r"bf16\[64,(197,12|12,197),64\]", text)
    # a prefetch (copy-done) may feed the kernel; a layout copy may not
    assert not any(re.search(r"custom-call\([^)]*%copy(\.\d+)?[,)]", line) for line in calls)


def test_fused_layer_norm_forward_and_grad_compile(chip):
    rows, d = 64 * 197, 768  # ViT-B/16 tokens at batch 64

    def loss(x, gamma, beta):
        return fused_norm.fused_layer_norm(x, gamma, beta).astype(jnp.float32).sum()

    _assert_mosaic(
        chip, jax.value_and_grad(loss, argnums=(0, 1, 2)),
        ((rows, d), jnp.bfloat16), ((d,), jnp.float32), ((d,), jnp.float32),
    )


def test_fused_rms_norm_compiles(chip):
    _assert_mosaic(
        chip, fused_norm.fused_rms_norm,
        ((2048, 4096), jnp.bfloat16), ((4096,), jnp.float32),
    )


@pytest.mark.parametrize("group_size", [0, 128], ids=["per-channel", "group-128"])
def test_int4_matmul_compiles(chip, group_size):
    rows, k, n = 8, 4096, 14336  # Llama-3-8B gate/up projection, decode rows
    tile_n = int4_matmul.tile_for(n, k)
    k_block = int4_matmul._k_block_for(k, tile_n, group_size)
    assert tile_n > 0 and k_block > 0
    shapes = [((rows, k), jnp.bfloat16), ((k, n // 2), jnp.int8)]
    if group_size:
        shapes.append(((k // group_size, n), jnp.float32))

        def fn(x, packed, scale):
            return int4_matmul._pallas_int4_grouped(
                x, packed, scale, n=n, tile_n=tile_n, k_block=k_block,
                group_size=group_size, interpret=False,
            )
    else:
        def fn(x, packed):
            return int4_matmul._pallas_int4(
                x, packed, n=n, tile_n=tile_n, k_block=k_block, interpret=False,
            )

    _assert_mosaic(chip, fn, *shapes)


# mixtral_chat_decode's expert layer (hidden 4096, 8 experts of 14336, top-2,
# int8 weights, bfloat16 rows) at a 256-token prefill bucket (512 routed rows:
# [512, 4096] x [8, 4096, 14336] for gate and up, then the down projection),
# the largest bucket, and the decode chunk's 32 slot rows (64 routed rows: the
# engine's decode chunk takes the dense dispatch, the kernel must still compile)
_MIXTRAL_LAYER = dict(d=4096, hidden=14336, experts=8, selected=2)


def _no_array_of_every_expert(text, tokens, d, hidden, experts, **_):
    # the weights stay int8 in HBM, and no array holds every expert's
    # products for every token
    assert f"s8[{experts},{d},{hidden}]" in text
    assert not re.search(rf"(?:bf16|f32)\[{experts},(?:{d},{hidden}|{hidden},{d})\]", text)
    # (float: at 2,048 tokens the int8 weights themselves are [64, 2048, 1536])
    assert not re.search(rf"(?:bf16|f32)\[{experts},{tokens},(?:{hidden}|{d})\]", text)


@pytest.mark.parametrize("tokens", [32, 256, 1024], ids=["decode_32_slots", "prefill_256", "prefill_1024"])
def test_moe_grouped_matmul_compiles(chip, tokens):
    d, hidden, experts, selected = _MIXTRAL_LAYER.values()

    def mlp(x, weights, indices, w_gate, w_up, w_down, *scales):
        return moe.grouped_expert_mlp(x, weights, indices, w_gate, w_up, w_down, scales=scales, impl="pallas")

    text = _assert_mosaic(
        chip, mlp,
        ((tokens, d), jnp.bfloat16), ((tokens, selected), jnp.bfloat16), ((tokens, selected), jnp.int32),
        ((experts, d, hidden), jnp.int8), ((experts, d, hidden), jnp.int8), ((experts, hidden, d), jnp.int8),
        ((experts, hidden), jnp.float32), ((experts, hidden), jnp.float32), ((experts, d), jnp.float32),
    )
    # gate + up with the SwiGLU in one call, the down projection in another,
    # under the name a trace shows
    assert len(re.findall(r"%moe_grouped_matmul(?:\.\d+)* = ", text)) == 2
    _no_array_of_every_expert(text, tokens, **_MIXTRAL_LAYER)


def test_moe_layer_of_a_prefill_holds_no_array_of_every_expert(chip):
    """`MoEMlp` as the 256-token prefill program traces it: the grouped
    dispatch, and nothing shaped `[8, 256, 14336]` or `bf16[8, 4096, 14336]`."""
    tokens = 256
    d, hidden, experts, selected = _MIXTRAL_LAYER.values()
    layer = moe.MoEMlp(
        num_experts=experts, num_selected=selected, hidden_dim=hidden, model_dim=d,
        dtype=jnp.bfloat16, quantized=True,
    )
    x = jax.ShapeDtypeStruct((1, tokens, d), jnp.bfloat16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x),
    )
    plan = moe.dispatch_plan(tokens, experts, selected, quantized=True)
    assert plan["dispatch"] == "grouped:moe_grouped_matmul" and plan["computed_over_routed"] < 4.0
    text = jax.jit(layer.apply).lower(params, x).compile().as_text()
    assert len(re.findall(r"%moe_grouped_matmul(?:\.\d+)* = ", text)) == 2
    _no_array_of_every_expert(text, tokens, **_MIXTRAL_LAYER)


# ---- the glm_flash_code_context_decode cell's kernels (PR 36): absorbed
# latent attention at 32 rows x 20 heads over latent rows of 576 values held
# in 640 lanes, a 4.0 GB pool of 16-row blocks and a table 293 wide (4,096 +
# 512 + the in-flight chunks' spare rows); whole prompts' expanded attention
# at head width 256; the sigmoid-routed experts (64, top-4, 2048 x 1536) at
# the decode chunk's rows and the largest bucket
@pytest.mark.parametrize("blocks,width", [(15_024, 293), (512, 11)], ids=["cell-32x293", "short-table"])
def test_paged_latent_attention_compiles(chip, blocks, width):
    from unionml_tpu.models.layers import LatentRows

    row = LatentRows(512, 64)
    assert row.stored_width == 640

    def fn(q, pool, table, lengths):
        return paged_attention.paged_latent_attention(
            q, pool, table, lengths, value_dim=512, scale=256 ** -0.5, impl="pallas",
        )

    text = _assert_mosaic(
        chip, fn, ((32, 20, 640), jnp.bfloat16), ((blocks, 16, 640), jnp.bfloat16),
        ((32, width), jnp.int32), ((32,), jnp.int32),
    )
    # chipbench's latent_attn_ms_per_step finds the kernel by this name
    assert re.search(r"%paged_latent_attention(\.\d+)* = ", text)
    # the pool goes to the kernel as it lies: no copy, no relayout of it
    assert not re.search(rf"bf16\[{blocks},16,640\]\S* (?:copy|transpose)\(", text)


def test_a_latent_pool_row_of_576_lanes_is_refused_by_the_chip(chip):
    """Why ``LatentRows`` stores 640: the chip lays 576 out in 640 lanes and
    its kernels copy whole lane tiles only."""
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(
            lambda q, pool, t, n: paged_attention.paged_latent_attention(
                q, pool, t, n, value_dim=512, scale=0.0625, impl="pallas")
        ).lower(*[
            jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
                ((32, 20, 576), jnp.bfloat16), ((512, 16, 576), jnp.bfloat16),
                ((32, 11), jnp.int32), ((32,), jnp.int32))
        ]).compile()


def test_flash_attention_at_the_latent_models_expanded_width_compiles(chip):
    qkv = ((1, 4096, 20, 256), jnp.bfloat16)
    _assert_mosaic(
        chip,
        lambda q, k, v, pads: flash_attention.flash_attention(
            q, k, v, causal=True, scale=256 ** -0.5, kv_valid_start=pads),
        qkv, qkv, qkv, ((1,), jnp.int32),
    )


_GLM_LAYER = dict(d=2048, hidden=1536, experts=64, selected=4)


@pytest.mark.parametrize(
    "tokens", [32, 512, 2048, 4096], ids=["decode_32_slots", "prefill_512", "prefill_2048", "prefill_4096"],
)
def test_moe_grouped_matmul_compiles_at_64_experts_top_4(chip, tokens):
    """The buckets' rows go in several row blocks (PR 37: the grid's
    outermost axis), the last of them ragged: 127 row tiles in blocks of 8
    and 12 at 2,048 tokens."""
    d, hidden, experts, selected = _GLM_LAYER.values()
    plan = moe.dispatch_plan(tokens, experts, selected, quantized=True, model_dim=d, hidden_dim=hidden)
    blocks = [plan[product]["row_blocks"] for product in ("gate_up", "down")]
    assert blocks == {32: [2, 1], 512: [6, 4], 2048: [16, 11], 4096: [24, 16]}[tokens]

    def mlp(x, weights, indices, w_gate, w_up, w_down, *scales):
        return moe.grouped_expert_mlp(x, weights, indices, w_gate, w_up, w_down, scales=scales, impl="pallas")

    text = _assert_mosaic(
        chip, mlp,
        ((tokens, d), jnp.bfloat16), ((tokens, selected), jnp.float32), ((tokens, selected), jnp.int32),
        ((experts, d, hidden), jnp.int8), ((experts, d, hidden), jnp.int8), ((experts, hidden, d), jnp.int8),
        ((experts, hidden), jnp.float32), ((experts, hidden), jnp.float32), ((experts, d), jnp.float32),
    )
    assert len(re.findall(r"%moe_grouped_matmul(?:\.\d+)* = ", text)) == 2
    _no_array_of_every_expert(text, tokens, **_GLM_LAYER)


# ---- the keye_vl2_long_context_decode cell's new programs (PR 40): 16 rows
# over a table 274 blocks of 64 positions wide (16,384 + 1,024 + the in-flight
# chunks' spare rows) in a 4.0 GB pool of 2,260 blocks a layer (a position's
# keys and values one 8 x 128 tile, the 64-wide indexer key held in 128
# lanes); the indexer's 16 heads of 64; the exact top-2,048; whole prompts in
# blocks of queries. Blocks of 16 (the other served cells') compile too
@pytest.mark.parametrize(
    "blocks,block,width", [(2_260, 64, 274), (9_042, 16, 1_093), (512, 16, 11)],
    ids=["cell-16x274x64", "blocks-of-16", "short-table"],
)
def test_paged_index_scores_compiles(chip, blocks, block, width):
    from unionml_tpu.models.layers import IndexedKVRows

    assert IndexedKVRows(4, 128, 64).index_stored == 128

    def fn(iq, iw, pool, table, lengths):
        return paged_attention.paged_index_scores(iq, iw, pool, table, lengths, impl="pallas")

    text = _assert_mosaic(
        chip, fn, ((16, 16, 128), jnp.bfloat16), ((16, 16), jnp.float32),
        ((blocks, block, 128), jnp.bfloat16), ((16, width), jnp.int32), ((16,), jnp.int32),
    )
    # chipbench's index_scores_roofline finds the kernel by this name
    assert re.search(r"%paged_index_scores(\.\d+)* = ", text)
    # the pool goes to the kernel as it lies: no copy, no relayout of it
    assert not re.search(rf"bf16\[{blocks},{block},128\]\S* (?:copy|transpose)\(", text)


def test_the_sparse_decode_read_walks_the_pool_as_it_lies_and_sorts_nothing(chip):
    """The exact top-2,048 of 17,536 scores a row as a mask and the attention
    over the selected rows, as the decode step runs them at the cell's
    shapes: the threshold descent (no ``sort``), then the kernel
    ``paged_sparse_attention``, which takes the pool whole: no gather of
    its tiles, no copy, transpose or fusion of it."""
    def fn(q, kv, table, lengths, scores):
        selected = sparse_attention.top_k_mask(scores, 2048)
        return paged_attention.paged_sparse_attention(q, kv, table, lengths, selected, impl="pallas")

    text = _assert_mosaic(
        chip, fn, ((16, 32, 128), jnp.bfloat16), ((2_260, 64, 8, 128), jnp.bfloat16),
        ((16, 274), jnp.int32), ((16,), jnp.int32), ((16, 17_536), jnp.float32),
    )
    # chipbench's sparse_attn_ms_per_step finds the kernel by this name
    assert re.search(r"%paged_sparse_attention(\.\d+)* = \S+ custom-call\(", text)
    assert not re.search(r"\bsort\(", text)
    assert not re.search(r"bf16\[(?:16,2048|32768),8,128\]", text)          # no picked tiles gathered
    assert not re.search(r" gather\(", text)
    assert not re.search(r"bf16\[2260,(?:64,8|512),128\]\S* (?:copy|transpose|fusion)\(", text)


@pytest.mark.parametrize("seq", [4096, 16384], ids=["smallest-bucket", "largest-bucket"])
def test_a_whole_prompts_sparse_attention_compiles_as_two_kernels(chip, seq):
    """One layer of a whole prompt at the cell's smallest and largest
    bucket: index scores and the selection in the kernel
    ``sparse_prefill_select`` (a tile of 128 queries' ordered scores over
    up to 16,384 keys in 8 MB of fast memory), the softmax over the selected
    set in ``sparse_prefill_attention``; no loop over blocks of queries and
    no float32 ``[S, S]`` array of scores."""
    def fn(q, k, v, iq, ik, iw, valid):
        return sparse_attention.sparse_attention(
            q, k, v, iq, ik, iw, jnp.arange(seq)[None, :], valid, topk=2048, scale=128 ** -0.5)

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        ((1, seq, 32, 128), jnp.bfloat16), ((1, seq, 4, 128), jnp.bfloat16), ((1, seq, 4, 128), jnp.bfloat16),
        ((1, seq, 16, 64), jnp.bfloat16), ((1, seq, 64), jnp.bfloat16), ((1, seq, 16), jnp.float32),
        ((1, seq), jnp.bool_))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(r"%sparse_prefill_select(\.\d+)* = ", text)
    assert re.search(r"%sparse_prefill_attention(\.\d+)* = ", text)
    assert "while(" not in text
    assert not re.search(rf"f32\[(?:1,)?(?:(?:32|4,8|16),)?{seq},{seq}\]", text)
    # the mask goes from one kernel to the other in tiles, as int8
    assert re.search(rf"s8\[1,{seq // 128},{seq // 512},128,512\]", text)


def test_a_block_forward_reads_the_pool_once_for_its_eight_queries(chip):
    """The decode chunk of a module that generates by blocks, at the cell's
    head widths (32 queries over 4 keys of 128, blocks of 4, 32 slots, pool
    blocks of 16; two layers of eight experts and a small vocabulary, so
    that it compiles in seconds): each layer's read of the pool is the
    kernel ``paged_attention`` with eight queries a row (the block a slot
    closes and the next one's first pass), 8 x 32 query rows, and the pool
    goes to it as it lies: no gather of its blocks, no copy, transpose or
    fusion of it, round the scatter of two blocks' rows a slot either; the
    head runs over the open block's four rows of each slot."""
    from unionml_tpu.models.generate import make_sampler
    from unionml_tpu.models.sdar_moe import SdarMoe, SdarMoeConfig
    from unionml_tpu.serving.programs import build_programs

    slots, blocks, block, width, steps = 32, 512, 16, 40, 2
    module = SdarMoe(SdarMoeConfig(
        vocab_size=8192, num_hidden_layers=2, num_experts=8, num_experts_per_tok=2, quantized=True,
        mask_token_id=8191, remasking_strategy="low_confidence_static",
    ))
    progs = build_programs(
        module, slots=slots, rows=width * block, pool_blocks=blocks, block=block, chunk_steps=steps,
        sample=make_sampler(), eos_id=None, pad_id=0,
    )

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    args = (
        params, jax.eval_shape(progs.init_state), jax.ShapeDtypeStruct((slots,), jnp.bool_),
        jax.ShapeDtypeStruct((slots, width), jnp.int32), jax.ShapeDtypeStruct((steps, 2), jnp.uint32),
    )
    text = progs.decode_chunk.lower(*on_chip(args)).compile().as_text()
    assert text.lstrip().startswith("HloModule jit_decode_chunk")
    calls = re.findall(r"%paged_attention(?:\.\d+)* = (\S+) custom-call\(", text)
    assert calls and all(c.startswith("bf16[32,256,128]") for c in calls)   # [slots, 8 x 32 query rows, 128]
    # logits of four rows a slot
    assert re.search(r"f32\[(?:128|32,4),8192\]", text) and not re.search(r"f32\[(?:256|32,8),8192\]", text)
    pool = rf"bf16\[{blocks},(?:{block},8|{block * 8}),128\]"     # fused rows: 4 key + 4 value heads
    assert not re.search(rf"{pool}\S* (?:copy|transpose|gather)\(", text)
    assert not [line for line in text.splitlines() if " gather(" in line and re.search(pool, line)]
    assert len(re.findall(r"%paged_attention(?:\.\d+)* = \S+ custom-call\(", text)) == 2
