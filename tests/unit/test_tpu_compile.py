"""The main path's Pallas kernels compile for the chip, at real widths.

Interpret mode on the CPU accepts kernels Mosaic refuses (the paged
decode kernel passed every interpret test and compiled at no shape), so
these tests hand the installed TPU compiler a DESCRIBED ``v5e:2x2`` chip
and shapes — nothing runs, no chip is needed — and assert the compiled
program holds a ``tpu_custom_call``. A compile that passes is not a chip
run: ``chip_smoke.py`` is what runs them.
"""

import os
import re

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
    paged_attention,
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

    for module in (flash_attention, fused_attention, fused_norm, gated_delta, paged_attention):
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


def test_gated_delta_step_compiles(chip):
    """The decode kernel of the gated delta rule at the hybrid cell's shape:
    32 slots, 30 heads of 96 x 192, two heads a state row."""
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


def test_fused_attention_forward_compiles(chip):
    _assert_mosaic(chip, fused_attention.fused_attention, VIT_QKV, VIT_QKV, VIT_QKV)


def test_fused_attention_backward_compiles(chip):
    def loss(q, k, v):
        return fused_attention.fused_attention(q, k, v).astype(jnp.float32).sum()

    _assert_mosaic(chip, jax.grad(loss, argnums=(0, 1, 2)), VIT_QKV, VIT_QKV, VIT_QKV)


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
