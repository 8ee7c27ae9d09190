"""Attention-family and MoE op tests: every implementation is checked
against the full-score XLA reference (SURVEY.md §4.3 strategy: numerical
equivalence on the CPU-simulated mesh)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.ops.attention import attention, blockwise_attention, mha_reference
from unionml_tpu.ops.flash_attention import flash_attention
from unionml_tpu.ops.ring_attention import ring_attention
from unionml_tpu.ops.ulysses import ulysses_attention
from unionml_tpu.parallel import make_mesh


def make_qkv(batch=2, seq=64, q_heads=4, kv_heads=4, dim=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, seq, q_heads, dim), dtype)
    k = jax.random.normal(ks[1], (batch, seq, kv_heads, dim), dtype)
    v = jax.random.normal(ks[2], (batch, seq, kv_heads, dim), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_blockwise_matches_reference(causal, kv_heads):
    q, k, v = make_qkv(kv_heads=kv_heads)
    ref = mha_reference(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_ragged_kv():
    # kv length not a multiple of the block size
    q, k, v = make_qkv(seq=50)
    ref = mha_reference(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = make_qkv(seq=128, dim=32)
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gqa_and_ragged():
    q, k, v = make_qkv(seq=72, q_heads=4, kv_heads=2, dim=32)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_kv_valid_start_matches_masked_reference():
    """Per-row left-pad masking (generation prefill): kv positions below
    kv_valid_start are invisible; fully-padded query rows return zeros."""
    q, k, v = make_qkv(batch=3, seq=96, q_heads=4, kv_heads=2, dim=32)
    pads = jnp.asarray([0, 17, 90], jnp.int32)

    from unionml_tpu.ops.attention import _repeat_kv

    kr, vr = _repeat_kv(k, 4), _repeat_kv(v, 4)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * (32 ** -0.5)
    qpos = jnp.arange(96)[None, None, :, None]
    kpos = jnp.arange(96)[None, None, None, :]
    mask = (kpos <= qpos) & (kpos >= pads[:, None, None, None])
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    rowvalid = (jnp.arange(96)[None, :] >= pads[:, None])[:, :, None, None]
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, vr) * rowvalid

    out = flash_attention(
        q, k, v, causal=True, kv_valid_start=pads, block_q=32, block_kv=32
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # pad = 0 everywhere must equal the plain causal kernel exactly
    out0 = flash_attention(
        q, k, v, causal=True, kv_valid_start=jnp.zeros(3, jnp.int32),
        block_q=32, block_kv=32,
    )
    plain = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(plain))


def test_flash_gradients_match_reference():
    q, k, v = make_qkv(seq=64, dim=16)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32, block_kv=32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_matches_reference(causal):
    from unionml_tpu.ops.fused_attention import fused_attention

    q, k, v = make_qkv(seq=72, dim=32)  # ragged: 72 not tile-aligned
    ref = mha_reference(q, k, v, causal=causal)
    out = fused_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fused_gradients_match_reference_gqa():
    from unionml_tpu.ops.fused_attention import fused_attention

    q, k, v = make_qkv(seq=72, q_heads=4, kv_heads=2, dim=32)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_fused(q, k, v):
        return jnp.sum(fused_attention(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


# (seq, q_heads, kv_heads, head_dim, causal) -> the blocks the rule takes:
# two 64-wide heads a lane tile, a 128- or 256-wide head over whole tiles,
# four 32-wide heads a tile; a width that fits no tile (80) or an odd head
# count at 64 keeps a head a block row
FUSED_LAYOUT_CASES = {
    "d64-s197": ((197, 4, 4, 64, False), ("rows", 2)),
    "d64-s64-causal": ((64, 4, 4, 64, True), ("rows", 2)),
    "d64-s197-causal-gqa": ((197, 4, 2, 64, True), ("rows", 2)),
    "d128-s64": ((64, 2, 2, 128, False), ("rows", 1)),
    "d128-s48-causal-gqa": ((48, 4, 2, 128, True), ("rows", 1)),
    "d256-s32-causal": ((32, 2, 2, 256, True), ("rows", 1)),
    "d32-s80-causal": ((80, 8, 8, 32, True), ("rows", 4)),
    "d80-s64-causal": ((64, 2, 2, 80, True), ("heads", 1)),
    "d64-odd-heads-s48": ((48, 3, 3, 64, False), ("heads", 1)),
}


@pytest.mark.parametrize("what", ["values", "gradients"])
@pytest.mark.parametrize("case", FUSED_LAYOUT_CASES)
def test_fused_layouts_match_reference(case, what):
    from unionml_tpu.ops.fused_attention import attention_layout, fused_attention

    (seq, q_heads, kv_heads, dim, causal), expected = FUSED_LAYOUT_CASES[case]
    q, k, v = make_qkv(batch=2, seq=seq, q_heads=q_heads, kv_heads=kv_heads, dim=dim)
    assert attention_layout(seq, q_heads, dim, q.dtype)[:2] == expected
    if what == "values":
        np.testing.assert_allclose(
            np.asarray(fused_attention(q, k, v, causal=causal)),
            np.asarray(mha_reference(q, k, v, causal=causal)), atol=2e-5,
        )
        return

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2)

    g_ref = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    g_fused = jax.grad(loss(fused_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fused):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


@pytest.mark.parametrize(
    "shape,layout,heads_per_tile,stored,values",
    [
        # the vit_b16_train cell: 197 rows lie in 208, nothing else is padding
        ((197, 12, 64, jnp.bfloat16), "rows", 2, 208 * 768 * 2, 197 * 768 * 2),
        ((128, 12, 64, jnp.bfloat16), "rows", 2, 128 * 768 * 2, 128 * 768 * 2),
        ((512, 32, 128, jnp.bfloat16), "rows", 1, 512 * 4096 * 2, 512 * 4096 * 2),
        ((197, 12, 64, jnp.float32), "rows", 2, 200 * 768 * 4, 197 * 768 * 4),
        ((256, 8, 80, jnp.bfloat16), "heads", 1, 8 * 256 * 128 * 2, 256 * 640 * 2),
        ((197, 3, 64, jnp.bfloat16), "heads", 1, 3 * 208 * 128 * 2, 197 * 192 * 2),
    ],
    ids=["vit-cell", "bert-base", "d128", "float32", "d80", "odd-heads"],
)
def test_fused_attention_layout_rule(shape, layout, heads_per_tile, stored, values):
    from unionml_tpu.ops.fused_attention import _heads_layout, attention_layout

    assert attention_layout(*shape) == (layout, heads_per_tile, stored, values)
    if layout == "heads":
        assert attention_layout(*shape) == _heads_layout(*shape)


def test_fused_attention_layout_at_the_cell_stores_what_it_holds():
    from unionml_tpu.ops.fused_attention import _heads_layout, attention_layout

    cell = (197, 12, 64, jnp.bfloat16)
    new, old = attention_layout(*cell), _heads_layout(*cell)
    assert round(new.stored_bytes / new.value_bytes, 3) == 1.056
    assert round(old.stored_bytes / old.value_bytes, 3) == 2.112


def test_fused_rejects_long_sequences():
    from unionml_tpu.ops.fused_attention import fused_attention

    q, k, v = make_qkv(batch=1, seq=2048, q_heads=1, dim=8)
    with pytest.raises(ValueError, match="short sequences"):
        fused_attention(q, k, v)


def test_fused_rejects_unequal_lengths():
    from unionml_tpu.ops.fused_attention import fused_attention

    q, k, v = make_qkv(seq=32, dim=16)
    with pytest.raises(ValueError, match="q_len == kv_len"):
        fused_attention(q[:, :16], k, v)


def test_flash_causal_cross_length_bottom_right_aligned():
    """Decode convention: with q_len < kv_len the queries are the LAST
    q_len positions — flash must match the reference's alignment in both
    forward and gradients."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 8, 4, 16))
    k = jax.random.normal(ks[1], (2, 40, 4, 16))
    v = jax.random.normal(ks[2], (2, 40, 4, 16))
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_flash = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=16, block_kv=16) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_flash_gradients_gqa_cross_length():
    # KV prefix longer than q (decode-style): GQA group-sum must reshape
    # with kv_len, not q_len
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 16, 4, 8))
    k = jax.random.normal(ks[1], (1, 32, 2, 8))
    v = jax.random.normal(ks[2], (1, 32, 2, 8))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=False, block_q=16, block_kv=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=False) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_flash_gradients_gqa_ragged():
    # GQA (group-summed dk/dv) + ragged tail blocks in the Pallas backward
    q, k, v = make_qkv(seq=72, q_heads=4, kv_heads=2, dim=32)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=False) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=False, block_q=32, block_kv=32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh({"sequence": 8})
    q, k, v = make_qkv(seq=64)
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, causal=causal, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_gqa():
    mesh = make_mesh({"sequence": 8})
    q, k, v = make_qkv(seq=64, q_heads=8, kv_heads=2)
    ref = mha_reference(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh, causal=True, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_gradients_match_reference():
    # sequence-parallel TRAINING: the backward differentiates through the
    # ppermute rotation (AD of collectives)
    mesh = make_mesh({"sequence": 8})
    q, k, v = make_qkv(seq=64)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True, block_size=8) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_ulysses_gradients_match_reference():
    mesh = make_mesh({"sequence": 4, "tensor": 2})
    q, k, v = make_qkv(seq=32, q_heads=8, kv_heads=8)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_uly(q, k, v):
        return jnp.sum(
            ulysses_attention(q, k, v, mesh, axis="sequence", causal=True) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_uly):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal):
    mesh = make_mesh({"sequence": 4, "tensor": 2})
    q, k, v = make_qkv(seq=32, q_heads=8, kv_heads=8)
    ref = mha_reference(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh, axis="sequence", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_attention_dispatcher():
    q, k, v = make_qkv(seq=32)
    for impl in ("xla", "blockwise", "flash", "fused", "auto"):
        out = attention(q, k, v, impl=impl, causal=True)
        assert out.shape == q.shape
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, k, v, impl="nope")


def test_attention_auto_routes_by_length():
    # short → fused; long or cross-length → flash (both numerically checked
    # against the reference elsewhere; here we check the routing decision
    # by matching each candidate's output exactly)
    from unionml_tpu.ops.flash_attention import flash_attention
    from unionml_tpu.ops.fused_attention import fused_attention

    q, k, v = make_qkv(seq=48, dim=16)
    np.testing.assert_array_equal(
        np.asarray(attention(q, k, v, impl="auto")),
        np.asarray(fused_attention(q, k, v)),
    )
    ql, kl, vl = make_qkv(batch=1, seq=1056, q_heads=2, kv_heads=2, dim=16)
    np.testing.assert_array_equal(
        np.asarray(attention(ql, kl, vl, impl="auto")),
        np.asarray(flash_attention(ql, kl, vl)),
    )


# ------------------------------------------------------------------ MoE


def test_moe_forward_and_balance():
    from unionml_tpu.ops.moe import MoEMlp, top_k_routing

    module = MoEMlp(num_experts=4, num_selected=2, hidden_dim=32, model_dim=16,
                    dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = module.init(jax.random.PRNGKey(1), x)
    out, aux = module.apply(params, x)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))

    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 4))
    weights, indices, aux = top_k_routing(logits, 2)
    assert weights.shape == (64, 2) and indices.shape == (64, 2)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-5)


def test_moe_differentiable():
    from unionml_tpu.ops.moe import MoEMlp

    module = MoEMlp(num_experts=4, num_selected=1, hidden_dim=16, model_dim=8,
                    dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 8))
    params = module.init(jax.random.PRNGKey(1), x)

    def loss(p):
        out, aux = module.apply(p, x)
        return jnp.sum(out**2) + 0.01 * aux

    grads = jax.grad(loss)(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
    assert any(np.any(np.asarray(l) != 0) for l in leaves)


def test_causal_decode_alignment_bottom_right():
    """q_len < kv_len causal (KV-cache decode): queries are the LAST q_len
    positions. Regression: the mask offset was applied to kv instead of q,
    masking everything for the final query row."""
    q, k, v = make_qkv(seq=16)
    full = mha_reference(q, k, v, causal=True)
    # last 4 queries against the full KV prefix must match the full result
    tail = mha_reference(q[:, -4:], k, v, causal=True)
    np.testing.assert_allclose(np.asarray(full[:, -4:]), np.asarray(tail), rtol=1e-5, atol=1e-5)
    # single-token decode: must attend to ALL kv (not be fully masked)
    one = mha_reference(q[:, -1:], k, v, causal=True)
    np.testing.assert_allclose(np.asarray(full[:, -1:]), np.asarray(one), rtol=1e-5, atol=1e-5)
    # blockwise agrees with the same convention
    bw = blockwise_attention(q[:, -4:], k, v, causal=True, block_size=8)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(bw), rtol=1e-4, atol=1e-4)


def test_partition_rule_tuple_entries_and_fallbacks():
    """Tuple spec entries shard one dim over multiple axes; axes missing
    from the mesh or not dividing the dim are dropped, not erroring."""
    from unionml_tpu.parallel import PartitionRule, ShardingConfig

    cfg = ShardingConfig(
        data=2, fsdp=2, tensor=2,
        rules=(
            PartitionRule(r"big/kernel", (("fsdp", "tensor"), None)),
            PartitionRule(r"odd/kernel", (None, "tensor")),
            PartitionRule(r"gone/kernel", ("expert", None)),
        ),
    )
    big = np.zeros((8, 4))
    spec = cfg.param_pspec("big/kernel", big)
    assert spec == jax.sharding.PartitionSpec(("fsdp", "tensor"), None)
    odd = np.zeros((4, 3))  # 3 not divisible by tensor=2 → dropped
    assert cfg.param_pspec("odd/kernel", odd) == jax.sharding.PartitionSpec(None, None)
    gone = np.zeros((4, 4))  # expert axis not in mesh → dropped
    assert cfg.param_pspec("gone/kernel", gone) == jax.sharding.PartitionSpec(None, None)


# --------------------------------------------------------------------- #
# ring flash attention (Pallas local compute + lse merge)
# --------------------------------------------------------------------- #

from unionml_tpu.ops.ring_attention import ring_flash_attention  # noqa: E402


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_reference(causal):
    q, k, v = make_qkv(seq=32)
    mesh = make_mesh({"sequence": 4}, devices=jax.devices()[:4])
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_flash_attention(q, k, v, mesh, causal=causal, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_ring_flash_gqa():
    q, k, v = make_qkv(seq=32, q_heads=4, kv_heads=2)
    mesh = make_mesh({"sequence": 2}, devices=jax.devices()[:2])
    ref = mha_reference(q, k, v, causal=True)
    out = ring_flash_attention(q, k, v, mesh, causal=True, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_ring_flash_gradients_match_reference(kv_heads):
    q, k, v = make_qkv(seq=16, q_heads=4, kv_heads=kv_heads, dim=8)
    mesh = make_mesh({"sequence": 2}, devices=jax.devices()[:2])

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_flash_attention(q, k, v, mesh, causal=True, block_size=8) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=3e-4)


def test_quantized_cache_attention_blockwise_matches_full():
    """The online-softmax block scan (long-context VMEM guard) must equal
    the single-fusion form up to float reduction order — including GQA,
    masked (bias) slots, per-position scales, and a non-dividing block."""
    import numpy as np

    from unionml_tpu.ops.attention import quantized_cache_attention

    rng = np.random.default_rng(0)
    B, S, Hq, Hk, D, Q = 2, 100, 4, 2, 16, 3
    q = jnp.asarray(rng.normal(size=(B, Q, Hq, D)), jnp.bfloat16)
    k_q = jnp.asarray(rng.integers(-127, 128, (B, S, Hk, D)), jnp.int8)
    v_q = jnp.asarray(rng.integers(-127, 128, (B, S, Hk, D)), jnp.int8)
    k_s = jnp.asarray(rng.uniform(0.5, 2.0, (B, S, Hk)), jnp.float32) / 127
    v_s = jnp.asarray(rng.uniform(0.5, 2.0, (B, S, Hk)), jnp.float32) / 127
    visible = jnp.asarray(rng.random((B, 1, Q, S)) < 0.8)
    bias = jnp.where(visible, 0.0, -1e30)
    # every query row must see at least one key
    bias = bias.at[..., 0].set(0.0)

    full = quantized_cache_attention(
        q, k_q, v_q, k_s, v_s, bias=bias, block_threshold=4096
    )
    blocked = quantized_cache_attention(
        q, k_q, v_q, k_s, v_s, bias=bias, block_threshold=32  # 100 -> 4 blocks, padded
    )
    np.testing.assert_allclose(
        np.asarray(full, np.float32), np.asarray(blocked, np.float32),
        atol=2e-2, rtol=2e-2,
    )
    # bias=None long path (decode without mask)
    full_nb = quantized_cache_attention(
        q, k_q, v_q, k_s, v_s, block_threshold=4096
    )
    blocked_nb = quantized_cache_attention(
        q, k_q, v_q, k_s, v_s, block_threshold=25
    )
    np.testing.assert_allclose(
        np.asarray(full_nb, np.float32), np.asarray(blocked_nb, np.float32),
        atol=2e-2, rtol=2e-2,
    )
