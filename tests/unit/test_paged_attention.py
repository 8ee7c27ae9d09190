"""Paged-attention op tests: reference parity + Pallas kernel numerics.

The reference path must be BIT-identical to the contiguous cached
attention on the same rows (that is the engine's paged-vs-contiguous
parity anchor); the Pallas kernel matches the reference within float
reduction order (the flash-kernel numerics contract), in interpreter
mode on CPU.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from unionml_tpu.ops.attention import cached_attention, quantized_cache_attention
import jax

from unionml_tpu.ops.paged_attention import (
    _ROWS_PER_STEP,
    _pages_per_step,
    paged_attention,
    paged_attention_reference,
    score_tile,
)

B, H, KVH, D, BS, W, N = 3, 4, 2, 16, 8, 4, 12


def _setup(dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((N, BS, KVH, D)), dtype)
    v = jnp.asarray(rng.standard_normal((N, BS, KVH, D)), dtype)
    table = jnp.asarray(rng.integers(1, N, (B, W)), jnp.int32)
    lengths = jnp.asarray([1, 13, W * BS], jnp.int32)
    return q, k, v, table, lengths


def _contiguous(pool, table):
    return jnp.take(pool, table.reshape(-1), axis=0).reshape(
        (B, W * BS) + pool.shape[2:]
    )


def _bias(lengths):
    kv_pos = jnp.arange(W * BS)[None, :]
    visible = kv_pos[None] <= (lengths - 1)[:, None, None]
    return jnp.where(visible, 0.0, -1e30)[:, None]


def test_reference_bit_identical_to_contiguous():
    q, k, v, table, lengths = _setup()
    ref = paged_attention_reference(q, k, v, table, lengths)
    contig = cached_attention(
        q[:, None], _contiguous(k, table), _contiguous(v, table),
        bias=_bias(lengths),
    )[:, 0]
    assert bool(jnp.all(ref == contig))


def test_reference_bit_identical_int8():
    rng = np.random.default_rng(1)
    q, _, _, table, lengths = _setup(seed=1)
    kq = jnp.asarray(rng.integers(-127, 128, (N, BS, KVH, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (N, BS, KVH, D)), jnp.int8)
    ks = jnp.asarray(rng.random((N, BS, KVH)) * 0.02 + 1e-3, jnp.float32)
    vs = jnp.asarray(rng.random((N, BS, KVH)) * 0.02 + 1e-3, jnp.float32)
    ref = paged_attention_reference(
        q, kq, vq, table, lengths, k_scale=ks, v_scale=vs
    )
    contig = quantized_cache_attention(
        q[:, None], _contiguous(kq, table), _contiguous(vq, table),
        _contiguous(ks, table), _contiguous(vs, table), bias=_bias(lengths),
    )[:, 0]
    assert bool(jnp.all(ref == contig))


# Table widths for the kernel's grouping (a row walks its table P pool
# blocks at a time, P from the shapes: test_pages_per_step_follows_the_shapes):
# the short table is narrower than one group (W < P), the wide one two
# groups with a partial last one (W not a multiple of P)
P = _ROWS_PER_STEP // BS
WIDE = P + 6
WIDTHS = pytest.mark.parametrize("width", [W, WIDE], ids=["short-table", "wide-table"])
# (q heads, kv heads): grouped-query and one kv head per q head, at toy
# width and at the two served geometries' head counts
HEADS = pytest.mark.parametrize(
    "heads", [(H, KVH), (H, H), (32, 8), (32, 32)], ids=["gqa", "mha", "gqa-32-8", "mha-32-32"]
)


def _edges(width):
    """A zero-length row, one visible row, a length at a group boundary
    and one past it (a block boundary on the short table), the full
    table, and a dead row (every table entry the trash block 0, its
    length stale)."""
    edge = min(P, width - 1) * BS
    return [0, 1, edge, edge + 1, width * BS, edge + 3], [False] * 5 + [True]


def _holes(width):
    """Rows that see nothing first (two: the search for the next row that
    sees anything has to skip both), between live rows and last; a length
    that ends inside a group and inside a block; the whole table; a stale
    length past a full table (the kernel clamps it, the reference sees the
    whole table either way); a dead row with a stale length."""
    inside = (min(P, width - 1) - 1) * BS + 3
    full = width * BS
    return [0, 0, inside, 0, full, 0, full + 37, inside + 5, 0, 0], [False] * 7 + [True] + [False] * 2


LAYOUTS = pytest.mark.parametrize("layout", [_edges, _holes], ids=["edges", "holes"])


def _ragged_setup(width, heads, dtype, quantized, seed, layout=_edges):
    """Rows of ``layout`` over a ``width``-block table of a pool whose
    trash block holds garbage; the rows to compare are those that see
    anything and are not dead."""
    q_heads, kv_heads = heads
    rng = np.random.default_rng(seed)
    n_blocks = 2 * width
    lengths, dead = (np.array(x) for x in layout(width))
    lengths = lengths.astype(np.int32)
    table = rng.integers(1, n_blocks, (len(lengths), width)).astype(np.int32)
    for b, n in enumerate(lengths):  # entries past coverage park on the trash block
        table[b, 0 if dead[b] else -(-int(n) // BS):] = 0
    q = jnp.asarray(rng.standard_normal((len(lengths), q_heads, D)), dtype)
    shape = (n_blocks, BS, kv_heads, D)
    kw = {}
    if quantized:
        k = rng.integers(-127, 128, shape).astype(np.int8)
        v = rng.integers(-127, 128, shape).astype(np.int8)
        kw = dict(
            k_scale=jnp.asarray(rng.random(shape[:3]) * 0.02 + 1e-3, jnp.float32),
            v_scale=jnp.asarray(rng.random(shape[:3]) * 0.02 + 1e-3, jnp.float32),
        )
    else:
        k, v = rng.standard_normal((2,) + shape).astype(np.float32)
    k[0] = v[0] = 100 if quantized else 1e4  # the trash block holds garbage
    args = (q, jnp.asarray(k, None if quantized else dtype),
            jnp.asarray(v, None if quantized else dtype),
            jnp.asarray(table), jnp.asarray(lengths))
    return args, kw, (lengths > 0) & ~dead


def _gap(args, kw, rows):
    ref = paged_attention(*args, impl="reference", **kw).astype(jnp.float32)
    pal = paged_attention(*args, impl="pallas", **kw).astype(jnp.float32)
    # dead rows are garbage by contract, but finite; a row that sees
    # nothing reads nothing and comes out as zeros
    assert bool(jnp.all(jnp.isfinite(pal)))
    assert not bool(jnp.any(pal[np.asarray(args[4]) == 0]))
    return float(jnp.max(jnp.abs(pal - ref)[rows]))


@LAYOUTS
@HEADS
@WIDTHS
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_matches_reference(dtype, width, heads, layout):
    args, kw, rows = _ragged_setup(width, heads, dtype, False, seed=4, layout=layout)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    assert _gap(args, kw, rows) < tol


@LAYOUTS
@HEADS
@WIDTHS
def test_pallas_matches_reference_int8(width, heads, layout):
    args, kw, rows = _ragged_setup(width, heads, jnp.float32, True, seed=5, layout=layout)
    assert _gap(args, kw, rows) < 1e-5


# ---- several queries a row that share its length (a block of positions
# that attend one another: a decoder that generates by blocks)


def _with_queries(args, queries, seed=11):
    """``_ragged_setup``'s arguments with ``queries`` queries a row."""
    q = args[0]
    rng = np.random.default_rng(seed)
    many = jnp.asarray(rng.standard_normal((q.shape[0], queries) + q.shape[1:]), q.dtype)
    return (many.at[:, 0].set(q),) + args[1:]


@LAYOUTS
@HEADS
@pytest.mark.parametrize("queries", [1, 4, 8])
def test_pallas_matches_reference_with_several_queries_a_row(queries, heads, layout):
    """Rows of unequal length, rows that see nothing and a dead row, each
    with 1, 4 or 8 queries: the kernel's rows are the gather's."""
    args, kw, rows = _ragged_setup(11, heads, jnp.float32, False, seed=6, layout=layout)
    args = _with_queries(args, queries)
    ref = paged_attention(*args, impl="reference", **kw)
    pal = paged_attention(*args, impl="pallas", **kw)
    assert ref.shape == pal.shape == args[0].shape
    assert bool(jnp.all(jnp.isfinite(pal))) and not bool(jnp.any(pal[np.asarray(args[4]) == 0]))
    assert float(jnp.max(jnp.abs(pal - ref)[rows])) < 1e-6


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_one_query_a_row_is_todays_output_bit_for_bit(impl, quantized):
    """``q [B, 1, Hq, D]`` runs the program ``q [B, Hq, D]`` runs."""
    args, kw, _ = _ragged_setup(11, (8, 2), jnp.float32, quantized, seed=7)
    one = paged_attention(*args, impl=impl, **kw)
    many = paged_attention(args[0][:, None], *args[1:], impl=impl, **kw)
    assert many.shape == (one.shape[0], 1) + one.shape[1:]
    assert np.array_equal(np.asarray(many[:, 0]), np.asarray(one))


@LAYOUTS
@pytest.mark.parametrize("queries", [0, 4], ids=["one-query", "four-queries"])
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_a_fused_pool_is_read_as_its_two_halves(impl, queries, layout):
    """One pool of rows that hold a position's key heads and its value
    heads behind them serves what the two pools serve."""
    args, kw, rows = _ragged_setup(11, (8, 2), jnp.float32, False, seed=9, layout=layout)
    if queries:
        args = _with_queries(args, queries)
    q, k, v, table, lengths = args
    want = paged_attention(q, k, v, table, lengths, impl="reference")
    got = paged_attention(q, jnp.concatenate([k, v], axis=2), None, table, lengths, impl=impl)
    assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want)[rows])) < 1e-6
    with pytest.raises(ValueError, match="fused pool"):
        paged_attention(q, k[:, :, :1], None, table, lengths)


# ---- fused rows: the kernel scores a key head's rows against that head's
# own query rows (group 8 as the served cell's 32 / 4, group 4, one kv head
# a q head, and an odd number of kv heads: a 16-bit pool's 32-bit words
# then hold the last key head beside the first value head)
FUSED_HEADS = pytest.mark.parametrize(
    "heads", [(16, 2), (8, 2), (4, 4), (3, 3)], ids=["group-8", "group-4", "group-1", "odd-kv-heads"]
)


def _fused(args):
    q, k, v, table, lengths = args
    return q, jnp.concatenate([k, v], axis=2), None, table, lengths


@LAYOUTS
@FUSED_HEADS
@WIDTHS
@pytest.mark.parametrize("queries", [0, 4], ids=["one-query", "four-queries"])
def test_fused_rows_match_reference(queries, width, heads, layout):
    """Rows of unequal length (a partial last group, a length inside a
    block), rows of length 0 between live rows and a dead row, over a table
    narrower and wider than one group: each key head's own score tile
    gives what the gather of the two halves gives."""
    args, kw, rows = _ragged_setup(width, heads, jnp.float32, False, seed=12, layout=layout)
    if queries:
        args = _with_queries(args, queries)
    want = paged_attention(*args, impl="reference")
    got = paged_attention(*_fused(args), impl="pallas")
    assert got.shape == want.shape == args[0].shape
    assert bool(jnp.all(jnp.isfinite(got))) and not bool(jnp.any(got[np.asarray(args[4]) == 0]))
    # float summation order: a row of the wide table sums 560 positions in two groups
    assert float(jnp.max(jnp.abs(got - want)[rows])) < (1e-6 if width == W else 2e-6)


@FUSED_HEADS
@pytest.mark.parametrize("queries", [0, 4], ids=["one-query", "four-queries"])
def test_fused_rows_of_16_bits_are_read_two_heads_a_word(queries, heads):
    """A bfloat16 pool: two stored heads of a position share a 32-bit word
    of the gather buffer, and a head's rows are put together from halves of
    the even and the odd positions' words."""
    args, kw, rows = _ragged_setup(11, heads, jnp.bfloat16, False, seed=13, layout=_holes)
    if queries:
        args = _with_queries(args, queries)
    want = paged_attention(*args, impl="reference").astype(jnp.float32)
    got = paged_attention(*_fused(args), impl="pallas").astype(jnp.float32)
    assert bool(jnp.all(jnp.isfinite(got))) and not bool(jnp.any(got[np.asarray(args[4]) == 0]))
    assert float(jnp.max(jnp.abs(got - want)[rows])) < 2e-2


def _kernel_dots(fn, *args):
    """Result shapes of the matmuls in the kernels ``fn`` traces."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn.outvars[0].aval.shape
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) else [param]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("queries", [0, 4], ids=["one-query", "four-queries"])
def test_the_score_tile_follows_the_pool(queries):
    """Two pools trace the one-matmul scheme they traced (every query row
    against every (position, kv head) row of a group); a fused pool a
    matmul a key head, its own query rows against the group's positions."""
    q_heads, kv_heads, width = 8, 2, 11
    args, kw, _ = _ragged_setup(width, (q_heads, kv_heads), jnp.float32, False, seed=14)
    if queries:
        args = _with_queries(args, queries)
    rows, positions = (queries or 1) * q_heads, width * BS

    def tile(fused):
        return score_tile(BS, q_heads, kv_heads, D, 4, width, queries=queries or 1, fused=fused)

    two = _kernel_dots(lambda *a: paged_attention(*a, impl="pallas"), *args)
    assert two == [(rows, positions * kv_heads), (rows, D)]
    assert tile(False) == [rows, positions * kv_heads]
    q, pool, _, table, lengths = _fused(args)
    fused = _kernel_dots(
        lambda *a: paged_attention(a[0], a[1], None, *a[2:], impl="pallas"), q, pool, table, lengths,
    )
    assert fused == [(rows // kv_heads, positions), (rows // kv_heads, D)] * kv_heads
    assert tile(True) == [rows, positions]
    # the served shapes: 4 queries x 32 heads over 4 + 4 stored heads; 32 heads over two pools of 8
    assert score_tile(16, 32, 4, 128, 2, 163, queries=4, fused=True) == [128, 512]
    assert score_tile(16, 32, 8, 128, 2, 101) == [32, 4096]


def test_a_fused_pool_of_8_bit_values_is_refused():
    q, k, v, table, lengths = _setup()
    pool = jnp.concatenate([k, v], axis=2).astype(jnp.int8)
    with pytest.raises(ValueError, match="16 or 32 bits"):
        paged_attention(q, pool, None, table, lengths, impl="pallas")


def test_every_query_of_a_row_sees_all_of_its_rows():
    """The queries of a row are not masked against one another: each sees
    the row's whole length, as a query alone with that length does."""
    args, kw, rows = _ragged_setup(11, (8, 2), jnp.float32, False, seed=8)
    args = _with_queries(args, 4)
    together = paged_attention(*args, impl="reference", **kw)
    for j in range(4):
        alone = paged_attention(args[0][:, j], *args[1:], impl="reference", **kw)
        assert float(jnp.max(jnp.abs(together[:, j] - alone)[rows])) < 1e-6


# ---- a limit a query (two blocks of positions in one call: the earlier
# blind to the later)


def _two_halves(width, heads, dtype, seed):
    """Eight queries a row over a fused pool, the first four of which see
    four positions fewer than the others: a row that sees nothing, a dead
    row with a stale length, a row whose second half alone sees anything,
    rows whose halves end in different pool blocks (a block boundary between
    them) and in different groups (the group's last position between them),
    rows inside a block, and the whole table. Returns the call's arguments,
    the limits, and the ``[rows, queries]`` to compare (a query that sees
    nothing reads zero from the kernel and an average of the table's rows
    from the gather)."""
    edge = min(P, width - 1) * BS          # the first group's end (a block's end on the short table)

    def layout(width):
        lengths = [0, 4, edge + 4, 21, edge + 2, BS + 4, width * BS, 0, 13]
        return lengths, [False] * 8 + [True]

    args, _, rows = _ragged_setup(width, heads, dtype, False, seed=seed, layout=layout)
    q, pool, _, table, lengths = _fused(_with_queries(args, 8))
    first = jnp.maximum(lengths - 4, 0)
    limits = jnp.concatenate([jnp.tile(first[:, None], (1, 4)), jnp.tile(lengths[:, None], (1, 4))], axis=1)
    return (q, pool, None, table, lengths), limits, rows[:, None] & (np.asarray(limits) > 0)


def _half_by_half(args, limits):
    """The gather's answer without its ``limits``: each half of a row's
    queries alone, at the length it may see."""
    q, pool, _, table, lengths = args
    halves = [
        paged_attention(
            q[:, at], pool, None, table, jnp.minimum(lengths, limits[:, at.start]), impl="reference",
        )
        for at in (slice(0, 4), slice(4, 8))
    ]
    return jnp.concatenate(halves, axis=1)


@FUSED_HEADS
@WIDTHS
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_rows_under_a_limit_a_query_match_the_gather_half_by_half(dtype, width, heads):
    args, limits, rows = _two_halves(width, heads, dtype, seed=21)
    want = _half_by_half(args, limits).astype(jnp.float32)
    lengths = np.asarray(args[4])
    # (the gather's einsums over eight queries sum in another order than over four)
    for impl, tol in (("reference", 1e-6), ("pallas", 2e-6)):
        tol = tol if dtype == jnp.float32 else 2e-2
        got = paged_attention(*args, impl=impl, limits=limits).astype(jnp.float32)
        assert got.shape == args[0].shape and bool(jnp.all(jnp.isfinite(got)))
        assert float(jnp.max(jnp.abs(got - want)[rows])) <= tol
        if impl == "pallas":
            assert not bool(jnp.any(got[lengths == 0]))
            # a query that sees nothing (the first half of a row of 4 positions) comes out zero
            assert not bool(jnp.any(got[1, :4])) and bool(jnp.any(got[1, 4:]))


def test_a_limit_never_reaches_past_the_rows_length():
    """What is copied and walked goes by ``lengths``: a limit beyond it sees
    the row's length (a slot in the middle of a block, whose second half is
    dead and whose limits run on)."""
    args, limits, rows = _two_halves(W, (8, 2), jnp.float32, seed=22)
    beyond = limits + jnp.asarray([0, 0, 0, 0, 4, 4, 4, 4])[None, :]
    for impl in ("reference", "pallas"):
        got = paged_attention(*args, impl=impl, limits=beyond)
        want = paged_attention(*args, impl=impl, limits=limits)
        assert float(jnp.max(jnp.abs(got - want)[rows])) == 0.0


# sha256 of ``str(jax.make_jaxpr(...))`` of the fused kernel's call with no
# limits, read on the tree before ``limits=`` existed (PR 45's), on the CPU
# (interpret mode), at the shapes below
_TEXT_BEFORE_LIMITS = {
    1: "aee3b6ca2a0655b8a437720d3ab0c583683370fb7645bcf77bf340803b100f77",
    4: "984f23bcf7bdf7c746c569ea9c8ded99941cbbda173e14de310d396a2d525ba3",
}


@pytest.mark.parametrize("queries", [1, 4], ids=["one-query", "four-queries"])
def test_without_limits_the_fused_kernel_traces_what_it_traced(queries):
    import hashlib

    shape = (3, 32, 128) if queries == 1 else (3, queries, 32, 128)
    text = str(jax.make_jaxpr(
        lambda q, pool, table, lens: paged_attention(q, pool, None, table, lens, impl="pallas")
    )(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16), jax.ShapeDtypeStruct((40, 16, 8, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((3, 12), jnp.int32), jax.ShapeDtypeStruct((3,), jnp.int32),
    ))
    assert hashlib.sha256(text.encode()).hexdigest() == _TEXT_BEFORE_LIMITS[queries]


def test_limits_are_a_fused_pools_and_come_a_query():
    q, k, v, table, lengths = _setup()
    many = jnp.stack([q, q], axis=1)
    with pytest.raises(ValueError, match="limits are \\[batch, queries\\]"):
        paged_attention(q, k, v, table, lengths, limits=jnp.ones((B, 1), jnp.int32))
    with pytest.raises(ValueError, match="limits are \\[batch, queries\\]"):
        paged_attention(many, k, v, table, lengths, limits=jnp.ones((B, 3), jnp.int32))
    with pytest.raises(ValueError, match="one pool of fused rows"):
        paged_attention(many, k, v, table, lengths, impl="pallas", limits=jnp.ones((B, 2), jnp.int32))
    # the plain gather takes them over two pools too
    out = paged_attention(many, k, v, table, lengths, impl="reference", limits=jnp.ones((B, 2), jnp.int32))
    alone = paged_attention(many, k, v, table, jnp.minimum(lengths, 1), impl="reference")
    assert bool(jnp.all(out == alone))


@pytest.mark.parametrize(
    "block,kv_heads,head_dim,itemsize,width,pages",
    [
        (16, 8, 128, 2, 101, 32),   # the benchmark's chat cell: 512 rows a step
        (16, 8, 128, 2, 11, 11),    # never more than the table is wide
        (64, 8, 128, 2, 101, 8),    # 512 rows whatever the pool's block
        (16, 16, 128, 2, 101, 16),  # MHA rows are twice as wide: the VMEM budget halves the step
        (16, 16, 128, 1, 101, 32),  # ... and an int8 pool's are not
        (1024, 8, 128, 2, 4, 1),    # a block larger than a step: one block a step
    ],
)
def test_pages_per_step_follows_the_shapes(block, kv_heads, head_dim, itemsize, width, pages):
    assert _pages_per_step(block, kv_heads, head_dim, itemsize, width) == pages


def test_zero_length_rows_are_finite():
    """Dead slots decode with length 0 (everything masked): the output
    is garbage by contract but must be FINITE — NaN would poison the
    residual stream of live slots through layer norms. A batch of
    nothing but such rows starts no copy in the kernel at all."""
    q, k, v, table, _ = _setup()
    lengths = jnp.zeros((B,), jnp.int32)
    for impl in ("reference", "pallas"):
        out = paged_attention(q, k, v, table, lengths, impl=impl)
        assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert not bool(jnp.any(out))


def test_gqa_groups_share_kv_head():
    """A pool whose two kv heads hold identical rows must produce
    identical outputs across the full q-head width (group mapping)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(
        np.tile(rng.standard_normal((B, 1, D)), (1, H, 1)), jnp.float32
    )
    one = rng.standard_normal((N, BS, 1, D))
    k = jnp.asarray(np.tile(one, (1, 1, KVH, 1)), jnp.float32)
    v = jnp.asarray(np.tile(one, (1, 1, KVH, 1)), jnp.float32)
    table = jnp.asarray(rng.integers(1, N, (B, W)), jnp.int32)
    lengths = jnp.asarray([5, 17, 30], jnp.int32)
    for impl in ("reference", "pallas"):
        out = paged_attention(q, k, v, table, lengths, impl=impl)
        spread = jnp.max(jnp.abs(out - out[:, :1]))
        assert float(spread) < 1e-5


def test_shape_validation():
    q, k, v, table, lengths = _setup()
    with pytest.raises(ValueError):
        paged_attention(q[0], k, v, table, lengths)  # q rank
    with pytest.raises(ValueError):
        paged_attention(q, k, v, table[:1], lengths)  # batch mismatch
    with pytest.raises(ValueError):
        paged_attention(q, k, v, table, lengths[:1])  # lengths shape
    with pytest.raises(ValueError):
        paged_attention(
            q, k, v, table, lengths, k_scale=jnp.ones((N, BS, KVH))
        )  # k_scale without v_scale
    with pytest.raises(ValueError):
        paged_attention(q, k, v, table, lengths, impl="nope")
