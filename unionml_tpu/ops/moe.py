"""Mixture-of-experts: top-k routing + expert dispatch.

Expert parallelism (SURVEY.md §2.4 TPU additions, §7.8 "EP: expert-sharded
MoE with all_to_all dispatch"). Three dispatch paths, one routing math:

- **Grouped dispatch** (:func:`grouped_expert_mlp`, what :class:`MoEMlp`
  serves int8 experts with): the routed (token, choice) pairs are ordered
  by expert and each row meets only its own expert's weights, through a
  grouped matmul (:func:`grouped_matmul`: the Pallas kernel
  ``moe_grouped_matmul`` on a TPU, ``jax.lax.ragged_dot`` elsewhere). No
  capacity limit — every routed token is processed — and top-k experts'
  FLOPs a token, not every expert's.
- **Dense einsum dispatch** (:func:`dense_expert_mlp`, what
  :class:`MoEMlp` runs float experts with): every expert on every token,
  unrouted products masked. Every tensor is static-shaped and
  differentiable; with the expert dim of the weights sharded over the
  mesh's ``expert`` axis, GSPMD inserts the all_to_all-style collectives
  (it cannot partition a Pallas call). The default for pjit training via
  partition rules, and the tests' plain reference for the grouped one.
- **Explicit all_to_all dispatch**
  (:func:`expert_parallel_moe_sharded` / :func:`expert_parallel_moe`):
  the GShard/Switch algorithm inside ``shard_map`` — tokens are bucketed
  per expert up to a static ``capacity``, buckets ride one
  ``lax.all_to_all`` over the ``expert`` axis to the expert-owning
  device, the expert MLP runs on its local shard, and a reverse
  all_to_all + combine-weighted sum scatters results back. Differentiable
  (all_to_all transposes to the reverse all_to_all).

Aux losses follow the standard load-balancing recipe (mean gate fraction
x mean routing fraction per expert).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from flax import linen as nn
from jax import lax
from jax.experimental import pallas as pl


def load_balance_stats(
    probs: jnp.ndarray, indices: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-expert token-MEAN stats behind the load-balance loss.

    probs: [tokens, experts] router softmax; indices: [tokens, k].
    Returns ``(routing_fraction [E], gate_fraction [E])``. The aux loss is
    ``E * sum(rf * gf)`` — both serial (:func:`top_k_routing`) and
    sequence-parallel (models/sequence_parallel.py, which pmeans the
    fractions across shards first) form it from THIS function, so the
    two training paths cannot drift apart.
    """
    num_experts = probs.shape[-1]
    routing_fraction = jnp.mean(
        jax.nn.one_hot(indices[..., 0], num_experts, dtype=jnp.float32), axis=0
    )
    gate_fraction = jnp.mean(probs.astype(jnp.float32), axis=0)
    return routing_fraction, gate_fraction


def top_k_routing(
    gate_logits: jnp.ndarray, num_selected: int, *, return_stats: bool = False
):
    """Softmax-normalized top-k routing.

    gate_logits: [tokens, experts]. Returns (weights [T, k],
    indices [T, k], aux_loss scalar) — plus the
    ``(routing_fraction, gate_fraction)`` pair behind the aux loss when
    ``return_stats`` (so callers that need the raw fractions, e.g. the
    sequence-parallel sow, don't recompute them).
    """
    num_experts = gate_logits.shape[-1]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    weights, indices = jax.lax.top_k(probs, num_selected)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    routing_fraction, gate_fraction = load_balance_stats(probs, indices)
    aux_loss = num_experts * jnp.sum(routing_fraction * gate_fraction)
    weights = weights.astype(gate_logits.dtype)
    if return_stats:
        return weights, indices, aux_loss, (routing_fraction, gate_fraction)
    return weights, indices, aux_loss


def sigmoid_top_k_routing(
    gate_logits: jnp.ndarray,
    selection_bias: jnp.ndarray,
    num_selected: int,
    *,
    scaling: float = 1.0,
):
    """Sigmoid routing with a selection-only bias (DeepSeek-V3's
    ``noaux_tc``, one expert group): every expert scores ``sigmoid(logit)``
    on its own, the ``num_selected`` experts are the top of ``score +
    selection_bias``, and their weights are the *scores* (the bias picks
    and does not weigh) divided by their sum and multiplied by ``scaling``.
    All of it float32. gate_logits: [tokens, experts]; selection_bias:
    [experts]. Returns (weights [T, k] float32, indices [T, k])."""
    scores = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    _, indices = jax.lax.top_k(scores + selection_bias.astype(jnp.float32).reshape(-1), num_selected)
    weights = jnp.take_along_axis(scores, indices, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20) * scaling
    return weights, indices


def expert_capacity(
    tokens: int, num_experts: int, num_selected: int, capacity_factor: float
) -> int:
    """Static per-expert token bucket size for capacity-based dispatch."""
    return max(1, int(math.ceil(num_selected * tokens * capacity_factor / num_experts)))


def make_dispatch(
    gate_logits: jnp.ndarray, num_selected: int, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Capacity-bucketed dispatch/combine tensors (GShard tokens-choose).

    gate_logits: [T, E]. Returns float32 ``(dispatch [T, E, C],
    combine [T, E, C], aux_loss)``: ``dispatch[t, e, c] == 1`` iff token t
    occupies slot c of expert e's bucket; ``combine`` carries the routing
    weight in the same slot. Priority is choice-major (every token's 1st
    choice is bucketed before any 2nd choice), position within a choice is
    token order; overflow beyond ``capacity`` is dropped.
    """
    tokens, num_experts = gate_logits.shape
    weights, indices, aux_loss = top_k_routing(gate_logits, num_selected)

    onehot = jax.nn.one_hot(indices, num_experts, dtype=jnp.int32)  # [T, k, E]
    # choice-major flattening so 1st choices win bucket slots
    flat = onehot.transpose(1, 0, 2).reshape(num_selected * tokens, num_experts)
    position = jnp.cumsum(flat, axis=0) - flat  # slot index within each expert
    keep = (position < capacity) & (flat > 0)
    slot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)  # [kT, E, C]
    slotted = keep[..., None].astype(jnp.float32) * slot
    slotted = slotted.reshape(num_selected, tokens, num_experts, capacity)
    slotted = slotted.transpose(1, 0, 2, 3)  # [T, k, E, C]
    dispatch = slotted.sum(axis=1)
    combine = (slotted * weights.astype(jnp.float32)[:, :, None, None]).sum(axis=1)
    return dispatch, combine, aux_loss


# ------------------------------------------------------------ grouped dispatch
#
# Every (token, choice) pair becomes one row, rows of one expert lie
# together, and a row meets only its own expert's weights. The rows of an
# expert start on a multiple of ``chunk`` (the kernel's row tile), so a
# tile belongs to one expert: no tile is masked or visited twice, and what
# is computed beyond the routed rows is each expert's last tile's padding.


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _mesh_in_sight() -> bool:
    """Whether the trace runs under a mesh with an axis of several devices
    that the compiler partitions (``jax.set_mesh``; not ``shard_map``'s
    manual axes): it does not partition a Pallas call."""
    mesh = jax.sharding.get_abstract_mesh()
    return any(size > 1 for axis, size in mesh.shape.items() if axis not in mesh.manual_axes)


_MXU_ROWS = 128  # the side of the chip's matrix unit


_MIN_ROW_TILE = 16  # a bfloat16 tile's sublanes


def _row_chunk(rows: int, num_experts: int) -> int:
    """Rows of the kernel's row tile for ``rows`` routed pairs. The MXU
    loads a weight tile in the time ``_MXU_ROWS`` rows take to stream, so a
    shorter tile costs what that one does and two of them twice as much:
    the tile is the MXU's side, or the smallest power of two under it that
    holds twice what even routing deals an expert (``rows / num_experts``),
    which cuts the padding and the rows held in VMEM (many small experts:
    64 experts' 128 pairs of a decode chunk lie in 1,088 rows of 16-row
    tiles, in 4,160 of 64-row ones)."""
    tile = _MXU_ROWS
    while tile > _MIN_ROW_TILE and 4 * rows <= num_experts * tile:
        tile //= 2
    return tile


def expected_experts_touched(tokens: int, num_experts: int, num_selected: int) -> float:
    """Experts that hold a routed row, expected under even routing: each
    of ``tokens`` rows draws ``num_selected`` distinct experts."""
    return num_experts * (1.0 - (1.0 - num_selected / num_experts) ** tokens)


# Up to ``_MXU_ROWS`` tokens the dense einsums run at the weight read's
# pace, so the grouped dispatch can only win by the experts no row routes
# to, whose weights it does not read; it pays a sort, two gathers and a
# grid for that (~50 us a layer). Measured at 64 experts top-4 of 2048 x
# 1536 (PERF.md section 6, PR 36; us a layer, dense / grouped at 16-row
# tiles): 32 rows, 87 % of the experts touched, 815 / 759; the same chunk
# with 14 rows live, 60 % touched, 815 / 597; 128 rows, every expert
# touched, 968 / 976. So the grouped dispatch serves such a program where
# fewer than this share of the experts is expected to hold a row ...
_DENSE_FROM_TOUCHED_SHARE = 0.9
# ... among this many experts or more: with fewer (8 experts top-2, where
# 8 rows already touch 90 %) the few-row programs are no cell's, nothing
# was measured, and the plan is left as PR 34 measured it.
_SPARSE_CHUNK_MIN_EXPERTS = 16


def dispatch_plan(
    tokens: int, num_experts: int, num_selected: int, *, quantized: bool,
    model_dim: Optional[int] = None, hidden_dim: Optional[int] = None,
) -> dict:
    """What :class:`MoEMlp` does with ``tokens`` rows, from static shapes
    (experts, top-k, rows, and the experts' widths where given): the
    dispatch, the rows the experts' matmuls compute and the rows the router
    sent (``tokens x num_selected``) and, with the widths, the experts
    expected to hold a routed row, the bytes of expert weights the layer
    reads (one byte a weight when ``quantized``, else two) and, where the
    kernel serves, its ``row_tile`` and for each of its two calls
    (``gate_up``, ``down``) the weight tile ``tk`` x ``tn``, the row tiles
    of a row block, the row blocks and the grid steps.
    The dense dispatch runs every expert on every token; the grouped one
    pads each expert's rows to the kernel's row tile, counted here at its
    worst (every expert's last tile holding one row), and reads the
    weights of the experts that hold a row.

    On a TPU the grouped kernel serves programs of more than ``_MXU_ROWS``
    tokens. Up to there every expert's matmul streams no more rows than a
    weight tile takes to load, so the dense einsums are at the weight
    read's pace already and nothing of the sort, the gathers and the grid
    is paid (a decode chunk's slot rows; measured in PERF.md section 6),
    unless the rows meet so many experts that a tenth or more of them hold
    no row and need not be read (``_DENSE_FROM_TOUCHED_SHARE``: a 32-row
    chunk at 64 experts top-4).
    """
    routed, on_chip = tokens * num_selected, not _interpret()
    touched = expected_experts_touched(tokens, num_experts, num_selected)
    weight_read = on_chip and tokens <= _MXU_ROWS and (
        num_experts < _SPARSE_CHUNK_MIN_EXPERTS
        or touched >= _DENSE_FROM_TOUCHED_SHARE * num_experts
    )
    if not quantized or _mesh_in_sight() or weight_read:
        dispatch, computed, read = "dense", tokens * num_experts, float(num_experts)
    elif on_chip:
        chunk = _row_chunk(routed, num_experts)
        dispatch, computed, read = (
            "grouped:moe_grouped_matmul", _padded_rows(routed, num_experts, chunk), touched,
        )
    else:
        dispatch, computed, read = "grouped:ragged_dot", routed, touched
    plan = {
        "dispatch": dispatch,
        "expert_rows_routed": routed,
        "expert_rows_computed": computed,
        "computed_over_routed": round(computed / routed, 3),
    }
    if model_dim and hidden_dim:
        per_expert = 3 * model_dim * hidden_dim * (1 if quantized else 2)
        plan["experts_touched"] = round(touched, 2)
        plan["expert_bytes_read"] = int(read * per_expert)
        if dispatch == "grouped:moe_grouped_matmul":
            # the kernel's grid, by the rule it runs (:func:`matmul_tiles`)
            plan["row_tile"] = chunk
            plan["gate_up"] = matmul_tiles(computed, model_dim, hidden_dim, 2, chunk)
            plan["down"] = matmul_tiles(computed, hidden_dim, model_dim, 1, chunk)
    return plan


def _padded_rows(rows: int, num_experts: int, chunk: int) -> int:
    """Rows of the layout in which every expert's rows start on a multiple
    of ``chunk``: the most that ``sum(ceil(size / chunk) * chunk)`` can be."""
    return (rows + num_experts * (chunk - 1)) // chunk * chunk


def group_rows(indices: jnp.ndarray, num_experts: int, chunk: int = 1, valid: Optional[jnp.ndarray] = None):
    """Order the ``[T, k]`` routed pairs by expert (stable: by token within
    an expert), each expert's rows starting on a multiple of ``chunk``.
    The pairs of a token that ``valid`` [T] leaves out (a right-padded
    prompt's padding) take no row: their ``slot`` is row 0, for the caller
    to leave unread.

    Returns ``(source [R], slot [T * k], group_sizes [E], tile_expert
    [R // chunk], tile_rows [R // chunk])``: ``source[r]`` is the token whose
    row sits at ``r`` (token 0 in padding, never read back), ``slot[j]`` the
    row of pair ``j``, ``tile_expert`` the expert of each row tile and
    ``tile_rows`` how many of its rows hold a routed pair (the tiles that
    hold any come first). ``R`` is static.
    """
    num_selected = indices.shape[-1]
    flat = indices.reshape(-1).astype(jnp.int32)
    pairs = flat.shape[0]
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, num_selected), flat, num_experts)   # sorted behind every expert
        group_sizes = jnp.bincount(flat, length=num_experts + 1)[:num_experts].astype(jnp.int32)
    else:
        group_sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    padded = (group_sizes + chunk - 1) // chunk * chunk
    ends, padded_ends = jnp.cumsum(group_sizes), jnp.cumsum(padded)
    order = jnp.argsort(flat, stable=True)
    expert = flat[order]
    rows = _padded_rows(pairs, num_experts, chunk)
    if valid is not None:
        taken, expert = expert < num_experts, jnp.minimum(expert, num_experts - 1)
    row = (padded_ends - padded)[expert] + jnp.arange(pairs, dtype=jnp.int32) - (ends - group_sizes)[expert]
    write = row
    if valid is not None:
        # a pair left out writes past the last row (dropped) and reads row 0
        write, row = jnp.where(taken, row, rows), jnp.where(taken, row, 0)
    source = jnp.zeros((rows,), jnp.int32).at[write].set((order // num_selected).astype(jnp.int32))
    slot = jnp.zeros((pairs,), jnp.int32).at[order].set(row)
    tile_start = jnp.arange(rows // chunk, dtype=jnp.int32) * chunk
    # the expert whose padded rows hold the tile's first row (the last
    # expert for tiles past every row: they are never computed)
    tile_expert = jnp.minimum(
        jnp.sum(tile_start[:, None] >= padded_ends[None, :], axis=1), num_experts - 1
    ).astype(jnp.int32)
    tile_rows = jnp.clip((padded_ends - padded + group_sizes)[tile_expert] - tile_start, 0, chunk)
    tile_rows = jnp.where(tile_start < padded_ends[-1], tile_rows, 0).astype(jnp.int32)
    return source, slot, group_sizes, tile_expert, tile_rows


_WEIGHT_TILE = 2048  # the most of a weight tile's depth and of its width
_ACC_BYTES = 12 * 2**20  # the float32 sums a row block keeps in VMEM
_VMEM_LIMIT = 96 * 2**20  # of the chip's 128 MiB; the default scope is 16


def _weight_tile(size: int) -> int:
    """A weight tile's extent along an axis of ``size``: the whole axis
    where ``_WEIGHT_TILE`` holds it (1,536: one tile, not three of 512),
    else the largest power-of-two multiple of 128 up to ``_WEIGHT_TILE``
    that divides it, or the axis itself if none does."""
    tile = _WEIGHT_TILE
    while size > tile >= 128:
        if size % tile == 0:
            return tile
        tile //= 2
    return size


def _tile_plan(rows: int, depth: int, width: int, chunk: int, tk: int, tn: int, block_tiles: int) -> dict:
    """The grid of one product from its tiles: the row tiles go in
    ``row_blocks`` blocks of ``block_tiles`` (the last may hold fewer), and
    every block passes its column tiles, k tiles and row tiles."""
    blocks = -(-(rows // chunk) // block_tiles)
    return {
        "tk": tk, "tn": tn, "row_block_tiles": block_tiles, "row_blocks": blocks,
        "grid_steps": blocks * (width // tn) * (depth // tk) * block_tiles,
    }


def matmul_tiles(rows: int, depth: int, width: int, n_rhs: int, chunk: int) -> dict:
    """The tiles of ``moe_grouped_matmul`` for ``rows`` rows in row tiles
    of ``chunk`` times ``n_rhs`` matrices of ``[depth, width]`` an expert,
    from shapes alone: the weight tile ``tk`` x ``tn``, the row tiles of a
    row block, the row blocks and the grid steps (:func:`_tile_plan`).

    The weight tile is as large as ``_WEIGHT_TILE`` lets it be, whatever
    the rows. The kernel keeps the float32 sums of one row block x one
    column tile in VMEM while the k tiles and, innermost, the block's row
    tiles pass, so the rows go in as many blocks of equal size as keep
    those sums within ``_ACC_BYTES``. Rows lie by expert, so a block holds
    a run of experts, and one whose rows straddle two blocks is read twice.
    Measured (PERF.md section 6, PR 37): that costs 64 small experts
    nothing and 8 large ones a few percent, where a column tile narrowed to
    keep every row's sums in VMEM cost 10 % at 2k-3k rows and half the time
    at 24k (128 columns left, 11,460 grid steps a layer of mostly fixed
    cost)."""
    tiles = rows // chunk
    tk, tn = _weight_tile(depth), _weight_tile(width)
    fit = max(_ACC_BYTES // (n_rhs * chunk * tn * 4), 1)  # the row tiles whose sums fit
    blocks = -(-tiles // fit)
    return _tile_plan(rows, depth, width, chunk, tk, tn, -(-tiles // blocks))


def _gmm_kernel(
    tile_expert, tile_rows, tiles_used, lhs_ref, *refs, chunk, block_tiles, k_tiles, n_rhs, scaled, gated,
):
    del tile_expert, tiles_used
    rhs_refs, refs = refs[:n_rhs], refs[n_rhs:]
    scale_refs, refs = (refs[:n_rhs], refs[n_rhs:]) if scaled else ((None,) * n_rhs, refs)
    out_ref, acc_refs = refs[0], refs[1:]
    k, tile = pl.program_id(2), pl.program_id(3)  # the row tile within its block
    rows = pl.ds(pl.multiple_of(tile * chunk, chunk), chunk)

    # a tile past the last one used holds no routed row
    @pl.when(tile_rows[pl.program_id(0) * block_tiles + tile] > 0)
    def _tile():
        x = lhs_ref[...]
        for acc, w in zip(acc_refs, rhs_refs):
            # int8 tiles were read from HBM as int8; the MXU sees bf16
            part = jnp.dot(x, w[...].astype(x.dtype), preferred_element_type=jnp.float32)

            @pl.when(k == 0)
            def _first():
                acc[rows, :] = part

            @pl.when(k > 0)
            def _rest():
                acc[rows, :] += part

        @pl.when(k == k_tiles - 1)
        def _store():
            # the float32 scale before the single cast down, as qmm did
            ys = [
                (acc[rows, :] if s is None else acc[rows, :] * s[...]).astype(out_ref.dtype)
                for acc, s in zip(acc_refs, scale_refs)
            ]
            if gated:  # the chip's vector unit has no bfloat16: float32 and one more cast
                gate, up = (y.astype(jnp.float32) for y in ys)
                ys = [(gate * jax.nn.sigmoid(gate) * up).astype(out_ref.dtype)]
            out_ref[rows, :] = ys[0]


# jitted: a model's layers trace and lower one kernel between them, and not
# one each (an engine's warm set-up pays tracing and lowering every process)
@functools.partial(jax.jit, static_argnames=("chunk", "gated", "interpret", "tiles"))
def _grouped_matmul_pallas(lhs, rhs, scales, tile_expert, tile_rows, *, chunk, gated, interpret, tiles=None):
    from jax.experimental.pallas import tpu as pltpu

    rows, depth = lhs.shape
    num_experts, _, width = rhs[0].shape
    n_rhs, scaled = len(rhs), scales is not None
    # ``tiles``: (tk, tn, row tiles a block) in place of the rule's, for
    # the benchmark (benchmarks/moe_dispatch.py --tiles) and the tests
    plan = (
        matmul_tiles(rows, depth, width, n_rhs, chunk) if tiles is None
        else _tile_plan(rows, depth, width, chunk, *tiles)
    )
    tk, tn, block_tiles, row_blocks = (plan[key] for key in ("tk", "tn", "row_block_tiles", "row_blocks"))
    k_tiles, n_tiles = depth // tk, width // tn
    tiles_used = jnp.sum(tile_rows > 0).astype(jnp.int32).reshape(1)
    # the last block's tiles past the last row: none holds a routed row
    tile_rows = jnp.pad(tile_rows, (0, row_blocks * block_tiles - rows // chunk))

    # a tile past the last one used repeats the block indices of the last
    # one used, and a whole block of them the last block's last indices:
    # nothing is fetched for either
    def used(block, n, k, tile, tiles_used):
        last = tiles_used[0] - 1
        past = block * block_tiles > last
        return (
            jnp.minimum(block * block_tiles + tile, last),
            jnp.where(past, k_tiles - 1, k), jnp.where(past, n_tiles - 1, n),
        )

    def lhs_map(block, n, k, tile, tile_expert, tile_rows, tiles_used):
        tile, k, _ = used(block, n, k, tile, tiles_used)
        return tile, k

    def rhs_map(block, n, k, tile, tile_expert, tile_rows, tiles_used):
        tile, k, n = used(block, n, k, tile, tiles_used)
        return tile_expert[tile], k, n

    def scale_map(block, n, k, tile, tile_expert, tile_rows, tiles_used):
        tile, _, n = used(block, n, k, tile, tiles_used)
        return tile_expert[tile], 0, n

    in_specs = [pl.BlockSpec((chunk, tk), lhs_map)]
    in_specs += [pl.BlockSpec((None, tk, tn), rhs_map)] * n_rhs
    operands = [lhs, *rhs]
    if scaled:
        in_specs += [pl.BlockSpec((None, 1, tn), scale_map)] * n_rhs
        operands += [s.reshape(num_experts, 1, width) for s in scales]
    kernel = functools.partial(
        _gmm_kernel, chunk=chunk, block_tiles=block_tiles, k_tiles=k_tiles, n_rhs=n_rhs, scaled=scaled,
        gated=gated,
    )
    block_rows = block_tiles * chunk
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # a column tile of a row block stays in VMEM while the k tiles
            # and, innermost, the block's row tiles pass: rows lie by
            # expert, so an expert's weight tile is fetched once however
            # many row tiles it has (once more where it straddles blocks)
            grid=(row_blocks, n_tiles, k_tiles, block_tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_rows, tn), lambda block, n, k, tile, *_: (block, n)),
            scratch_shapes=[pltpu.VMEM((block_rows, tn), jnp.float32)] * n_rhs,
        ),
        out_shape=jax.ShapeDtypeStruct((rows, width), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile_expert, tile_rows, tiles_used, *operands)


def grouped_matmul(
    lhs: jnp.ndarray,
    rhs: Sequence[jnp.ndarray],
    group_sizes: jnp.ndarray,
    *,
    scales: Optional[Sequence[jnp.ndarray]] = None,
    tile_expert: Optional[jnp.ndarray] = None,
    tile_rows: Optional[jnp.ndarray] = None,
    chunk: int = 1,
    impl: str = "auto",
) -> jnp.ndarray:
    """``lhs`` rows times their own expert's matrix: ``[R, K] x [E, K, N]
    -> [R, N]`` in ``lhs.dtype``, the rows of expert ``e`` contiguous and
    ``group_sizes[e]`` many, float32 accumulation. ``rhs`` holds one matrix
    per expert, or two (gate and up): then the result is ``silu(lhs @
    gate) * (lhs @ up)``. Int8 matrices come with ``scales`` (``[E, N]``
    float32 each), applied to the float32 sums before the single cast down.

    ``impl``: ``"pallas"`` (the kernel ``moe_grouped_matmul``; it wants the
    layout of :func:`group_rows` at ``chunk`` >= 16 with its ``tile_expert``
    and ``tile_rows``; interpreter mode off-TPU), ``"ragged_dot"``
    (``jax.lax.ragged_dot`` on rows packed without padding, ``chunk`` 1:
    differentiable, and the CPU's path), or ``"auto"``.
    """
    gated = len(rhs) == 2
    if impl == "auto":
        impl = "ragged_dot" if _interpret() else "pallas"
    if impl == "pallas":
        return _grouped_matmul_pallas(
            lhs, tuple(rhs), None if scales is None else tuple(scales), tile_expert, tile_rows,
            chunk=chunk, gated=gated, interpret=_interpret(),
        )
    if impl != "ragged_dot":
        raise ValueError(f"unknown grouped matmul impl {impl!r}")
    if chunk != 1:
        raise ValueError("ragged_dot takes rows packed without padding (chunk 1)")
    row_expert = jnp.repeat(
        jnp.arange(group_sizes.shape[0]), group_sizes, total_repeat_length=lhs.shape[0]
    )
    ys = []
    for i, w in enumerate(rhs):
        y = lax.ragged_dot(lhs, w.astype(lhs.dtype), group_sizes, preferred_element_type=jnp.float32)
        if scales is not None:
            y = y * scales[i][row_expert]
        ys.append(y.astype(lhs.dtype))
    return jax.nn.silu(ys[0]) * ys[1] if gated else ys[0]


def grouped_expert_mlp(
    tokens, weights, indices, w_gate, w_up, w_down, *, scales=None, impl="auto", valid=None,
):
    """The experts' SwiGLU over ``tokens`` [T, d] for the routing ``weights``
    / ``indices`` [T, k]: every routed pair computed by its own expert, none
    dropped. ``w_gate`` / ``w_up`` [E, d, h], ``w_down`` [E, h, d]; int8
    with ``scales = (gate, up, down)``, each [E, n] float32. A token that
    ``valid`` [T] leaves out is sent to no expert and comes back zero."""
    num_experts = w_gate.shape[0]
    t, k = indices.shape
    if impl == "auto":
        impl = "ragged_dot" if _interpret() else "pallas"
    chunk = _row_chunk(t * k, num_experts) if impl == "pallas" else 1
    with jax.named_scope("group_rows"):
        source, slot, group_sizes, tile_expert, tile_rows = group_rows(indices, num_experts, chunk, valid)
    layout = dict(tile_expert=tile_expert, tile_rows=tile_rows, chunk=chunk, impl=impl)
    gate_up, down = (None, None) if scales is None else (scales[:2], scales[2:])
    with jax.named_scope("gather"):
        rows = tokens[source]
    with jax.named_scope("experts"):
        hidden = grouped_matmul(rows, (w_gate, w_up), group_sizes, scales=gate_up, **layout)
        out = grouped_matmul(hidden, (w_down,), group_sizes, scales=down, **layout)
    # a token's k rows, weighted and summed in the order of its choices
    with jax.named_scope("combine"):
        routed = out[slot].reshape(t, k, -1) * weights.astype(out.dtype)[..., None]
        if valid is not None:
            routed = jnp.where(valid[:, None, None], routed, 0)
        return jnp.sum(routed, axis=1)


def _swiglu_experts(x, w_gate, w_up, w_down):
    """x: [E, C, d]; w_*: [E, d, h] / [E, h, d] -> [E, C, d]."""
    gated = jax.nn.silu(jnp.einsum("ecd,edh->ech", x, w_gate))
    up = jnp.einsum("ecd,edh->ech", x, w_up)
    return jnp.einsum("ech,ehd->ecd", gated * up, w_down)


def dense_expert_mlp(tokens, weights, indices, w_gate, w_up, w_down, *, scales=None):
    """The experts' SwiGLU by one-hot dispatch, every expert on every token
    and the unrouted products masked to zero: static shapes, differentiable,
    and GSPMD inserts the collectives when the expert dim is sharded. Same
    arguments and result as :func:`grouped_expert_mlp`."""
    dtype = tokens.dtype
    with jax.named_scope("group_rows"):
        dispatch = jax.nn.one_hot(indices, w_gate.shape[0], dtype=dtype)
        combine = jnp.einsum("tke,tk->te", dispatch, weights.astype(dtype))  # [T, E] routing weight
        mask = (combine > 0).astype(dtype)
    with jax.named_scope("gather"):
        expert_in = jnp.einsum("te,td->etd", mask, tokens)
    if scales is None:
        with jax.named_scope("experts"):
            expert_out = _swiglu_experts(expert_in, w_gate, w_up, w_down)
    else:
        # int8->compute-dtype converts fuse into the einsums (HBM reads
        # stay int8); accumulate fp32 and apply the fp32 scale BEFORE
        # the single cast down — same recipe as QuantizedDenseGeneral
        def qmm(x, w_q, w_s):
            y = jnp.einsum(
                "etd,edh->eth", x, w_q.astype(dtype),
                preferred_element_type=jnp.float32,
            )
            return (y * w_s[:, None, :]).astype(dtype)

        with jax.named_scope("experts"):
            gated = jax.nn.silu(qmm(expert_in, w_gate, scales[0]))
            up = qmm(expert_in, w_up, scales[1])
            expert_out = qmm(gated * up, w_down, scales[2])
    with jax.named_scope("combine"):
        return jnp.einsum("etd,te->td", expert_out, combine)


def expert_parallel_moe_sharded(
    x: jnp.ndarray,
    router_kernel: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    axis: str = "expert",
    num_selected: int = 2,
    capacity_factor: float = 2.0,
    capacity: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-shard expert-parallel MoE body (call inside shard_map).

    ``x``: local token shard [T_local, d]; ``router_kernel``: replicated
    [d, E_global]; ``w_gate/w_up/w_down``: local expert shards
    [E_local, ...] with E_global = axis_size * E_local. Returns the local
    output shard [T_local, d] and the group-mean aux loss (replicated).
    """
    ep = lax.axis_size(axis)
    t_local, d = x.shape
    e_global = router_kernel.shape[-1]
    assert w_gate.shape[0] * ep == e_global, (
        f"expert weights shard {w_gate.shape[0]} x axis {ep} != {e_global} experts"
    )
    cap = (
        expert_capacity(t_local, e_global, num_selected, capacity_factor)
        if capacity is None
        else capacity
    )
    if cap < 1:
        raise ValueError(f"capacity must be >= 1, got {cap}")

    gate_logits = (x @ router_kernel.astype(x.dtype)).astype(jnp.float32)
    dispatch, combine, aux = make_dispatch(gate_logits, num_selected, cap)

    # bucket local tokens per global expert: [E_global, C, d]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    # expert e lives on device e // E_local: one all_to_all ships every
    # bucket to its owner, concatenating source devices along the slot dim
    expert_in = lax.all_to_all(expert_in, axis, split_axis=0, concat_axis=1, tiled=True)
    out = _swiglu_experts(expert_in, w_gate, w_up, w_down)  # [E_local, ep*C, d]
    # reverse route: slot-dim chunks back to their source devices
    out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0, tiled=True)
    y = jnp.einsum("ecd,tec->td", out, combine.astype(x.dtype))
    return y.astype(x.dtype), lax.pmean(aux, axis)


def expert_parallel_moe(
    x: jnp.ndarray,
    router_kernel: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    mesh,
    *,
    axis: str = "expert",
    num_selected: int = 2,
    capacity_factor: float = 2.0,
    capacity: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE over globally-shaped tensors.

    ``x``: [T, d] with T sharded over ``mesh[axis]``; expert weights
    [E, ...] sharded the same way on their expert dim. Returns (out [T, d]
    sharded like x, aux_loss scalar).
    """
    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    body = functools.partial(
        expert_parallel_moe_sharded,
        axis=axis,
        num_selected=num_selected,
        capacity_factor=capacity_factor,
        capacity=capacity,
    )
    tok = P(axis, None)
    ew = P(axis, None, None)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(tok, P(None, None), ew, ew, ew),
        out_specs=(tok, P()),
        check_vma=False,
    )(x, router_kernel, w_gate, w_up, w_down)


class MoEMlp(nn.Module):
    """Mixture-of-experts SwiGLU MLP block; every routed token is processed
    (no capacity drops).

    Weight shapes carry a leading expert dim. Float experts take the dense
    dispatch: shard the expert dim with a ``PartitionRule(r"moe/.*",
    ("expert", ...))`` to get expert parallelism on the mesh (GSPMD inserts
    the dispatch collectives), and differentiate it as it is. Int8 experts
    (``quantized``, the serving path) take the grouped dispatch where
    :func:`dispatch_plan` says so from the static row count; on a TPU that
    is a Pallas kernel, which JAX refuses to partition: under a mesh the
    trace can see they fall back to the dense dispatch. For explicit
    capacity-bucketed all_to_all dispatch use the functional
    :func:`expert_parallel_moe` / :func:`expert_parallel_moe_sharded` ops:
    their expert-sharded weight shapes cannot be created by module init
    outside ``shard_map``, so they are not a module knob.
    """

    num_experts: int
    num_selected: int
    hidden_dim: int
    model_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    quantized: bool = False  # int8 weight-only experts (serving path)
    # the published router: "softmax" (:func:`top_k_routing`, Mixtral) or
    # "sigmoid" (:func:`sigmoid_top_k_routing`: a float32 router with the
    # selection-only ``e_score_correction_bias`` and ``routed_scaling``;
    # no auxiliary loss)
    router: str = "softmax"
    routed_scaling: float = 1.0

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: [batch, seq, model_dim] -> (out, aux_loss). ``valid`` [batch,
        seq] marks the real tokens of a right-padded prompt: the grouped
        dispatch sends the others to no expert (their output is zero), the
        dense dispatch computes them as it computes every token."""
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)

        # router params stay float32 (compute casts down): routing updates
        # are tiny and round to zero in bf16 master weights
        router_kernel = self.param(
            "router_kernel", nn.initializers.lecun_normal(),
            (d, self.num_experts), jnp.float32,
        )
        if self.quantized:
            # int8 weights + per-(expert, out-channel) fp32 scales, filled
            # by quantize_params (LLAMA_QUANT_PATTERNS matches `moe$`)
            def qparam(name, k, n):
                q = self.param(f"{name}_q", nn.initializers.zeros,
                               (self.num_experts, k, n), jnp.int8)
                s = self.param(f"{name}_scale", nn.initializers.ones,
                               (self.num_experts, n), jnp.float32)
                return q, s

            pairs = [
                qparam("w_gate", d, self.hidden_dim), qparam("w_up", d, self.hidden_dim),
                qparam("w_down", self.hidden_dim, d),
            ]
            experts, scales = zip(*pairs)
        else:
            h = self.hidden_dim
            shapes = {"w_gate": (d, h), "w_up": (d, h), "w_down": (h, d)}
            experts, scales = [
                self.param(name, nn.initializers.lecun_normal(), (self.num_experts, *shape), self.dtype)
                for name, shape in shapes.items()
            ], None

        if self.router == "sigmoid":
            # [experts, 1]: a column, so that seeded fills give it the
            # deviation of a kernel's column and not a vector's
            bias = self.param(
                "e_score_correction_bias", nn.initializers.zeros, (self.num_experts, 1), jnp.float32,
            )
            with jax.named_scope("router"):
                gate_logits = jnp.matmul(
                    tokens.astype(jnp.float32), router_kernel, precision=lax.Precision.HIGHEST,
                )
                weights, indices = sigmoid_top_k_routing(
                    gate_logits, bias, self.num_selected, scaling=self.routed_scaling,
                )
            aux_loss = jnp.zeros((), jnp.float32)
        elif self.router == "softmax":
            with jax.named_scope("router"):
                gate_logits = tokens @ router_kernel.astype(tokens.dtype)
                weights, indices, aux_loss, (routing_frac, gate_frac) = top_k_routing(
                    gate_logits, self.num_selected, return_stats=True
                )

            # the load-balance loss is a product of token-MEAN stats, so it is
            # not additive across sequence shards — sow the raw fractions into
            # a separate collection so sharded consumers (sequence_parallel)
            # can pmean them globally before re-forming E*sum(rf*gf). A no-op
            # (flax drops the sow) unless "moe_stats" is made mutable.
            self.sow("moe_stats", "fractions", jnp.stack([routing_frac, gate_frac]))
        else:
            raise ValueError(f"unknown router {self.router!r}")

        plan = dispatch_plan(b * s, self.num_experts, self.num_selected, quantized=self.quantized)
        if plan["dispatch"] == "dense":
            out = dense_expert_mlp(tokens.astype(self.dtype), weights, indices, *experts, scales=scales)
        else:
            out = grouped_expert_mlp(
                tokens.astype(self.dtype), weights, indices, *experts, scales=scales,
                valid=None if valid is None else valid.reshape(b * s),
            )
        return out.reshape(b, s, d).astype(self.dtype), aux_loss


def migrate_moe_router_params(params):
    """Rename old-layout MoE router params to the current layout.

    ``MoEMlp``'s router used to be an ``nn.Dense`` submodule, stored as
    ``{'router': {'kernel': ...}}``; it is now a direct fp32
    ``router_kernel`` param (routing updates are tiny and round to zero in
    bf16, so the master copy must stay fp32). Checkpoints saved under the
    old layout fail to restore with a param-tree mismatch — pass their
    params through this helper first. Works on whole-model trees: every
    nested ``{'router': {'kernel': ...}}`` is rewritten in a copied tree;
    an old ``router/bias`` is dropped (the current router is bias-free).
    Accepts any Mapping (plain dicts, ``flax.core.FrozenDict``, …) and
    returns plain nested dicts.
    """
    from collections.abc import Mapping

    if not isinstance(params, Mapping):
        return params
    out = {}
    for k, v in params.items():
        if (
            k == "router"
            and isinstance(v, Mapping)
            and set(v) <= {"kernel", "bias"}
            and "kernel" in v
        ):
            out["router_kernel"] = jnp.asarray(v["kernel"], jnp.float32)
        else:
            out[k] = migrate_moe_router_params(v)
    return out
