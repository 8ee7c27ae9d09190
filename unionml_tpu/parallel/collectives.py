"""Named collective wrappers for shard_map kernels.

TPU-native replacement for the reference's absent NCCL/MPI layer
(SURVEY.md §5.8): all hot-path tensor exchange is XLA collectives compiled
over ICI/DCN. Inside ``jax.jit`` GSPMD inserts these automatically from
shardings; these explicit wrappers are for ``shard_map`` kernels (ring
attention KV rotation, Ulysses all-to-all, MoE dispatch — and the
overlapped gradient-accumulation step's :func:`bucketed_psum`, whose
byte-bounded buckets are what lets XLA's async collectives pipeline a
gradient all-reduce behind the next microbatch's backward; see
docs/performance.md "Overlapped training") where the communication
schedule is the algorithm.
"""

from __future__ import annotations

from typing import Any, List, Sequence, Union

from jax import lax


AxisName = Union[str, Sequence[str]]

#: Default all-reduce bucket size for :func:`bucketed_psum`. Big enough
#: that per-collective launch overhead amortizes, small enough that the
#: first bucket's all-reduce can start while later buckets' grads are
#: still being produced/scheduled (the classic DDP bucketing trade-off).
DEFAULT_PSUM_BUCKET_BYTES = 4 << 20


def psum(x: Any, axis: AxisName):
    """Sum-reduce across an axis (gradient reduction on the data axis)."""
    return lax.psum(x, axis)


def bucketed_psum(
    tree: Any,
    axis: AxisName,
    *,
    bucket_bytes: int = DEFAULT_PSUM_BUCKET_BYTES,
) -> Any:
    """``lax.psum(tree, axis)`` issued as one collective per byte-bounded
    bucket of leaves instead of one monolithic collective.

    Values are bitwise identical to the un-bucketed psum — bucketing
    only changes how many all-reduce ops XLA sees, never which shards
    reduce together — but the chunking is what makes latency hiding
    work: a single whole-gradient all-reduce can only start once every
    leaf is ready and must finish before ANY consumer runs, while
    per-bucket collectives start as their leaves close and overlap
    each other (and, in the deferred-accumulation step, the next
    microbatch's backward). Leaves above ``bucket_bytes`` get their own
    bucket — a tensor is never split. Only callable inside
    ``shard_map``/``pmap`` where ``axis`` is bound.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be > 0, got {bucket_bytes}")
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buckets: List[List[int]] = []
    sizes: List[int] = []
    for i, leaf in enumerate(leaves):
        nbytes = int(getattr(leaf, "size", 1)) * int(
            getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        )
        if buckets and sizes[-1] + nbytes <= bucket_bytes:
            buckets[-1].append(i)
            sizes[-1] += nbytes
        else:
            buckets.append([i])
            sizes.append(nbytes)
    reduced: List[Any] = [None] * len(leaves)
    for bucket in buckets:
        out = lax.psum([leaves[i] for i in bucket], axis)
        for i, val in zip(bucket, out):
            reduced[i] = val
    return jax.tree_util.tree_unflatten(treedef, reduced)


def pmean(x: Any, axis: AxisName):
    return lax.pmean(x, axis)


def all_gather(x: Any, axis: AxisName, *, gather_axis: int = 0, tiled: bool = True):
    """Gather shards along ``gather_axis`` (fsdp param gather)."""
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x: Any, axis: AxisName, *, scatter_axis: int = 0):
    """Sum-reduce then scatter along ``scatter_axis`` (fsdp grad shard)."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def ppermute_shift(x: Any, axis: str, *, shift: int = 1):
    """Rotate shards around a ring (ring-attention KV rotation over ICI)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def all_to_all(x: Any, axis: str, *, split_axis: int, concat_axis: int, tiled: bool = True):
    """Transpose sharding between two tensor dims (Ulysses head↔sequence
    reshuffle, MoE token dispatch)."""
    return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)
