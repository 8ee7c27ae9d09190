"""Seeded weights, made on the device in one jitted program per tree.

The benchmark makes the weights, not the program: the same arrays go to the
system under test and to the plain reference. One program per tree because
one tiny jit per leaf shape cost 48 s of set-up (PR 22), and the key is an
argument, not a closure constant, so the executable stays small enough for
the persistent compile cache. Keys use the ``rbg`` generator (XLA's
RngBitGenerator): threefry over 12 GB of int8 weights is slow on a TPU and
makes 32-bit temporaries four times the leaf.
"""

from __future__ import annotations

import math
from typing import Any, Optional


def seed_key(seed: int, stream: int = 0, impl: str = "rbg"):
    """A typed PRNG key from any non-negative ``--seed`` (also past 2**31).
    ``rbg`` is fast but its values may depend on how a program is
    partitioned; ``threefry2x32`` gives the same values under any sharding,
    which a tree that is made twice (the state, then the reference's copy)
    needs."""
    import jax

    key = jax.random.key(int(seed) & 0x7FFFFFFF, impl=impl)
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.random.fold_in(key, stream)


def _names(path) -> list:
    return [getattr(p, "key", getattr(p, "name", str(p))) for p in path]


def _fill_leaf(key, names: list, shape, dtype, siblings: dict):
    import jax
    import jax.numpy as jnp
    from jax import lax

    name = names[-1]
    parent = names[-2] if len(names) > 1 else ""
    if dtype == jnp.int8:
        bits = jax.random.bits(key, shape, jnp.uint8)
        return lax.bitcast_convert_type(bits, jnp.int8)
    quant_of = None
    if name in ("scale", "scale_g") and "kernel_q" in siblings:
        quant_of = siblings["kernel_q"]
        fan_in = quant_of.shape[0]
    elif name.endswith("_scale") and f"{name[:-6]}_q" in siblings:
        quant_of = siblings[f"{name[:-6]}_q"]
        fan_in = quant_of.shape[-2]
    if quant_of is not None:
        # int8 uniform on [-128, 127] has std ~74: the dequantised weight
        # then has the lecun std 1/sqrt(fan_in)
        return jnp.full(shape, 1.0 / (74.0 * math.sqrt(fan_in)), dtype)
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name in ("embedding", "pos_embed", "cls"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if len(shape) == 3 and parent in ("q", "k", "v"):
        fan_in = shape[0]                      # [in, heads, head_dim]
    elif len(shape) == 3 and name.startswith("w_"):
        fan_in = shape[1]                      # experts: [E, in, out]
    elif len(shape) >= 2:
        fan_in = math.prod(shape[:-1])         # dense, conv (HWIO), attention o
    else:
        fan_in = 1
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def make_tree(shapes: Any, seed: int, *, wrap=None, out_shardings: Optional[Any] = None,
              stream: int = 0, impl: str = "rbg"):
    """Fill the tree of ``ShapeDtypeStruct`` ``shapes`` from ``seed`` in one
    jitted call. ``wrap(tree)`` (optional) runs inside the same program, e.g.
    to build a train state around the parameters; ``out_shardings`` places
    the result sharded from the start (nothing is staged on one chip)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    by_parent: dict = {}
    for path, leaf in flat:
        names = _names(path)
        by_parent.setdefault(tuple(names[:-1]), {})[names[-1]] = leaf

    def build(key):
        leaves = []
        for i, (path, leaf) in enumerate(flat):
            names = _names(path)
            leaves.append(_fill_leaf(
                jax.random.fold_in(key, i), names, leaf.shape, leaf.dtype,
                by_parent[tuple(names[:-1])],
            ))
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        return wrap(tree) if wrap is not None else tree

    fn = jax.jit(build) if out_shardings is None else jax.jit(build, out_shardings=out_shardings)
    return fn(seed_key(seed, stream, impl))
