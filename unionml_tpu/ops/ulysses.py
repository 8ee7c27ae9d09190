"""Ulysses sequence parallelism: all-to-all head<->sequence reshuffle.

The alternative long-context strategy to ring attention (SURVEY.md §5.7):
instead of rotating K/V shards, one ``all_to_all`` re-shards
sequence-sharded activations into head-sharded ones, every device runs
*full-sequence* attention over its subset of heads (any local impl —
XLA, flash), and a second all_to_all restores sequence sharding. Two
collectives total per attention call, both riding ICI; requires
``num_heads % axis_size == 0``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp

from jax import lax


def _seq_to_heads(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """[B, S/n, H, D] -> [B, S, H/n, D] via all_to_all."""
    # split the head axis across devices, concat the sequence axis
    return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)


def _heads_to_seq(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """[B, S, H/n, D] -> [B, S/n, H, D] via all_to_all."""
    return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis: str = "sequence",
    causal: bool = False,
    impl: str = "xla",
    scale: Optional[float] = None,
    block_size: int = 512,
) -> jnp.ndarray:
    """Per-shard Ulysses body (call inside shard_map).

    Local shards are [B, S/n, H, D]; K/V may have fewer (GQA) heads but
    they must still divide the axis size.
    """
    from unionml_tpu.ops.attention import attention

    n = lax.axis_size(axis)
    for name, t in (("q", q), ("k", k), ("v", v)):
        if t.shape[2] % n:
            raise ValueError(
                f"ulysses requires {name} heads ({t.shape[2]}) divisible by "
                f"axis size ({n})"
            )
    q_full = _seq_to_heads(q, axis)
    k_full = _seq_to_heads(k, axis)
    v_full = _seq_to_heads(v, axis)
    out = attention(
        q_full, k_full, v_full, causal=causal, impl=impl, scale=scale,
        block_size=block_size,
    )
    return _heads_to_seq(out, axis)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    *,
    axis: str = "sequence",
    causal: bool = False,
    impl: str = "xla",
    scale: Optional[float] = None,
    block_size: int = 512,
) -> jnp.ndarray:
    """Ulysses attention over globally-shaped [B,S,H,D] tensors."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis, None, None)
    body = functools.partial(
        ulysses_attention_sharded, axis=axis, causal=causal, impl=impl,
        scale=scale, block_size=block_size,
    )
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
