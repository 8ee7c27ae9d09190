"""Mixture-of-experts: top-k routing + expert-parallel dispatch.

Expert parallelism (SURVEY.md §2.4 TPU additions, §7.8 "EP: expert-sharded
MoE with all_to_all dispatch"). Two dispatch paths, one routing math:

- **Dense einsum dispatch** (:class:`MoEMlp`): every tensor is
  static-shaped; with the expert dim of the weights sharded over the
  mesh's ``expert`` axis, GSPMD inserts the all_to_all-style collectives.
  No capacity limit — every routed token is processed. The default for
  pjit training via partition rules.
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

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from flax import linen as nn
from jax import lax


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


def _swiglu_experts(x, w_gate, w_up, w_down):
    """x: [E, C, d]; w_*: [E, d, h] / [E, h, d] -> [E, C, d]."""
    gated = jax.nn.silu(jnp.einsum("ecd,edh->ech", x, w_gate))
    up = jnp.einsum("ecd,edh->ech", x, w_up)
    return jnp.einsum("ech,ehd->ecd", gated * up, w_down)


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
    """Expert-parallel SwiGLU MLP block.

    Weight shapes carry a leading expert dim — shard it with a
    ``PartitionRule(r"moe/.*", ("expert", ...))`` to get expert parallelism
    on the mesh (GSPMD inserts the dispatch collectives; every routed
    token is processed — no capacity drops). For explicit capacity-bucketed
    all_to_all dispatch use the functional
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

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: [batch, seq, model_dim] -> (out, aux_loss)."""
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

            gate_q, gate_s = qparam("w_gate", d, self.hidden_dim)
            up_q, up_s = qparam("w_up", d, self.hidden_dim)
            down_q, down_s = qparam("w_down", self.hidden_dim, d)
        else:
            w_gate = self.param(
                "w_gate", nn.initializers.lecun_normal(),
                (self.num_experts, d, self.hidden_dim), self.dtype,
            )
            w_up = self.param(
                "w_up", nn.initializers.lecun_normal(),
                (self.num_experts, d, self.hidden_dim), self.dtype,
            )
            w_down = self.param(
                "w_down", nn.initializers.lecun_normal(),
                (self.num_experts, self.hidden_dim, d), self.dtype,
            )

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

        # dense one-hot dispatch: static shapes, collectives inserted by
        # GSPMD when the expert dim is sharded
        dispatch = jax.nn.one_hot(indices, self.num_experts, dtype=self.dtype)
        # [T, k, E] x [T, d] -> per-expert token batches [E, T, d] weighted later
        combine = jnp.einsum("tke,tk->te", dispatch, weights.astype(self.dtype))

        mask = (combine > 0).astype(self.dtype)
        expert_in = jnp.einsum("te,td->etd", mask, tokens.astype(self.dtype))
        if self.quantized:
            # int8->compute-dtype converts fuse into the einsums (HBM reads
            # stay int8); accumulate fp32 and apply the fp32 scale BEFORE
            # the single cast down — same recipe as QuantizedDenseGeneral
            def qmm(x, w_q, w_s):
                y = jnp.einsum(
                    "etd,edh->eth", x, w_q.astype(self.dtype),
                    preferred_element_type=jnp.float32,
                )
                return (y * w_s[:, None, :]).astype(self.dtype)

            gated = jax.nn.silu(qmm(expert_in, gate_q, gate_s))
            up = qmm(expert_in, up_q, up_s)
            expert_out = qmm(gated * up, down_q, down_s)
        else:
            expert_out = _swiglu_experts(expert_in, w_gate, w_up, w_down)
        out = jnp.einsum("etd,te->td", expert_out, combine)
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
