"""Sequence-parallel Llama training: the full step under shard_map.

Long-context training (SURVEY.md §5.7): activations — not parameters —
are the memory bottleneck, so the sequence dimension shards over the
mesh's ``sequence`` axis and attention runs sequence-sharded: a ring
(``attn_impl="ring"`` or the Pallas-local ``"ring_flash"``,
ops/ring_attention.py — ppermute KV rotation) or ``"ulysses"``
(ops/ulysses.py — an all_to_all head<->sequence reshuffle each way).
Everything else in the decoder is position-local (embedding, RMSNorm,
MLP, lm_head), so attention's collectives are the only cross-shard
exchange in the whole forward.

Mechanics:

- the WHOLE loss runs inside one ``shard_map`` over ``(data, sequence)``;
  parameters enter replicated (in_spec ``P()``) and shard_map's
  transpose psums their cotangents automatically, so ``jax.grad``
  through the shard_map yields exact global gradients with no manual
  collectives;
- RoPE positions are global: each shard offsets by
  ``axis_index(sequence) * S_local``;
- next-token targets are built OUTSIDE the shard_map by shifting the
  full sequence (last global position gets ``ignore_id``), so the
  shard-boundary token never needs a neighbor exchange;
- the loss is a masked-CE ratio of two ``psum``s (token sums over both
  mesh axes), replicated on every device;
- MoE composes: each ``MoEMlp`` sows its token-mean routing/gate
  fractions (``moe_stats``); the step pmeans them over the mesh axes and
  re-forms the load-balance loss ``E * sum(rf * gf)`` from the GLOBAL
  fractions — exactly serial ``lm_step``'s aux, since the fractions are
  token means over equal-size shards (a mean of per-shard aux products
  would NOT match).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from unionml_tpu.models.llama import Llama, LlamaConfig
from unionml_tpu.models.train import TrainState


def sequence_parallel_config(
    cfg: LlamaConfig, *, attn: str = "ring", seq_axis: str = "sequence"
) -> LlamaConfig:
    """The same model with sequence-sharded attention bound to the axis.

    ``attn``: ``"ring"`` / ``"ring_flash"`` (ppermute KV rotation) or
    ``"ulysses"`` (all-to-all head<->sequence reshuffle; requires q AND
    kv head counts divisible by the axis size).
    """
    if attn not in ("ring", "ring_flash", "ulysses"):
        raise ValueError(
            f"sequence-parallel attention must be ring/ring_flash/ulysses, got {attn!r}"
        )
    return LlamaConfig(
        **{**cfg.__dict__, "attn_impl": attn, "sequence_axis": seq_axis}
    )


def sequence_parallel_lm_step(
    cfg: LlamaConfig,
    *,
    mesh,
    attn: str = "ring",
    data_axis: Optional[str] = "data",
    seq_axis: str = "sequence",
    ignore_id: int = -100,
    aux_loss_weight: float = 0.01,
) -> Callable:
    """``step(state, tokens[B, S]) -> (state, metrics)`` with the sequence
    dimension sharded over ``mesh[seq_axis]``.

    ``S`` must divide by the sequence axis size; ``B`` by the data axis.
    jit the returned step (e.g. via ``compile_step`` with a
    ``ShardingConfig(data=m, sequence=n)`` — parameters replicate, the
    batch spec shards [B, S] over (data, sequence)).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sp_cfg = sequence_parallel_config(cfg, attn=attn, seq_axis=seq_axis)
    n_seq = mesh.shape[seq_axis]
    kv_heads = cfg.num_kv_heads or cfg.num_heads
    if attn == "ulysses" and (cfg.num_heads % n_seq or kv_heads % n_seq):
        # fail at config time, not deep inside jit tracing
        raise ValueError(
            f"ulysses needs q heads ({cfg.num_heads}) and kv heads "
            f"({kv_heads}) divisible by the sequence axis size "
            f"({n_seq}); use ring/ring_flash instead"
        )
    module = Llama(sp_cfg)
    axes = (data_axis, seq_axis) if data_axis else (seq_axis,)

    def local_loss_sums(params, tok_shard, tgt_shard):
        """-> (ce_sum, token_count, moe fraction leaves) for this shard."""
        s_loc = tok_shard.shape[1]
        positions = lax.axis_index(seq_axis) * s_loc + jnp.arange(s_loc)[None, :]
        logits, mods = module.apply(
            {"params": params}, tok_shard, positions=positions,
            mutable=["moe_stats"],
        )
        logits = logits.astype(jnp.float32)
        mask = (tgt_shard != ignore_id).astype(jnp.float32)
        safe = jnp.where(tgt_shard == ignore_id, 0, tgt_shard)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
        fracs = jax.tree_util.tree_leaves(mods.get("moe_stats", {}))
        return (ce * mask).sum(), mask.sum(), fracs

    def sharded_loss(params, tokens, targets):
        ce_sum, count, fracs = local_loss_sums(params, tokens, targets)
        for ax in axes:
            ce_sum = lax.psum(ce_sum, ax)
            count = lax.psum(count, ax)
            # token-MEAN fractions: shards hold equal token counts, so the
            # pmean over shards is exactly the global token mean
            fracs = [lax.pmean(f, ax) for f in fracs]
        ce = ce_sum / jnp.maximum(count, 1.0)
        if fracs:
            # re-form the load-balance loss from GLOBAL fractions (same
            # formula as ops/moe.py top_k_routing) — exactly the serial
            # lm_step aux, unlike a mean of per-shard products
            per_layer = [
                cfg.num_experts * jnp.sum(f[0] * f[1]) for f in fracs
            ]
            aux = sum(per_layer) / len(per_layer)
        else:
            aux = jnp.float32(0.0)
        return ce + aux_loss_weight * aux, (ce, aux)

    batch_spec = P(data_axis, seq_axis) if data_axis else P(None, seq_axis)
    loss_sm = shard_map(
        sharded_loss,
        mesh=mesh,
        in_specs=(P(), batch_spec, batch_spec),
        out_specs=(P(), (P(), P())),
        check_vma=False,
    )

    def step(state: TrainState, tokens: jnp.ndarray):
        # global shift: target of the last position is ignore_id, so shard
        # boundaries never need the neighbor's first token
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.full((tokens.shape[0], 1), ignore_id, tokens.dtype)],
            axis=1,
        )

        (_, (loss, aux)), grads = jax.value_and_grad(
            lambda p: loss_sm(p, tokens, targets), has_aux=True
        )(state.params)
        state = state.apply_gradients(grads=grads)
        return state, {
            "loss": loss,
            "perplexity": jnp.exp(loss),
            "aux_loss": aux,
        }

    return step
