"""Collective/compute overlap (docs/performance.md "Overlapped
training"): the deferred-consumption accumulation scan and the
shard_map bucketed-psum step must trace the bit-identical loss
trajectory of the serial accumulate — overlap is a SCHEDULING change,
never a numerics change — and bucketed_psum itself must be bitwise
equal to a plain psum under shard_map."""

import numpy as np
import pytest

pytestmark = pytest.mark.quick

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from unionml_tpu.execution import resolve_grad_overlap, run_step_trainer
from unionml_tpu.models.train import (
    GradOverlap,
    accumulated_value_and_grad,
    classification_step,
    create_train_state,
    grad_overlap_scope,
)
from unionml_tpu.parallel import ShardingConfig, bucketed_psum, compile_step


class _Mlp(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(4)(nn.relu(nn.Dense(32)(x)))


def _data(n=256, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 4, size=(n,)).astype(np.int32)
    return x, y


def _loss_fn(module):
    def loss_fn(params, mb):
        feats, labels = mb
        logits = module.apply({"params": params}, feats)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        ).mean()
        return loss, {"acc": jnp.float32(0.0)}

    return loss_fn


def _leaves_equal(a, b):
    return all(
        np.array_equal(np.asarray(p), np.asarray(q))
        for p, q in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


# ------------------------------------------------------- trajectory parity


def _loss_trajectory(module, x, y, cfg, overlap):
    """Per-step losses + final params of a 6-step accumulated run,
    compiled under `overlap` (None = serial)."""
    loss_fn = _loss_fn(module)

    def step(state, batch):
        (loss, _aux), grads = accumulated_value_and_grad(
            loss_fn, state.params, batch, overlap=overlap
        )
        return state.apply_gradients(grads=grads), {"loss": loss}

    state = create_train_state(module, x[:4], learning_rate=1e-2, seed=1)
    mcfg = cfg.microbatched()
    compiled, state = compile_step(step, state, sharding=mcfg)
    bspec = mcfg.batch_sharding()
    losses = []
    for i in range(6):
        xb = x[i * 32:(i + 1) * 32].reshape(4, 8, -1)
        yb = y[i * 32:(i + 1) * 32].reshape(4, 8)
        state, m = compiled(
            state, (jax.device_put(xb, bspec), jax.device_put(yb, bspec))
        )
        losses.append(np.asarray(m["loss"]).item())
    return losses, state


def test_defer_mode_bit_identical_on_2x2_mesh():
    """The GSPMD deferred-consumption scan on the dp2×fsdp2 mesh: the
    loss trajectory (not just the final state) is BITWISE equal to the
    serial accumulate — same adds in the same order plus an exact +0."""
    module = _Mlp()
    x, y = _data()
    cfg = ShardingConfig(data=2, fsdp=2, devices=jax.devices()[:4])
    serial, s_final = _loss_trajectory(module, x, y, cfg, None)
    defer, d_final = _loss_trajectory(
        module, x, y, cfg, GradOverlap(mode="defer")
    )
    assert serial == defer  # bitwise: float == float
    assert _leaves_equal(s_final.params, d_final.params)


def test_shard_map_mode_bit_identical_on_dp_mesh():
    """The explicit shard_map + deferred bucketed-psum step on a pure-DP
    mesh traces the bitwise-identical trajectory (power-of-two rows and
    device count: every scale factor is exact)."""
    module = _Mlp()
    x, y = _data()
    cfg = ShardingConfig(data=4, devices=jax.devices()[:4])
    serial, s_final = _loss_trajectory(module, x, y, cfg, None)
    overlap = GradOverlap(mode="shard_map", mesh=cfg.mesh(), axes=("data",))
    sm, m_final = _loss_trajectory(module, x, y, cfg, overlap)
    assert serial == sm
    assert _leaves_equal(s_final.params, m_final.params)


def test_trainer_overlap_grads_end_to_end():
    """run_step_trainer(overlap_grads=True) on the mixed mesh reaches
    the bitwise final state of the serial run — the ambient
    grad_overlap_scope reaches the zoo factory's scan at trace time."""
    module = _Mlp()
    x, y = _data(seed=5)

    def run(overlap_grads):
        return run_step_trainer(
            step_fn=classification_step(module, accumulate_steps=4),
            state=create_train_state(module, x[:4], learning_rate=1e-2, seed=4),
            features=x, targets=y, batch_size=8, accumulate_steps=4,
            num_epochs=2, seed=9,
            sharding=ShardingConfig(data=2, fsdp=2, devices=jax.devices()[:4]),
            overlap_grads=overlap_grads,
        )

    assert _leaves_equal(run(False).params, run(True).params)


# ----------------------------------------------------- strategy selection


def test_resolve_grad_overlap_selection():
    dp = ShardingConfig(data=4, devices=jax.devices()[:4])
    mixed = ShardingConfig(data=2, fsdp=2, tensor=2)
    assert resolve_grad_overlap(dp, 4).mode == "shard_map"
    assert resolve_grad_overlap(dp, 4).axes == ("data",)
    assert resolve_grad_overlap(mixed, 4).mode == "defer"
    assert resolve_grad_overlap(None, 4).mode == "defer"
    # nothing to overlap without a microbatch pipeline
    assert resolve_grad_overlap(dp, 1) is None


def test_grad_overlap_scope_is_ambient():
    with grad_overlap_scope(GradOverlap(mode="defer")):
        from unionml_tpu.models.train import current_grad_overlap

        assert current_grad_overlap().mode == "defer"
    from unionml_tpu.models.train import current_grad_overlap

    assert current_grad_overlap() is None


def test_unknown_overlap_mode_rejected():
    module = _Mlp()
    x, y = _data(n=32)
    state = create_train_state(module, x[:4])
    micro = (x.reshape(4, 8, -1), y.reshape(4, 8))
    with pytest.raises(ValueError, match="GradOverlap mode"):
        accumulated_value_and_grad(
            _loss_fn(module), state.params, micro,
            overlap=GradOverlap(mode="wat"),
        )
    with pytest.raises(ValueError, match="mesh"):
        accumulated_value_and_grad(
            _loss_fn(module), state.params, micro,
            overlap=GradOverlap(mode="shard_map"),
        )


# ------------------------------------------------------------ bucketed psum


def test_bucketed_psum_matches_plain_psum():
    """Bucketing changes how many collectives XLA sees, never the
    values: bitwise equal to leaf-wise psum under shard_map, for bucket
    sizes that split the tree anywhere from one-bucket to one-per-leaf."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    cfg = ShardingConfig(data=8)
    mesh = cfg.mesh()
    rng = np.random.default_rng(0)
    tree = {
        "a": rng.normal(size=(8, 128)).astype(np.float32),   # 4 KB/shard
        "b": rng.normal(size=(8, 4)).astype(np.float32),
        "c": {"d": rng.normal(size=(8, 513)).astype(np.float32)},
    }

    def reduce_with(bucket_bytes):
        fn = shard_map(
            lambda t: bucketed_psum(t, "data", bucket_bytes=bucket_bytes),
            mesh=mesh, in_specs=(P("data"),), out_specs=P(), check_vma=False,
        )
        return fn(tree)

    plain = shard_map(
        lambda t: jax.lax.psum(t, "data"),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(), check_vma=False,
    )(tree)
    for bucket_bytes in (1, 600, 1 << 20):
        out = reduce_with(bucket_bytes)
        for a, b in zip(
            jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(out)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="bucket_bytes"):
        bucketed_psum(tree, "data", bucket_bytes=0)


def test_bucketed_psum_grouping():
    """The byte-bounded grouping itself: greedy fill, oversized leaves
    get their own bucket, order preserved."""
    calls = []

    class _FakeLax:
        @staticmethod
        def psum(leaves, axis):
            calls.append(len(leaves))
            return leaves

    import unionml_tpu.parallel.collectives as c

    real_lax = c.lax
    c.lax = _FakeLax
    try:
        tree = [
            np.zeros(100, np.float32),   # 400 B
            np.zeros(100, np.float32),   # 400 B  -> bucket 1 (800 <= 1000)
            np.zeros(100, np.float32),   # 400 B  -> bucket 2
            np.zeros(1000, np.float32),  # 4000 B -> its own bucket 3
            np.zeros(10, np.float32),    # 40 B   -> bucket 4
        ]
        out = bucketed_psum(tree, "data", bucket_bytes=1000)
        assert calls == [2, 1, 1, 1]
        assert [o.shape for o in out] == [t.shape for t in tree]
    finally:
        c.lax = real_lax
