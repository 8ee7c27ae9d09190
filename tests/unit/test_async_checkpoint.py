"""Async checkpointing (checkpoint/async_writer.py): caller stalls for
the host snapshot only, the background commit is atomic (rename + commit
marker), a kill mid-commit always leaves the previous checkpoint
restorable, and restore refuses torn dirs. The elastic trainer adopts
the writer through make_checkpoint_manager — preemption/resume stays
bit-identical with the async backend, double-buffered prefetch, and
overlapped gradients all on (docs/performance.md "Overlapped
training")."""

import pathlib
import threading

import numpy as np
import pytest

# measured sub-minute module: part of the `-m quick` tier
pytestmark = pytest.mark.quick

import jax
import jax.numpy as jnp

from unionml_tpu.checkpoint import AsyncCheckpointManager, make_checkpoint_manager
from unionml_tpu.checkpoint.async_writer import AsyncCheckpointWriter, is_committed
from unionml_tpu.telemetry import MetricsRegistry


def _state(scale: float = 1.0):
    return {"w": jnp.arange(8, dtype=jnp.float32) * scale,
            "b": jnp.full((2, 2), scale)}


def _target():
    return {"w": jnp.zeros(8, jnp.float32), "b": jnp.zeros((2, 2))}


def test_roundtrip_and_rotation(tmp_path):
    reg = MetricsRegistry()
    with AsyncCheckpointManager(tmp_path, max_to_keep=2, registry=reg) as mgr:
        for s in (1, 2, 3, 4):
            mgr.save(s, _state(float(s)))
        mgr.wait()
        assert mgr._steps() == [3, 4]  # rotation kept the newest two
        restored = mgr.restore(_target())
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(8, dtype=np.float32) * 4
        )
        older = mgr.restore(_target(), step=3)
        np.testing.assert_array_equal(
            np.asarray(older["b"]), np.full((2, 2), 3.0)
        )
    # every committed dir carries the marker
    for p in pathlib.Path(tmp_path).glob("step_*"):
        assert is_committed(p)


def test_resave_same_step_overwrites_committed_dir(tmp_path):
    """Re-saving an already-committed step (manual manager use, or a
    rolled-back run re-reaching the step number) must commit the NEW
    state — os.replace alone cannot replace a non-empty directory, so
    this used to die with ENOTEMPTY and kill the run."""
    reg = MetricsRegistry()
    with AsyncCheckpointManager(tmp_path, registry=reg) as mgr:
        mgr.save(1, _state(1.0))
        mgr.wait()
        mgr.save(1, _state(7.0))   # same step, new contents
        mgr.wait()                 # raises on commit failure
        restored = mgr.restore(_target(), step=1)
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(8, dtype=np.float32) * 7
        )
    # no stale move-aside dirs left behind; the step dir is committed
    assert not list(pathlib.Path(tmp_path).glob("*.tmp-*"))
    assert is_committed(pathlib.Path(tmp_path) / "step_1")


def test_save_returns_before_commit_and_metrics_split(tmp_path):
    """The caller-stall/commit split (docs/observability.md "Checkpoint
    I/O"): save() returns while the commit is still in flight — the
    pending gauge is up, latest_step still names the previous step —
    and save_ms/commit_ms land as separate series."""
    reg = MetricsRegistry()
    with AsyncCheckpointManager(tmp_path, registry=reg) as mgr:
        mgr.save(10, _state(1.0))
        mgr.wait()
        gate = threading.Event()
        mgr_gated = AsyncCheckpointManager(
            tmp_path, registry=reg, commit_hook=lambda p: gate.wait(10)
        )
        mgr_gated.save(20, _state(2.0))  # returns with the commit gated
        assert mgr_gated.latest_step() == 10
        snap = reg.snapshot()
        assert snap["unionml_checkpoint_pending"][""] == 1.0
        # the caller stall was observed even though the commit is open
        assert snap["unionml_checkpoint_save_ms"]["kind=async"]["count"] == 2
        gate.set()
        mgr_gated.wait()
        assert mgr_gated.latest_step() == 20
        snap = reg.snapshot()
        assert snap["unionml_checkpoint_pending"][""] == 0.0
        assert snap["unionml_checkpoint_commit_ms"]["kind=async"]["count"] == 2
        assert snap["unionml_checkpoint_save_bytes_total"]["kind=async"] > 0
        mgr_gated.close()


def test_kill_mid_commit_restores_previous_step(tmp_path):
    """The chaos contract: a commit that dies before the atomic rename
    leaves no step dir, latest_step/restore fall back to the previous
    committed checkpoint, and the failure surfaces on the strict wait()
    barrier (close() only logs — safe in trainer finally blocks)."""
    reg = MetricsRegistry()
    with AsyncCheckpointManager(tmp_path, registry=reg) as mgr:
        mgr.save(10, _state(1.0))
        mgr.wait()

    def die(final_path):
        raise OSError("simulated kill mid-commit")

    chaos = AsyncCheckpointManager(tmp_path, registry=reg, commit_hook=die)
    chaos.save(20, _state(2.0))
    with pytest.raises(RuntimeError, match="previous checkpoint"):
        chaos.wait()
    assert chaos.latest_step() == 10
    restored = chaos.restore(_target())
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(8, dtype=np.float32)
    )
    # no half-written final dir, no tmp leftovers after the cleanup
    assert not list(pathlib.Path(tmp_path).glob("step_20*"))


def test_restore_refuses_torn_checkpoint(tmp_path):
    """A step dir without its commit marker (external interference: a
    partial copy, a crashed rsync) is skipped by latest_step and
    REFUSED by an explicit restore — torn state never loads."""
    reg = MetricsRegistry()
    mgr = AsyncCheckpointManager(tmp_path, registry=reg)
    mgr.save(5, _state(1.0))
    mgr.wait()
    torn = pathlib.Path(tmp_path) / "step_9"
    torn.mkdir()
    (torn / "state.msgpack").write_bytes(b"partial garbage")
    assert mgr.latest_step() == 5
    with pytest.raises(ValueError, match="torn checkpoint"):
        mgr.restore(_target(), step=9)
    restored = mgr.restore(_target())  # falls back to the committed step
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(8, dtype=np.float32)
    )
    mgr.close()


def test_stale_tmp_dirs_swept_on_construction(tmp_path):
    leftover = pathlib.Path(tmp_path) / "step_7.tmp-123-1"
    leftover.mkdir(parents=True)
    (leftover / "state.msgpack").write_bytes(b"junk from a dead process")
    mgr = AsyncCheckpointManager(tmp_path)
    assert not leftover.exists()
    assert mgr.latest_step() is None
    mgr.close()


def test_writer_restore_preserves_device_placement(tmp_path):
    """Restore re-places leaves per the target's sharding — the elastic
    resume path hands in the freshly compiled (placed) state."""
    from unionml_tpu.parallel import ShardingConfig

    cfg = ShardingConfig(data=8)
    writer = AsyncCheckpointWriter(registry=MetricsRegistry())
    state = jax.device_put(
        {"w": jnp.arange(16, dtype=jnp.float32)},
        {"w": cfg.batch_sharding()},
    )
    writer.save(tmp_path / "step_1", state)
    writer.wait()
    target = jax.device_put(
        {"w": jnp.zeros(16, jnp.float32)}, {"w": cfg.batch_sharding()}
    )
    out = writer.restore(tmp_path / "step_1", target)
    np.testing.assert_array_equal(
        np.asarray(out["w"]), np.arange(16, dtype=np.float32)
    )
    assert out["w"].sharding.is_equivalent_to(cfg.batch_sharding(), 1)


def test_forced_async_backend_refuses_orbax_format_dir(tmp_path):
    """A FORCED async/sync backend over a marker-less (Orbax-format)
    directory must refuse at construction instead of seeing zero
    committed steps and silently restarting the run from step 0
    (backend='auto' detects the format and picks Orbax instead)."""
    orbax_style = pathlib.Path(tmp_path) / "step_12"
    orbax_style.mkdir()
    (orbax_style / "array_data").write_bytes(b"orbax-era payload")
    with pytest.raises(ValueError, match="backend='orbax'"):
        AsyncCheckpointManager(tmp_path)
    with pytest.raises(ValueError, match="backend='orbax'"):
        make_checkpoint_manager(tmp_path, backend="sync")
    # …but a dir that ALSO holds a committed async step is ours: the
    # marker-less stray is a torn external copy, skipped per the
    # restore contract (see test_restore_refuses_torn_checkpoint)
    ours = pathlib.Path(tmp_path) / "ours"
    with AsyncCheckpointManager(ours) as mgr:
        mgr.save(1, _state(1.0))
    (ours / "step_2").mkdir()
    mgr2 = AsyncCheckpointManager(ours)
    assert mgr2.latest_step() == 1
    mgr2.close()


def test_make_checkpoint_manager_sticks_with_orbax_dirs(tmp_path):
    """auto must not silently restart an existing Orbax-format run from
    scratch: marker-less step dirs pin the Orbax backend."""
    from unionml_tpu.checkpoint.sharded import CheckpointManager

    with CheckpointManager(str(tmp_path), async_save=False) as mgr:
        mgr.save(3, {"w": jnp.ones((4,))})
    picked = make_checkpoint_manager(tmp_path, backend="auto")
    assert isinstance(picked, CheckpointManager)
    assert picked.latest_step() == 3
    picked.close()
    # a fresh dir single-process picks the async writer
    fresh = make_checkpoint_manager(tmp_path / "fresh", backend="auto")
    assert isinstance(fresh, AsyncCheckpointManager)
    fresh.close()
    with pytest.raises(ValueError, match="backend"):
        make_checkpoint_manager(tmp_path, backend="nope")


def _make_problem():
    from flax import linen as nn

    from unionml_tpu.models.train import classification_step, create_train_state

    class Mlp(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(nn.relu(nn.Dense(16)(x)))

    module = Mlp()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(128,)).astype(np.int32)
    step = classification_step(module, accumulate_steps=2)
    state = create_train_state(module, x[:4], learning_rate=1e-2, seed=1)
    return step, state, x, y


def test_elastic_async_preemption_resume_bit_identical(tmp_path):
    """The full overlapped stack — async checkpoint backend,
    double-buffered donated prefetch, overlap_grads — still satisfies
    the elastic contract: kill + relaunch reaches the bit-identical
    final state of an uninterrupted run (replay-after-preemption works
    because resumed feeds rebuild fresh donated buffers from the
    deterministic (seed, epoch) order)."""
    from unionml_tpu.elastic import Preemption, run_elastic_trainer
    from unionml_tpu.parallel import ShardingConfig

    def run(d, state, step, fault=None):
        return run_elastic_trainer(
            step_fn=step, state=state, arrays=[x, y],
            checkpoint_dir=str(d), num_epochs=2, batch_size=8,
            accumulate_steps=2, checkpoint_every=4, seed=3,
            sharding=ShardingConfig(data=2, fsdp=2, devices=jax.devices()[:4]),
            overlap_grads=True, double_buffer=True, fault_hook=fault,
        )

    step, state0, x, y = _make_problem()
    ref_state, ref_steps = run(tmp_path / "ref", state0, step)

    step2, state1, _, _ = _make_problem()

    def bomb(global_step):
        if global_step == 6:
            raise Preemption("simulated")

    with pytest.raises(Preemption):
        run(tmp_path / "run", state1, step2, fault=bomb)
    # the kill landed past the step-4 checkpoint: async commit already
    # durable, resume point is step 4
    assert make_checkpoint_manager(tmp_path / "run").latest_step() == 4

    step3, state2, _, _ = _make_problem()
    out_state, out_steps = run(tmp_path / "run", state2, step3)
    assert out_steps == ref_steps
    for a, b in zip(
        jax.tree_util.tree_leaves(ref_state.params),
        jax.tree_util.tree_leaves(out_state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_kill_mid_commit_resumes_previous(tmp_path):
    """Chaos at the manager level THROUGH the trainer: the commit of
    the step-8 checkpoint dies mid-write; the trainer's finally-path
    close() only logs, and a relaunch resumes from the intact step-4
    checkpoint instead of a torn step-8."""
    from unionml_tpu.elastic import run_elastic_trainer

    step, state0, x, y = _make_problem()
    boom = {"armed": False}

    def flaky_commit(final_path):
        if final_path.name == "step_8" and not boom["armed"]:
            boom["armed"] = True
            raise OSError("power loss mid-commit")

    # run the loop manually against a chaos manager: monkeypatching via
    # the backend factory would hide which save failed
    mgr = AsyncCheckpointManager(tmp_path, commit_hook=flaky_commit)
    import jax as _jax

    compiled = _jax.jit(step, donate_argnums=())
    from unionml_tpu.execution import to_microbatches

    state = state0
    for i in range(8):
        xb = x[i * 16:(i + 1) * 16]
        yb = y[i * 16:(i + 1) * 16]
        batch = to_microbatches((xb, yb), 2, 8)
        state, _ = compiled(state, batch)
        if (i + 1) % 4 == 0:
            mgr.save(i + 1, state)
    mgr.close()  # drains; the step_8 failure was logged, not raised
    assert mgr.latest_step() == 4

    # relaunch through the trainer: resumes at 4, finishes, and the
    # terminal checkpoint commits cleanly this time
    step2, state1, _, _ = _make_problem()
    out, steps = run_elastic_trainer(
        step_fn=step2, state=state1, arrays=[x, y],
        checkpoint_dir=str(tmp_path), num_epochs=1, batch_size=8,
        accumulate_steps=2, checkpoint_every=4, seed=0,
    )
    assert steps == 8
    assert make_checkpoint_manager(tmp_path).latest_step() == 8
