"""Two-tier trainer API: @model.train_step compiled over a mesh.

This is the TPU-native hot path (SURVEY.md §3.1: "the hot loop ... becomes
a pjit-compiled step function"), exercised end-to-end through the same
Dataset/Model spec surface the reference uses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn
from flax.training import train_state

from unionml_tpu import Dataset, Model
from unionml_tpu.parallel import ShardingConfig


class MLP(nn.Module):
    hidden: int = 32

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(self.hidden)(x)
        x = nn.relu(x)
        return nn.Dense(2)(x)


def make_app(sharding=None):
    dataset = Dataset(name="blobs", test_size=0.25, shuffle=True, random_state=7)

    @dataset.reader
    def reader(n: int = 256) -> dict:
        rng = np.random.default_rng(0)
        half = n // 2
        x = np.concatenate(
            [
                rng.normal(loc=-2.0, size=(half, 4)),
                rng.normal(loc=2.0, size=(n - half, 4)),
            ]
        ).astype(np.float32)
        y = np.concatenate([np.zeros(half), np.ones(n - half)]).astype(np.int32)
        order = rng.permutation(n)
        return {"features": x[order], "targets": y[order]}

    @dataset.splitter
    def splitter(data: dict, test_size: float, shuffle: bool, random_state: int):
        n = len(data["features"])
        k = int(n * (1 - test_size))
        return (
            {"features": data["features"][:k], "targets": data["targets"][:k]},
            {"features": data["features"][k:], "targets": data["targets"][k:]},
        )

    @dataset.parser
    def parser(data: dict, features, targets):
        return (data["features"], data["targets"])

    def init_state(learning_rate: float = 0.05) -> train_state.TrainState:
        module = MLP()
        params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))["params"]
        return train_state.TrainState.create(
            apply_fn=module.apply, params=params, tx=optax.adam(learning_rate)
        )

    model = Model(name="mlp", init=init_state, dataset=dataset)

    @model.train_step(sharding=sharding)
    def train_step(state, batch):
        x, y = batch

        def loss_fn(params):
            logits = state.apply_fn({"params": params}, x)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), {"loss": loss}

    @model.predictor(jit=True)
    def predictor(state: train_state.TrainState, features: np.ndarray) -> jnp.ndarray:
        logits = state.apply_fn({"params": state.params}, features)
        return jnp.argmax(logits, axis=-1)

    @model.evaluator
    def evaluator(state: train_state.TrainState, features: np.ndarray, targets: np.ndarray) -> float:
        logits = state.apply_fn({"params": state.params}, features)
        return float((jnp.argmax(logits, axis=-1) == targets).mean())

    return dataset, model


def test_train_step_single_device():
    _, model = make_app(sharding=None)
    state, metrics = model.train(
        hyperparameters={"learning_rate": 0.05},
        trainer_kwargs={"num_epochs": 5, "batch_size": 32},
        n=256,
    )
    assert metrics["train"] > 0.95
    assert metrics["test"] > 0.95
    preds = model.predict(features=np.full((3, 4), 2.0, dtype=np.float32))
    assert preds.shape == (3,)
    assert all(p == 1 for p in preds)


def test_train_step_dp_mesh():
    """Same app, data-parallel over the 8-device simulated mesh."""
    _, model = make_app(sharding=ShardingConfig(data=-1))
    state, metrics = model.train(
        hyperparameters={"learning_rate": 0.05},
        trainer_kwargs={"num_epochs": 5, "batch_size": 64},
        n=512,
    )
    assert metrics["test"] > 0.95


def test_train_step_fsdp_mesh():
    _, model = make_app(sharding=ShardingConfig(data=2, fsdp=4))
    state, metrics = model.train(
        hyperparameters={"learning_rate": 0.05},
        trainer_kwargs={"num_epochs": 4, "batch_size": 64},
        n=512,
    )
    assert metrics["test"] > 0.9


def test_pytree_artifact_roundtrip(tmp_path):
    _, model = make_app()
    model.train(
        hyperparameters={"learning_rate": 0.05},
        trainer_kwargs={"num_epochs": 2, "batch_size": 32},
        n=128,
    )
    path = tmp_path / "model.utpu"
    model.save(path)

    _, fresh = make_app()
    loaded = fresh.load(path)
    orig_leaves = jax.tree_util.tree_leaves(model.artifact.model_object.params)
    new_leaves = jax.tree_util.tree_leaves(loaded.params)
    for a, b in zip(orig_leaves, new_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# streaming trainers (execution.run_step_trainer, SURVEY.md §7.4)
# --------------------------------------------------------------------- #

def _stream_problem():
    from unionml_tpu.models import Mlp, MlpConfig, classification_step, create_train_state

    module = Mlp(MlpConfig(hidden_dims=(16,), num_classes=2))
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(-2, 1, (64, 4)), rng.normal(2, 1, (64, 4))]).astype(np.float32)
    y = np.concatenate([np.zeros(64), np.ones(64)]).astype(np.int32)
    state = create_train_state(module, jnp.asarray(x[:1]), learning_rate=0.05)
    return classification_step(module), state, x, y


def test_streaming_trainer_callable_per_epoch():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _stream_problem()

    def epoch_stream():
        for i in range(0, 128, 32):
            yield (jnp.asarray(x[i:i + 32]), jnp.asarray(y[i:i + 32]))

    out = run_step_trainer(
        step_fn=step, state=state, features=epoch_stream, num_epochs=4,
    )
    logits = out.apply_fn({"params": out.params}, jnp.asarray(x))
    acc = float((jnp.argmax(logits, -1) == jnp.asarray(y)).mean())
    assert acc > 0.9


def test_streaming_trainer_one_shot_iterator():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _stream_problem()
    stream = ((jnp.asarray(x[i:i + 32]), jnp.asarray(y[i:i + 32]))
              for i in range(0, 128, 32))
    out = run_step_trainer(step_fn=step, state=state, features=stream)
    assert out.step == 4  # consumed exactly the four streamed batches


def test_streaming_trainer_rejections():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _stream_problem()
    stream = iter([(jnp.asarray(x[:32]), jnp.asarray(y[:32]))])
    with pytest.raises(ValueError, match="cannot be replayed"):
        run_step_trainer(step_fn=step, state=state, features=stream, num_epochs=2)
    with pytest.raises(ValueError, match="streaming trainers"):
        run_step_trainer(
            step_fn=step, state=state, features=iter([]), targets=np.zeros(4),
        )


def test_streaming_trainer_reiterable_loader_multi_epoch():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _stream_problem()

    class Loader:  # DataLoader-like: __iter__ only, fresh pass each time
        def __iter__(self):
            for i in range(0, 128, 32):
                yield (jnp.asarray(x[i:i + 32]), jnp.asarray(y[i:i + 32]))

    out = run_step_trainer(step_fn=step, state=state, features=Loader(), num_epochs=3)
    assert out.step == 12


def test_streaming_trainer_exhausted_callable_raises():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _stream_problem()
    gen = ((jnp.asarray(x[i:i + 32]), jnp.asarray(y[i:i + 32]))
           for i in range(0, 64, 32))
    with pytest.raises(ValueError, match="FRESH iterable"):
        run_step_trainer(step_fn=step, state=state, features=lambda: gen, num_epochs=3)


def test_streaming_trainer_empty_stream_raises():
    from unionml_tpu.execution import run_step_trainer

    step, state, x, y = _stream_problem()
    with pytest.raises(ValueError, match="no batches in epoch 1"):
        run_step_trainer(step_fn=step, state=state, features=iter([]))


def test_adamw_bf16_first_moment():
    """mu_dtype=bfloat16 quarters adam-state bytes; the trajectory stays
    close to fp32 (m is momentum — low-precision-tolerant; v stays fp32)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import Mlp, MlpConfig, classification_step, create_train_state
    from unionml_tpu.models.train import adamw

    module = Mlp(MlpConfig(num_classes=2, hidden_dims=(16,)))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    y = jnp.asarray((np.asarray(x).sum(1) > 0).astype(np.int32))
    step = jax.jit(classification_step(module))

    losses = {}
    for name, dtype in (("fp32", None), ("bf16", jnp.bfloat16)):
        state = create_train_state(
            module, x[:1], optimizer=adamw(1e-2, mu_dtype=dtype)
        )
        if dtype is not None:
            mus = [
                leaf
                for leaf in jax.tree_util.tree_leaves(state.opt_state)
                if hasattr(leaf, "dtype") and leaf.dtype == jnp.bfloat16
            ]
            assert mus, "first moment not stored in bf16"
        for _ in range(20):
            state, metrics = step(state, (x, y))
        losses[name] = float(metrics["loss"])
    assert losses["bf16"] < 0.5  # actually trains
    assert abs(losses["bf16"] - losses["fp32"]) < 0.15
