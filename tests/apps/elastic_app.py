"""Fixture app: TPU-native train_step with preemption-safe checkpointing.

The test seam: with UNIONML_TEST_DIE_AT=N set AND no checkpoint yet on
disk, the elastic trainer's fault hook hard-kills the process
(``os._exit``) at global step N — a faithful slice preemption (no
cleanup, no terminal status). A relaunch finds checkpoints, disarms,
and resumes to completion.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from unionml_tpu import Dataset, Model
from unionml_tpu.defaults import Resources

_die_at = int(os.environ.get("UNIONML_TEST_DIE_AT", "0"))
_ckpt_dir = "elastic_ckpts"   # relative: resolves against the runner cwd
if _die_at and not glob.glob(os.path.join(_ckpt_dir, "step_*")):
    # arm the preemption bomb only on a FRESH run (no checkpoints):
    # the relaunch must resume, not die again at the same step
    import unionml_tpu.elastic as _elastic

    _real = _elastic.run_elastic_trainer

    def _with_fault(**kwargs):
        def hook(step):
            if step == _die_at:
                os._exit(17)  # hard kill: no finally blocks, like SIGKILL

        return _real(fault_hook=hook, **kwargs)

    _elastic.run_elastic_trainer = _with_fault

dataset = Dataset(name="elastic_dataset", test_size=0.25, shuffle=True,
                  random_state=11, targets=["y"])
model = Model(name="elastic_model", dataset=dataset)


@model.init
def init(hyperparameters: dict) -> dict:
    return {"w": jnp.zeros((2,), jnp.float32),
            "b": jnp.zeros((), jnp.float32)}


@dataset.reader
def reader(n: int = 64) -> pd.DataFrame:
    rng = np.random.default_rng(3)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    y = 2.0 * x1 - x2 + 0.1 * rng.normal(size=n)
    return pd.DataFrame({"x1": x1, "x2": x2, "y": y})


@model.train_step(
    checkpoint_dir=_ckpt_dir, save_every=2,
    resources=Resources(cpu="1", mem="1Gi", chips=0),
)
def step(state: dict, batch: tuple) -> tuple:
    x, y = batch
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32).reshape(-1)

    def loss_fn(params):
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(state)
    new_state = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, state, grads)
    return new_state, {"loss": loss}


@model.predictor
def predictor(params: dict, features: pd.DataFrame) -> list:
    x = jnp.asarray(np.asarray(features), jnp.float32)
    return np.asarray(x @ params["w"] + params["b"]).tolist()


@model.evaluator
def evaluator(params: dict, features: pd.DataFrame, target: pd.DataFrame) -> float:
    x = jnp.asarray(np.asarray(features), jnp.float32)
    y = jnp.asarray(np.asarray(target), jnp.float32).reshape(-1)
    return float(jnp.mean((x @ params["w"] + params["b"] - y) ** 2))
