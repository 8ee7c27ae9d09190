"""BERT-base fine-tune through the remote lifecycle (BASELINE.json #4).

The app is deployed and executed via ``Model.remote_deploy`` →
``Model.remote_train`` (reference lifecycle: model.py:672-796): the
RUNNER process — not this driver — runs the timed fine-tune loop on the
TPU, and the measured samples/sec/chip travels back as the execution's
metrics, so the recorded number is sourced from the remote execution
itself. Run on the TPU host::

    python benchmarks/remote_bert/app.py

CPU smoke: ``JAX_PLATFORMS=cpu UNIONML_TPU_BENCH_PRESET=tiny python
benchmarks/remote_bert/app.py`` (tiny BERT, 3 steps).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu import Dataset, Model
from unionml_tpu.models import (
    BertClassifier,
    BertConfig,
    classification_step,
    create_train_state,
)
from unionml_tpu.models.train import TrainState

dataset = Dataset(name="bert_ft_data", test_size=0.5)
model = Model(name="bert_remote_ft", dataset=dataset)

# module handle shared between init (which builds it) and trainer (which
# builds the jitted step from it); keyed per-process, exactly one config
_ctx: dict = {}


@dataset.reader
def reader(n: int = 64, seq: int = 128, tiny: int = 0) -> dict:
    rng = np.random.default_rng(0)
    vocab = 1024 if tiny else 30522
    return {
        "features": rng.integers(0, vocab, size=(n, seq)).astype(np.int32),
        "targets": rng.integers(0, 2, size=(n,)).astype(np.int32),
    }


@dataset.splitter
def splitter(data: dict, test_size: float, shuffle: bool, random_state: int):
    k = int(len(data["features"]) * (1 - test_size))
    return (
        {"features": data["features"][:k], "targets": data["targets"][:k]},
        {"features": data["features"][k:], "targets": data["targets"][k:]},
    )


@dataset.parser
def parser(data: dict, features, targets):
    return (data["features"], data["targets"])


@model.init
def init(hyperparameters: dict) -> TrainState:
    tiny = bool(hyperparameters.get("tiny", False))
    cfg = BertConfig.tiny() if tiny else BertConfig.base()
    module = BertClassifier(cfg)
    _ctx["module"] = module
    return create_train_state(
        module, jnp.zeros((1, 8), jnp.int32),
        learning_rate=hyperparameters.get("learning_rate", 2e-5),
    )


@model.trainer
def trainer(
    state: TrainState,
    features: np.ndarray,
    targets: np.ndarray,
    *,
    batch_size: int = 32,
    steps: int = 100,
    warmup: int = 10,
) -> TrainState:
    """Timed fine-tune loop: warmup, then a >=100-step window on TPU
    that ends in ``block_until_ready`` on the donated final state."""
    ids = jnp.asarray(features[:batch_size])
    labels = jnp.asarray(targets[:batch_size])
    step = jax.jit(classification_step(_ctx["module"]), donate_argnums=0)
    for _ in range(warmup):
        state, metrics = step(state, (ids, labels))
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, (ids, labels))
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    _ctx["samples_per_sec"] = batch_size * steps / dt
    return state


@model.evaluator
def evaluator(state: TrainState, features: np.ndarray, targets: np.ndarray) -> float:
    # surfaces the throughput measured inside the remote trainer as the
    # execution's metric (the artifact's model-quality signal is not the
    # point of this config — the remote-lifecycle timing is)
    return float(_ctx.get("samples_per_sec", 0.0))


if __name__ == "__main__":
    tiny = os.environ.get("UNIONML_TPU_BENCH_PRESET") == "tiny"
    model.remote(project="bert-remote-bench")
    version = model.remote_deploy(app_version="r2-bench", allow_uncommitted=True)
    artifact = model.remote_train(
        app_version=version,
        hyperparameters={"tiny": tiny},
        trainer_kwargs=(
            {"batch_size": 8, "steps": 3, "warmup": 1} if tiny
            else {"batch_size": 32, "steps": 100, "warmup": 10}
        ),
        n=64,
        seq=128,
        tiny=int(tiny),
    )
    print(json.dumps({
        "metric": "bert_remote_ft_train_samples_per_sec_per_chip",
        "value": round(artifact.metrics["train"], 1),
        "unit": "samples/sec/chip",
        "lifecycle": "remote_deploy -> remote_train (LocalBackend subprocess)",
        "tiny": tiny,
    }))
