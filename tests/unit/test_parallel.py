"""Mesh/sharding tests on the CPU-simulated 8-device mesh (SURVEY.md §4.3)."""

import jax
import jax.numpy as jnp
import dataclasses

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from unionml_tpu.parallel import (
    PartitionRule,
    ShardingConfig,
    compile_step,
    make_mesh,
)


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8


def test_make_mesh_inferred_axis():
    mesh = make_mesh({"data": -1})
    assert mesh.shape == {"data": 8}
    mesh2 = make_mesh({"data": -1, "tensor": 2})
    assert mesh2.shape["data"] == 4 and mesh2.shape["tensor"] == 2


def test_make_mesh_bad_sizes():
    with pytest.raises(ValueError):
        make_mesh({"data": 3})
    with pytest.raises(ValueError):
        make_mesh({"data": -1, "tensor": -1})


def test_make_mesh_hybrid_dcn_axes():
    """Multi-slice layout: the `data` axis spans 2 slices over DCN while
    `tensor` stays inside a slice on ICI — a psum over `data` still
    reduces correctly across the whole hybrid mesh."""
    mesh = make_mesh({"data": 4, "tensor": 2}, dcn_axes={"data": 2})
    assert mesh.shape == {"data": 4, "tensor": 2}

    from jax import shard_map

    x = jnp.arange(8.0)

    def body(x):
        return jax.lax.psum(x, "data")

    out = shard_map(
        body, mesh=mesh, in_specs=P(("data", "tensor")), out_specs=P(("data", "tensor"))
    )(x)
    # device (d, t) holds element d*2+t; psum over `data` gives, for fixed
    # t, sum_d x[d*2+t] = 12 + 4t — wrong reduction groups would differ
    np.testing.assert_allclose(
        np.asarray(out), np.array([12.0, 16.0] * 4)
    )


def test_make_mesh_dcn_axes_validated():
    with pytest.raises(ValueError, match="not a mesh axis"):
        make_mesh({"data": 4, "tensor": 2}, dcn_axes={"dat": 2})
    with pytest.raises(ValueError, match="must divide"):
        make_mesh({"data": 4, "tensor": 2}, dcn_axes={"data": 3})
    with pytest.raises(ValueError, match="must divide"):
        make_mesh({"data": 4, "tensor": 2}, dcn_axes={"data": 0})


def test_bert_attn_impl_validated():
    from unionml_tpu.models import BertClassifier, BertConfig

    model = BertClassifier(
        dataclasses.replace(BertConfig.tiny(), attn_impl="nope")
    )
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="unknown attention impl"):
        model.init(jax.random.PRNGKey(0), tokens)
    # the padded-batch (bias) path must validate too, not silently fall
    # back to the reference kernel
    with pytest.raises(ValueError, match="unknown attention impl"):
        model.init(
            jax.random.PRNGKey(0), tokens,
            attention_mask=jnp.ones((1, 8), jnp.int32),
        )


def test_serve_gradio_gated_without_dependency():
    from unionml_tpu import Dataset, Model

    ds = Dataset(name="g_ds")
    m = Model(name="g", dataset=ds)
    with pytest.raises((ImportError, ValueError), match="gradio|artifact"):
        m.serve_gradio()


def test_sharding_config_dp():
    cfg = ShardingConfig(data=-1)
    assert cfg.mesh().shape == {"data": 8}
    assert cfg.batch_pspec() == P("data")


def test_sharding_config_dp_fsdp_batch_axes():
    cfg = ShardingConfig(data=2, fsdp=4)
    assert cfg.batch_pspec() == P(("data", "fsdp"))
    # fsdp fallback shards the largest divisible dim
    leaf = jnp.zeros((16, 3))
    assert cfg.param_pspec("dense/kernel", leaf) == P("fsdp", None)
    scalar = jnp.zeros(())
    assert cfg.param_pspec("step", scalar) == P()


def test_partition_rules_tensor_parallel():
    cfg = ShardingConfig(
        data=-1,
        tensor=2,
        rules=[
            PartitionRule(r"attn/.*kernel", (None, "tensor")),
            PartitionRule(r"mlp/out/kernel", ("tensor", None)),
        ],
    )
    leaf = jnp.zeros((8, 8))
    assert cfg.param_pspec("layer0/attn/q/kernel", leaf) == P(None, "tensor")
    assert cfg.param_pspec("layer0/mlp/out/kernel", leaf) == P("tensor", None)
    assert cfg.param_pspec("layer0/norm/scale", leaf) == P()


def test_compile_step_dp_training():
    """A linear-regression step compiled over the 8-device data axis: the
    gradient psum over ICI is inserted by GSPMD from the shardings."""
    cfg = ShardingConfig(data=-1)

    def step(state, batch):
        x, y = batch
        w, b = state["w"], state["b"]

        def loss_fn(w, b):
            pred = x @ w + b
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(w, b)
        return {"w": w - 0.1 * grads[0], "b": b - 0.1 * grads[1]}, {"loss": loss}

    state = {"w": jnp.zeros((4,)), "b": jnp.zeros(())}
    compiled, placed = compile_step(step, state, sharding=cfg, donate_state=False)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    true_w = np.array([1.0, -2.0, 0.5, 3.0], dtype=np.float32)
    y = x @ true_w + 0.25

    batch = jax.device_put((x, y), cfg.batch_sharding())
    state = placed
    for _ in range(200):
        state, metrics = compiled(state, batch)
    np.testing.assert_allclose(np.asarray(state["w"]), true_w, atol=0.05)
    np.testing.assert_allclose(np.asarray(state["b"]), 0.25, atol=0.05)
    assert float(metrics["loss"]) < 1e-3


def test_compile_step_fsdp_state_sharded():
    cfg = ShardingConfig(data=2, fsdp=4)

    def step(state, batch):
        return jax.tree_util.tree_map(lambda p: p + jnp.mean(batch), state), {}

    state = {"w": jnp.ones((8, 4))}
    compiled, placed = compile_step(step, state, sharding=cfg, donate_state=False)
    # the parameter is physically sharded over the fsdp axis
    sh = placed["w"].sharding
    assert sh.spec == P("fsdp", None)
    out, _ = compiled(placed, jnp.ones((8, 1)))
    assert out["w"].sharding.spec == P("fsdp", None)
