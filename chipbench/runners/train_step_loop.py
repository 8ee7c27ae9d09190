"""Runner ``train_step_loop``: train steps back to back for the window.

Set-up builds one object, the compiled step with its state made from the
seed, drives it through its first three steps on three different batches
(whose losses, first gradient and parameter change are what ``correct``
compares) and hands that same object to the window. The configuration's
``training.entry`` says which of the program's loops carries the steps:
``run_step_trainer`` (the loop behind ``Model.train()``) or ``compile_step``
over a mesh, driven as its users drive it.
"""

from __future__ import annotations

import importlib
import time
from typing import Any, Iterable, List, Optional

from chipbench import judge, weights
from chipbench.yardstick import say

_IMPL = "threefry2x32"  # the same values under any sharding: the tree is made more than once


class Cell:
    """One configuration's train step, built once in a process: the
    program's module and loop, the seeded state and batches, the first three
    steps, and the reference's side of them."""

    def __init__(self, cfg: dict, mix: dict, chips: int):
        import jax
        import jax.numpy as jnp

        self.cfg, self.mix, self.chips = cfg, mix, chips
        self.train = train = cfg["training"]
        adapter = importlib.import_module(f"chipbench.adapters.{cfg['family']}")
        self.built = built = adapter.build(cfg)
        self.batch = train["batch_per_chip"] * chips
        self.params_shapes = built["abstract_params"]()
        self.sharding = None
        self.state_shardings = None
        if train["entry"] == "compile_step":
            from unionml_tpu.parallel import ShardingConfig

            self.sharding = ShardingConfig(rules=built["partition_rules"], **train["mesh"])
            state_shapes = jax.eval_shape(built["make_state"], self.params_shapes)
            self.state_shardings = self.sharding.state_shardings(state_shapes)
        self._step = None
        self._leaf_norms = jax.jit(lambda tree: [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(tree)
        ])
        self._diff_norms = jax.jit(lambda a, b: [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
        ])

    # ---- seeded state and batches, each one program

    def make_state(self, seed: int):
        return weights.make_tree(
            self.params_shapes, seed, wrap=self.built["make_state"],
            out_shardings=self.state_shardings, impl=_IMPL,
        )

    def make_params(self, seed: int, shardings=None):
        if shardings is None and self.state_shardings is not None:
            shardings = self.state_shardings.params
        return weights.make_tree(self.params_shapes, seed, out_shardings=shardings, impl=_IMPL)

    def make_batches(self, seed: int) -> List[Any]:
        import jax

        n = int(self.mix["distinct_batches"])
        make = lambda key: self.built["make_batches"](key, n, self.batch)  # noqa: E731
        if self.sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            spec = PartitionSpec(None, *self.sharding.batch_pspec())
            fn = jax.jit(make, out_shardings=NamedSharding(self.sharding.mesh(), spec))
        else:
            fn = jax.jit(make)
        pool = fn(weights.seed_key(seed, stream=1, impl=_IMPL))
        return [self.built["take_batch"](pool, i) for i in range(n)]

    # ---- the program's loop: the same callable for the check and the window

    def drive(self, state, feed: Iterable):
        """Run steps until ``feed`` ends; returns (state, last loss)."""
        import jax

        if self.train["entry"] == "run_step_trainer":
            from unionml_tpu import telemetry
            from unionml_tpu.execution import run_step_trainer

            state = run_step_trainer(
                step_fn=self.built["step_fn"], state=state, features=iter(feed),
                batch_size=self.batch, seed=0,
            )
            return state, float(telemetry.get_registry().gauge("unionml_trainer_loss").value)
        if self.train["entry"] != "compile_step":
            raise SystemExit(f"chipbench: unknown training.entry {self.train['entry']!r}")
        if self._step is None:
            from unionml_tpu.parallel import compile_step

            self._step, state = compile_step(self.built["step_fn"], state, sharding=self.sharding)
        lookahead = int(self.mix["steps_in_flight"])
        pending: List[Any] = []
        loss = None
        for b in feed:
            state, metrics = self._step(state, b)
            pending.append(metrics["loss"])
            if len(pending) > lookahead:
                loss = pending.pop(0)
                loss.block_until_ready()
        jax.block_until_ready(state)
        return state, float(pending[-1] if pending else loss)

    def first_three(self, state, batches, seed: int):
        """Three steps through :meth:`drive`; what ``correct`` compares."""
        import jax

        got = {"losses": []}
        for i in range(3):
            state, loss = self.drive(state, [batches[i]])
            got["losses"].append(loss)
            if i == 0:  # the first gradient as the optimizer got it: mu_1 = (1 - b1) g
                mu = state.opt_state[0].mu
                b1 = self.built["adam_b1"]
                got["grad_norms"] = [float(x) / (1.0 - b1) for x in jax.device_get(self._leaf_norms(mu))]
        fresh = self.make_params(seed)
        got["update_norms"] = [float(x) for x in jax.device_get(self._diff_norms(state.params, fresh))]
        return state, got

    def reference(self, seed: int, batches, control: Optional[str] = None) -> dict:
        """The plain reference's three steps, from the same seeded tree."""
        import jax

        ref = importlib.import_module(f"chipbench.reference.{self.cfg['reference']}")
        shardings = None
        if self.sharding is not None:
            # the float32 copies (parameters, moments, gradients) are five
            # times the parameters: spread every leaf over all the chips
            from jax.sharding import NamedSharding, PartitionSpec

            mesh = self.sharding.mesh()
            axes, n_dev = tuple(mesh.axis_names), mesh.devices.size

            def spread(leaf):
                for dim, size in enumerate(leaf.shape):
                    if size % n_dev == 0:
                        return NamedSharding(mesh, PartitionSpec(*([None] * dim + [axes])))
                return NamedSharding(mesh, PartitionSpec())

            shardings = jax.tree_util.tree_map(spread, self.params_shapes)
        fresh = self.make_params(seed, shardings)
        return judge.reference_three_steps(
            lambda p, b: ref.loss(p, b, self.cfg, control), fresh, batches[:3],
            lr=self.train["learning_rate"],
        )


def run(ctx: dict) -> dict:
    import jax

    cfg, mix, seed, seconds = ctx["config"], ctx["traffic"], ctx["seed"], ctx["seconds"]
    marks = ctx["marks"]
    cell = Cell(cfg, mix, ctx["chips"])
    marks.append(("imports and module", time.perf_counter()))
    state = jax.block_until_ready(cell.make_state(seed))
    marks.append(("weights and optimizer state", time.perf_counter()))
    batches = jax.block_until_ready(cell.make_batches(seed))
    marks.append(("batches", time.perf_counter()))
    state, got = cell.first_three(state, batches, seed)
    marks.append(("compile or cache load, first three steps", time.perf_counter()))

    # ---- the window
    tracing = {"dir": None, "t0": None, "t1": None, "t_clean": None, "n_clean": 0}
    n_steps = [0]
    t_first = [None]
    trace_from = int(mix["trace_from_step"]) if ctx["trace"] else -1
    trace_steps = int(mix["trace_steps"])

    def feed():
        i = 3
        t_first[0] = time.perf_counter()
        deadline = t_first[0] + seconds
        while time.perf_counter() < deadline:
            if n_steps[0] == trace_from:
                tracing["dir"] = ctx["trace_dir"]
                jax.profiler.start_trace(tracing["dir"])
                tracing["t0"] = time.perf_counter()
            if tracing["t0"] is not None and tracing["t1"] is None and n_steps[0] == trace_from + trace_steps:
                tracing["t1"] = time.perf_counter()
                jax.profiler.stop_trace()
                # stopping the profiler takes longer than the steps in flight:
                # the device has drained, so what follows starts with no lead
                tracing["t_clean"], tracing["n_clean"] = time.perf_counter(), n_steps[0]
            n_steps[0] += 1
            yield batches[i % len(batches)]
            i += 1

    compiles = ctx["compile_counter"]
    c0 = compiles()
    state, _ = cell.drive(state, feed())
    t_end = time.perf_counter()
    if tracing["t0"] is not None and tracing["t1"] is None:
        tracing["t1"] = time.perf_counter()
        jax.profiler.stop_trace()
    window = t_end - t_first[0]
    compiles_in_window = compiles() - c0
    say(f"window: {n_steps[0]} steps of {cell.batch} samples in {window:.4f} s "
        f"({compiles_in_window} compilations inside it)")
    peak = ctx["memory_peak"]()
    # a traced run's whole window holds the profiler's own stall; its steps
    # after the trace, closed by the same block_until_ready, do not
    clean_s_per_step = None
    if tracing["t_clean"] is not None and n_steps[0] > tracing["n_clean"]:
        clean_s_per_step = (t_end - tracing["t_clean"]) / (n_steps[0] - tracing["n_clean"])

    # ---- the reference, after the program's state is freed
    del state
    t_ref = time.perf_counter()
    want = cell.reference(seed, batches)
    say(f"reference: three float32 steps in {time.perf_counter() - t_ref:.1f} s (not in setup_s, outside the window)")
    for i, (g, w) in enumerate(zip(got["losses"], want["losses"])):
        say(f"correct: step {i + 1} loss {g:.6f} against the reference's {w:.6f}")
    return {
        "end_to_end": {
            "train_samples_per_s": n_steps[0] * cell.batch / window,
            "setup_s": t_first[0] - ctx["t_start"],
        },
        "attempted": n_steps[0], "failed": 0,
        "numbers": judge.compare_training(got, want, cfg["correct"]),
        "memory_peak_bytes": peak, "window_s": window, "steps": n_steps[0],
        "batch": cell.batch, "chips": ctx["chips"], "compiles_in_window": compiles_in_window,
        "trace_dir": tracing["dir"], "clean_s_per_step": clean_s_per_step,
        "trace_host_window_s": None if tracing["t0"] is None else tracing["t1"] - tracing["t0"],
    }
