"""Training-throughput benchmarks beyond the headline ViT (bench.py).

Two training configs on one chip:

- ``bert_ft``  — BERT-base classification fine-tune (batch 32, seq 128),
  samples/sec/chip — uses the donation-safe ``adamw`` chain.
- ``llama_lc`` — long-context LM training (0.19B-param Llama geometry,
  batch 2, seq 4096, Pallas flash attention), tokens/sec/chip.

Prints one JSON line per config. Timing: warmup, then a >=100-step
window on TPU that ends in ``block_until_ready`` on the final donated
state.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _time_steps(step, state, batch, steps, warmup):
    import jax

    for _ in range(warmup):
        state, metrics = step(state, batch)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    jax.block_until_ready(state)
    return time.perf_counter() - t0


def main() -> None:
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from unionml_tpu.models import (
        BertClassifier,
        BertConfig,
        Llama,
        LlamaConfig,
        classification_step,
        create_train_state,
        lm_step,
    )

    tiny = os.environ.get("UNIONML_TPU_BENCH_PRESET") == "tiny" or (
        jax.default_backend() == "cpu"
    )
    steps, warmup = (3, 1) if tiny else (100, 10)
    rng = np.random.default_rng(0)

    # -- BERT-base fine-tune ------------------------------------------- #
    bcfg = BertConfig.tiny() if tiny else BertConfig.base(num_classes=2)
    batch, seq = (4, 16) if tiny else (32, 128)
    bert = BertClassifier(bcfg)
    ids = jnp.asarray(rng.integers(0, bcfg.vocab_size, size=(batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 2, size=(batch,)), jnp.int32)
    state = create_train_state(bert, ids[:1], learning_rate=2e-5)
    step = jax.jit(classification_step(bert), donate_argnums=0)
    dt = _time_steps(step, state, (ids, labels), steps, warmup)
    print(json.dumps({
        "metric": "bert_ft_train_samples_per_sec_per_chip",
        "batch": batch, "seq": seq,
        "value": round(batch * steps / dt, 1),
        "unit": "samples/sec/chip",
    }))

    # -- long-context Llama LM ----------------------------------------- #
    if tiny:
        lcfg = LlamaConfig.tiny(vocab_size=256)
        batch, seq = 2, 64
    else:
        # ~0.19B params: 12 x 768 Llama geometry, flash attention
        lcfg = LlamaConfig(
            vocab_size=32_000, hidden_dim=768, num_layers=12, num_heads=12,
            num_kv_heads=4, mlp_dim=2048, max_len=4096, attn_impl="flash",
        )
        batch, seq = 2, 4096
    lm = Llama(lcfg)
    tokens = jnp.asarray(rng.integers(0, lcfg.vocab_size, size=(batch, seq)), jnp.int32)
    state = create_train_state(lm, tokens[:1, :8], learning_rate=1e-3)
    step = jax.jit(lm_step(lm), donate_argnums=0)
    dt = _time_steps(step, state, tokens, steps, warmup)
    print(json.dumps({
        "metric": "llama_lc_train_tokens_per_sec_per_chip",
        "batch": batch, "seq": seq,
        "value": round(batch * (seq - 1) * steps / dt, 1),
        "unit": "tokens/sec/chip",
    }))

    # -- QLoRA fine-tune (UNIONML_TPU_BENCH_PRESET=qlora_8b) ------------ #
    # The serving flagship run in reverse: fine-tune Llama-3-8B on ONE
    # chip. Full fine-tuning cannot fit (bf16 params + fp32 master + adam
    # m/v ~ 96 GB); QLoRA does: the int8 base (~8.6 GB, the same tree the
    # serving path streams) is frozen, and only rank-16 adapters (~42M
    # params, ~0.5 GB with adam state) train. Per-block remat keeps
    # activations at one block.
    if os.environ.get("UNIONML_TPU_BENCH_PRESET") == "qlora_8b" or tiny:
        from benchmarks.serve_latency import random_quantized_params

        from unionml_tpu.models import create_lora_train_state

        if tiny:
            qcfg = LlamaConfig.tiny(vocab_size=256, quantized=True)
            batch, seq, rank = 2, 32, 4
        else:
            qcfg = LlamaConfig(
                quantized=True, remat=True, attn_impl="flash", max_len=2048
            )
            batch, seq, rank = 1, 1024, 16
        base = random_quantized_params(Llama(qcfg))
        import dataclasses

        lcfg = dataclasses.replace(qcfg, lora_rank=rank)
        lora_llama = Llama(lcfg)
        state = create_lora_train_state(
            lora_llama, jnp.zeros((1, 8), jnp.int32), base_params=base,
            learning_rate=1e-4,
        )
        del base  # the state holds the only reference now
        tokens = jnp.asarray(
            rng.integers(0, qcfg.vocab_size, size=(batch, seq)), jnp.int32
        )
        step = jax.jit(lm_step(lora_llama), donate_argnums=0)
        n_steps = steps if tiny else 30  # ~0.5 s/step at 8B: 30 suffice
        dt = _time_steps(step, state, tokens, n_steps, warmup if tiny else 5)
        print(json.dumps({
            "metric": "qlora_8b_train_tokens_per_sec_per_chip",
            "batch": batch, "seq": seq, "lora_rank": rank,
            "value": round(batch * (seq - 1) * n_steps / dt, 1),
            "unit": "tokens/sec/chip",
        }))

    # -- long-context scaling (UNIONML_TPU_BENCH_LC_SCALE=1) ------------ #
    # tokens/sec vs sequence length at a constant 8192-token batch:
    # flash attention keeps memory linear in seq; per-block remat trades
    # recompute for activation memory at 16k+
    if os.environ.get("UNIONML_TPU_BENCH_LC_SCALE") and not tiny:
        for b, s, remat, accum in (
            (1, 8192, False, 1),
            (1, 16384, True, 1),
            # HBM caps the 16k config at microbatch 1; gradient
            # accumulation restores an effective batch of 4 with the
            # same activation footprint — the accumulate_steps knob's
            # long-context cost is this row vs the one above
            (1, 16384, True, 4),
        ):
            scfg = LlamaConfig(**{**lcfg.__dict__, "max_len": s, "remat": remat})
            lm_s = Llama(scfg)
            toks = jnp.asarray(
                rng.integers(0, scfg.vocab_size, size=(b * accum, s)), jnp.int32
            )
            if accum > 1:
                toks = toks.reshape(accum, b, s)
            st = create_train_state(
                lm_s, jnp.zeros((1, 8), jnp.int32), learning_rate=1e-3
            )
            stp = jax.jit(lm_step(lm_s, accumulate_steps=accum), donate_argnums=0)
            n_steps = max(20, steps // 4)  # longer steps: fewer suffice
            dt = _time_steps(stp, st, toks, n_steps, max(2, warmup // 2))
            print(json.dumps({
                "metric": "llama_lc_scale_tokens_per_sec_per_chip",
                "batch": b, "seq": s, "remat": remat,
                "accumulate_steps": accum,
                "value": round(b * accum * (s - 1) * n_steps / dt, 1),
                "unit": "tokens/sec/chip",
            }))


def goodput_leg() -> None:
    """``UNIONML_TPU_BENCH_PRESET=train_goodput``: goodput attribution
    on a fault-injected training loop (docs/observability.md
    "Training goodput").

    Three measurements, asserted not just recorded:

    1. **Attribution** — an elastic-trainer run with a forced data
       stall (the stream sleeps), synchronous checkpoints on the loop,
       and an induced recompile (one odd-shaped batch mid-stream) must
       have its compute + badput buckets explain >= 95% of wall time,
       with each injected fault visible in its named bucket.
    2. **Overhead** — the same in-memory streaming loop with goodput
       instrumentation off vs. on (min of 3 interleaved trials each,
       pre-warmed jit cache) must differ by <= 2%.
    3. **SLO coupling** — a `GaugeObjective` on
       ``unionml_train_goodput_ratio`` flips the PR 5 watchdog to
       breached at the first evaluation after an induced goodput
       collapse (deterministic ``evaluate(now=)`` clock).
    """
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn
    from flax.training import train_state

    from unionml_tpu.elastic import run_elastic_trainer
    from unionml_tpu.execution import run_step_trainer
    from unionml_tpu.goodput import GoodputTracker
    from unionml_tpu.slo import GaugeObjective, SloWatchdog
    from unionml_tpu.telemetry import (
        FlightRecorder, MetricsRegistry, TraceRecorder,
    )

    class _Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(32)(x)
            x = nn.relu(x)
            return nn.Dense(2)(x)

    net = _Net()

    def make_state():
        params = net.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
        return train_state.TrainState.create(
            apply_fn=net.apply, params=params, tx=optax.adam(1e-3)
        )

    def make_step():
        # a FRESH function object per call: _jitted caches per function,
        # so the attribution run gets a real cold compile while the
        # overhead legs share one warmed cache
        def step(state, batch):
            x, y = batch

            def loss_fn(p):
                logits = state.apply_fn({"params": p}, x)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                ).mean()

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads=grads), {"loss": loss}

        return step

    rng = np.random.default_rng(0)

    def batch(rows):
        x = rng.normal(size=(rows, 8)).astype(np.float32)
        return x, (x[:, 0] > 0).astype(np.int32)

    n_steps, stall_steps, stall_s = 60, range(20, 25), 0.025
    batches = [batch(16) for _ in range(n_steps)]
    odd_batch = batch(24)  # one stray shape: the induced recompile

    def faulted_stream():
        for i in range(n_steps):
            if i in stall_steps:
                time.sleep(stall_s)  # forced data stall (host starvation)
            yield odd_batch if i == 40 else batches[i]

    # ---- 1. attribution on the fault-injected elastic run ------------- #
    import tempfile

    reg = MetricsRegistry()
    tracker = GoodputTracker(
        registry=reg, tracer=TraceRecorder(registry=reg),
        flight=FlightRecorder(),
    )
    run_elastic_trainer(
        step_fn=make_step(), state=make_state(), stream=faulted_stream,
        checkpoint_dir=tempfile.mkdtemp(prefix="train-goodput-"),
        checkpoint_every=10, goodput=tracker,
        # this leg asserts attribution with checkpoint stalls ON the
        # loop (see docstring); the async backend's identity is what
        # the train_overlap leg asserts
        checkpoint_backend="sync",
    )
    rep = tracker.report()
    bad = rep["badput_s"]
    assert rep["attributed_fraction"] >= 0.95, (
        f"attribution explains only {rep['attributed_fraction']:.1%} of "
        f"wall time (bar: 95%): {rep}"
    )
    injected_stall = len(stall_steps) * stall_s
    assert bad["data_wait"] >= injected_stall * 0.8, (
        f"injected {injected_stall}s data stall, data_wait bucket saw "
        f"only {bad['data_wait']}s"
    )
    assert bad["compile"] > 0, f"induced recompile not attributed: {bad}"
    assert bad["checkpoint"] > 0, f"checkpoint stall not attributed: {bad}"
    print(json.dumps({
        "metric": "train_goodput_attributed_fraction",
        "steps": rep["steps"],
        "value": rep["attributed_fraction"],
        "goodput_ratio": rep["goodput_ratio"],
        "badput_s": bad,
        "unit": "fraction",
    }))

    # ---- 2. instrumentation overhead on the in-memory loop ------------ #
    step = make_step()  # ONE function: both legs share the jit cache
    state0 = make_state()  # shared, donate_state=False below: reusing
    # one committed state keeps jit re-traces out of both legs — on a
    # shared CPU the per-run retrace jitters far more than the 2% bar

    paced_steps, pace_s = 100, 0.008

    def spin(seconds):
        # deterministic pacing floor: a sleep() here couples the
        # comparison to kernel timer quantization (measured: the extra
        # instrumentation syscalls shift sleep wakeups by far more than
        # the instrumentation itself costs); a spin burns exactly the
        # budget regardless of what ran between paces
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            pass

    def stream_paced():
        # every step paced like a loader-fed loop, giving the percentage
        # comparison a deterministic wall floor
        for i in range(paced_steps):
            spin(pace_s)
            yield batches[i % n_steps]

    def run_once(goodput):
        t0 = time.perf_counter()
        run_step_trainer(
            step_fn=step, state=state0, features=stream_paced,
            registry=MetricsRegistry(), goodput=goodput,
            donate_state=False,
        )
        return time.perf_counter() - t0

    run_once(None)  # warm the jit cache out of both legs
    walls = {"off": [], "on": []}
    for _ in range(4):  # interleaved: drift hits both legs alike
        walls["off"].append(run_once(None))
        walls["on"].append(run_once(
            GoodputTracker(
                registry=MetricsRegistry(),
                tracer=TraceRecorder(registry=MetricsRegistry()),
                flight=FlightRecorder(),
            )
        ))
    t_off, t_on = min(walls["off"]), min(walls["on"])
    overhead_pct = (t_on - t_off) / t_off * 100.0
    assert overhead_pct <= 2.0, (
        f"goodput instrumentation overhead {overhead_pct:.2f}% exceeds "
        f"the 2% bar (off {t_off * 1e3:.1f} ms, on {t_on * 1e3:.1f} ms)"
    )
    print(json.dumps({
        "metric": "train_goodput_overhead_pct",
        "off_ms": round(t_off * 1e3, 1),
        "on_ms": round(t_on * 1e3, 1),
        "value": round(overhead_pct, 3),
        "unit": "%",
    }))

    # ---- 3. goodput collapse breaches the SLO watchdog ---------------- #
    reg = MetricsRegistry()
    tracker = GoodputTracker(
        registry=reg, tracer=TraceRecorder(registry=reg),
        flight=FlightRecorder(),
    )
    watchdog = SloWatchdog(
        [GaugeObjective(
            "train_goodput", "unionml_train_goodput_ratio", min_value=0.3,
        )],
        registry=reg, fast_window_s=5.0, slow_window_s=5.0,
    )

    # a heavier step for this leg: with measure_device_time every step
    # syncs, so real compute honestly dominates the healthy run's wall
    # time and the ratio is workload-determined, not scheduler noise
    class _Wide(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(256)(x)
            x = nn.relu(x)
            return nn.Dense(2)(x)

    wide = _Wide()
    wparams = wide.init(jax.random.PRNGKey(0), jnp.zeros((1, 32)))["params"]
    wstate = train_state.TrainState.create(
        apply_fn=wide.apply, params=wparams, tx=optax.adam(1e-3)
    )

    def wide_step(state, batch):
        x, y = batch

        def loss_fn(p):
            logits = state.apply_fn({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), {"loss": loss}

    wx = rng.normal(size=(64, 32)).astype(np.float32)
    wbatch = (wx, (wx[:, 0] > 0).astype(np.int32))

    def wide_stream(steps, stall=0.0):
        def it():
            for _ in range(steps):
                if stall:
                    time.sleep(stall)  # goodput collapse: starvation
                yield wbatch

        return it

    # warm the wide step's jit cache OUTSIDE the tracked runs, so no
    # compile debit muddies the healthy ratio
    run_step_trainer(
        step_fn=wide_step, state=wstate, features=wide_stream(3),
        registry=MetricsRegistry(), donate_state=False,
    )
    run_step_trainer(
        step_fn=wide_step, state=wstate, features=wide_stream(40),
        registry=reg, goodput=tracker, donate_state=False,
        measure_device_time=True,
    )
    healthy_ratio = tracker.report()["goodput_ratio"]
    report = watchdog.evaluate(now=100.0)
    assert not report["breached"], (
        f"healthy run (ratio {healthy_ratio:.3f}) must not breach: "
        f"{report['breached']}"
    )
    run_step_trainer(
        step_fn=wide_step, state=wstate,
        features=wide_stream(30, stall=stall_s),
        registry=reg, goodput=tracker, donate_state=False,
        measure_device_time=True,
    )
    # first post-collapse evaluation one fast window later: the healthy
    # sample has aged out, the collapsed ratio fills both windows
    report = watchdog.evaluate(now=110.0)
    assert "train_goodput" in report["breached"], (
        f"goodput collapse (ratio "
        f"{tracker.report()['goodput_ratio']:.3f}) did not breach: "
        f"{report}"
    )
    print(json.dumps({
        "metric": "train_goodput_slo_breached",
        "value": 1,
        "goodput_ratio": tracker.report()["goodput_ratio"],
        "unit": "bool",
    }))


def overlap_leg() -> None:
    """``UNIONML_TPU_BENCH_PRESET=train_overlap``: the overlapped-training
    stack (docs/performance.md "Overlapped training") measured against
    its own serial twin on the SAME workload.

    Two elastic-trainer runs over an identical paced, checkpointed,
    gradient-accumulated stream:

    - **off** — inline feed, synchronous checkpoint commits
      (``checkpoint_backend="sync"``), serial accumulation;
    - **on**  — ``double_buffer=True`` (threaded donated feed),
      ``overlap_grads=True`` (deferred-consumption scan), async
      background commits.

    Asserted, not just reported: bit-identical final state (overlap is
    scheduling, never numerics), the ``checkpoint`` + ``data_wait``
    buckets shrinking and ``host_to_device`` draining to zero,
    attribution ≥ 95% in BOTH modes, and overlap-on finishing faster —
    the paced feed gives the on-leg a structural, not statistical,
    wall-clock advantage.
    """
    import tempfile

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from flax import linen as nn

    from unionml_tpu.elastic import run_elastic_trainer
    from unionml_tpu.goodput import GoodputTracker
    from unionml_tpu.models.train import classification_step, create_train_state
    from unionml_tpu.telemetry import (
        FlightRecorder, MetricsRegistry, TraceRecorder,
    )

    class _Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(2048)(x)
            x = nn.relu(x)
            return nn.Dense(4)(x)

    net = _Net()
    rng = np.random.default_rng(0)
    n_steps, accum, micro = 40, 2, 32
    # per-batch host production cost (loader/augment), sized BELOW the
    # ~4 ms step so the threaded feed can fully hide it — overlap can
    # only drain host cost up to the compute duration
    pace_s = 0.003
    batches = [
        (
            rng.normal(size=(accum * micro, 256)).astype(np.float32),
            rng.integers(0, 4, size=(accum * micro,)).astype(np.int32),
        )
        for _ in range(n_steps)
    ]

    def stream(start_step):
        for i in range(start_step, n_steps):
            time.sleep(pace_s)  # the host-side cost the feed can overlap
            yield batches[i]

    # ONE step-function object for every run: _jitted caches per function
    # identity, so the warm-up runs below can only warm the measured legs
    # if they share this object (each mode still compiles its own
    # executable under its overlap/donate cache key)
    step_fn = classification_step(net, accumulate_steps=accum)

    def run(overlap: bool):
        reg = MetricsRegistry()
        tracker = GoodputTracker(
            registry=reg, tracer=TraceRecorder(registry=reg),
            flight=FlightRecorder(),
        )
        state = create_train_state(
            net, batches[0][0][:4], learning_rate=1e-2, seed=1
        )
        t0 = time.perf_counter()
        state, steps = run_elastic_trainer(
            step_fn=step_fn,
            state=state, stream=stream,
            checkpoint_dir=tempfile.mkdtemp(prefix="train-overlap-"),
            checkpoint_every=5, batch_size=micro, accumulate_steps=accum,
            checkpoint_backend="async" if overlap else "sync",
            overlap_grads=overlap, double_buffer=overlap,
            goodput=tracker,
        )
        wall = time.perf_counter() - t0
        assert steps == n_steps, f"expected {n_steps} steps, ran {steps}"
        return tracker.report(), state, wall

    # warm the jit cache out of the comparison (both modes: serial and
    # overlapped executables live under different cache keys)
    run(False)
    run(True)
    off, state_off, wall_off = run(False)
    on, state_on, wall_on = run(True)

    # 1. loss parity: overlap must be a scheduling change only
    for a, b in zip(
        jax.tree_util.tree_leaves(state_off.params),
        jax.tree_util.tree_leaves(state_on.params),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            "overlap-on final state diverged from the serial run"
        )

    # 2. the three attacked buckets shrink
    off_bad, on_bad = off["badput_s"], on["badput_s"]
    assert on_bad["checkpoint"] < off_bad["checkpoint"], (
        f"async commit did not shrink the checkpoint bucket: "
        f"{on_bad['checkpoint']:.4f}s vs {off_bad['checkpoint']:.4f}s"
    )
    assert off_bad["data_wait"] >= n_steps * pace_s * 0.8, (
        f"paced stream should dominate the off-leg data_wait bucket: "
        f"{off_bad['data_wait']:.4f}s"
    )
    assert on_bad["data_wait"] < off_bad["data_wait"] * 0.5, (
        f"threaded feed did not drain data_wait: "
        f"{on_bad['data_wait']:.4f}s vs {off_bad['data_wait']:.4f}s"
    )
    assert on_bad["host_to_device"] == 0.0 < off_bad["host_to_device"], (
        "threaded feed must take the device-put dispatch off the "
        f"critical path: on={on_bad['host_to_device']:.4f}s "
        f"off={off_bad['host_to_device']:.4f}s"
    )

    # 3. attribution identity holds in both modes
    for name, rep in (("off", off), ("on", on)):
        assert rep["attributed_fraction"] >= 0.95, (
            f"{name}-leg attribution {rep['attributed_fraction']:.1%} "
            "below the 95% bar"
        )

    # 4. the overlap pays for itself on wall clock (structural: the
    # paced feed + commit I/O now run behind compute)
    assert wall_on < wall_off, (
        f"overlap-on slower than off: {wall_on:.3f}s vs {wall_off:.3f}s"
    )

    samples = n_steps * accum * micro
    print(json.dumps({
        "metric": "train_overlap_samples_per_sec",
        "off": round(samples / wall_off, 1),
        "value": round(samples / wall_on, 1),
        "unit": "samples/sec",
    }))
    print(json.dumps({
        "metric": "train_overlap_badput_deltas_s",
        "value": {
            cause: round(off_bad[cause] - on_bad[cause], 4)
            for cause in ("checkpoint", "data_wait", "host_to_device")
        },
        "off_badput_s": off_bad,
        "on_badput_s": on_bad,
        "attributed_fraction": {
            "off": off["attributed_fraction"],
            "on": on["attributed_fraction"],
        },
        "loss_parity": "bit-identical",
        "unit": "seconds saved per 40-step run",
    }))


if __name__ == "__main__":
    preset = os.environ.get("UNIONML_TPU_BENCH_PRESET")
    if preset in ("train_goodput", "train_overlap"):
        if len(sys.argv) > 1:
            # hardcoded workload, same rule as the serve_latency legs
            raise SystemExit(
                f"UNIONML_TPU_BENCH_PRESET={preset} takes no CLI "
                f"flags (got {sys.argv[1:]}); its workload is hardcoded"
            )
        goodput_leg() if preset == "train_goodput" else overlap_leg()
    else:
        main()
