"""Device execution engine: jit/pjit compilation of step functions.

This is the TPU-native execution substrate the reference delegates to
flytekit's local executor (reference: unionml/model.py:425-440 runs the
user trainer opaquely). Here, a registered ``train_step`` is compiled once
with ``jax.jit`` — optionally over a ``jax.sharding.Mesh`` with
NamedSharding in/out specs — and driven by a host batching loop that:

- keeps shapes **static** (remainder batches are dropped) so XLA compiles
  exactly one executable,
- **donates** the state buffers so parameter memory is reused in-place,
- streams batches through the double-buffered device feed
  (:mod:`unionml_tpu.data.pipeline`) to overlap host→HBM transfer with
  compute.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Iterable, Optional

import numpy as np

from unionml_tpu import telemetry
from unionml_tpu._logging import logger


def publish_hbm_gauges(registry: Optional[Any] = None) -> int:
    """Publish each local device's ``memory_stats()['bytes_in_use']`` as
    the ``unionml_trainer_hbm_bytes_in_use{device=...}`` gauge; returns
    the number of devices that reported. Safe everywhere: backends
    without memory stats (CPU, some plugins) simply publish nothing.
    """
    import jax

    reg = registry if registry is not None else telemetry.get_registry()
    gauge = reg.gauge(
        "unionml_trainer_hbm_bytes_in_use",
        "Device memory in use per jax.Device.memory_stats().",
        ("device",),
    )
    published = 0
    for device in jax.local_devices():
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            gauge.labels(device=str(device.id)).set(float(stats["bytes_in_use"]))
            published += 1
    return published


def _publish_loss(metrics: Any, gauge: Any) -> None:
    """Set ``gauge`` from the first scalar metric leaf whose path names
    'loss' (readback — call only at a window boundary that already
    syncs)."""
    import jax

    try:
        flat, _ = jax.tree_util.tree_flatten_with_path(metrics)
        for path, leaf in flat:
            name = jax.tree_util.keystr(path).lower()
            if "loss" in name and np.ndim(leaf) == 0:
                gauge.set(float(np.asarray(leaf)))
                return
    except Exception:  # metrics trees are user-shaped: never fail a step
        pass


@functools.lru_cache(maxsize=128)
def _jitted(
    fn: Callable,
    donate_state: bool,
    donate_batch: bool = False,
    overlap: Any = None,
):
    """Per-function jit cache (bounded: entries pin user closures + XLA
    executables, which can be large for big models). Interactive sessions
    that re-define step functions churn entries that pin executables until
    eviction — call :func:`clear_jit_cache` to drop them eagerly.

    ``donate_batch`` donates the batch argument too (the double-buffer
    prefetch contract: every fed batch is a fresh device buffer consumed
    exactly once, so XLA may recycle it for step temporaries — HBM holds
    the in-flight batches, not the consumed ones). ``overlap`` (a
    :class:`~unionml_tpu.models.train.GradOverlap` or None) is part of
    the cache key ONLY: the overlap strategy is read at trace time from
    the ambient :func:`~unionml_tpu.models.train.grad_overlap_scope`,
    and keying on it keeps serial and overlapped executables from
    aliasing when the same step function is trained both ways."""
    import jax

    donate = (0,) if donate_state else ()
    if donate_batch:
        donate = donate + (1,)
    return jax.jit(fn, donate_argnums=donate)


def clear_jit_cache() -> None:
    """Drop every cached jit wrapper (and the XLA executables + user
    closures it pins). Useful in long-lived interactive sessions after
    re-defining step functions or models."""
    _jitted.cache_clear()


def jit_predictor(fn: Callable) -> Callable:
    """jit-compile a predictor body ``(model_object, features) -> preds``.

    Shares the bounded per-function cache; XLA's own cache handles
    shape/dtype polymorphism across calls.
    """
    return _jitted(fn, False)


def resolve_grad_overlap(sharding: Any, accumulate_steps: int) -> Any:
    """The :class:`~unionml_tpu.models.train.GradOverlap` strategy for a
    trainer run with ``overlap_grads=True`` — ONE selection rule shared
    by :func:`run_step_trainer` and the elastic trainer.

    - ``accumulate_steps == 1``: None (no microbatch pipeline exists to
      overlap; the step is one fused forward/backward).
    - pure data parallelism (every mesh axis but ``data`` trivial, no
      partition rules): ``mode="shard_map"`` — the scan runs under
      ``shard_map`` and issues explicit deferred
      :func:`~unionml_tpu.parallel.collectives.bucketed_psum` chunks.
    - anything else (fsdp/tensor/… sharded params, or no mesh at all):
      ``mode="defer"`` — GSPMD keeps inserting the collectives and the
      scan defers their consumption one microbatch, the structure
      XLA's collective pipeliner hides latency in.
    """
    from unionml_tpu.models.train import GradOverlap

    if accumulate_steps <= 1:
        logger.info(
            "overlap_grads: accumulate_steps=1 has no microbatch "
            "pipeline to overlap — running the serial step"
        )
        return None
    if sharding is None:
        return GradOverlap(mode="defer")
    mesh = sharding.mesh()
    model_axes = {
        name: size for name, size in dict(mesh.shape).items()
        if name != "data" and size > 1
    }
    if not model_axes and not tuple(sharding.rules) and mesh.shape.get("data", 1) > 1:
        return GradOverlap(mode="shard_map", mesh=mesh, axes=("data",))
    return GradOverlap(mode="defer")


def _num_examples(features: Any) -> int:
    import jax

    leaves = jax.tree_util.tree_leaves(features)
    if not leaves:
        raise ValueError("train_step features pytree has no array leaves")
    return int(leaves[0].shape[0])


def _slice_batch(data: Any, idx: np.ndarray) -> Any:
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x)[idx], data)


def to_microbatches(batch: Any, accumulate_steps: int, batch_size: int) -> Any:
    """Reshape a fed batch's leaves to ``[accumulate_steps, batch_size, ...]``.

    The gradient-accumulation feeding contract shared by
    :func:`run_step_trainer` and the elastic trainer: raises a clear
    error when the leading dim isn't ``accumulate_steps * batch_size``
    (e.g. a stream still yielding un-accumulated batches), and
    materializes list-like leaves once.
    """
    import jax

    feed_rows = accumulate_steps * batch_size

    def reshape(x):
        if not hasattr(x, "reshape"):
            # list-like leaf: materialize once; device-resident arrays
            # reshape in place (np.asarray here would round-trip them
            # device->host->device every step)
            x = np.asarray(x)
        if x.shape[0] != feed_rows:
            raise ValueError(
                f"accumulation batch has leading dim {x.shape[0]}, "
                f"expected accumulate_steps * batch_size = {feed_rows}"
            )
        return x.reshape((accumulate_steps, batch_size) + x.shape[1:])

    return jax.tree_util.tree_map(reshape, batch)


def is_stream(features: Any) -> bool:
    """The trainer-feed streaming rule, ONE home (run_step_trainer and
    the checkpoint_dir elastic route must agree or a stream silently
    np.asarray's into garbage): streams are callables (fresh iterable
    per epoch), iterators (one pass), or re-iterable loader objects
    (DataLoader-likes). Pytree containers and arrays are NOT streams —
    they carry the (features[, targets]) array contract."""
    return callable(features) or (
        hasattr(features, "__iter__")
        and not isinstance(features, (dict, list, tuple, str, bytes))
        and not hasattr(features, "__array__")
        and not hasattr(features, "shape")
    )


def batch_indices(
    n: int, batch_size: int, *, shuffle: bool, seed: int, drop_remainder: bool = True
) -> Iterable[np.ndarray]:
    """Static-shape batch index generator. Remainder batches are dropped so
    the jitted step sees one shape (no XLA recompiles)."""
    order = np.arange(n)
    if shuffle:
        np.random.default_rng(seed).shuffle(order)
    n_batches = n // batch_size if drop_remainder else -(-n // batch_size)
    if n_batches == 0 and n > 0:
        # fewer examples than batch_size: single undersized batch
        yield order
        return
    for i in range(n_batches):
        yield order[i * batch_size : (i + 1) * batch_size]


_FEED_END = object()  # the feed's exhaustion, told apart from any batch


def run_step_trainer(
    *,
    step_fn: Callable,
    state: Any,
    features: Any,
    targets: Any = None,
    num_epochs: int = 1,
    batch_size: int = 32,
    seed: int = 0,
    sharding: Any = None,
    donate_state: bool = True,
    accumulate_steps: int = 1,
    overlap_grads: bool = False,
    double_buffer: bool = False,
    donate_batch: Optional[bool] = None,
    profile_dir: Optional[str] = None,
    registry: Optional[Any] = None,
    goodput: Any = None,
    measure_device_time: bool = False,
    skew_every: int = 50,
) -> Any:
    """Synthesized trainer loop around a jittable per-batch step.

    ``step_fn(state, batch) -> (state, metrics)`` where ``batch`` is
    ``(features, targets)`` sliced along the leading axis (or just
    ``features`` when no targets exist, e.g. self-supervised LM batches).

    ``accumulate_steps=N`` (gradient accumulation): each fed batch holds
    ``N * batch_size`` examples reshaped to a leading microbatch axis
    ``[N, batch_size, ...]``, and the step must scan it with ONE
    optimizer update (the zoo factories' ``accumulate_steps`` builds
    such steps — :func:`unionml_tpu.models.train.accumulated_value_and_grad`).
    Under a ``sharding`` config the microbatch axis stays unsharded
    (each device scans its own microbatch shards); streams must yield
    batches of ``N * batch_size`` rows.

    With a ``sharding`` config (:class:`unionml_tpu.parallel.ShardingConfig`)
    the step is compiled under its mesh: state placed per the config's param
    spec, batches sharded along the data axis, XLA inserting the gradient
    ``psum`` over ICI automatically.

    **Streaming**: ``features`` may instead be an iterator/generator of
    ready batches (one pass; ``num_epochs`` must be 1) or a zero-arg
    callable returning one iterable per epoch (SURVEY.md §7.4 "reader →
    host prefetch, made streaming"). Each yielded item is fed to the step
    as-is (build ``(x, y)`` tuples in the stream); batch shapes must be
    constant or XLA recompiles per shape. ``targets`` must be None.

    **Telemetry**: the loop publishes into the shared
    :mod:`unionml_tpu.telemetry` registry (``registry=`` overrides):
    ``unionml_trainer_step_ms`` (per-step host dispatch wall time;
    window boundaries force a readback so windowed numbers stay honest),
    ``unionml_trainer_loss`` (last scalar 'loss' metric at a window
    boundary), ``unionml_trainer_samples_per_sec`` (windowed StepTimer
    rate), steps/examples counters, and per-device
    ``unionml_trainer_hbm_bytes_in_use`` gauges from
    ``jax.Device.memory_stats()`` — the same registry the serving
    layers scrape through ``GET /metrics``.

    ``measure_device_time=True`` adds a ``block_until_ready`` sync
    point after EVERY step dispatch so ``unionml_trainer_step_ms``
    samples real device step latency instead of host dispatch time
    (async dispatch makes the default per-step sample an enqueue
    measurement; only window boundaries force a readback). Opt-in: the
    sync defeats dispatch pipelining, so expect a small throughput
    cost — it exists for latency attribution, not production runs.

    **Goodput accounting** (docs/observability.md "Training
    goodput"): ``goodput=True`` (or a
    :class:`~unionml_tpu.goodput.GoodputTracker` instance) attributes
    the loop's wall time into compute vs. badput buckets — data-wait
    and host→device dispatch in the prefetch feed, compile/recompile
    (via the program tracker's compile events), jitted compute — and
    publishes ``unionml_train_goodput_ratio`` /
    ``unionml_train_badput_seconds_total{cause}``, per-phase trace
    spans, the step-time regression detector, and (every
    ``skew_every`` steps under ``jax.process_count() > 1``) per-host
    step-skew gauges with straggler flight events.

    **Overlapped training** (docs/performance.md "Overlapped
    training"): ``overlap_grads=True`` restructures the gradient
    accumulation so the dp/fsdp all-reduce of microbatch *i* overlaps
    the backward of microbatch *i+1* (:func:`resolve_grad_overlap`
    picks the shard_map bucketed-psum or GSPMD deferred-consumption
    form; loss trajectories stay bit-identical to the serial scan —
    no-op at ``accumulate_steps=1`` or for steps not built on
    :func:`~unionml_tpu.models.train.accumulated_value_and_grad`).
    ``double_buffer=True`` moves the whole data feed (host batch pull
    + device transfer dispatch) to a background thread, draining the
    ``data_wait``/``host_to_device`` badput buckets, and — unless
    ``donate_batch=False`` — donates the fed batch buffers to the step
    so prefetch depth does not double batch HBM. Donation is only
    unsafe for sources that YIELD already-device-resident arrays they
    retain (the feed would hand the same buffer to the step twice);
    host-side sources (numpy arrays, loaders, generators) are always
    safe. In overlap mode the trailing ``block_until_ready`` drain
    still lands in the ``compute`` bucket — overlapped transfers are
    never misattributed to ``data_wait``.
    """
    import jax

    streaming = is_stream(features)
    if streaming:
        if targets is not None:
            raise ValueError(
                "streaming trainers take batches from `features` alone — "
                "yield (x, y) tuples from the stream instead of passing targets"
            )
        if hasattr(features, "__next__") and num_epochs != 1:
            raise ValueError(
                "a one-shot batch iterator cannot be replayed for "
                f"num_epochs={num_epochs}; pass a callable returning a fresh "
                "iterable per epoch"
            )
    n = 0 if streaming else _num_examples(features)
    has_targets = targets is not None

    if accumulate_steps < 1:
        raise ValueError(f"accumulate_steps must be >= 1, got {accumulate_steps}")
    feed_rows = batch_size * accumulate_steps
    overlap = (
        resolve_grad_overlap(sharding, accumulate_steps)
        if overlap_grads else None
    )
    if donate_batch is None:
        donate_batch = double_buffer
    if accumulate_steps > 1:
        if not streaming and n < feed_rows:
            raise ValueError(
                "gradient accumulation needs at least accumulate_steps * "
                f"batch_size = {feed_rows} examples per step, got {n}"
            )
        if sharding is not None:
            sharding = sharding.microbatched()

        def _to_microbatches(batch: Any) -> Any:
            return to_microbatches(batch, accumulate_steps, batch_size)

    if sharding is not None:
        from unionml_tpu.parallel import compile_step

        step, state = compile_step(
            step_fn, state, sharding=sharding,
            donate_state=donate_state, donate_batch=donate_batch,
        )
    else:
        step = _jitted(step_fn, donate_state, donate_batch, overlap)

    from unionml_tpu.data.pipeline import prefetch_to_device

    def _is_plain_array(x: Any) -> bool:
        return not isinstance(x, (dict, list, tuple)) and hasattr(x, "__array__")

    def host_batches():
        if streaming:
            for epoch in range(num_epochs):
                stream = features() if callable(features) else iter(features)
                got = 0
                for item in stream:
                    got += 1
                    yield _to_microbatches(item) if accumulate_steps > 1 else item
                if got == 0:
                    # silent zero-batch epochs under-train with no signal:
                    # an already-exhausted iterator, or a callable returning
                    # the SAME exhausted iterator each epoch
                    raise ValueError(
                        "streaming source yielded no batches in epoch "
                        f"{epoch + 1}/{num_epochs}. A callable must return a "
                        "FRESH iterable per call (a lambda closing over one "
                        "generator replays an exhausted stream); an iterator "
                        "must not be consumed before training"
                    )
            return
        # fast path: plain (features[, targets]) arrays go through the
        # native threaded batch loader. copy=True: device_put only
        # ENQUEUES the host→HBM transfer (PJRT may read the host buffer
        # after returning), so zero-copy staging buffers must not be
        # recycled under an in-flight DMA
        if (
            _is_plain_array(features)
            and (not has_targets or _is_plain_array(targets))
            and n >= feed_rows
        ):
            from unionml_tpu.data.native import BatchLoader

            arrays = [np.asarray(features)]
            if has_targets:
                arrays.append(np.asarray(targets))
            loader = BatchLoader(
                arrays, batch_size=feed_rows, seed=seed, shuffle=True,
                drop_remainder=True, copy=True,
            )
            try:
                for epoch in range(num_epochs):
                    for batch in loader.epoch(epoch):
                        out = batch if has_targets else batch[0]
                        yield _to_microbatches(out) if accumulate_steps > 1 else out
            finally:
                loader.close()
            return
        for epoch in range(num_epochs):
            for idx in batch_indices(n, feed_rows, shuffle=True, seed=seed + epoch):
                xb = _slice_batch(features, idx)
                out = (xb, _slice_batch(targets, idx)) if has_targets else xb
                yield _to_microbatches(out) if accumulate_steps > 1 else out

    from unionml_tpu.diagnostics import StepTimer, trace

    reg = registry if registry is not None else telemetry.get_registry()
    h_step = reg.histogram(
        "unionml_trainer_step_ms",
        "Per-step wall time. Default: host dispatch (async enqueue; "
        "window boundaries force a data-dependent readback so windowed "
        "rates measure compute). With measure_device_time= every step "
        "syncs, so samples are real device step latency.",
    )
    g_loss = reg.gauge(
        "unionml_trainer_loss",
        "Last scalar 'loss' metric read back at a window boundary.",
    )
    g_rate = reg.gauge(
        "unionml_trainer_samples_per_sec",
        "Windowed training throughput (latest StepTimer window).",
    )
    c_steps = reg.counter(
        "unionml_trainer_steps_total", "Train steps dispatched.",
    )
    c_examples = reg.counter(
        "unionml_trainer_examples_total", "Training examples consumed.",
    )

    from unionml_tpu.goodput import (
        GoodputTracker, allgather_step_times, phase_scope,
    )

    tracker = None
    if goodput:
        tracker = (
            goodput if isinstance(goodput, GoodputTracker)
            else GoodputTracker(registry=reg)
        )

    # program introspection (docs/observability.md): compile events on
    # the step record XLA cost-analysis flops/bytes + compile time, and
    # the unionml_program_mfu_ratio{component="trainer",
    # program="trainer.step"} gauge reports live MFU against the device
    # peak — the same scrape surface as the serving layers
    from unionml_tpu.introspection import ProgramTracker

    step = ProgramTracker(
        registry=reg, component="trainer",
        on_compile=tracker.note_compile_ms if tracker is not None else None,
    ).wrap("trainer.step", step)

    # the overlap scope must be open while the loop runs: jit traces the
    # step at its FIRST call, and accumulated_value_and_grad reads the
    # ambient GradOverlap at trace time. Imported BEFORE tracker.start():
    # a cold models.train import is tens of ms of setup the goodput
    # identity should not have to explain
    from unionml_tpu.models.train import grad_overlap_scope

    timer = StepTimer()
    steps = 0
    metrics = None
    if tracker is not None:
        tracker.start()
    ctx = trace(profile_dir) if profile_dir else contextlib.nullcontext()
    overlap_ctx = (
        grad_overlap_scope(overlap) if overlap is not None
        else contextlib.nullcontext()
    )
    # finish() must run on the exception path too (mirrors elastic.py):
    # a raising stream would otherwise leave the trainer trace timeline
    # open forever, and a retry with the same tracker would count the
    # crash-to-retry gap as unattributed wall time
    feed = prefetch_to_device(
        host_batches(), sharding=sharding, goodput=tracker,
        double_buffer=double_buffer,
    )
    try:
        # host spans on the profiler's clock (docs/observability.md):
        # train.feed_wait around the feed's next(), train.step (a
        # StepTraceAnnotation carrying the step number) around the
        # step's dispatch; both only exist while a profiler session is
        # open and cost a check otherwise
        span = telemetry.get_tracer().span
        with ctx, overlap_ctx, contextlib.closing(feed):
            batches = iter(feed)
            while True:
                with span(None, "train.feed_wait", step_num=steps):
                    batch = next(batches, _FEED_END)
                if batch is _FEED_END:
                    break
                t_step = time.perf_counter()
                with phase_scope(tracker, "compute"):
                    with span(None, "train.step", step=steps):
                        state, metrics = step(state, batch)
                    window_closed = timer.closes_window()
                    if measure_device_time:
                        # opt-in sync point: the step_ms sample below then
                        # measures real device latency, not host dispatch
                        jax.block_until_ready((state, metrics))
                    elif window_closed:
                        # force a readback data-dependent on this step so the
                        # window measures compute, not async dispatch (step()
                        # only enqueues work)
                        leaves = jax.tree_util.tree_leaves(metrics)
                        if leaves:
                            np.asarray(leaves[0])
                # the sync above is part of step time; the publishes below
                # are host-side bookkeeping and must not inflate the sample
                step_s = time.perf_counter() - t_step
                h_step.observe(step_s * 1e3)
                if tracker is not None:
                    # under async dispatch the window-boundary readback
                    # drains a whole window of device work into this one
                    # sample — not comparable to the dispatch-scale
                    # baseline, so keep it out of the regression detector
                    # (with measure_device_time every step syncs and all
                    # samples are comparable)
                    tracker.step_complete(
                        step_s,
                        detect=measure_device_time or not window_closed,
                    )
                    if skew_every > 0 and (steps + 1) % skew_every == 0:
                        # multihost sync point only (process_count > 1):
                        # single-host runs never pay a collective here
                        times = allgather_step_times(step_s)
                        if times is not None:
                            tracker.record_step_skew(steps + 1, times)
                if window_closed:
                    # the window already synced: piggyback the loss/HBM
                    # publishes on it instead of adding readbacks per step
                    _publish_loss(metrics, g_loss)
                    publish_hbm_gauges(reg)
                # actual leading dim (streamed batches may differ from batch_size);
                # with accumulation the example count spans the two leading axes
                rows = next(
                    (
                        leaf.shape[0] * leaf.shape[1]
                        if accumulate_steps > 1 and getattr(leaf, "ndim", 0) >= 2
                        else leaf.shape[0]
                        for leaf in jax.tree_util.tree_leaves(batch)
                        if getattr(leaf, "ndim", 0) >= 1
                    ),
                    batch_size,
                )
                timer.tick(rows)
                c_steps.inc()
                c_examples.inc(rows)
                if timer.rates:
                    g_rate.set(timer.rates[-1])
                steps += 1
        if steps:
            # the trailing drain is device compute still in flight
            with phase_scope(tracker, "compute"):
                jax.block_until_ready(state)
            last = jax.tree_util.tree_map(lambda x: np.asarray(x).item() if np.ndim(x) == 0 else x, metrics)
            _publish_loss(metrics, g_loss)
            publish_hbm_gauges(reg)
            rate = timer.summary().get("samples_per_sec_median")
            if rate:
                g_rate.set(rate)
            suffix = f", ~{rate:.0f} samples/sec" if rate else ""
            logger.info(f"step trainer: {steps} steps, final metrics: {last}{suffix}")
    finally:
        if tracker is not None:
            tracker.finish()
    return state
