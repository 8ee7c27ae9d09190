"""XLA program introspection & continuous profiling.

The telemetry layer (:mod:`unionml_tpu.telemetry`) records what the
*host* saw — wall-clock latencies, queue depths — but nothing in the
stack could say what the *hardware* did: FLOPs issued, HBM bytes moved,
how many times XLA recompiled a hot program, or where device memory
went. This module closes that loop:

- :class:`ProgramTracker` — wraps the ``jit``/``pjit`` callables on the
  hot paths (engine prefill/decode/splice, batcher predict, trainer
  step) with a zero-copy shim that detects **compile events** (the
  executable cache grew during a call), records compile time and a
  recompile count, and — only on those rare events — runs
  ``jitted.lower(...).cost_analysis()`` over *abstract* arguments to
  capture per-program **flops** and **bytes accessed** (lowering alone:
  no second XLA compile, and donated/deleted buffers still carry the
  shape/dtype metadata the abstract trace needs). Steady-state calls
  pay only a cache-size read, one dict lookup, and counter increments —
  the introspection cost lives at compile time, off the serving path.
- **MFU / roofline gauges** — each tracked program keeps a bounded
  window of ``(t, cumulative flops, cumulative bytes)`` samples;
  ``unionml_program_mfu_ratio`` / ``unionml_program_hbm_ratio`` gauges
  divide the windowed achieved rate by the device peak from
  :data:`DEVICE_PEAKS` (per ``device_kind``, overridable for unknown
  chips via :data:`PEAK_FLOPS_ENV` / :data:`PEAK_HBM_ENV`).
- :func:`capture_profile` — the on-demand ``jax.profiler`` capture
  behind ``POST /debug/profile?seconds=N`` on both HTTP transports
  (building on :func:`unionml_tpu.diagnostics.trace`); one capture at a
  time (:class:`ProfileInProgress` maps to HTTP 409).
- :func:`device_memory_breakdown` — the ``GET /debug/memory`` body:
  per-device ``memory_stats()`` plus a live-buffer census from
  ``jax.live_arrays()`` grouped by dtype and top shapes (works on CPU,
  where ``memory_stats()`` is None but the buffer census is not).

Everything degrades gracefully: a non-jitted callable is tracked
opaquely (calls and wall time, no cost analysis), a backend without
profiling support captures an empty trace with a log line, and cost
analysis failures record zeros instead of failing the serving path.
A program whose cost no analysis answered for (opaque, or any program
on the TPU, where a lowering has no cost analysis) publishes no MFU /
HBM ratio series at all rather than a 0.
CPU-testable end to end (``cost_analysis`` works on CPU jit).
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

from unionml_tpu._logging import logger
from unionml_tpu import telemetry

__all__ = [
    "DEVICE_PEAKS",
    "PEAK_FLOPS_ENV",
    "PEAK_HBM_ENV",
    "ProfileInProgress",
    "ProgramTracker",
    "capture_profile",
    "device_memory_breakdown",
    "resolve_device_peaks",
]

# env overrides for chips the table doesn't know (or partial overrides
# to correct a table entry): absolute FLOP/s and HBM GB/s
PEAK_FLOPS_ENV = "UNIONML_TPU_PEAK_FLOPS"
PEAK_HBM_ENV = "UNIONML_TPU_PEAK_HBM_GBPS"

# per-chip peaks: (dense bf16 FLOP/s, HBM bytes/s), keyed on a
# lowercase substring of `device.device_kind` (longest key wins, so
# "tpu v5 lite" matches before "tpu v5"). Sources: public TPU spec
# sheets. A device that is not here (the CPU included) has no roofline:
# it resolves to source "unknown" and the ratio gauges report 0.
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "tpu v2": (45e12, 700e9),
    "tpu v3": (123e12, 900e9),
    "tpu v4": (275e12, 1228e9),
    "tpu v5 lite": (197e12, 819e9),
    "tpu v5e": (197e12, 819e9),
    "tpu v5p": (459e12, 2765e9),
    "tpu v5": (459e12, 2765e9),
    "tpu v6 lite": (918e12, 1640e9),
    "tpu v6e": (918e12, 1640e9),
}


def resolve_device_peaks(device: Any = None) -> dict:
    """``{"platform", "kind", "peak_flops", "peak_bytes_per_s",
    "source"}`` for ``device`` (default: the first local device).

    Env overrides (:data:`PEAK_FLOPS_ENV` FLOP/s, :data:`PEAK_HBM_ENV`
    GB/s) win over the table — the escape hatch for chips the table
    doesn't know; either can be set alone. ``source`` is ``env``,
    ``table``, or ``unknown`` (no match: peaks are ``None`` and the
    MFU gauges report 0 rather than a made-up ratio)."""
    platform, kind = "unknown", "unknown"
    try:
        if device is None:
            import jax

            device = jax.local_devices()[0]
        platform = str(getattr(device, "platform", "unknown"))
        kind = str(getattr(device, "device_kind", platform))
    except Exception as exc:  # no backend: peaks resolve from env only
        logger.info(f"device peak resolution: no device ({exc!r})")
    flops: Optional[float] = None
    bandwidth: Optional[float] = None
    source = "unknown"
    lowered = kind.lower()
    for key in sorted(DEVICE_PEAKS, key=len, reverse=True):
        if key in lowered or key in platform.lower():
            flops, bandwidth = DEVICE_PEAKS[key]
            source = "table"
            break
    env_flops = os.environ.get(PEAK_FLOPS_ENV)
    env_hbm = os.environ.get(PEAK_HBM_ENV)
    if env_flops or env_hbm:
        try:
            if env_flops:
                flops = float(env_flops)
            if env_hbm:
                bandwidth = float(env_hbm) * 1e9
            source = "env"
        except ValueError:
            logger.info(
                f"ignoring malformed peak override "
                f"{PEAK_FLOPS_ENV}={env_flops!r} {PEAK_HBM_ENV}={env_hbm!r}"
            )
    return {
        "platform": platform,
        "kind": kind,
        "peak_flops": flops,
        "peak_bytes_per_s": bandwidth,
        "source": source,
    }


def _abstract_args(args: tuple, kwargs: dict):
    """Shape/dtype skeletons for an AOT ``lower()`` — works even on
    donated (deleted) device buffers, whose metadata survives deletion;
    non-array leaves (static ints, None) pass through unchanged."""
    import jax

    def to_sds(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return leaf
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    return (
        jax.tree_util.tree_map(to_sds, args),
        jax.tree_util.tree_map(to_sds, kwargs),
    )


class _Program:
    """Per-key tracking state (guarded by the tracker lock)."""

    __slots__ = (
        "key", "calls", "compiles", "cum_flops", "cum_bytes",
        "cost_by_sig", "last_cost", "window", "last_t", "cost_known",
        "m_calls", "m_compiles", "m_flops", "m_bytes", "h_compile",
    )

    def __init__(self, key: str):
        self.key = key
        self.calls = 0
        self.compiles = 0
        self.cum_flops = 0.0
        self.cum_bytes = 0.0
        # signature -> (flops, bytes accessed) from cost analysis; the
        # sig is whatever the program's sig_fn returns (a bucket shape,
        # a static length) — None for single-shape programs
        self.cost_by_sig: Dict[Any, Tuple[float, float]] = {}
        self.last_cost: Tuple[float, float] = (0.0, 0.0)
        self.window: "deque[Tuple[float, float, float]]" = deque(maxlen=256)
        self.last_t = 0.0
        # a cost analysis has answered for this program: only then do
        # its MFU / HBM ratio series exist (absent, not 0, otherwise)
        self.cost_known = False


class ProgramTracker:
    """Cost-analysis registry over a component's compiled programs.

    ``wrap(key, fn, sig_fn=...)`` returns a drop-in callable. For a
    jitted ``fn`` the wrapper detects compiles via ``_cache_size()``
    growth and records the new executable's ``cost_analysis()`` (flops,
    bytes accessed) keyed by ``sig_fn``'s cheap per-call signature (a
    bucket shape — NOT a full aval tree, which would put a tree
    traversal on the hot path); steady-state calls attribute that
    signature's flops/bytes to the cumulative counters and the MFU
    window. A non-jitted ``fn`` is tracked opaquely (calls only).

    All series land in the shared telemetry registry labeled
    ``{component, program}``; :meth:`stats` is the ``stats()
    ["programs"]`` view.
    """

    WINDOW_S = 60.0

    def __init__(
        self,
        registry: Optional[telemetry.MetricsRegistry] = None,
        component: str = "program",
        window_s: float = WINDOW_S,
        on_compile: Optional[Callable[[str, float], None]] = None,
    ):
        self._registry = (
            registry if registry is not None else telemetry.get_registry()
        )
        self.component = component
        self.window_s = float(window_s)
        # compile-event push seam: called as on_compile(key, call_ms)
        # AFTER the compiling call returns — the goodput tracker
        # (unionml_tpu.goodput) subscribes to debit compile time out of
        # the compute bucket. Exceptions are swallowed: an observer bug
        # must never fail the hot path.
        self.on_compile = on_compile
        self._lock = threading.Lock()
        self._programs: Dict[str, _Program] = {}
        self._peaks: Optional[dict] = None
        R = self._registry
        labels = ("component", "program")
        self._f_calls = R.counter(
            "unionml_program_calls_total",
            "Dispatches of a tracked compiled program.", labels,
        )
        self._f_compiles = R.counter(
            "unionml_program_compiles_total",
            "XLA compile events per tracked program (a count above the "
            "expected shape set = recompiles).", labels,
        )
        self._f_flops = R.counter(
            "unionml_program_flops_total",
            "FLOPs dispatched per XLA cost analysis.", labels,
        )
        self._f_bytes = R.counter(
            "unionml_program_bytes_total",
            "HBM bytes accessed per XLA cost analysis.", labels,
        )
        self._f_compile_ms = R.histogram(
            "unionml_program_compile_ms",
            "Wall time of calls that compiled (trace + XLA compile + "
            "first run).", labels,
        )
        self._f_mfu = R.gauge(
            "unionml_program_mfu_ratio",
            "Windowed achieved FLOP/s over the device peak "
            "(model-flops utilization; 0 when idle or peak unknown).",
            labels,
        )
        self._f_hbm = R.gauge(
            "unionml_program_hbm_ratio",
            "Windowed achieved bytes/s over peak HBM bandwidth "
            "(roofline memory utilization; 0 when idle or peak "
            "unknown).", labels,
        )

    # ------------------------------------------------------------------ #

    def _get(self, key: str) -> _Program:
        with self._lock:
            prog = self._programs.get(key)
            if prog is None:
                prog = _Program(key)
                lbl = (self.component, key)
                prog.m_calls = self._f_calls.labels(*lbl)
                prog.m_compiles = self._f_compiles.labels(*lbl)
                prog.m_flops = self._f_flops.labels(*lbl)
                prog.m_bytes = self._f_bytes.labels(*lbl)
                prog.h_compile = self._f_compile_ms.labels(*lbl)
                self._programs[key] = prog
            return prog

    def _publish_ratios(self, prog: _Program) -> None:
        """Create ``prog``'s MFU / HBM ratio series, at the first cost
        analysis that answers. A program whose cost is unknown (an
        opaque callable; any program on a backend whose lowering has no
        cost analysis, the TPU among them) publishes NO ratio series: a
        0 there would read as an idle chip, not as an unknown."""
        with self._lock:
            if prog.cost_known:
                return
            prog.cost_known = True
        lbl = (self.component, prog.key)
        self._f_mfu.labels(*lbl).set_function(
            lambda p=prog: self._utilization(p)[0]
        )
        self._f_hbm.labels(*lbl).set_function(
            lambda p=prog: self._utilization(p)[1]
        )

    def wrap(
        self,
        key: str,
        fn: Callable,
        sig_fn: Optional[Callable[..., Any]] = None,
    ) -> Callable:
        """Instrument ``fn`` under ``key``. ``sig_fn(*args, **kwargs)``
        must be CHEAP (one shape attribute, a static kwarg) and only
        distinct enough to separate the executables this one callable
        compiles (e.g. the token-bucket shape for prefill); ``None``
        declares a single-executable program."""
        prog = self._get(key)
        jitted = hasattr(fn, "_cache_size") and hasattr(fn, "lower")

        def wrapper(*args, **kwargs):
            before = fn._cache_size() if jitted else -1
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dt_ms = (time.perf_counter() - t0) * 1e3
            sig = None
            if sig_fn is not None:
                try:
                    sig = sig_fn(*args, **kwargs)
                except Exception:
                    sig = None
            if jitted and fn._cache_size() > before:
                self._on_compile(prog, fn, args, kwargs, sig, dt_ms)
            else:
                self._on_call(prog, sig)
            return out

        wrapper.__wrapped__ = fn
        wrapper.program_key = key
        return wrapper

    def _on_compile(
        self, prog: _Program, fn, args, kwargs, sig, dt_ms: float
    ) -> None:
        """Compile event (rare, off the steady-state path): record the
        compile and run the abstract-args cost analysis for the new
        signature. Lowering re-traces but never re-compiles, and the
        abstract skeleton sidesteps donated buffers.

        On the TPU the lowering's cost analysis is ``None`` (only a
        compiled executable has one there), and the executable this
        call just compiled lives in jit's own cache with no handle to
        it: asking again (``lower().compile()``) is a second compile or
        a cache load at serve time, which this tracker never pays. The
        program then counts calls and compiles only, and publishes no
        MFU / HBM ratio series."""
        cost = (0.0, 0.0)
        try:
            a_args, a_kwargs = _abstract_args(args, kwargs)
            analysis = fn.lower(*a_args, **a_kwargs).cost_analysis()
            if isinstance(analysis, (list, tuple)):
                analysis = analysis[0] if analysis else None
            if analysis is None:
                logger.info(
                    f"no cost analysis for {prog.key} on this backend: "
                    "its MFU / HBM ratio series are not published"
                )
            else:
                cost = (
                    float(analysis.get("flops", 0.0) or 0.0),
                    float(analysis.get("bytes accessed", 0.0) or 0.0),
                )
                self._publish_ratios(prog)
        except Exception as exc:
            logger.info(f"cost analysis unavailable for {prog.key}: {exc!r}")
        with self._lock:
            prog.compiles += 1
            prog.cost_by_sig[sig] = cost
            prog.last_cost = cost
        prog.m_compiles.inc()
        prog.h_compile.observe(dt_ms)
        if self.on_compile is not None:
            try:
                self.on_compile(prog.key, dt_ms)
            except Exception:
                pass
        self._account(prog, cost)

    def cost(self, key: str, sig: Any = None) -> Tuple[float, float]:
        """Last-known ``(flops, bytes)`` of one dispatch of program
        ``key`` at signature ``sig`` (falling back to the program's
        last compiled cost; ``(0, 0)`` for untracked programs) — the
        per-dispatch numerator the usage ledger splits across tenants
        (docs/observability.md "Usage metering & cost attribution")."""
        with self._lock:
            prog = self._programs.get(key)
            if prog is None:
                return (0.0, 0.0)
            return prog.cost_by_sig.get(sig, prog.last_cost)

    def _on_call(self, prog: _Program, sig) -> None:
        with self._lock:
            cost = prog.cost_by_sig.get(sig, prog.last_cost)
        self._account(prog, cost)

    def _account(self, prog: _Program, cost: Tuple[float, float]) -> None:
        now = time.monotonic()
        flops, nbytes = cost
        with self._lock:
            prog.calls += 1
            prog.cum_flops += flops
            prog.cum_bytes += nbytes
            prog.window.append((now, prog.cum_flops, prog.cum_bytes))
            while (
                len(prog.window) > 2
                and now - prog.window[0][0] > self.window_s
            ):
                prog.window.popleft()
            prog.last_t = now
        prog.m_calls.inc()
        if flops:
            prog.m_flops.inc(flops)
        if nbytes:
            prog.m_bytes.inc(nbytes)

    # ------------------------------------------------------------------ #

    def peaks(self) -> dict:
        """Device peaks, resolved once per tracker (jax is loaded by the
        time any tracked program has compiled)."""
        with self._lock:
            if self._peaks is None:
                self._peaks = resolve_device_peaks()
            return self._peaks

    def _rates(self, prog: _Program) -> Tuple[float, float]:
        """Windowed achieved (FLOP/s, bytes/s); 0 when idle (no
        dispatch within the window) or under 2 samples."""
        now = time.monotonic()
        with self._lock:
            if len(prog.window) < 2 or now - prog.last_t > self.window_s:
                return 0.0, 0.0
            t0, f0, b0 = prog.window[0]
            t1, f1, b1 = prog.window[-1]
        dt = t1 - t0
        if dt <= 0:
            return 0.0, 0.0
        return (f1 - f0) / dt, (b1 - b0) / dt

    def _utilization(self, prog: _Program) -> Tuple[float, float]:
        """(MFU, HBM-roofline) ratios for the gauges; 0 when the peak
        is unknown rather than a fabricated ratio."""
        flops_s, bytes_s = self._rates(prog)
        peaks = self.peaks()
        mfu = (
            flops_s / peaks["peak_flops"] if peaks["peak_flops"] else 0.0
        )
        hbm = (
            bytes_s / peaks["peak_bytes_per_s"]
            if peaks["peak_bytes_per_s"] else 0.0
        )
        return mfu, hbm

    def stats(self) -> dict:
        """The ``stats()["programs"]`` view: per program — calls,
        compiles, compile-time summary, flops/bytes per call and total,
        windowed achieved rates, and the MFU/roofline ratios — plus a
        ``device`` entry naming the peaks they are measured against."""
        peaks = self.peaks()
        out: dict = {"device": dict(peaks)}
        with self._lock:
            programs = list(self._programs.values())
        for prog in programs:
            mfu, hbm = self._utilization(prog)
            flops_s, bytes_s = self._rates(prog)
            with self._lock:
                entry = {
                    "calls": prog.calls,
                    "compiles": prog.compiles,
                    "cost_known": prog.cost_known,
                    "flops_per_call": prog.last_cost[0],
                    "bytes_per_call": prog.last_cost[1],
                    "flops_total": prog.cum_flops,
                    "bytes_total": prog.cum_bytes,
                }
            summary = prog.h_compile.summary()
            if summary:
                entry["compile_ms"] = summary
            entry["achieved_flops_per_s"] = round(flops_s, 1)
            entry["achieved_bytes_per_s"] = round(bytes_s, 1)
            entry["mfu"] = round(mfu, 6)
            entry["hbm_utilization"] = round(hbm, 6)
            out[prog.key] = entry
        return out

    def reset(self) -> None:
        """Zero cumulative counters and windows (benchmarks call this
        between phases); compiled-cost signatures are kept — they
        describe executables that still exist."""
        with self._lock:
            programs = list(self._programs.values())
        for prog in programs:
            with self._lock:
                prog.calls = 0
                prog.compiles = 0
                prog.cum_flops = 0.0
                prog.cum_bytes = 0.0
                prog.window.clear()
                prog.last_t = 0.0
            for m in (prog.m_calls, prog.m_compiles, prog.m_flops,
                      prog.m_bytes, prog.h_compile):
                m.reset()


# --------------------------------------------------------------------- #
# on-demand profiler capture (POST /debug/profile)
# --------------------------------------------------------------------- #


class ProfileInProgress(RuntimeError):
    """A capture is already running (the transports answer 409): the
    profiler is a process-global singleton and nested traces corrupt
    the artifact."""


_capture_lock = threading.Lock()

MAX_CAPTURE_SECONDS = 120.0


def capture_profile(
    seconds: float = 2.0, log_dir: Optional[str] = None
) -> dict:
    """Capture a ``jax.profiler`` trace for ``seconds`` (clamped to
    :data:`MAX_CAPTURE_SECONDS`) and return the artifact directory.

    Blocks the calling thread for the capture window (the transports
    serve it from a request thread, so in-flight traffic keeps running
    — that traffic is exactly what the trace is for). Builds on
    :func:`unionml_tpu.diagnostics.trace`: a profiler that will not
    start raises. One capture at a time: raises
    :class:`ProfileInProgress` when another is running."""
    seconds = float(seconds)
    if not seconds > 0:
        raise ValueError(f"seconds must be positive, got {seconds}")
    seconds = min(seconds, MAX_CAPTURE_SECONDS)
    if not _capture_lock.acquire(blocking=False):
        raise ProfileInProgress(
            "a profiler capture is already in progress; retry when it "
            "finishes"
        )
    try:
        from unionml_tpu.diagnostics import trace

        if log_dir is None:
            log_dir = tempfile.mkdtemp(prefix="unionml-tpu-profile-")
        t0 = time.perf_counter()
        with trace(log_dir):
            time.sleep(seconds)
        captured_s = time.perf_counter() - t0
        files = []
        for root, _, names in os.walk(log_dir):
            for name in names:
                files.append(
                    os.path.relpath(os.path.join(root, name), log_dir)
                )
        return {
            "trace_dir": log_dir,
            "seconds": round(captured_s, 3),
            "file_count": len(files),
            "files": sorted(files)[:50],
        }
    finally:
        _capture_lock.release()


# --------------------------------------------------------------------- #
# device-memory breakdown (GET /debug/memory)
# --------------------------------------------------------------------- #


def device_memory_breakdown(top: int = 10) -> dict:
    """Per-device memory truth: ``device.memory_stats()`` (TPU/GPU; CPU
    backends report none) plus a live-buffer census from
    ``jax.live_arrays()`` — total bytes, per-dtype totals, and the
    ``top`` largest (shape, dtype) groups, which is where a leaked KV
    cache or a forgotten checkpoint tree shows up by name. Also reports
    the size of the pprof ``device_memory_profile`` artifact (the
    heavyweight offline view) without shipping its bytes."""
    import jax

    devices = []
    for device in jax.local_devices():
        stats = None
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        devices.append({
            "id": int(device.id),
            "platform": str(device.platform),
            "kind": str(getattr(device, "device_kind", device.platform)),
            "memory_stats": {
                str(k): int(v) for k, v in (stats or {}).items()
                if isinstance(v, (int, float))
            },
        })
    groups: Dict[Tuple[str, Tuple[int, ...]], Dict[str, int]] = {}
    by_dtype: Dict[str, int] = {}
    total_bytes = 0
    count = 0
    for arr in jax.live_arrays():
        try:
            nbytes = int(arr.nbytes)
            dtype = str(arr.dtype)
            shape = tuple(int(s) for s in arr.shape)
        except Exception:
            continue  # deleted/exotic arrays: skip, never fail the scrape
        count += 1
        total_bytes += nbytes
        by_dtype[dtype] = by_dtype.get(dtype, 0) + nbytes
        group = groups.setdefault(
            (dtype, shape), {"count": 0, "bytes": 0}
        )
        group["count"] += 1
        group["bytes"] += nbytes
    top_groups = [
        {
            "dtype": dtype,
            "shape": list(shape),
            "count": info["count"],
            "bytes": info["bytes"],
        }
        for (dtype, shape), info in sorted(
            groups.items(), key=lambda kv: kv[1]["bytes"], reverse=True
        )[: max(0, int(top))]
    ]
    profile_bytes = None
    try:
        profile_bytes = len(jax.profiler.device_memory_profile())
    except Exception:
        pass
    return {
        "devices": devices,
        "live_arrays": {
            "count": count,
            "bytes": total_bytes,
            "by_dtype": by_dtype,
            "top": top_groups,
        },
        "device_memory_profile_bytes": profile_bytes,
    }
