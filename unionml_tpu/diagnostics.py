"""Profiling, tracing, and numerical-debug toggles.

The reference has NO tracing/profiling subsystem (SURVEY.md §5.1: the
closest thing is console-URL surfacing, reference: unionml/model.py:785-789)
and no sanitizers (§5.2 — concurrency is owned by Flyte). On TPU those
gaps matter: regressions hide inside one fused XLA program, and a NaN born
in step 40k of a bf16 run surfaces as a silent accuracy cliff. This module
supplies the rebuild obligations:

- :class:`StepTimer` — per-step wall timing (dispatch is asynchronous, so
  a window ends with a host readback that is data-dependent on the
  step), windowed samples/sec.
- :func:`trace` — ``jax.profiler`` trace context for TensorBoard; a
  profiler that will not start is an error.
- :func:`nan_guard` / :func:`assert_finite` — jit-wide debug-NaN toggle
  and a pytree finiteness check that names the offending leaf path.
- :func:`describe_sharding` / :func:`assert_sharding` — inspect and assert
  the realized shardings of a pytree against expected PartitionSpecs
  (catches silent GSPMD re-layout and donation mismatches).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, Optional

import numpy as np

from unionml_tpu._logging import logger
from unionml_tpu.telemetry import percentile_summary


class StepTimer:
    """Windowed samples/sec meter for a training loop.

    ``tick(batch_examples)`` once per step; every ``window`` steps the
    meter records a sample. ``summary()`` reports the median rate (robust
    to host jitter). The caller is responsible for making timing honest
    — i.e. perform a host readback of a value data-dependent on the last
    step before reading ``summary()``.
    """

    def __init__(self, window: int = 50):
        self.window = window
        self._t0: Optional[float] = None
        self._steps = 0
        self._examples = 0
        self.rates: list = []
        self.total_steps = 0
        self.total_examples = 0

    def closes_window(self) -> bool:
        """True when the NEXT tick ends a window — the caller should do a
        host readback of the current step's output before that tick so
        the window measures compute, not async dispatch."""
        return self._steps + 1 >= self.window

    def tick(self, batch_examples: int) -> None:
        now = time.perf_counter()
        self.total_steps += 1
        self.total_examples += batch_examples
        if self._t0 is None:
            # the first tick only anchors the clock: counting its examples
            # without its duration would overstate the first window by
            # window/(window-1)
            self._t0 = now
            return
        self._steps += 1
        self._examples += batch_examples
        if self._steps >= self.window:
            dt = now - self._t0
            if dt > 0:
                self.rates.append(self._examples / dt)
            self._t0 = now
            self._steps = 0
            self._examples = 0

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "steps": float(self.total_steps),
            "examples": float(self.total_examples),
        }
        if self.rates:
            # the shared nearest-rank formula (telemetry.percentile
            # _summary) — same percentile semantics as every serving
            # stats() surface, so trainer and server numbers compare
            s = percentile_summary(self.rates)
            out["samples_per_sec_median"] = float(s["p50"])
            out["samples_per_sec_last"] = float(self.rates[-1])
            out["samples_per_sec"] = s
        return out


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """``jax.profiler.trace`` context (TensorBoard format).

    A profiler that will not start or stop raises: a caller that asked
    for a trace must not get an untraced run back without noticing.
    """
    import jax

    with jax.profiler.trace(log_dir):
        yield
    logger.info(f"profiler trace written to {log_dir}")


@contextlib.contextmanager
def nan_guard(enable: bool = True) -> Iterator[None]:
    """Enable ``jax_debug_nans`` within a scope (jit-wide NaN detection).

    XLA re-runs the offending computation un-jitted to locate the origin;
    expensive, so scope it to repro runs, not production training.
    """
    import jax

    if not enable:
        yield
        return
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)


def _leaf_paths(tree: Any):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        yield jax.tree_util.keystr(path), leaf


def assert_finite(tree: Any, *, name: str = "pytree") -> None:
    """Raise ``FloatingPointError`` naming the first non-finite leaf."""
    for path, leaf in _leaf_paths(tree):
        arr = np.asarray(leaf)
        if not np.issubdtype(arr.dtype, np.floating):
            continue
        if not np.all(np.isfinite(arr)):
            bad = int(np.size(arr) - np.sum(np.isfinite(arr)))
            raise FloatingPointError(
                f"{name}{path} has {bad} non-finite value(s) "
                f"(shape {arr.shape}, dtype {arr.dtype})"
            )


def describe_sharding(tree: Any) -> Dict[str, str]:
    """Map each leaf path to a human-readable sharding description."""
    out: Dict[str, str] = {}
    for path, leaf in _leaf_paths(tree):
        sharding = getattr(leaf, "sharding", None)
        out[path] = repr(sharding) if sharding is not None else "<host>"
    return out


def assert_sharding(tree: Any, expected: Dict[str, Any], *, name: str = "pytree") -> None:
    """Assert realized leaf shardings match expected PartitionSpecs.

    ``expected`` maps leaf-path substrings to ``jax.sharding.PartitionSpec``
    (or to a callable ``spec -> bool``). Catches GSPMD silently choosing a
    different layout than the config intended (SURVEY.md §5.2 rebuild:
    sharding-mismatch checks).
    """
    checked = set()
    for path, leaf in _leaf_paths(tree):
        sharding = getattr(leaf, "sharding", None)
        for pattern, want in expected.items():
            if pattern in path:
                checked.add(pattern)
                spec = getattr(sharding, "spec", None)
                ok = want(spec) if callable(want) else spec == want
                if not ok:
                    raise AssertionError(
                        f"{name}{path}: realized sharding spec {spec!r} != "
                        f"expected {want!r}"
                    )
    missing = set(expected) - checked
    if missing:
        raise AssertionError(
            f"{name}: no leaves matched expected sharding pattern(s) {sorted(missing)}"
        )
