"""Serving goodput plane: batch-occupancy accounting + perf watchdog.

The serving-side twin of :mod:`unionml_tpu.goodput` (PR 7's training
goodput layer). The training tracker classifies trainer wall time into
compute vs. badput causes; this module classifies the decode engine's
*device passes* — every dispatcher pass lands in a bounded ring as one
of :data:`PASS_KINDS`:

- ``full_batch``  — every resident slot carried a live request; the
  chunk's slot-steps were all useful work.
- ``padded_slots`` — the chunk ran with empty slots; the padded
  slot-steps are the serving analogue of training's badput.
- ``prefill_mix`` — the chunk ran while a chunked admission was
  interleaving prefill into the decode cadence (useful, but decode
  throughput is degraded by the mixed program).
- ``idle`` — no resident, nothing queued and nothing in flight:
  wall time with the device parked. A dispatcher poll that found the
  pipeline's credits taken (or only a parked admission) is NOT a pass:
  the chip is busy then, and the poll is counted under ``polls``.

Beside the ring, the plane keeps plain sums since :meth:`reset` that a
wrapped ring cannot cut short: dispatched / occupied / starved
slot-steps, admissions, prefill tokens, dispatcher polls by reason
(:data:`POLL_REASONS`) and the dispatcher thread's seconds by phase
(:data:`DISPATCHER_PHASES`).

:class:`ServingPerfPlane` owns the ring, publishes the
``unionml_serving_goodput_ratio`` / ``unionml_serving_occupancy_ratio``
/ ``unionml_serving_kv_pressure_ratio`` gauges per engine, and carries
a :class:`ServingRegressionWatchdog` — rolling-baseline detectors
(reusing PR 7's :class:`~unionml_tpu.goodput
.StepTimeRegressionDetector` hysteresis) over TTFT, inter-token
latency, and the goodput ratio itself. Regression transitions emit
``perf_regression`` flight events whose ``reason`` comes from the
closed :data:`PERF_REGRESSION_REASONS` set (lint-enforced against
docs/observability.md, like the rollout decision reasons), and
:meth:`ServingRegressionWatchdog.advisory` is the signal the
autoscaler and the rollout SLO guard can poll.

Everything here is pure host math — no jax, no device work, no wall
clocks (``clock`` is injectable monotonic seconds) — so the
classification and hysteresis are unit-testable on synthetic traces,
and the hot-path cost per dispatcher pass is one deque append plus a
few float ops (the ``serve_perf`` bench holds the on/off p99 delta
under 1%).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, Optional

from unionml_tpu import telemetry
from unionml_tpu.goodput import StepTimeRegressionDetector

__all__ = [
    "DISPATCHER_PHASES",
    "PASS_KINDS",
    "POLL_REASONS",
    "PERF_REGRESSION_REASONS",
    "ServingPerfPlane",
    "ServingRegressionWatchdog",
]

#: The device-pass kinds (docs/observability.md "Serving goodput &
#: tail attribution"). Every dispatcher pass is exactly one of these.
PASS_KINDS = (
    "full_batch",     # all slots occupied: pure useful decode
    "padded_slots",   # some slots empty: padded slot-steps wasted
    "prefill_mix",    # chunked admission interleaved into the cadence
    "idle",           # no work at all: device parked
)

#: Why the dispatcher slept a pass away: ``no_credit`` = all
#: ``pipeline_depth`` chunks are awaiting harvest (the chip is busy and
#: the dispatcher is ahead of it); ``no_work`` = nothing admittable or
#: dispatchable (an empty engine, residents waiting only for their last
#: harvest, or an admission parked on the KV pool).
POLL_REASONS = ("no_work", "no_credit")

#: Where the dispatcher thread's wall time goes. ``admit``, ``dispatch``,
#: ``poll`` and ``other`` are disjoint and add up to the thread's time;
#: ``enqueue`` is the part of ``admit`` + ``dispatch`` spent inside the
#: jitted calls themselves (prefill and decode chunk, with their
#: host-to-device arguments) — near the whole window it means an
#: enqueue blocks and the pass is paced by the device.
DISPATCHER_PHASES = ("admit", "dispatch", "enqueue", "poll", "other")

#: Closed reasons vocabulary for ``perf_regression`` flight events —
#: lint-enforced both ways against the docs table, like
#: ROLLBACK/DECISION reasons (scripts/lint_basics.py).
PERF_REGRESSION_REASONS = (
    "ttft_regression",    # submit-to-first-token crossed the baseline band
    "itl_regression",     # inter-token latency crossed the baseline band
    "goodput_collapse",   # goodput ratio fell against its baseline
)

#: Feed the goodput watchdog every Nth dispatcher pass — the detector
#: wants a sampled trend, not one update per 2 ms chunk.
_GOODPUT_FEED_EVERY = 32

#: Goodput ratios are inverted (lower is worse) before they feed the
#: shared higher-is-worse detector; the floor keeps a cold-start 0.0
#: ratio from producing an unbounded inverse.
_GOODPUT_FLOOR = 0.05


class ServingRegressionWatchdog:
    """Rolling-baseline regression detection over serving perf signals.

    One :class:`StepTimeRegressionDetector` per
    :data:`PERF_REGRESSION_REASONS` entry. TTFT and ITL feed their
    detectors directly (ms, higher is worse); the goodput ratio feeds
    as ``1 / max(ratio, 0.05)`` so a collapse (ratio down) reads as a
    regression (value up) to the same hysteresis machinery. State
    *transitions* emit ``perf_regression`` flight events; the steady
    state is readable via :meth:`advisory` (what the autoscaler and
    rollout SLO guard poll).

    ``flight=None`` disables event emission (pure-math tests); the
    engine passes its recorder plus its ``engine``/``phase`` identity
    so fleet dumps attribute the event.
    """

    def __init__(
        self,
        *,
        flight: Optional[telemetry.FlightRecorder] = None,
        engine: str = "engine",
        phase: str = "colocated",
        window: int = 50,
        threshold: float = 1.5,
        clear_threshold: float = 1.2,
        consecutive: int = 3,
        min_samples: int = 10,
    ):
        self._flight = flight
        self._engine = engine
        self._phase = phase
        self._lock = threading.Lock()
        self._detectors: Dict[str, StepTimeRegressionDetector] = {
            reason: StepTimeRegressionDetector(
                window=window, threshold=threshold,
                clear_threshold=clear_threshold,
                consecutive=consecutive, min_steps=min_samples,
            )
            for reason in PERF_REGRESSION_REASONS
        }
        self._last_ratio = {r: 1.0 for r in PERF_REGRESSION_REASONS}

    def _feed(self, reason: str, value: float, raw: float) -> dict:
        with self._lock:
            verdict = self._detectors[reason].update(value)
            self._last_ratio[reason] = verdict["ratio"]
        if (verdict["entered"] or verdict["cleared"]) and (
            self._flight is not None
        ):
            tag = {} if self._phase == "colocated" else {"phase": self._phase}
            self._flight.record(
                "perf_regression",
                engine=self._engine,
                **tag,
                reason=reason,
                state="entered" if verdict["entered"] else "cleared",
                ratio=round(verdict["ratio"], 3),
                value=round(raw, 4),
            )
        return verdict

    def observe_ttft(self, ttft_ms: float) -> dict:
        """Feed one completed request's TTFT (ms)."""
        return self._feed("ttft_regression", float(ttft_ms), float(ttft_ms))

    def observe_itl(self, itl_ms: float) -> dict:
        """Feed one completed request's mean inter-token latency (ms)."""
        return self._feed("itl_regression", float(itl_ms), float(itl_ms))

    def observe_goodput(self, ratio: float) -> dict:
        """Feed one goodput-ratio sample (0..1, higher is better)."""
        ratio = float(ratio)
        return self._feed(
            "goodput_collapse", 1.0 / max(ratio, _GOODPUT_FLOOR), ratio
        )

    def advisory(self) -> dict:
        """The poll surface: ``{"regressed", "reasons", "detail"}`` —
        ``reasons`` lists the currently-regressed signals, ``detail``
        has each detector's live ratio/anomaly counters."""
        with self._lock:
            detail = {
                reason: {
                    "regressed": det.regressed,
                    "ratio": round(self._last_ratio[reason], 4),
                    "anomalies": det.anomalies,
                    "baseline": det.baseline(),
                }
                for reason, det in self._detectors.items()
            }
        active = [r for r in PERF_REGRESSION_REASONS if detail[r]["regressed"]]
        return {
            "regressed": bool(active),
            "reasons": active,
            "detail": detail,
        }


class ServingPerfPlane:
    """Bounded-ring device-pass accountant for one decode engine.

    The engine's dispatcher calls :meth:`note_pass` after every chunk
    dispatch, :meth:`note_idle` on a pass that found the engine empty
    (no resident, nothing queued, nothing in flight) and
    :meth:`note_dispatcher` once per loop iteration with where its
    time went; the harvester calls :meth:`note_tokens` per harvested
    chunk. The ring
    (newest ``ring`` passes) is the goodput window: ratios are over
    *recent* passes, so a burst of idle at startup ages out instead of
    depressing the gauge forever.

    - ``goodput_ratio``  = occupied slot-steps / all slot-steps in the
      ring (idle passes count the full batch as lost).
    - ``occupancy_ratio`` = occupied slot-steps / dispatched
      slot-steps (idle passes excluded — the padding-only view).
    - ``kv_pressure_ratio`` = blocks in use / pool capacity at the
      last dispatch pass.
    """

    def __init__(
        self,
        *,
        registry: Optional[telemetry.MetricsRegistry] = None,
        flight: Optional[telemetry.FlightRecorder] = None,
        engine: str = "engine",
        phase: str = "colocated",
        slots: int = 1,
        chunk_steps: int = 1,
        state_bytes: int = 0,
        ring: int = 2048,
        clock: Callable[[], float] = time.perf_counter,
        watchdog: Optional[ServingRegressionWatchdog] = None,
    ):
        self._registry = (
            registry if registry is not None else telemetry.get_registry()
        )
        self._engine = engine
        self._phase = phase
        self._slots = max(1, int(slots))
        self._chunk_steps = max(1, int(chunk_steps))
        self._state_bytes = int(state_bytes)  # recurrent state, all slots
        self._clock = clock
        self._lock = threading.Lock()
        # ring entries: (kind, occupied_slot_steps, total_slot_steps),
        # with the slot-step sums carried incrementally (evictions
        # subtract, appends add) so the per-pass ratio math is O(1),
        # not a walk of a 2048-entry ring per 2 ms dispatcher pass
        self._ring: deque = deque(maxlen=max(16, int(ring)))
        self._occ_steps = 0
        self._disp_steps = 0
        self._idle_steps = 0
        self._passes = 0
        self._tokens = 0
        self._t0 = clock()
        self._kv_pressure = 0.0
        self._kv_tokens = 0  # cached positions resident at the last dispatch
        self._zero_window_locked()
        self.watchdog = (
            watchdog
            if watchdog is not None
            else ServingRegressionWatchdog(
                flight=flight, engine=engine, phase=phase
            )
        )
        R, lbl = self._registry, {"engine": engine}

        def gauge(name, help):
            return R.gauge(name, help, ("engine",)).labels(**lbl)

        self._g_goodput = gauge(
            "unionml_serving_goodput_ratio",
            "Occupied slot-steps over all slot-steps in the recent "
            "dispatcher-pass ring (idle passes count the whole batch "
            "as lost; 1.0 = every pass was a full batch).",
        )
        self._g_occupancy = gauge(
            "unionml_serving_occupancy_ratio",
            "Occupied slot-steps over dispatched slot-steps in the "
            "recent ring (idle passes excluded: the padded-slot view).",
        )
        self._g_kv_pressure = gauge(
            "unionml_serving_kv_pressure_ratio",
            "KV pool blocks in use over pool capacity at the last "
            "dispatch pass (0 on non-paged engines).",
        )
        # lazy gauges, sampled at scrape/read time: the dispatcher
        # calls note_pass/note_idle every ~2 ms, and three eager
        # Gauge.set calls per pass would be paid there — the scrape
        # path pays instead
        self._g_goodput.set_function(lambda: self._sample_ratios()[0])
        self._g_occupancy.set_function(lambda: self._sample_ratios()[1])
        self._g_kv_pressure.set_function(lambda: self._sample_ratios()[2])

    # -- dispatcher hooks --------------------------------------------------

    def note_pass(
        self,
        occupied: int,
        *,
        prefill_mix: bool = False,
        kv_in_use: int = 0,
        kv_capacity: int = 0,
        kv_tokens: int = 0,
        selected_positions: int = 0,
        visible_positions: int = 0,
        waiting: int = 0,
        admitted: int = 0,
        prefill_tokens: int = 0,
    ) -> None:
        """One dispatched decode chunk: ``occupied`` slots carried live
        requests (of the engine's ``slots``); ``prefill_mix`` flags a
        chunk that ran while chunked admission was interleaving.
        ``waiting`` is the waiting room's depth at the dispatch — the
        chunk's empty slot-steps are *starved* when it is above 0 —
        ``kv_tokens`` the cached positions the pool's blocks hold,
        ``visible_positions`` / ``selected_positions`` the cached rows
        the chunk's live sequences could see and the rows a learned
        selection lets their attention read, summed over its steps (the
        engine reckons both from its host-side fills; 0 for a module
        without a selection), and
        ``admitted`` / ``prefill_tokens`` are the admissions
        completed and prompt tokens prefilled since the previous
        chunk."""
        occupied = min(self._slots, max(0, int(occupied)))
        if prefill_mix:
            kind = "prefill_mix"
        elif occupied >= self._slots:
            kind = "full_batch"
        else:
            kind = "padded_slots"
        total = self._slots * self._chunk_steps
        occ = occupied * self._chunk_steps
        goodput = None
        with self._lock:
            self._append_locked(kind, occ, total)
            self._passes += 1
            self._win_disp_steps += total
            self._win_occ_steps += occ
            if waiting > 0:
                self._win_starved_steps += total - occ
            self._win_admissions += int(admitted)
            self._win_prefill_tokens += int(prefill_tokens)
            self._win_selected += int(selected_positions)
            self._win_visible += int(visible_positions)
            if kv_capacity > 0:
                self._kv_pressure = min(
                    1.0, max(0.0, kv_in_use / kv_capacity)
                )
                self._kv_tokens = int(kv_tokens)
            if self._passes % _GOODPUT_FEED_EVERY == 0:
                goodput = self._ratios_locked()[0]
        if goodput is not None:
            self.watchdog.observe_goodput(goodput)

    def note_idle(self) -> None:
        """One dispatcher pass that found the engine empty (no resident,
        nothing queued, nothing in flight): the whole batch's
        slot-steps are classified idle. A poll with the chip busy is
        :meth:`note_dispatcher`'s, not this."""
        total = self._slots * self._chunk_steps
        with self._lock:
            self._append_locked("idle", 0, total)
            self._passes += 1

    def note_dispatcher(
        self,
        *,
        admit_s: float = 0.0,
        dispatch_s: float = 0.0,
        enqueue_s: float = 0.0,
        poll_s: float = 0.0,
        other_s: float = 0.0,
        poll: Optional[str] = None,
    ) -> None:
        """One iteration of the dispatcher loop: its seconds by phase
        (:data:`DISPATCHER_PHASES`; ``enqueue_s`` lies inside
        ``admit_s`` + ``dispatch_s``) and, when the iteration slept,
        why (:data:`POLL_REASONS`). Polls enter neither the ring nor
        the lost slot-steps."""
        with self._lock:
            d = self._win_dispatcher_s
            d["admit"] += admit_s
            d["dispatch"] += dispatch_s
            d["enqueue"] += enqueue_s
            d["poll"] += poll_s
            d["other"] += other_s
            if poll is not None:
                self._win_polls[poll] = self._win_polls.get(poll, 0) + 1

    def note_parked(self) -> None:
        """One admission found a free slot and no pool blocks, and parked
        (counted once an admission, however often it is retried)."""
        with self._lock:
            self._win_parked += 1

    def note_blocks(
        self, *, forwards: int, commits: int, fused_commits: int, decided: int, emitted: int,
    ) -> None:
        """One harvested chunk of a module that generates by blocks: the
        forwards its live slots ran (a slot-step of such a chunk is one
        forward over a slot's open block), those among them that committed
        a block (wrote its final tokens' rows), those of these that in the
        same forward denoised the next block (``fused_commits``: every one,
        as the chunk is built), the entries the forwards decided and the
        tokens the chunk's requests were handed."""
        with self._lock:
            self._win_block_forwards += int(forwards)
            self._win_block_commits += int(commits)
            self._win_block_fused_commits += int(fused_commits)
            self._win_tokens_decided += int(decided)
            self._win_tokens_emitted += int(emitted)

    def note_tokens(self, n: int) -> None:
        """``n`` tokens harvested (the achieved-throughput numerator)."""
        with self._lock:
            self._tokens += int(n)

    # -- request hooks (from the harvester's finish path) ------------------

    def observe_request(self, ttft_ms: float, itl_mean_ms: float) -> None:
        """Feed one completed request's TTFT and mean ITL into the
        regression watchdog (ITL only when the request decoded more
        than its first token)."""
        self.watchdog.observe_ttft(ttft_ms)
        if itl_mean_ms > 0.0:
            self.watchdog.observe_itl(itl_mean_ms)

    # -- reporting ---------------------------------------------------------

    def _zero_window_locked(self) -> None:
        # plain sums since reset(): a wrapped ring cannot cut them short
        self._win_disp_steps = 0
        self._win_occ_steps = 0
        self._win_starved_steps = 0
        self._win_admissions = 0
        self._win_parked = 0
        self._win_prefill_tokens = 0
        self._win_selected = 0
        self._win_visible = 0
        self._win_block_forwards = 0
        self._win_block_commits = 0
        self._win_block_fused_commits = 0
        self._win_tokens_decided = 0
        self._win_tokens_emitted = 0
        self._win_polls = {reason: 0 for reason in POLL_REASONS}
        self._win_dispatcher_s = {phase: 0.0 for phase in DISPATCHER_PHASES}

    def _append_locked(self, kind, occ, total) -> None:
        # deque(maxlen) evicts silently on append, which would desync
        # the running sums — pop the victim explicitly first
        if len(self._ring) == self._ring.maxlen:
            k0, o0, t0 = self._ring.popleft()
            if k0 == "idle":
                self._idle_steps -= t0
            else:
                self._occ_steps -= o0
                self._disp_steps -= t0
        self._ring.append((kind, occ, total))
        if kind == "idle":
            self._idle_steps += total
        else:
            self._occ_steps += occ
            self._disp_steps += total

    def _ratios_locked(self):
        occ = self._occ_steps
        disp = self._disp_steps
        idle = self._idle_steps
        goodput = occ / (disp + idle) if (disp + idle) else 0.0
        occupancy = occ / disp if disp else 0.0
        return goodput, occupancy, self._kv_pressure

    def _sample_ratios(self):
        with self._lock:
            return self._ratios_locked()

    def report(self) -> dict:
        """The ``/debug/goodput`` body for this engine: ring
        classification counts + slot-step sums, the three ratios,
        achieved tokens/s since construction (or :meth:`reset`), the
        plain sums since then (``window_*``, ``starved_slot_steps``,
        ``admissions``, ``admissions_parked_on_pool``, ``prefill_tokens``,
        ``polls``, ``dispatcher_s``), and the watchdog advisory. The ring wrapped
        when ``total_passes > ring_passes``: the ratios then cover the
        newest passes only, the sums still the whole window."""
        with self._lock:
            ring = list(self._ring)
            passes = self._passes
            tokens = self._tokens
            elapsed = max(1e-9, self._clock() - self._t0)
            ratios = self._ratios_locked()
            window = {
                "window_s": round(elapsed, 6),
                "window_dispatched_slot_steps": self._win_disp_steps,
                "window_occupied_slot_steps": self._win_occ_steps,
                "starved_slot_steps": self._win_starved_steps,
                "admissions": self._win_admissions,
                "admissions_parked_on_pool": self._win_parked,
                "prefill_tokens": self._win_prefill_tokens,
                "selected_positions": self._win_selected,
                "visible_positions": self._win_visible,
                "block_forwards": self._win_block_forwards,
                "block_commits": self._win_block_commits,
                "block_fused_commits": self._win_block_fused_commits,
                "tokens_decided": self._win_tokens_decided,
                "tokens_emitted": self._win_tokens_emitted,
                "polls": dict(self._win_polls),
                "dispatcher_s": {
                    phase: round(s, 6)
                    for phase, s in self._win_dispatcher_s.items()
                },
            }
        counts = {kind: 0 for kind in PASS_KINDS}
        slot_steps = {kind: 0 for kind in PASS_KINDS}
        occupied = 0
        for kind, occ, total in ring:
            counts[kind] += 1
            slot_steps[kind] += total
            occupied += occ
        goodput, occupancy, pressure = ratios
        return {
            "engine": self._engine,
            "phase": self._phase,
            "slots": self._slots,
            "chunk_steps": self._chunk_steps,
            "ring_passes": len(ring),
            "total_passes": passes,
            "passes": counts,
            "slot_steps": slot_steps,
            "occupied_slot_steps": occupied,
            "goodput_ratio": round(goodput, 6),
            "occupancy_ratio": round(occupancy, 6),
            "kv_pressure_ratio": round(pressure, 6),
            "state_bytes_resident": self._state_bytes,
            "kv_tokens_resident": self._kv_tokens,
            "tokens": tokens,
            "tokens_per_s": round(tokens / elapsed, 3),
            **window,
            "watchdog": self.watchdog.advisory(),
        }

    def reset(self) -> None:
        """Clear the ring and re-anchor the throughput window (the
        windowed ``stats()``/bench reset path)."""
        with self._lock:
            self._ring.clear()
            self._occ_steps = 0
            self._disp_steps = 0
            self._idle_steps = 0
            self._passes = 0
            self._tokens = 0
            self._t0 = self._clock()
            self._kv_pressure = 0.0
            self._zero_window_locked()
