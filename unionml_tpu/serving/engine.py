"""Continuous-batching decode engine: step-boundary request joins.

Supersedes the reference's one-predictor-call-per-request loop
(reference: unionml/fastapi.py:50-64) *and* this package's own
full-batch micro-batcher for LLM serving: the MicroBatcher drains the
queue, runs one ``generate()`` to completion, and only then admits the
next batch — a request arriving one step after a batch launches waits
the entire in-flight decode plus its own.

This module is the engine's HOST side: the queue and waiting room,
admission, the dispatcher and harvester threads, the pool allocator and
block tables, preemption, recovery and stats. Everything that is traced
— the prefill family, the decode chunk, the residencies that say where a
slot's cache lives — is in :mod:`unionml_tpu.serving.programs`, which
this module calls and which imports nothing from here; nothing is
jitted in this file (``scripts/lint_basics.py`` holds that).

The engine holds a **fixed-slot decode batch** resident on device:

- the KV cache is ``[slots, L, kv_heads, head_dim]`` per layer with a
  per-slot fill index (vector ``cache_index`` — see
  :class:`unionml_tpu.models.layers.Attention`);
- a new request's prompt is **prefilled into a free slot** between
  decode steps (its own small ``[1, bucket]`` program, then one
  ``dynamic_update_slice`` of the produced KV rows into the slot);
  buckets larger than ``prefill_chunk`` admit **chunked**: the lead
  chunks fill a standalone fresh cache one ``[1, chunk]`` program at a
  time with decode chunks interleaved between them, so resident slots
  keep streaming tokens while an 8k-class prompt admits instead of
  head-of-line-blocking behind its whole prefill (the long-context
  serving path; only ``ceil(true_len / chunk)`` chunk programs run, so
  a short prompt in a long bucket pays for its own length);
- decode runs in **chunks of ``chunk_steps`` inside one scan**, and
  up to ``pipeline_depth`` chunks are **dispatched
  asynchronously** — the dispatcher thread never blocks on a chunk's
  tokens before enqueueing the next; a separate HARVESTER thread blocks
  on the oldest in-flight readback and accounts its tokens.
  Device-side state donation chains the chunks in dispatch order, so
  correctness never depends on host timing. How much the overlap buys
  depends on the host↔device round trip relative to a chunk's compute
  (not measured on the current chip — ROADMAP S2);
- finished slots (eos / token budget) are retired when their tokens are
  harvested and immediately reusable; a per-slot **generation counter**
  keeps tokens from an in-flight chunk dispatched for the *previous*
  occupant from leaking into the new one. Device-side ``done``/
  ``active`` masking keeps retired slots from corrupting live cache
  rows, and ``(pipeline_depth + 1) * chunk_steps`` spare cache rows
  absorb the decode overshoot between a request's completion and the
  host noticing it.

TPU-first notes: every program has static shapes (slots, bucket set,
chunk length are fixed at construction — XLA compiles
``len(prompt_buckets) + 1`` executables total, plus three per chunked
bucket: fresh-init, lead chunk, final chunk); the per-slot cache write
is a vmapped ``dynamic_update_slice`` (one scatter); state is donated
through both programs so the multi-GB cache never copies.

Prompts are placed **unpadded** at cache rows ``[0, P)`` — per-slot
positions make left-padding unnecessary, so a slot-decoded sequence is
token-identical to its solo :func:`~unionml_tpu.models.generate
.make_generator` run (tested in tests/unit/test_engine.py).

Automatic prefix reuse: built with a
:class:`~unionml_tpu.serving.prefix_cache.RadixPrefixCache`, admission walks
a radix tree of previously-served prompt prefixes, splices the matched
KV block rows host→device into the fresh cache (one compiled
``[1, block]`` splice program, dispatched through the same interleaved
admission loop as chunked prefill), and prefills only the uncovered
suffix; prefill completion extracts the prompt's new full blocks
device→host (async copy) and inserts them back into the tree. A shared
``system_prefix`` is a back-compat shim over this path: its tokens are
prepended to every request and its blocks are pinned in the cache, so
it is prefilled once and never evicted (docs/prefix_caching.md).

Fault tolerance (docs/robustness.md): submissions pass **admission
control** — a bounded queue (``max_queue_depth`` →
:class:`~unionml_tpu.serving.faults.Overloaded`), per-request deadlines
(``deadline_ms``, or an ambient :func:`~unionml_tpu.serving.faults
.deadline_scope`) shed at dequeue before they consume prefill, and a
**circuit breaker** that rejects fast while the engine is repeatedly
failing to rebuild. A failed device program no longer kills every
in-flight request: :meth:`_recover` fails only the poisoned batch (the
resident occupants + the in-progress admission, whose donated device
state the error invalidated), rebuilds decode state, and lets queued
survivors re-admit; in-flight readbacks from the poisoned era are
epoch-tagged and never materialized. :meth:`drain` stops admissions and
finishes in-flight streams for graceful shutdown/redeploy, and a
:class:`~unionml_tpu.serving.faults.FaultInjector` provides the
deterministic injection points that make all of the above CPU-testable.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu import telemetry
from unionml_tpu._logging import logger
from unionml_tpu.serving.faults import (
    DeadlineExceeded,
    EngineUnavailable,
    Overloaded,
    current_deadline_ms,
)
from unionml_tpu.serving.kv_pool import KVBlockPool, PoolExhausted
from unionml_tpu.serving.programs import build_programs, cache_layout, generation_scheme
from unionml_tpu.serving.scheduler import (
    DEFAULT_PRIORITY,
    PRIORITIES,
    PreemptiveScheduler,
    SchedulerConfig,
    current_priority,
    current_token_cap,
    priority_rank,
    validate_phase,
    validate_priority,
)
from unionml_tpu.serving.usage import (
    DEFAULT_TENANT,
    current_tenant,
    validate_tenant,
)

__all__ = ["DecodeEngine"]


def _start_host_copy(arr) -> None:
    """Kick off the device→host transfer early so the later harvest's
    ``np.asarray`` finds the bytes already local."""
    try:
        arr.copy_to_host_async()
    except AttributeError:
        pass


def _place(ids):
    """A program's ``place`` argument (serving/programs.py): the pool
    blocks a prefill scatters into, or the block table a decode chunk
    reads through, on the device; ``None`` for slot rows, which need no
    placing."""
    return None if ids is None else jnp.asarray(ids)


def _host_blocks(full, j0: int, j1: int):
    """Owned ``[1, block, ...]`` host copies of blocks ``[j0, j1)``
    from a block-major extract ([n_blocks, block, ...] per buffer —
    the table-addressed gather): block j is row j, re-leading-axised
    to the prefix cache's store form. The SINGLE home for the
    re-axis (the harvest-insert and preempt-save paths both feed the
    same store — a layout change applied to one and not the other
    would silently corrupt resumes or cache hits); ``.copy()`` so a
    stored block never pins the whole extract window in RAM."""
    return [
        tuple(
            tuple(buf[j][None].copy() for buf in layer)
            for layer in full
        )
        for j in range(j0, j1)
    ]


def _concat_rows(trees):
    """Concatenate host KV block trees along the row axis (axis 1) —
    groups cache blocks into one splice-unit tree host-side, so the
    device splice count scales with the admission's chunk unit, not the
    cache's block size."""
    return tuple(
        tuple(np.concatenate(bufs, axis=1) for bufs in zip(*layers))
        for layers in zip(*trees)
    )


@dataclass
class _Admission:
    """A chunked prefill in progress: host cursor over the lead chunks.

    The fresh cache lives here (device-side), not in the engine state —
    lead chunk dispatches donate it forward while decode chunks donate
    the resident state, so the two program streams never contend for a
    buffer and interleave freely in dispatch order."""

    req: "_Request"
    slot: int
    bucket: int
    chunk: int                      # tokens per program (prefill_chunk,
    #                                 or the prefix-cache block size)
    n_chunks: int                   # total programs incl. the final
    padded: np.ndarray              # [bucket] right-padded prompt
    fresh: Any                      # [1, bucket] cache being filled
    # paged mode: the slot's pool block ids for the final scatter
    # ([bucket/block] int32; uncovered tail entries = trash block)
    pool_ids: Optional[np.ndarray] = None
    next_chunk: int = 0
    # prefix-cache hit: one entry per chunk-sized splice unit (a tuple
    # of cached host block trees covering rows [i*chunk, (i+1)*chunk)),
    # spliced before the remaining chunks run (next_chunk starts past
    # them)
    splice_rows: List[Any] = field(default_factory=list)
    next_splice: int = 0


@dataclass(eq=False)  # identity semantics: the waiting room's parked
# lane membership tests (`req in parked`) must never field-compare two
# requests — the numpy prompt would make `==` ambiguous
class _Request:
    prompt: np.ndarray                  # int32 [P], truncated to max bucket
    max_new_tokens: int
    submitted: float = field(default_factory=time.perf_counter)
    tokens: List[int] = field(default_factory=list)
    event: threading.Event = field(default_factory=threading.Event)
    error: Optional[BaseException] = None
    # streaming consumers: harvested token chunks are mirrored here as
    # they land (lists of ints; None terminates; the terminal push
    # follows error/event so a drained stream is a finished request)
    stream: Optional["queue.Queue"] = None
    # observability (ms). prefill_ms and decode_ms are measured at token
    # HARVEST, so each includes one in-flight readback lag — honest at
    # the request boundary, not a pure device timing. ttft_ms is
    # submit→first-harvested-token: the latency a streaming client sees
    # to its first event.
    queue_wait_ms: float = 0.0
    prefill_ms: float = 0.0
    decode_ms: float = 0.0
    ttft_ms: float = 0.0
    abandoned: bool = False             # waiter gave up (timeout): retire asap
    rid: str = ""                       # telemetry trace-span request id
    # usage metering (docs/observability.md "Usage metering"): the
    # validated tenant id this request's resource vector is billed to
    tenant: str = DEFAULT_TENANT
    # preemptive scheduling (docs/robustness.md "Preemption &
    # fairness"): the validated priority class (X-Priority header /
    # generate(priority=)); the waiting room orders admissions by it
    # and the scheduler may evict strictly-lower-priority residents
    priority: str = DEFAULT_PRIORITY
    # absolute perf_counter deadline (None = none): checked at DEQUEUE,
    # so an expired request is shed before it consumes prefill
    deadline: Optional[float] = None
    _prefill_end: float = 0.0
    _dispatch_t: float = 0.0
    _expected: int = 0                  # tokens covered by dispatched work
    _chunk_i: int = 0                   # harvested decode chunks (trace names)
    _lease: Optional[Any] = None        # PrefixLease pinning matched blocks
    _matched_blocks: int = 0            # radix-tree blocks found at admission
    _prefilled_tokens: int = 0          # prompt tokens actually prefilled
    _saved_tokens: int = 0              # prompt tokens spliced from cache
    # paged mode: device pool bookkeeping (engine lock guards all three)
    _block_ids: List[int] = field(default_factory=list)  # taken pool blocks
    _resv_blocks: int = 0               # reserved, not yet taken
    _rows_cap: int = 0                  # prompt + max_new (block budget)
    _park_logged: bool = False          # one pool_pressure event per park
    _pool_gen: int = 0                  # pool generation at reservation
    # usage metering: pool-block take timestamps (parallel to
    # _block_ids' take order) and dispatched-prefill FLOPs accumulated
    # from the tracker's per-program cost analysis
    _block_t0: List[float] = field(default_factory=list)
    _attr_flops: float = 0.0
    # preemption bookkeeping: times evicted, when the last eviction
    # happened (resume-wait span anchor; also marks the request as
    # resumed so ttft/queue timings are not overwritten), and the
    # lease pinning the evicted KV blocks in the host prefix cache
    # until the resume admission takes its own
    _preempts: int = 0
    _preempted_at: float = 0.0
    _resume_lease: Optional[Any] = None
    # generated tokens already FOLDED INTO ``prompt`` by a previous
    # resume: the next eviction appends only tokens[_prompt_incl:], or
    # a twice-preempted stream would duplicate its first segment
    _prompt_incl: int = 0
    # disaggregated prefill (docs/serving.md "Disaggregated serving"):
    # set by prefill_export and signalled once the request's KV blocks
    # have landed in the host prefix-cache store (the insert entry's
    # lease release — or any terminal path, so a waiter never hangs)
    _kv_event: Optional[threading.Event] = None
    # serving goodput plane (docs/observability.md "Serving goodput &
    # tail attribution"): admission_ms is the host-side admission span
    # (dispatch start → final prefill program dispatched — the
    # chunked-admission machinery's share of prefill_ms); _itl_anchor
    # is the harvest time of this decode segment's previous tokens
    # (0.0 = unanchored: before the first token, or cleared by
    # preemption so the evict→resume gap never counts as inter-token
    # latency); the accumulators feed itl_mean_ms per request
    admission_ms: float = 0.0
    _itl_anchor: float = 0.0
    _itl_sum_ms: float = 0.0
    _itl_n: int = 0
    # generation by blocks: for each served token the forward of its block
    # (0, 1, ...) that decided it, and the forwards dispatched for this
    # request so far (what the dispatcher reckons the tokens due from)
    decided_at: List[int] = field(default_factory=list)
    _forwards: int = 0

    def emit(self, chunk: List[int]) -> None:
        if self.stream is not None and chunk:
            self.stream.put(chunk)

    def finish_stream(self) -> None:
        if self.stream is not None:
            self.stream.put(None)


class DecodeEngine:
    """Continuous-batching generation over a fixed slot batch.

    ``generate(params, prompts)`` is thread-safe and blocking — concurrent
    callers' requests join the resident decode at chunk boundaries. Use as
    an ``@model.predictor`` body with ``ServingApp(batch=False)`` (each
    HTTP thread submits directly; batching happens *here*, not in the
    transport).

    Args:
        module: a cache-capable decoder (``unionml_tpu.models.Llama``). What
            its layers cache it says with ``cache_layout()``, and, where it
            does not emit one token a forward, how it generates with
            ``generation_scheme()`` (``models.SdarMoe``: by diffusion over
            blocks; docs/serving.md "Decoders that generate by blocks").
        slots: resident batch size — the max concurrent decodes.
        max_new_tokens: per-request generation cap (requests may ask for
            fewer via ``generate(..., max_new_tokens=n)``).
        prompt_buckets: prompt lengths to compile prefill programs for;
            prompts are left-truncated to the largest bucket. The shared
            cache is sized ``max(buckets) + max_new_tokens +
            (pipeline_depth + 1) * chunk_steps`` — decode attention reads
            all of it every step, so keep the bucket set tight for the
            traffic you serve.
        prefill_chunk: when set, a bucket LARGER than this prefills in
            ``prefill_chunk``-token programs instead of one monolithic
            ``[1, bucket]`` pass. The lead chunks fill a standalone fresh
            cache that never touches the resident state, so the
            dispatcher interleaves DECODE chunks between them — resident
            slots keep streaming tokens while a long prompt admits,
            instead of head-of-line-blocking behind its whole prefill
            (the long-context admission path; VMEM for the prefill
            score buffer is bounded by the chunk, the same knob
            :func:`~unionml_tpu.models.generate.make_generator` uses for
            8k contexts). Only ``ceil(true_len / prefill_chunk)`` chunk
            programs run per admission — a short prompt routed into a
            long bucket pays for its own length, not the bucket's.
            Chunked buckets must divide evenly by ``prefill_chunk``.
        chunk_steps: decode steps per dispatched chunk (join granularity).
        pipeline_depth: max decode chunks in flight before their token
            readbacks are harvested. Size it so ``depth * chunk compute``
            covers the host↔device round trip (the default 8 was sized
            for a slow link; on a directly attached host 2 is plenty and
            the extra depth is harmless).
        temperature/top_k/top_p/eos_id/pad_id: sampling config, matching
            :func:`~unionml_tpu.models.generate.make_generator`.
        draft_module: a smaller same-vocabulary decoder enabling
            SPECULATIVE decoding: each decode chunk becomes
            ``chunk_steps`` rounds of per-slot draft proposals + ONE
            shared ``[slots, k+1]`` verify forward (amortizing the
            target's weight stream across every resident slot), with
            greedy acceptance advancing per-slot fills —
            token-identical to plain greedy decoding of the target for
            any draft. ``bind``/``generate`` then take the
            ``{"target": ..., "draft": ...}`` params mapping. Greedy
            only; composes with ``system_prefix`` (the prefix rides
            through both models' prefills) but not with
            ``prefix_cache`` (the draft would need a mirrored block
            store). Pays above a crossover acceptance rate that is
            not measured on the current chip.
        speculate_k: draft tokens proposed per round (k+1 emitted max;
            a round costs k+1 draft steps + one (k+1)-token verify).
        system_prefix: token ids prepended to EVERY request's prompt (a
            shared system prompt). Back-compat shim over the prefix
            cache: the prefix blocks are pinned there, so after the
            first admission computes them they are spliced — never
            re-prefilled — and can never be evicted. Buckets are
            widened by the prefix length (and rounded up to splice
            alignment) internally.
        prefix_cache: a :class:`~unionml_tpu.serving.prefix_cache
            .RadixPrefixCache` (or ``True`` for a default one) enabling
            automatic cross-request prefix reuse: admission splices the
            longest cached block-prefix of the prompt into the slot and
            prefills only the uncovered suffix; completion inserts the
            prompt's KV blocks back. Buckets are rounded up to
            ``lcm(block_size, prefill_chunk)`` multiples so cached
            admissions stay shape-static. One cache per weight binding:
            ``bind`` to different params clears it. Defaults to a
            private cache when ``system_prefix`` is set (the shim),
            else disabled.
        registry/tracer: explicit telemetry sinks
            (:mod:`unionml_tpu.telemetry`). Default to the process-global
            registry and trace recorder, so a ``ServingApp``'s
            ``GET /metrics`` covers this engine automatically and every
            request's ``queue → prefill → decode-chunk[i] → harvest``
            spans land in the exportable trace.
        max_queue_depth: admission control — submissions beyond this
            many queued (not-yet-admitted) requests raise
            :class:`~unionml_tpu.serving.faults.Overloaded` instead of
            queueing unboundedly (the transports map it to HTTP 429
            with ``Retry-After``). ``None`` (default) keeps the
            historical unbounded queue.
        breaker_threshold/breaker_window_s/breaker_cooldown_s: the
            circuit breaker — ``breaker_threshold`` recoveries within
            ``breaker_window_s`` seconds open it for
            ``breaker_cooldown_s`` seconds, during which submissions
            fail fast with :class:`~unionml_tpu.serving.faults
            .EngineUnavailable` and ``health()`` reports ``degraded``
            (a persistently-poisoned device must shed load, not grind
            every request through another doomed rebuild). Any
            successfully completed request closes the failure window.
        fault_injector: a :class:`~unionml_tpu.serving.faults
            .FaultInjector` whose ``engine.prefill`` /
            ``engine.dispatch`` / ``engine.harvest`` /
            ``engine.dequeue`` points this engine fires — the chaos
            harness that makes recovery, shedding, and breaker behavior
            deterministically reproducible in CPU-only tests. ``None``
            (production default) is zero-cost.
        introspect: program introspection + flight recording
            (docs/observability.md). When True (default), every
            compiled program (prefill, decode chunk, splice/extract) is
            wrapped by a :class:`~unionml_tpu.introspection
            .ProgramTracker` — compile events record XLA
            ``cost_analysis()`` flops/bytes and compile time, live MFU/
            roofline gauges land in ``/metrics``, and
            ``stats()["programs"]`` reports per-program hardware truth
            — and request lifecycle events stream into the flight
            recorder. Steady-state overhead is a cache-size read plus
            counter increments per *chunk* dispatch; ``False`` disables
            both for an instrumentation-free engine.
        flight: explicit :class:`~unionml_tpu.telemetry.FlightRecorder`
            for lifecycle events; defaults to the process-global one
            (``GET /debug/flight``). Ignored when ``introspect=False``.
        usage: a :class:`~unionml_tpu.serving.usage.UsageLedger` (or
            ``True`` for a default one on this engine's registry)
            enabling per-tenant usage metering (docs/observability.md
            "Usage metering & cost attribution"): every request's
            queue wait, prefill/cached/decode tokens, attributed
            device-seconds and FLOPs (per-dispatch cost split across
            the live batch by harvested-token share), and — in paged
            mode — KV block-seconds are billed to its tenant (the
            ``X-Tenant-ID`` header via the ambient
            :func:`~unionml_tpu.serving.usage.tenant_scope`, or the
            ``tenant=`` argument of :meth:`generate`). Per-tenant
            aggregates export as bounded-cardinality
            ``unionml_tenant_*`` series; ``None`` (default) disables
            metering entirely — every record site is one attr-is-None
            check.
        perf: the serving goodput plane (docs/observability.md
            "Serving goodput & tail attribution"): every dispatcher
            pass is classified into a bounded ring (full-batch /
            padded-slots / prefill-mix / idle →
            ``unionml_serving_goodput_ratio`` and friends, read by
            ``GET /debug/goodput``), decode-chunk harvests feed the
            ``unionml_engine_itl_ms`` inter-token-latency histograms
            and per-request ITL accumulators, completed requests tag
            the latency histograms with rid exemplars (``GET
            /debug/tail``), and a :class:`~unionml_tpu.serving.perf
            .ServingRegressionWatchdog` watches TTFT/ITL/goodput for
            regressions (``perf_regression`` flight events). ``None``
            (default) enables the plane iff ``introspect`` is on;
            ``False`` disables it (every hook is one attr-is-None
            check); an explicit
            :class:`~unionml_tpu.serving.perf.ServingPerfPlane`
            injects one.
        paged/kv_pool_bytes/kv_pool_blocks/kv_block_size: BLOCK-PAGED
            device KV (docs/performance.md "Paged KV attention";
            PagedAttention lineage). Instead of ``slots`` contiguous
            ``cache_len``-row caches, device KV lives in one global
            pool of ``kv_block_size``-token blocks sized by an HBM
            byte budget (``kv_pool_bytes``) or a block count
            (``kv_pool_blocks``; default: the contiguous equivalent,
            a pure layout change), with a per-slot int32 block table
            grown one block at a time as decode proceeds — a short
            prompt in a long bucket charges HBM for its own tokens,
            not the bucket's, so the effective batch at a fixed byte
            budget rises with the traffic's long-tail. Admission
            RESERVES a request's worst-case blocks up front (prompt +
            ``max_new_tokens``), so growth can never fail mid-decode:
            a transiently full pool parks the admission until blocks
            free (queued behind it, admission control sheds the
            overflow), and a request that can NEVER fit is rejected
            ``Overloaded`` at submit. Decode attention runs through
            :mod:`~unionml_tpu.ops.paged_attention` (the module
            config's ``paged_impl`` picks kernel vs reference; the
            reference path is bit-identical to the contiguous
            layout). Block size defaults to the prefix cache's (the
            two MUST share one block unit — mismatches raise), else
            16; buckets round to ``lcm(block, prefill_chunk)`` via
            the same ``_block_geometry()`` the prefix cache uses.
            Pool telemetry: ``unionml_kv_pool_*``. Not composable
            with ``draft_module`` (the draft would need its own
            pool).
        scheduler: a :class:`~unionml_tpu.serving.scheduler
            .SchedulerConfig` tuning the PREEMPTIVE, PRIORITY-AWARE
            admission scheduler (docs/robustness.md "Preemption &
            fairness"). Every engine runs the scheduler's waiting
            room: requests carry a priority class (``X-Priority``
            header / ``generate(priority=)``) and admissions drain
            per-(priority, tenant) deficit-weighted queues — a
            single-tenant, single-priority stream degenerates to the
            historical FIFO. Preemption (evicting a strictly
            lower-priority resident's KV blocks to the host
            prefix-cache store so a higher-priority waiter can admit,
            resuming the victim later via the splice path with exact
            token parity) auto-enables when the engine is ``paged``
            AND has a ``prefix_cache`` (the lossless evict/resume
            prerequisites); ``SchedulerConfig(preempt=True)`` makes
            missing prerequisites a construction error instead of a
            silent park-only fallback. ``None`` (default) uses the
            default config.
    """

    # the block chunk built WRONGLY on purpose (it keeps a block's last
    # denoising forward's rows): set on a subclass or patched by the tests
    # and the benchmark's control, never by a caller
    _block_stale_commit = False

    def __init__(
        self,
        module,
        *,
        slots: int = 8,
        max_new_tokens: int = 32,
        prompt_buckets: Sequence[int] = (64,),
        prefill_chunk: Optional[int] = None,
        chunk_steps: int = 8,
        pipeline_depth: int = 8,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        pad_id: int = 0,
        seed: int = 0,
        submit_timeout: float = 300.0,
        system_prefix: Optional[Sequence[int]] = None,
        draft_module=None,
        speculate_k: int = 4,
        prefix_cache=None,
        registry: Optional[telemetry.MetricsRegistry] = None,
        tracer: Optional[telemetry.TraceRecorder] = None,
        max_queue_depth: Optional[int] = None,
        breaker_threshold: int = 3,
        breaker_window_s: float = 30.0,
        breaker_cooldown_s: float = 5.0,
        fault_injector=None,
        introspect: bool = True,
        flight=None,
        usage=None,
        perf=None,
        paged: bool = False,
        kv_pool_bytes: Optional[int] = None,
        kv_pool_blocks: Optional[int] = None,
        kv_block_size: Optional[int] = None,
        scheduler: Optional[SchedulerConfig] = None,
        phase: Optional[str] = None,
    ):
        from unionml_tpu.models.generate import make_sampler

        if slots < 1:
            raise ValueError("need at least one slot")
        if not prompt_buckets:
            raise ValueError("need at least one prompt bucket")
        # what each layer of the module caches (models/layers.py): rows of
        # keys and values, which a paged engine keeps in its block pool,
        # or a state of fixed size, which it keeps per slot
        self._layout = cache_layout(module)
        self._owns_rows = tuple(l.owns_rows for l in self._layout)
        self._state_layers = len(self._layout) - sum(self._owns_rows)
        self._latent_layers = sum(l.kind == "latent" for l in self._layout)
        self._index_layers = sum(l.kind == "kv+index" for l in self._layout)
        # a learned selection inside attention: a decode step reads at most
        # this many of a sequence's cached rows (None: it reads them all)
        self._index_topk = (
            getattr(module.config, "index_topk", None) if self._index_layers else None
        )
        self._decode_read: Optional[str] = None
        # bytes of recurrent state one slot keeps on the device
        self._state_bytes_per_slot = sum(
            l.nbytes() for l, kv in zip(self._layout, self._owns_rows) if not kv
        )
        if not any(self._owns_rows):
            raise ValueError(
                "no layer of this module caches keys and values: the engine's "
                "buckets and pool are sized from those layers"
            )
        for given, what in (
            (prefix_cache not in (None, False), "prefix_cache="),
            (system_prefix is not None, "system_prefix="),
            (draft_module is not None, "draft_module="),
            (scheduler is not None and scheduler.preempt, "SchedulerConfig(preempt=True)"),
        ):
            if given:
                self._refuse_recurrent(what)
        if draft_module is not None:
            # nor can a rejected proposal be rolled back out of a draft's
            # recurrent state (ROADMAP.md, Queue 2 M)
            self._refuse_recurrent("draft_module=", cache_layout(draft_module))
        # how the module generates: a token a step, or by blocks (the
        # module says: models/layers.py BlockDiffusion). By blocks a decode
        # step is one forward over every slot's open block and yields a
        # whole block's tokens or none.
        self.module = module
        self._blocks = generation_scheme(module)
        for given, what in (
            (prefix_cache not in (None, False), "prefix_cache="),
            (system_prefix is not None, "system_prefix="),
            (draft_module is not None, "draft_module="),
            (prefill_chunk is not None, "prefill_chunk="),
            (scheduler is not None and scheduler.preempt, "SchedulerConfig(preempt=True)"),
        ):
            if given:
                self._refuse_blocks(what)
        # serving phase (docs/serving.md "Disaggregated serving"):
        # which half of a generative request this engine's pool owns.
        # The engine itself serves any request either way — the label
        # rides health()/stats()/flight events so a phase-split
        # fleet's telemetry is attributable per pool, and the
        # phase-aware router picks by it.
        self.phase = validate_phase(phase)
        # model version currently bound into this engine (docs/
        # robustness.md "Rollouts & rollback"): set by the rollout
        # controller's bind()-then-tag choreography (and by
        # EngineReplica(version=...)), None when nobody versioned the
        # weights. Rides usage vectors so per-tenant billing splits by
        # model version during a canary bake.
        self.model_version: Optional[str] = None
        self.draft = draft_module
        self.speculate_k = int(speculate_k)
        if self.draft is not None:
            # SPECULATIVE engine: per-slot draft proposals + one shared
            # [slots, k+1] verify forward per round, greedy acceptance
            # advancing per-slot fills — token-identical to plain greedy
            # decoding of the target (the make_speculative_generator
            # acceptance rule, restructured for the resident slot batch)
            if temperature != 0.0:
                raise ValueError(
                    "the speculative engine is greedy-only (sampled "
                    "speculation needs the rejection-sampling correction; "
                    "match make_speculative_generator)"
                )
            if prefix_cache not in (None, False):
                raise ValueError(
                    "the speculative engine does not compose with the "
                    "prefix KV-cache yet — the draft model would need a "
                    "mirrored block store; drop prefix_cache "
                    "(system_prefix alone is fine: the prefix rides "
                    "through both prefills)"
                )
            if self.draft.config.vocab_size != module.config.vocab_size:
                raise ValueError(
                    f"target/draft vocabularies differ: "
                    f"{module.config.vocab_size} vs "
                    f"{self.draft.config.vocab_size}"
                )
            if self.speculate_k < 1:
                raise ValueError(f"speculate_k must be >= 1, got {speculate_k}")
            if self.speculate_k + 1 > min(int(b) for b in prompt_buckets):
                # idle slots write k+1 garbage draft/verify rows from
                # their parked fill; admission's full-bucket splice must
                # cover them
                raise ValueError(
                    f"speculate_k + 1 = {self.speculate_k + 1} exceeds the "
                    f"smallest prompt bucket {min(prompt_buckets)}"
                )
        # rows a dispatched chunk can advance a slot: 1 per decode step,
        # or k+1 per speculative round
        self._round_stride = 1 if self.draft is None else self.speculate_k + 1
        # rows a dispatched chunk can move a slot's fill on by, the rows past
        # its fill that a step writes besides (the block tables grow ahead
        # of both) and those a request holds past its asked length: a row a
        # step and none. By blocks a forward runs two blocks' rows a slot
        # (the block it closes and the next one's first pass), a block may
        # close every forward (the dynamic rule), and the last block is
        # written whole
        self._chunk_advance, self._step_rows, self._open_rows = chunk_steps, 0, 0
        if self._blocks is not None:
            self._round_stride = self._open_rows = self._blocks.block_length
            self._step_rows = 2 * self._blocks.block_length
            self._chunk_advance = self._blocks.block_length * chunk_steps
        self.cfg = module.config
        self.slots = slots
        self.max_new_tokens = max_new_tokens
        self.prefill_chunk = None if prefill_chunk is None else int(prefill_chunk)
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.chunk_steps = chunk_steps
        self.pipeline_depth = max(1, pipeline_depth)
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.submit_timeout = submit_timeout
        # fault tolerance: admission control + supervision knobs
        if max_queue_depth is not None and max_queue_depth < 1:
            raise ValueError("max_queue_depth must be >= 1 when set")
        if breaker_threshold < 1:
            raise ValueError("breaker_threshold must be >= 1")
        self.max_queue_depth = max_queue_depth
        self.breaker_threshold = breaker_threshold
        self.breaker_window_s = breaker_window_s
        self.breaker_cooldown_s = breaker_cooldown_s
        self._faults = fault_injector
        self._draining = False
        self._breaker_open_until = 0.0
        # recovery timestamps within the breaker window (lock-guarded);
        # cleared on any successful completion, so only CONSECUTIVE
        # rebuild failures accumulate toward the threshold
        self._recovery_times: "deque[float]" = deque()
        # bumped by _recover: in-flight readbacks dispatched under an
        # older epoch belong to the poisoned era and are never
        # materialized (their requests were already failed)
        self._epoch = 0
        # telemetry sinks before the cache: a default-constructed cache
        # registers its series in the engine's registry
        self._registry = registry if registry is not None else telemetry.get_registry()
        self._tracer = tracer if tracer is not None else telemetry.get_tracer()
        self.instance = telemetry.instance_label("engine")
        # introspection sinks (None when introspect=False: every record
        # site is a single attr-is-None check)
        self.introspect = bool(introspect)
        self._flight = (
            (flight if flight is not None else telemetry.get_flight_recorder())
            if self.introspect else None
        )
        # usage metering (off-switch: None leaves every record site a
        # single attr check)
        if usage is True:
            from unionml_tpu.serving.usage import UsageLedger

            usage = UsageLedger(registry=self._registry)
        self._usage = usage or None
        # serving goodput plane (docs/observability.md "Serving
        # goodput & tail attribution"): dispatcher-pass classification
        # into the bounded ring, ITL histograms + tail exemplars, and
        # the perf-regression watchdog. Defaults on with introspection
        # (perf=None); ``False`` disables it, an explicit
        # ServingPerfPlane injects one. Every hook below is a single
        # attr-is-None check.
        if perf is None:
            perf = self.introspect
        if perf is True:
            from unionml_tpu.serving.perf import ServingPerfPlane

            perf = ServingPerfPlane(
                registry=self._registry, flight=self._flight,
                engine=self.instance, phase=self.phase,
                slots=self.slots, chunk_steps=self.chunk_steps,
                state_bytes=self._state_bytes_per_slot * slots,
            )
        self._perf = perf or None
        # harvester-thread clock: end of the previous readback, so each
        # entry's attributed device time is the wall it exclusively
        # occupied the device pipeline (consecutive-harvest spacing ==
        # per-chunk device time once the pipeline saturates)
        self._last_harvest_end = 0.0
        self._programs = None
        # shared system prefix (back-compat shim over the prefix cache):
        # the tokens are PREPENDED to every request's prompt and their
        # KV blocks pinned in the cache — the first admission prefills
        # them, every later one splices them, and they can never be
        # evicted. This replaces the old seed-once broadcast programs.
        self._prefix_tokens = (
            None
            if system_prefix is None
            else np.asarray(system_prefix, np.int32).ravel()
        )
        if self._prefix_tokens is not None and self._prefix_tokens.size == 0:
            raise ValueError("system_prefix must be non-empty when given")
        self.prefix_len = (
            0 if self._prefix_tokens is None else len(self._prefix_tokens)
        )
        if (
            prefix_cache is None
            and self._prefix_tokens is not None
            and self.draft is None
        ):
            prefix_cache = True  # the shim keeps old system_prefix reuse
        if prefix_cache is True:
            from unionml_tpu.serving.prefix_cache import RadixPrefixCache

            prefix_cache = RadixPrefixCache(registry=self._registry)
        self.prefix_cache = prefix_cache or None
        if self._prefix_tokens is not None and self.prefix_cache is not None:
            self.prefix_cache.pin(self._prefix_tokens)
        # block-paged device KV: pool geometry resolves through
        # _block_geometry() so the device pool and the prefix cache's
        # host store can never disagree on the block unit
        self.paged = bool(
            paged or kv_pool_bytes is not None or kv_pool_blocks is not None
        )
        if self._blocks is not None and not self.paged:
            raise ValueError(
                f"{type(module).__name__} generates by blocks "
                f"(block_length {self._blocks.block_length}), which this engine serves from "
                "the block pool only: pass paged=True (or kv_pool_bytes= / kv_pool_blocks=)"
            )
        if self.paged and self.draft is not None:
            raise ValueError(
                "the speculative engine does not compose with the paged "
                "KV pool yet — the draft model would need a mirrored "
                "pool; drop paged/kv_pool_* or draft_module"
            )
        self._kv_block_size_arg = (
            None if kv_block_size is None else int(kv_block_size)
        )
        if self._kv_block_size_arg is not None and self._kv_block_size_arg < 1:
            raise ValueError("kv_block_size must be >= 1")
        # device-resident LRU of recently-spliced units (dispatcher
        # thread only): a hot prefix — the pinned system_prefix above
        # all — uploads host→device ONCE, not per admission. Entries
        # hold the host block tuples too, so an id() key can never be
        # recycled while its entry lives. The cap bounds device bytes
        # (cap × unit tokens of KV).
        self._dev_splice: "OrderedDict" = OrderedDict()
        self._dev_splice_cap = 8
        # bucket set: the prefix shim widens every bucket by the prefix
        # length (prompts now INCLUDE the prefix), and a shared block
        # unit (prefix cache and/or paged pool — ONE geometry, resolved
        # by _block_geometry) rounds buckets up to lcm(block,
        # prefill_chunk) so cached admissions (block-granularity
        # chunks), paged block scatters, and chunked prefill all keep
        # static, evenly-covered shapes
        self._kv_block_size, align = self._block_geometry()
        if self._blocks is not None and self._kv_block_size % self._blocks.block_length:
            raise ValueError(
                f"kv_block_size {self._kv_block_size} must be a multiple of the module's "
                f"block_length {self._blocks.block_length}: a block of positions is written "
                "and committed whole"
            )
        raw = sorted(set(int(b) for b in prompt_buckets))
        if self.prefix_len or self.prefix_cache is not None or self.paged:
            raw = sorted(set(
                -(-(b + self.prefix_len) // align) * align for b in raw
            ))
        self.buckets = tuple(raw)
        # per-request prompts are truncated to this BEFORE the prefix is
        # prepended, so the prefix can never be cut by a long prompt
        self._user_max = self.buckets[-1] - self.prefix_len
        if self.prefill_chunk is not None:
            bad = [
                b for b in self.buckets
                if b > self.prefill_chunk and b % self.prefill_chunk
            ]
            if bad:
                raise ValueError(
                    f"buckets {bad} are not multiples of prefill_chunk "
                    f"{self.prefill_chunk} — chunked prefill needs even "
                    "chunk coverage (pad the bucket or change the chunk)"
                )
        # spare rows: a slot may overshoot its token budget by up to the
        # full in-flight window (pipeline_depth chunks dispatched before
        # the host harvests the completion, plus the chunk being
        # dispatched) before the host retires it; sparing those rows keeps
        # the fill invariant (fill always points at a masked-False row)
        # without per-slot write redirection
        self.cache_len = (
            self.buckets[-1]
            + max_new_tokens
            + (self.pipeline_depth + 1) * chunk_steps * self._round_stride
            # a speculative round writes k rows past its counted advance
            + (self._round_stride - 1)
        )
        if self.paged:
            # the logical row space maps exactly onto whole pool blocks
            # (table width = cache_len / block); overshoot rows past a
            # request's reserved blocks write the trash block instead
            self.cache_len = (
                -(-self.cache_len // self._kv_block_size)
                * self._kv_block_size
            )
        max_lens = [self.cfg.max_len] + (
            [self.draft.config.max_len] if self.draft is not None else []
        )
        if self.cache_len > min(max_lens):
            raise ValueError(
                f"cache length {self.cache_len} (= max bucket "
                f"{self.buckets[-1]} incl. any system prefix + "
                f"max_new_tokens {max_new_tokens} + (pipeline_depth "
                f"{self.pipeline_depth} + 1) * chunk_steps {chunk_steps} "
                f"* round stride {self._round_stride} spare rows) exceeds "
                f"model max_len {min(max_lens)}; lower pipeline_depth/"
                "chunk_steps or raise max_len"
            )
        # device block pool (paged mode): host-side free-list allocator
        # + per-slot block tables; the device arrays live in _state
        self.kv_pool: Optional[KVBlockPool] = None
        self._table: Optional[np.ndarray] = None
        self._dispatch_seq = 0      # decode chunks dispatched (fence clock)
        self._harvest_seq = 0       # decode chunks harvested
        # (fence, block ids): freed only once every chunk dispatched
        # before the retirement has been harvested — an in-flight chunk
        # may still write a just-retired slot's rows, and a recycled
        # block must never see them
        self._deferred_free: List = []
        if self.paged:
            blk = self._kv_block_size
            self._table_width = self.cache_len // blk
            block_nbytes = self._kv_block_nbytes(blk)
            if kv_pool_blocks is not None:
                num_blocks = int(kv_pool_blocks)
            elif kv_pool_bytes is not None:
                num_blocks = max(2, int(kv_pool_bytes) // block_nbytes)
            else:
                # default: the contiguous layout's worst case — a pure
                # layout change until a byte budget tightens it
                num_blocks = 1 + slots * self._table_width
            self.kv_pool = KVBlockPool(
                num_blocks=num_blocks, block_size=blk,
                block_nbytes=block_nbytes, registry=self._registry,
            )
            self._table = np.zeros((slots, self._table_width), np.int32)
            if self._index_topk is not None:
                # which read of the selected rows the decode chunk compiles
                self._decode_read = module.decode_read(self._table_width * blk)
            self._slot_covered = [0] * slots   # taken blocks per slot row
            self._slot_rows = [0] * slots      # dispatched-rows upper bound
        self._sample = make_sampler(
            temperature=temperature, top_k=top_k, top_p=top_p
        )
        self._key = jax.random.PRNGKey(seed)
        self._params: Any = None
        self._state: Any = None
        self._occupant: List[Optional[_Request]] = [None] * slots
        # bumped on every (re)admission: an in-flight chunk snapshot with a
        # stale generation must not credit its tokens to the new occupant
        self._slot_gen: List[int] = [0] * slots
        # requests popped from the queue but not yet visible in _occupant
        # (admission spans the prefill dispatch): bind()'s busy check must
        # see them or a concurrent swap lands mid-admission
        self._admitting = 0
        # chunked admission in progress (dispatcher thread only); its
        # reserved slot keeps occupant None until the final chunk lands
        self._admission: Optional[_Admission] = None
        # preemptive, priority-aware admission scheduling
        # (docs/robustness.md "Preemption & fairness"): the waiting
        # room replaces the old FIFO queue + single-slot park —
        # per-(priority, tenant) deficit-weighted queues with a
        # bounded parked lane for pool-exhausted admissions
        sched_cfg = scheduler if scheduler is not None else SchedulerConfig()
        can_preempt = self.paged and self.prefix_cache is not None
        if sched_cfg.preempt and not can_preempt:
            raise ValueError(
                "SchedulerConfig(preempt=True) needs a paged engine "
                "with a prefix cache — eviction extracts the victim's "
                "pool blocks into the host prefix-cache store and "
                "resume splices them back (pointer swaps, exact token "
                "parity); pass paged=True and prefix_cache=..."
            )
        self._preempt_enabled = (
            can_preempt if sched_cfg.preempt is None else bool(sched_cfg.preempt)
        )
        self._mix_budget = sched_cfg.mix_prefill_tokens
        self._sched = PreemptiveScheduler(
            sched_cfg, registry=self._registry,
            engine_label=self.instance, usage=self._usage,
            phase=self.phase,
        )
        self._room = self._sched.room
        self._lock = threading.Lock()
        # dispatch→harvest pipeline: FIFO of in-flight readbacks; the
        # semaphore caps chunk entries at pipeline_depth
        self._inflight: "queue.Queue" = queue.Queue()
        self._chunk_credits = threading.Semaphore(self.pipeline_depth)
        # observability: every tally lives in the shared telemetry
        # registry (one scrape surface across engine/batcher/HTTP/
        # trainer); stats() is a thin view over these instruments. The
        # instance label keeps concurrent engines' series separate.
        # (registry/tracer/instance were resolved above, before the
        # prefix cache registered its own series.)
        self._build_instruments()
        # harvest-span anchor: set at the top of each _process_entry
        # (harvester thread only), read by _finish_if_done under the lock
        self._harvest_t0 = 0.0
        # dispatcher thread only: the open engine.pass span, this
        # iteration's seconds by phase, whether its chunk dispatch
        # found the pipeline's credits taken, and the admissions
        # completed since the previous chunk (count, prefill tokens)
        self._pass_span = None
        self._it_admit_s = self._it_dispatch_s = self._it_enqueue_s = 0.0
        self._no_credit = False
        self._admitted_since_chunk = [0, 0]
        self._build_programs()
        if self.introspect:
            self._instrument_programs()
        self._stop = threading.Event()
        self._worker = threading.Thread(
            target=self._run, daemon=True, name="unionml-tpu-decode-engine"
        )
        self._harvester = threading.Thread(
            target=self._harvest_loop, daemon=True,
            name="unionml-tpu-decode-harvest",
        )
        self._worker.start()
        self._harvester.start()

    def _build_instruments(self):
        """Register this instance's metric series (get-or-create: the
        family schemas are shared, the ``engine`` label isolates us)."""
        R, lbl = self._registry, {"engine": self.instance}

        def counter(name, help):
            return R.counter(name, help, ("engine",)).labels(**lbl)

        def hist(name, help):
            return R.histogram(name, help, ("engine",)).labels(**lbl)

        self._m_requests = counter(
            "unionml_engine_requests_total",
            "Requests completed and delivered to their waiter.",
        )
        self._m_errors = counter(
            "unionml_engine_errors_total",
            "Requests failed by an engine/admission error.",
        )
        self._m_abandoned = counter(
            "unionml_engine_abandoned_total",
            "Requests whose waiter gave up before completion.",
        )
        self._m_timeouts = counter(
            "unionml_engine_timeouts_total",
            "generate()/generate_stream() waits that hit submit_timeout.",
        )
        self._m_steps = counter(
            "unionml_engine_decode_steps_total",
            "Decode steps dispatched (all slots advance together).",
        )
        self._m_chunks = counter(
            "unionml_engine_chunks_total", "Decode chunks dispatched.",
        )
        self._m_occupied = counter(
            "unionml_engine_occupied_slot_steps_total",
            "Slot-steps dispatched with a live occupant (occupancy "
            "numerator; denominator is decode_steps * slots).",
        )
        self._m_slots_busy = R.gauge(
            "unionml_engine_slots_in_use",
            "Slots currently holding a live request.", ("engine",),
        ).labels(**lbl)
        R.gauge(
            "unionml_engine_slots", "Resident decode slots.", ("engine",)
        ).labels(**lbl).set(self.slots)
        self._h_queue = hist(
            "unionml_engine_queue_wait_ms",
            "Submit-to-admission wait per completed request.",
        )
        self._h_prefill = hist(
            "unionml_engine_prefill_ms",
            "Prefill dispatch-to-first-token-harvest per completed request.",
        )
        self._h_decode = hist(
            "unionml_engine_decode_ms",
            "First-token-to-retirement decode time per completed request.",
        )
        self._h_ttft = hist(
            "unionml_engine_ttft_ms",
            "Submit-to-first-harvested-token per completed request.",
        )
        self._h_dispatch = hist(
            "unionml_engine_chunk_dispatch_ms",
            "Host time to enqueue one decode chunk (sampler keys + jit "
            "call; the dispatcher's per-chunk cost).",
        )
        self._h_harvest = hist(
            "unionml_engine_chunk_harvest_ms",
            "Blocking readback + accounting per harvested decode chunk "
            "(includes in-flight pipeline lag).",
        )
        self._m_spec_rounds = counter(
            "unionml_engine_spec_rounds_total",
            "Speculative rounds whose tokens were served.",
        )
        self._m_spec_accepted = counter(
            "unionml_engine_spec_accepted_tokens_total",
            "Draft tokens accepted by the target verify forward.",
        )
        # fault tolerance: admission control / supervision series
        rejected = R.counter(
            "unionml_engine_rejected_total",
            "Submissions rejected at admission control, by reason "
            "(queue_full -> 429, breaker_open/draining -> 503).",
            ("engine", "reason"),
        )
        self._m_rejected = {
            reason: rejected.labels(engine=self.instance, reason=reason)
            for reason in (
                "queue_full", "breaker_open", "draining", "pool_full",
            )
        }
        self._m_deadline_shed = counter(
            "unionml_engine_deadline_shed_total",
            "Requests shed at dequeue because their deadline expired "
            "before prefill (no device work burned).",
        )
        self._m_recoveries = counter(
            "unionml_engine_recoveries_total",
            "Supervised recoveries: a failed device program failed only "
            "its poisoned batch and the decode state was rebuilt.",
        )
        self._g_breaker = R.gauge(
            "unionml_engine_breaker_open",
            "1 while the circuit breaker rejects submissions.",
            ("engine",),
        ).labels(**lbl)
        self._g_queue_depth = R.gauge(
            "unionml_engine_queue_depth",
            "Requests queued awaiting admission.", ("engine",),
        ).labels(**lbl)
        R.gauge(
            "unionml_engine_recurrent_state_bytes",
            "Device bytes of per-slot recurrent state (the layers that "
            "cache a state of fixed size, not keys and values), all slots; "
            "0 for a module whose every layer caches keys and values.",
            ("engine",),
        ).labels(**lbl).set(self._state_bytes_per_slot * self.slots)
        self._h_drain = hist(
            "unionml_engine_drain_ms",
            "drain() wall time: stop-admissions to queue+slots idle.",
        )
        # per-token attribution (the serving goodput plane): chunk
        # harvest spacing over the chunk's harvested tokens, split by
        # priority class — observed only while the perf plane is on,
        # so a plane-off engine records nothing here. Children are
        # pre-resolved: the harvester must not pay the family-lock
        # labels() lookup per chunk.
        itl = R.histogram(
            "unionml_engine_itl_ms",
            "Inter-token latency per harvested decode chunk (harvest "
            "spacing / tokens in the chunk), by priority class.",
            ("engine", "phase", "priority"),
        )
        self._h_itl = {
            p: itl.labels(
                engine=self.instance, phase=self.phase, priority=p
            )
            for p in PRIORITIES
        }

    def _instrument_programs(self):
        """Wrap the compiled hot-path programs in a cost-analysis
        tracker (docs/observability.md): compile events record XLA
        flops/bytes + compile time per program key, dispatches feed the
        MFU/roofline gauges, and ``stats()["programs"]`` becomes the
        hardware-truth view. The sig lambdas are deliberately ONE shape
        attribute each — they run per dispatch and exist only to tell a
        program's bucketed executables apart."""
        from unionml_tpu.introspection import ProgramTracker

        tr = ProgramTracker(registry=self._registry, component=self.instance)
        self._programs = tr
        self._init_state = tr.wrap("engine.init_state", self._init_state)
        self._prefill = tr.wrap(
            "engine.prefill", self._prefill,
            sig_fn=lambda p, st, slot, place, toks, *a, **k: toks.shape,
        )
        self._prefill_final = tr.wrap(
            "engine.prefill_final", self._prefill_final,
            sig_fn=lambda p, st, fresh, slot, place, toks, *a, **k: toks.shape,
        )
        self._prefill_step = tr.wrap(
            "engine.prefill_chunk", self._prefill_step,
            sig_fn=lambda p, fresh, toks, start: toks.shape,
        )
        self._decode_chunk = tr.wrap("engine.decode", self._decode_chunk)
        self._init_fresh = tr.wrap(
            "engine.init_fresh", self._init_fresh,
            sig_fn=lambda **k: k.get("bucket"),
        )
        if self.prefix_cache is not None:
            self._splice_block = tr.wrap(
                "engine.splice_block", self._splice_block,
                sig_fn=lambda fresh, rows, start: rows[0][0].shape,
            )
            # engine.extract_rows or engine.extract_blocks: the
            # residency's own name for it
            self._extract = tr.wrap(
                f"engine.{self._extract.__name__}", self._extract,
                sig_fn=lambda st, slot, place, **k: k.get("n"),
            )

    def _flight_rec(self, kind: str, **fields) -> None:
        """O(1) flight-recorder append (no-op when introspect=False).
        numpy scalars (slot indices from mask walks) become plain ints
        so a dumped event is always JSON-safe."""
        if self._flight is not None:
            # phase-split fleets tag every lifecycle event with the
            # pool that recorded it (colocated engines stay untagged —
            # the historical event shape is unchanged for them)
            tag = {} if self.phase == "colocated" else {"phase": self.phase}
            self._flight.record(kind, engine=self.instance, **tag, **{
                k: (v.item() if isinstance(v, np.generic) else v)
                for k, v in fields.items()
            })

    def _slots_in_use_locked(self) -> int:
        """Occupied-slot count; call with the lock held."""
        return sum(1 for r in self._occupant if r is not None)

    def _fire(self, point: str) -> None:
        """Chaos-injection site (zero-cost without an injector)."""
        if self._faults is not None:
            self._faults.fire(point)

    @property
    def usage(self):
        """The engine's :class:`~unionml_tpu.serving.usage.UsageLedger`
        (``None`` when metering is off) — share it with the
        ``ServingApp`` so ``GET /debug/usage`` serves this engine's
        per-tenant resource vectors."""
        return self._usage

    @usage.setter
    def usage(self, ledger) -> None:
        """Swap the metering seam on a live engine — ONLY while idle
        (no request in flight), or a request's vector straddles two
        ledgers. An on/off comparison toggles this so both legs run on
        the SAME engine instance (two separately-constructed engines
        differ by several percent from thread/allocator placement
        alone); the
        attribution window is clamped at each chunk's dispatch time,
        so the off-leg's idle gap never inflates the first on-leg
        window."""
        self._usage = ledger or None

    @property
    def perf(self):
        """The engine's :class:`~unionml_tpu.serving.perf
        .ServingPerfPlane` (``None`` when the goodput plane is off) —
        ``GET /debug/goodput`` reads it via :meth:`goodput_report`."""
        return self._perf

    @perf.setter
    def perf(self, plane) -> None:
        """Swap the goodput plane on a live engine — ONLY while idle,
        like the ``usage`` seam above, and for the same reason: an
        on/off comparison runs both legs on the SAME engine instance."""
        self._perf = plane or None
        # the waiting room's fair-share weighting follows the swap
        self._room._usage = self._usage

    @property
    def registry(self):
        """The engine's :class:`~unionml_tpu.telemetry.MetricsRegistry`
        — the fleet router's metrics federation reads it to expose this
        replica's series under the router's ``replica`` label (or to
        skip the merge when the replica already shares the router
        app's registry)."""
        return self._registry

    @property
    def tracer(self):
        """The engine's :class:`~unionml_tpu.telemetry.TraceRecorder`
        — the stitched ``/debug/trace`` fetches this replica's request
        timelines through it (identity with the router app's recorder
        means the local merge already covers them)."""
        return self._tracer

    @property
    def flight(self):
        """The engine's :class:`~unionml_tpu.telemetry.FlightRecorder`
        (``None`` when disabled) — the fleet ``/debug/flight`` merge
        reads replica rings through it."""
        return self._flight

    @property
    def breaker_open(self) -> bool:
        """True while the circuit breaker rejects submissions (the
        cooldown after ``breaker_threshold`` recoveries in the window).
        Reading it keeps the ``unionml_engine_breaker_open`` gauge
        honest — the breaker closes by TIME passing, not by an event."""
        is_open = time.monotonic() < self._breaker_open_until
        self._g_breaker.set(1.0 if is_open else 0.0)
        return is_open

    def _gated_submit(self, reqs: List[_Request]) -> None:
        """Admission control + enqueue, atomically under the engine
        lock (shared by ``generate`` and ``generate_stream``): reject
        BEFORE any request is enqueued, so a multi-prompt call never
        partially admits — and so N concurrent submitters cannot each
        pass a depth check and push the queue past ``max_queue_depth``
        (the exact overload the bound exists for)."""
        with self._lock:
            self._admission_gate_locked(reqs)
            for req in reqs:
                # recorded BEFORE the put, inside the lock: a request's
                # 'submit' flight event can never land after its
                # 'prefill' in the trail. queue_depth = requests ahead.
                self._flight_rec(
                    "submit", rid=req.rid, tenant=req.tenant,
                    priority=req.priority,
                    prompt_tokens=len(req.prompt),
                    queue_depth=self._room.qsize(),
                )
                self._room.put(req)
        self._g_queue_depth.set(self._room.qsize())

    def _usage_rejected(self, reqs: List[_Request], reason: str) -> None:
        """Tenant dimension on admission-control rejections (all reqs
        in one submit share a tenant — one gated call per generate)."""
        if self._usage is not None and reqs:
            self._usage.record_rejected(reqs[0].tenant, reason, len(reqs))

    def _admission_gate_locked(self, reqs: List[_Request]) -> None:
        n_new = len(reqs)
        tenant = reqs[0].tenant if reqs else DEFAULT_TENANT
        if self.paged:
            # a request whose worst case exceeds the WHOLE pool can
            # never be admitted — reject now (transient fullness parks
            # at admission instead; the queue bound sheds the backlog)
            for req in reqs:
                needed = self.kv_pool.blocks_for_rows(
                    min(len(req.prompt) + req.max_new_tokens,
                        self.cache_len)
                )
                if needed > self.kv_pool.capacity:
                    self._m_rejected["pool_full"].inc(n_new)
                    self._usage_rejected(reqs, "pool_full")
                    self._flight_rec(
                        "reject", reason="pool_full", n=n_new,
                        tenant=tenant, needed_blocks=needed,
                        capacity_blocks=self.kv_pool.capacity,
                    )
                    raise Overloaded(
                        f"kv pool can never fit this request: "
                        f"{needed} blocks needed "
                        f"({len(req.prompt)} prompt + "
                        f"{req.max_new_tokens} new tokens), pool "
                        f"capacity {self.kv_pool.capacity} blocks",
                        retry_after_s=60.0,
                    )
        if self._draining:
            self._m_rejected["draining"].inc(n_new)
            self._usage_rejected(reqs, "draining")
            self._flight_rec(
                "reject", reason="draining", n=n_new, tenant=tenant,
            )
            raise EngineUnavailable(
                "decode engine is draining and not accepting requests",
                reason="draining", retry_after_s=1.0,
            )
        remaining = self._breaker_open_until - time.monotonic()
        if remaining > 0:
            self._m_rejected["breaker_open"].inc(n_new)
            self._usage_rejected(reqs, "breaker_open")
            self._flight_rec(
                "reject", reason="breaker_open", n=n_new, tenant=tenant,
            )
            raise EngineUnavailable(
                "decode engine circuit breaker is open "
                f"({len(self._recovery_times)} recent recovery failures); "
                f"retry in {remaining:.1f}s",
                reason="breaker_open", retry_after_s=max(0.1, remaining),
            )
        if self.max_queue_depth is not None:
            depth = self._room.qsize()
            if depth + n_new > self.max_queue_depth:
                self._m_rejected["queue_full"].inc(n_new)
                self._usage_rejected(reqs, "queue_full")
                self._flight_rec(
                    "reject", reason="queue_full", n=n_new,
                    tenant=tenant, queue_depth=depth,
                )
                raise Overloaded(
                    f"decode engine queue is full ({depth} queued + "
                    f"{n_new} new > max_queue_depth "
                    f"{self.max_queue_depth})",
                    retry_after_s=1.0,
                )

    def health(self) -> dict:
        """Readiness surface for ``GET /health``: ``status`` is ``ok``,
        ``degraded`` (circuit breaker open), or ``draining``; plus the
        queue depth and breaker state the transports report."""
        breaker = self.breaker_open
        if self._draining:
            status = "draining"
        elif breaker:
            status = "degraded"
        else:
            status = "ok"
        out = {
            "status": status,
            "queue_depth": self._room.qsize(),
            "breaker_open": breaker,
        }
        if self.phase != "colocated":
            out["phase"] = self.phase
        return out

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain: stop admitting (new submissions raise
        :class:`~unionml_tpu.serving.faults.EngineUnavailable` and
        ``health()`` flips to ``draining``), then block until every
        queued and in-flight request — streams included — has finished
        and all readbacks are harvested. Returns True when fully
        drained, False on ``timeout`` (work may still be in flight;
        admissions stay stopped either way). Reversible with
        :meth:`resume`; observability lands in the
        ``unionml_engine_drain_ms`` histogram."""
        t0 = time.perf_counter()
        self._draining = True
        drained = False
        while True:
            with self._lock:
                drained = (
                    self._room.empty()
                    and self._admitting == 0
                    and self._admission is None
                    and all(r is None for r in self._occupant)
                    and self._inflight.empty()
                )
            if drained:
                break
            if (
                timeout is not None
                and time.perf_counter() - t0 > timeout
            ):
                break
            time.sleep(0.005)
        self._h_drain.observe((time.perf_counter() - t0) * 1e3)
        return drained

    def resume(self) -> None:
        """Reopen admissions after :meth:`drain` (rolling-restart flows
        that drain, swap weights via :meth:`bind`, and serve again)."""
        self._draining = False

    def _block_geometry(self):
        """The SINGLE home for KV block geometry: ``(block, align)``.

        ``block`` is the shared block unit of the paged device pool AND
        the prefix cache's host store — the two must agree (splice and
        extract are per-block copies addressed by table entries), so an
        explicit ``kv_block_size`` that contradicts the attached prefix
        cache raises instead of silently desyncing. ``align`` is the
        bucket rounding unit, ``lcm(block, prefill_chunk)`` — applied
        whenever ANY block consumer is configured (prefix cache, paged
        pool, or the prefix shim), so paged and prefix-cache bucket
        geometry can never disagree either."""
        cache_blk = (
            self.prefix_cache.block_size
            if self.prefix_cache is not None else None
        )
        pool_blk = self._kv_block_size_arg if self.paged else None
        if (
            pool_blk is not None
            and cache_blk is not None
            and pool_blk != cache_blk
        ):
            raise ValueError(
                f"kv_block_size {pool_blk} != prefix cache block_size "
                f"{cache_blk} — the device pool and the host block store "
                "share one block unit (admission splice and harvest "
                "extract are per-block copies); drop kv_block_size or "
                "rebuild the cache with the matching block_size"
            )
        block = pool_blk or cache_blk or (16 if self.paged else None)
        align = block or 1
        if self.prefill_chunk is not None:
            align = math.lcm(align, self.prefill_chunk)
        return block, align

    def _kv_block_nbytes(self, blk: int) -> int:
        """Device bytes of one pool block across every layer that owns
        pool rows (``pool_row_nbytes``: bf16 k/v, or int8 k/v + fp32
        per-(row, head) scales under ``kv_quant``; a latent row as the
        chip tiles it)."""
        return blk * sum(
            l.pool_row_nbytes() for l, kv in zip(self._layout, self._owns_rows) if kv
        )

    def _refuse_recurrent(self, what: str, layout=None) -> None:
        """``what`` restores a sequence from its KV blocks alone; refuse
        it for a module (the served one, or the one with this cache
        ``layout``) with recurrent layers."""
        layout = self._layout if layout is None else layout
        states = sum(not l.owns_rows for l in layout)
        if states:
            raise ValueError(
                f"{what} rebuilds a sequence from its KV blocks, and "
                f"{states} of this module's {len(layout)} "
                "layers keep a recurrent state that a block prefix does not "
                "restore. State snapshots at block boundaries are not built "
                "(ROADMAP.md, Queue 2): serve this module without it"
            )

    def _refuse_blocks(self, what: str) -> None:
        """``what`` cuts a sequence at positions of its own choosing, or
        rebuilds one from rows it did not compute; refuse it for a module
        that generates by blocks, whose committed rows depend on their
        whole block."""
        if self._blocks is not None:
            raise ValueError(
                f"{what} is not built for a module that generates by blocks "
                f"({type(self.module).__name__}: "
                f"block_length {self._blocks.block_length}): a committed row depends on "
                "its whole block, so every cut and every reused prefix would have to lie "
                "on a block boundary, and an open block's decided entries would have to "
                "travel with it (ROADMAP.md, Queue 2 M7): serve this module without it"
            )

    # ------------------------------------------------------------------ #
    # device programs (serving/programs.py; compiled once per shape)
    # ------------------------------------------------------------------ #

    def _build_programs(self):
        """Bind the device programs of :mod:`~unionml_tpu.serving.programs`:
        one family whatever the residency, one call signature (``place``
        is the pool's block ids or table, ``None`` for slot rows)."""
        progs = build_programs(
            self.module, draft=self.draft, speculate_k=self.speculate_k,
            slots=self.slots, rows=self.cache_len,
            pool_blocks=None if self.kv_pool is None else self.kv_pool.num_blocks,
            block=self._kv_block_size, chunk_steps=self.chunk_steps,
            sample=self._sample, eos_id=self.eos_id, pad_id=self.pad_id,
            stale_commit=self._blocks is not None and self._block_stale_commit,
        )
        self._init_state = progs.init_state
        self._init_fresh = progs.init_fresh
        self._prefill = progs.prefill
        self._prefill_step = progs.prefill_step
        self._prefill_final = progs.prefill_final
        self._decode_chunk = progs.decode_chunk
        self._splice_block = progs.splice_block
        self._extract = progs.extract

    # ------------------------------------------------------------------ #
    # public API
    # ------------------------------------------------------------------ #

    def generate(
        self,
        params,
        prompts: Sequence[Sequence[int]],
        *,
        max_new_tokens: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
    ) -> list:
        """Generate for a list of token-id prompts; blocks until all done.

        Compatible with the ``make_lm_predictor`` row-lists contract:
        returns one token list per prompt. ``params`` binds on first call
        (pass serving-ready weights — cast/quantized).

        ``deadline_ms`` (or an ambient :func:`~unionml_tpu.serving
        .faults.deadline_scope` — how ``X-Deadline-Ms`` reaches here
        through the transports) bounds each request's total latency:
        still-queued requests whose deadline expires are shed at
        dequeue with :class:`~unionml_tpu.serving.faults
        .DeadlineExceeded`, before they consume prefill.

        ``tenant`` (or the ambient :func:`~unionml_tpu.serving.usage
        .tenant_scope` the transports open from ``X-Tenant-ID``) names
        who this call's resource vector is billed to when the engine
        runs a usage ledger; defaults to ``anonymous``.

        ``priority`` (or the ambient :func:`~unionml_tpu.serving
        .scheduler.priority_scope` the transports open from
        ``X-Priority``) sets the scheduling class — ``high`` /
        ``normal`` / ``low`` — the waiting room orders admissions by
        and the preemptive scheduler arbitrates pool pressure with
        (docs/robustness.md "Preemption & fairness").
        """
        self.bind(params)
        tenant = (
            validate_tenant(tenant) if tenant is not None
            else current_tenant()
        )
        priority = (
            validate_priority(priority) if priority is not None
            else current_priority()
        )
        if max_new_tokens is None:
            # the ambient per-request cap the transports open from the
            # /predict payload's max_new_tokens field (the deadline-
            # scope pattern) — how a caller's cap survives the router
            # hop without threading a kwarg through every predictor
            max_new_tokens = current_token_cap()
        n = max_new_tokens if max_new_tokens is not None else self.max_new_tokens
        if not 1 <= n <= self.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {n} outside [1, {self.max_new_tokens}] "
                "(raise the engine's max_new_tokens)"
            )
        if deadline_ms is None:
            deadline_ms = current_deadline_ms()
        # validate EVERY prompt before creating any request or trace
        # rid, so a bad later prompt cannot leak earlier ones' state
        rows = [self._canonical_row(p) for p in prompts]
        reqs = []
        for row in rows:
            req = _Request(
                prompt=row, max_new_tokens=n, tenant=tenant,
                priority=priority,
            )
            if deadline_ms is not None:
                req.deadline = req.submitted + deadline_ms / 1e3
            req.rid = self._tracer.new_request("generate")
            reqs.append(req)
        try:
            self._gated_submit(reqs)
        except BaseException:
            # rejected before enqueue: close the trace timelines or the
            # recorder leaks one live request per shed submission —
            # precisely under the sustained overload shedding exists for
            for req in reqs:
                self._tracer.finish_request(req.rid)
            raise
        out = []
        for req in reqs:
            if not req.event.wait(self.submit_timeout):
                # abandon the whole call: queued siblings are dropped at
                # admission and in-slot ones retired at the next harvest,
                # so orphans stop burning device time and slots
                self._m_timeouts.inc()
                for r in reqs:
                    r.abandoned = True
                raise TimeoutError("decode engine did not finish in time")
            if req.error is not None:
                raise req.error
            out.append(list(req.tokens))
        return out

    def generate_stream(
        self,
        params,
        prompt: Sequence[int],
        *,
        max_new_tokens: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
    ):
        """Yield token chunks for ONE prompt as the engine harvests them.

        The streaming surface behind ``POST /predict/stream``: the first
        chunk arrives after prefill (one token — the TTFT event), then
        one chunk per harvested decode chunk (``chunk_steps`` tokens at
        the engine's natural emission granularity). Concatenating the
        chunks yields exactly ``generate(params, [prompt])[0]`` (tested
        in tests/unit/test_engine.py). Raises the engine's error, or
        ``TimeoutError`` when no chunk lands within ``submit_timeout``.
        For a module that generates by blocks a prefill yields no token:
        the first chunk is the first block's generated entries, and every
        chunk carries whole blocks (the last one cut at the asked length).
        """
        self.bind(params)
        tenant = (
            validate_tenant(tenant) if tenant is not None
            else current_tenant()
        )
        priority = (
            validate_priority(priority) if priority is not None
            else current_priority()
        )
        if max_new_tokens is None:
            max_new_tokens = current_token_cap()  # payload-field cap
        n = max_new_tokens if max_new_tokens is not None else self.max_new_tokens
        if not 1 <= n <= self.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {n} outside [1, {self.max_new_tokens}] "
                "(raise the engine's max_new_tokens)"
            )
        if deadline_ms is None:
            deadline_ms = current_deadline_ms()
        row = self._canonical_row(prompt)
        req = _Request(
            prompt=row, max_new_tokens=n, stream=queue.Queue(),
            tenant=tenant, priority=priority,
        )
        if deadline_ms is not None:
            req.deadline = req.submitted + deadline_ms / 1e3
        req.rid = self._tracer.new_request("stream")
        try:
            self._gated_submit([req])
        except BaseException:
            self._tracer.finish_request(req.rid)  # no leak on rejection
            raise
        try:
            while True:
                try:
                    chunk = req.stream.get(timeout=self.submit_timeout)
                except queue.Empty:
                    self._m_timeouts.inc()
                    raise TimeoutError(
                        "decode engine produced no chunk in time"
                    ) from None
                if chunk is None:
                    if req.error is not None:
                        raise req.error
                    return
                yield chunk
        finally:
            # consumer stopped early (client disconnect → GeneratorExit,
            # timeout, error): mark abandoned so the slot is retired at
            # the next harvest instead of decoding to max_new_tokens for
            # a dead request
            if not req.event.is_set():
                req.abandoned = True

    def _canonical_row(self, prompt) -> np.ndarray:
        """The engine's canonical prompt row: left-truncated to the
        user budget, system prefix prepended — ONE home shared by the
        generate paths and the KV export, so a disaggregated prefill
        engine and its decode peer (configured identically) key the
        same bytes under the same tokens."""
        row = np.asarray(prompt, dtype=np.int32).ravel()
        if row.size == 0:
            raise ValueError("empty prompt")
        row = row[-self._user_max:]
        if self._prefix_tokens is not None:
            row = np.concatenate([self._prefix_tokens, row])
        return row

    def prefill_export(
        self,
        params,
        prompt: Sequence[int],
        *,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
    ) -> dict:
        """Prefill-only admission — the disaggregated serving prefill
        leg (docs/serving.md "Disaggregated serving", DistServe/
        Splitwise lineage): run the prompt's (possibly chunked)
        prefill through the NORMAL admission machinery, let the
        harvest finalize the prompt's full KV blocks into the host
        prefix-cache store (the same extract/insert path every
        admission takes — pointer handoff, no extra copies), and
        return a KV handle instead of streaming:

        ``{"tokens": [first_token], "prompt": [...canonical row...],
        "cached_tokens": N, "lease": PrefixLease, "rid": ...}``

        The first sampled token gives the router its TTFT emission;
        ``lease`` pins the exported path against LRU eviction until
        the decode leg has spliced (release it exactly once — it is
        idempotent, so the router's finally is safe under retries);
        ``cached_tokens`` is how much of the prompt a decode engine
        sharing this host store will splice instead of recomputing.
        Blocks that could not be stored (byte budget) simply shrink
        the match — the decode leg recomputes the difference, so the
        handoff degrades, never errors. Billing is exactly a normal
        1-token request's: the prefill window goes to the admitting
        tenant under this engine's ``phase`` label."""
        self._refuse_recurrent("prefill_export")
        self._refuse_blocks("prefill_export")
        if self.prefix_cache is None:
            raise ValueError(
                "prefill_export needs a prefix cache — the harvested "
                "KV blocks land in its host block store for the decode "
                "leg to splice; construct the engine with "
                "prefix_cache=True (or a shared RadixPrefixCache)"
            )
        self.bind(params)
        tenant = (
            validate_tenant(tenant) if tenant is not None
            else current_tenant()
        )
        priority = (
            validate_priority(priority) if priority is not None
            else current_priority()
        )
        if deadline_ms is None:
            deadline_ms = current_deadline_ms()
        row = self._canonical_row(prompt)
        req = _Request(
            prompt=row, max_new_tokens=1, tenant=tenant, priority=priority,
        )
        req._kv_event = threading.Event()
        if deadline_ms is not None:
            req.deadline = req.submitted + deadline_ms / 1e3
        req.rid = self._tracer.new_request("prefill")
        try:
            self._gated_submit([req])
        except BaseException:
            self._tracer.finish_request(req.rid)  # no leak on rejection
            raise
        if not req.event.wait(self.submit_timeout):
            self._m_timeouts.inc()
            req.abandoned = True
            raise TimeoutError("prefill did not finish in time")
        if req.error is not None:
            raise req.error
        # the request finished at its prefill harvest; the insert
        # entry carrying its KV blocks into the host store is FIFO
        # right behind it — wait for the lease release that marks the
        # insert processed, so the handle's lease actually covers the
        # just-exported path (a timeout here degrades to a shorter
        # match, never an error)
        req._kv_event.wait(self.submit_timeout)
        lease = self.prefix_cache.lease(row)
        return {
            "tokens": list(req.tokens),
            "prompt": [int(t) for t in row],
            "cached_tokens": int(lease.n_tokens),
            "lease": lease,
            "rid": req.rid,
            "engine": self.instance,
        }

    def kv_export(
        self, prompt: Sequence[int], *, wait_s: float = 0.25,
    ) -> List[dict]:
        """Export the host prefix-cache block entries covering
        ``prompt`` — the donor half of the CROSS-PROCESS KV handoff
        (the ``POST /debug/kv/export`` handler; same-host pools share
        the store object and never need this). ``wait_s`` bounds a
        short poll for in-flight inserts: the caller typically asks
        right after its prefill response, while the harvest pipeline
        may still be attaching the final blocks — whatever is covered
        when the budget expires is exported (the decode side
        recomputes the rest: degrade, never error)."""
        self._refuse_recurrent("kv_export")
        self._refuse_blocks("kv_export")
        cache = self.prefix_cache
        if cache is None:
            raise ValueError(
                "no prefix cache on this engine — KV export needs the "
                "host block store; construct with prefix_cache=True"
            )
        row = self._canonical_row(prompt)
        target = (len(row) // cache.block_size) * cache.block_size
        deadline = time.monotonic() + max(0.0, wait_s)
        while cache.peek(row) < target and time.monotonic() < deadline:
            time.sleep(0.005)
        return cache.export_request(row)

    def kv_import(self, entries: Sequence[dict]) -> int:
        """Attach a donor's exported block entries to this engine's
        host prefix-cache store (the ``POST /debug/kv/import``
        handler / the router's cross-store transfer): each entry
        rides the normal insert budget/eviction machinery; returns
        blocks newly attached."""
        self._refuse_recurrent("kv_import")
        self._refuse_blocks("kv_import")
        cache = self.prefix_cache
        if cache is None:
            raise ValueError(
                "no prefix cache on this engine — KV import needs the "
                "host block store; construct with prefix_cache=True"
            )
        return int(cache.import_blocks(entries))

    def bind(self, params):
        """Set (or swap) the served weights; state allocates lazily.

        Swapping while requests are in flight would mix weights within a
        decode (later chunks of an in-flight request would run under the
        new tree against a KV cache built with the old one) — refuse
        instead of corrupting silently.
        """
        if params is self._params:
            return
        if self.draft is not None:
            from collections.abc import Mapping

            if not (
                isinstance(params, Mapping)
                and "target" in params
                and "draft" in params
            ):
                raise ValueError(
                    'a speculative engine binds a mapping {"target": '
                    'params, "draft": params} (the '
                    "make_speculative_predictor artifact contract)"
                )
        with self._lock:
            busy = (
                any(r is not None for r in self._occupant)
                or self._admitting > 0
                or not self._room.empty()
                # a preempted stream in evict→resume limbo lives only
                # in the in-flight pipeline: its host KV belongs to
                # the CURRENT weights, so a swap must wait for it
                or not self._inflight.empty()
            )
            if self._params is not None and busy:
                raise RuntimeError(
                    "cannot swap engine params while requests are in "
                    "flight — drain the engine (or create a new one) first"
                )
            if self._params is not None and self.prefix_cache is not None:
                # stored KV blocks belong to the OLD weights; splicing
                # them under the new tree would corrupt silently (pin
                # registrations survive — the prefix re-pins on
                # reinsert). The device-resident splice memo goes with
                # them.
                self.prefix_cache.clear()
                self._dev_splice.clear()
            self._params = params

    def warmup(self, params) -> int:
        """Pre-compile the engine executables: per bucket, the cold
        prefill, and — with a prefix cache — that bucket's cached
        admission path too (splice + ``[1, block]`` finish via a
        full-hit pass, the ``[1, block]`` lead chunk via a partial-hit
        pass where the bucket has room), plus the decode chunk and the
        extract programs. A live request must never pay a serve-time
        XLA compile just because it HIT the cache. Returns the number
        of cold-path executables; the cache is left empty."""
        self.bind(params)
        # 2 tokens, not 1: a 1-token request completes at prefill and
        # would never compile the decode chunk
        n = min(2, self.max_new_tokens)
        for b in self.buckets:
            if self.prefix_cache is not None:
                # each bucket must MISS first so its cold program
                # compiles (every admission inserts, and the warmup
                # prompts share prefixes across buckets)
                self.prefix_cache.clear()
            ones = np.ones(b - self.prefix_len, np.int32)
            self.generate(params, [ones], max_new_tokens=n)
            if self.prefix_cache is not None:
                blk = self.prefix_cache.block_size
                # full hit: splices + the [1, block] finish program
                self.generate(params, [ones], max_new_tokens=n)
                if b >= 3 * blk:
                    # partial hit (>= 1 matched block, >= 2 uncovered):
                    # compiles the [1, block] lead-chunk program
                    part = ones.copy()
                    part[-2 * blk:] = 2
                    self.generate(params, [part], max_new_tokens=n)
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        return len(self.buckets) + 1

    def stats(self) -> dict:
        """Serving observability: request timing splits + slot occupancy.

        A thin view over this instance's telemetry-registry series (the
        same numbers ``GET /metrics`` exposes) keeping the historical
        key shape; percentiles come from the histograms' exact sample
        windows, not bucket interpolation."""
        steps = int(self._m_steps.value)
        occupied = int(self._m_occupied.value)
        out = {
            "engine": "continuous",
            "phase": self.phase,
            "slots": self.slots,
            "chunk_steps": self.chunk_steps,
            "pipeline_depth": self.pipeline_depth,
            "completed_requests": int(self._m_requests.value),
            "decode_steps": steps,
            "slot_occupancy": round(occupied / max(1, steps * self.slots), 3),
        }
        out["generation"] = {"scheme": "next_token"}
        if self._blocks is not None:
            window = self._perf.report() if self._perf is not None else {}
            fwd, commits = window.get("block_forwards", 0), window.get("block_commits", 0)
            out["generation"] = {
                "scheme": "block_diffusion",
                "block_length": self._blocks.block_length,
                "denoising_steps": self._blocks.denoising_steps,
                "remasking": self._blocks.remasking,
                "threshold": self._blocks.threshold,
                # of the perf plane's window: tokens decided a live forward,
                # and live forwards a block committed (denoising_steps at the
                # most, a commit riding with the next block's first forward;
                # a request's last block takes none)
                "tokens_per_forward": round(window.get("tokens_decided", 0) / fwd, 4) if fwd else None,
                "forwards_per_block": round(fwd / commits, 4) if commits else None,
            }
        if self.draft is not None:
            spec_rounds = int(self._m_spec_rounds.value)
            spec_accepted = int(self._m_spec_accepted.value)
            out["speculative"] = {
                "k": self.speculate_k,
                "rounds": spec_rounds,
                "accepted_draft_tokens": spec_accepted,
                # fraction of proposed draft tokens the target accepted
                "acceptance_rate": round(
                    spec_accepted / max(1, spec_rounds * self.speculate_k), 3
                ),
            }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self.kv_pool is not None:
            from unionml_tpu.ops.paged_attention import _pages_per_step, score_tile

            rows = next(l for l in self._layout if l.owns_rows)
            out["kv_pool"] = {
                **self.kv_pool.stats(),
                # what a cached position costs over every layer that
                # owns rows, as the chip tiles it, and what the row is
                "bytes_per_token": self._kv_block_nbytes(1),
                "row_layout": rows.kind,  # "kv", "latent" or "kv+index"
                # what the decode kernel walks: a live row's visible
                # blocks (at most the table's width), this many a group
                "table_width": self._table_width,
                "kernel_blocks_per_group": _pages_per_step(
                    self._kv_block_size, *rows.pool_row, self._table_width,
                ),
            }
            if getattr(rows, "q_heads", 0):
                # [query rows, columns] of the score tile a group of the
                # kernel ``paged_attention`` works on (rows of keys and values)
                out["kv_pool"]["score_tile"] = score_tile(
                    self._kv_block_size, rows.q_heads, *rows.pool_row, self._table_width,
                    queries=self._step_rows or 1, fused=rows.fused,
                )
        if self._index_topk is not None and self._perf is not None:
            # a learned selection: of the cached rows the dispatched steps'
            # live sequences could see, those the selection lets their
            # attention read (reckoned from the slots' fills)
            report = self._perf.report()
            out["attention"] = {
                "index_layers": self._index_layers,
                "index_topk": self._index_topk,
                # "walk": a step fetches a sequence's visible rows and
                # weighs the selected ones; "dense": the table cannot hold
                # a row that is not selected (None: no block pool)
                "decode_read": self._decode_read,
                "selected_positions": report["selected_positions"],
                "visible_positions": report["visible_positions"],
            }
        if self._state_layers:
            out["state"] = {
                "layers": self._state_layers,
                "bytes_per_slot": self._state_bytes_per_slot,
                "bytes_resident": self._state_bytes_per_slot * self.slots,
            }
            # what a state layer's decode kernel moves besides the states,
            # where the module counts it from its shapes
            operand_bytes = getattr(self.module, "step_operand_bytes", None)
            if operand_bytes is not None:
                out["state"]["step_operand_bytes"] = operand_bytes(self.slots)
        moe_dispatch = getattr(self.module, "moe_dispatch", None)
        if moe_dispatch is not None and moe_dispatch(self.slots) is not None:
            # what each compiled program's expert layers do with its rows:
            # the dispatch, and the rows computed over the rows routed
            chunk = self.prefill_chunk or self.buckets[-1]
            out["moe"] = {
                "decode_chunk": moe_dispatch(self.slots * (self._step_rows or self._round_stride)),
                **{f"prefill_{b}": moe_dispatch(min(b, chunk)) for b in self.buckets},
            }
        if self._usage is not None:
            # the compact per-tenant view (GET /debug/usage has the
            # full per-tenant resource vectors)
            out["usage"] = self._usage.stats()
        if self._programs is not None:
            # hardware truth per compiled program: flops/bytes, compile
            # counts, MFU/roofline ratios (docs/observability.md)
            out["programs"] = self._programs.stats()
        out["robustness"] = {
            "queue_depth": self._room.qsize(),
            "rejected": {
                reason: int(c.value)
                for reason, c in self._m_rejected.items()
            },
            "deadline_shed": int(self._m_deadline_shed.value),
            "recoveries": int(self._m_recoveries.value),
            "breaker_open": self.breaker_open,
            "draining": self._draining,
        }
        # the preemptive scheduler's view: per-class waiting depths,
        # parked pool-exhausted admissions, evictions performed
        out["scheduler"] = self._sched.stats()
        for name, h in (
            ("queue_wait_ms", self._h_queue),
            ("prefill_ms", self._h_prefill),
            ("decode_ms", self._h_decode),
            ("ttft_ms", self._h_ttft),
        ):
            summary = h.summary()
            if summary:
                out[name] = summary
        # decode-lane-pure inter-token latency (the perf plane's
        # chunk-spacing histograms merged across priority classes):
        # unlike decode_ms, no harvest/admission gaps are lumped in
        itl = self._itl_summary()
        if itl:
            out["itl_ms"] = itl
            out["itl_mean_ms"] = itl["mean"]
            out["itl_p99_ms"] = itl["p99"]
        if self._perf is not None:
            out["goodput"] = self._perf.report()
        return out

    def _itl_summary(self) -> dict:
        """Exact percentile summary of the ITL histograms' retained
        windows merged across this engine's priority children
        (``{}`` when the plane is off or nothing decoded yet)."""
        samples: List[float] = []
        for child in self._h_itl.values():
            samples.extend(child.samples())
        if not samples:
            return {}
        return telemetry.percentile_summary(samples)

    def goodput_report(self) -> dict:
        """The ``GET /debug/goodput`` body for this engine: the perf
        plane's ring classification + ratios + watchdog advisory,
        with the ITL/TTFT summaries and — when introspection is on —
        the per-program MFU/roofline view, so achieved tokens/s and
        hardware utilization read off one dashboard. Raises
        ``ValueError`` when the plane is off (transports map it to
        422)."""
        if self._perf is None:
            raise ValueError(
                "serving perf plane is off — construct the engine "
                "with perf=True (the default while introspect=True)"
            )
        out = self._perf.report()
        itl = self._itl_summary()
        if itl:
            out["itl_ms"] = itl
        ttft = self._h_ttft.summary()
        if ttft:
            out["ttft_ms"] = ttft
        if self._programs is not None:
            progs = self._programs.stats()
            out["programs"] = {
                name: {
                    "mfu": p["mfu"],
                    "hbm_utilization": p.get("hbm_utilization"),
                    "achieved_flops_per_s": p.get("achieved_flops_per_s"),
                }
                for name, p in progs.items()
                if isinstance(p, dict) and "mfu" in p
            }
        return out

    def reset_stats(self) -> None:
        """Zero this instance's observability series (benchmarks call
        this between scenarios so each phase's /stats describes only
        that phase); scrapers see the resets as counter restarts."""
        for m in (
            self._m_requests, self._m_errors, self._m_abandoned,
            self._m_timeouts, self._m_steps, self._m_chunks,
            self._m_occupied, self._m_spec_rounds, self._m_spec_accepted,
            self._m_deadline_shed, self._m_recoveries,
            *self._m_rejected.values(),
            self._h_queue, self._h_prefill, self._h_decode, self._h_ttft,
            self._h_dispatch, self._h_harvest, self._h_drain,
            *self._h_itl.values(),
        ):
            m.reset()
        if self._perf is not None:
            self._perf.reset()
        if self.prefix_cache is not None:
            self.prefix_cache.reset_stats()
        if self.kv_pool is not None:
            self.kv_pool.reset_stats()
        if self._usage is not None:
            self._usage.reset_stats()
        if self._programs is not None:
            self._programs.reset()
        self._sched.reset_stats()

    def close(self):
        self._stop.set()
        self._worker.join(timeout=5.0)
        self._harvester.join(timeout=5.0)
        with self._lock:
            adm, self._admission = self._admission, None
        if adm is not None:
            self._drop_admission(adm.req, RuntimeError("decode engine closed"))
        while True:
            parked = self._room.take_parked()
            if parked is None:
                break
            self._drop_admission(parked, RuntimeError("decode engine closed"))
        # drain the in-flight pipeline the harvester no longer owns:
        # stranded insert entries still hold lease refcounts — leaking
        # them would pin blocks in a user-supplied cache forever — and
        # a stranded preempt entry holds a request in evict→resume
        # limbo that no queue or slot structure can see
        while True:
            try:
                entry = self._inflight.get_nowait()
            except queue.Empty:
                break
            if entry[0] == "insert":
                self._release_lease(entry[2])
            elif entry[0] == "preempt":
                self._fail_orphan(
                    entry[2], RuntimeError("decode engine closed")
                )
        for req in self._room.pop_all():
            req.error = RuntimeError("decode engine closed")
            self._release_lease(req)  # a resumed-queued stream's pin
            self._tracer.finish_request(req.rid)
            req.event.set()
            req.finish_stream()
        for req in self._occupant:
            if req is not None:
                req.error = RuntimeError("decode engine closed")
                self._tracer.finish_request(req.rid)
                self._release_lease(req)
                req.event.set()
                req.finish_stream()
        self._occupant = [None] * self.slots
        self._m_slots_busy.set(0)

    # ------------------------------------------------------------------ #
    # engine loop
    # ------------------------------------------------------------------ #

    def _bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        return self.buckets[-1]

    def _asked_arg(self, req: _Request) -> tuple:
        """What a prefill program takes behind its key: by blocks the
        tokens the request asks for (the device ends it with the last of
        them decided), otherwise nothing."""
        return () if self._blocks is None else (jnp.int32(req.max_new_tokens),)

    def _tokens_due(self, req: _Request) -> int:
        """Generation by blocks: the tokens that the forwards dispatched
        for ``req`` are sure to have emitted. A block is emitted by the
        forward that decides its last entry, at the latest the last of the
        ``forwards_per_block`` it takes (the next block's first forward
        commits it besides), and the first block may hold as little as one
        generated entry."""
        blocks = req._forwards // self._blocks.forwards_per_block
        held = len(req.prompt) % self._blocks.block_length
        return max(0, blocks * self._blocks.block_length - held)

    def _next_key(self, num: int = 1):
        self._key, *subs = jax.random.split(self._key, num + 1)
        return subs

    def _admission_preamble(self, req: _Request):
        """The shared start of every admission (monolithic and chunked —
        ONE home so timing/padding policy cannot desync): pick the free
        slot, stamp queue-wait, right-pad the prompt to its bucket."""
        with self._lock:
            slot = self._occupant.index(None)
        t0 = time.perf_counter()
        if req._preempted_at:
            # a resumed stream: the original queue wait already landed
            # in the histogram/span — record the evict→re-admit gap as
            # its own span instead of corrupting the queue timing
            self._tracer.record_span(
                req.rid, f"resume-wait[{req._preempts - 1}]",
                req._preempted_at, t0,
            )
        else:
            req.queue_wait_ms = (t0 - req.submitted) * 1e3
            self._tracer.record_span(req.rid, "queue", req.submitted, t0)
        req._dispatch_t = t0
        bucket = self._bucket_for(len(req.prompt))
        padded = np.full(bucket, self.pad_id, np.int32)
        padded[: len(req.prompt)] = req.prompt
        return slot, bucket, padded

    def _admit(self, req: _Request):
        """Dispatch ``req``'s prefill into a free slot WITHOUT blocking on
        the first token (its readback is harvested later, in dispatch
        order). Dispatcher thread only; occupancy mutates under the lock."""
        slot, _bucket, padded = self._admission_preamble(req)
        (key,) = self._next_key()
        with self._lock:
            ep0 = self._epoch
            st = self._state
            ids = (
                self._take_covered_locked(req, slot, _bucket)
                if self.paged else None
            )
        if st is None:
            st = self._init_state()
        with self._tracer.span(
            req.rid, "admit.enqueue", annotation="engine.admit.enqueue",
            program="prefill",
        ) as sp:
            new_state, first = self._prefill(
                self._params, st, jnp.int32(slot), _place(ids),
                jnp.asarray(padded), jnp.int32(len(req.prompt)), key,
                *self._asked_arg(req),
            )
        self._it_enqueue_s += sp.end_s - sp.start_s
        _start_host_copy(first)
        if self._usage is not None:
            # the monolithic prefill's cost-analysis FLOPs, accumulated
            # for attribution at this request's prefill harvest
            req._attr_flops += self._program_cost(
                "engine.prefill", tuple(padded.shape)
            )
        with self._lock:
            if self._epoch != ep0:
                # _recover ran (harvester thread) while this prefill was
                # in flight: new_state derives from the invalidated
                # resident buffers — DISCARD it (self._state stays the
                # recovery's None, so the next admission rebuilds) and
                # fail this request with the poisoned batch (the raise
                # lands in _start_admission's error path).
                raise RuntimeError(
                    "engine recovered while this admission's prefill "
                    "was in flight; the request failed with the "
                    "poisoned batch"
                )
            self._state = new_state
            self._occupant[slot] = req
            self._slot_gen[slot] += 1
            # resumed streams already hold harvested tokens; dispatch
            # accounting continues from them (fresh admissions: 0 + 1;
            # by blocks a prefill yields no token)
            req._expected = len(req.tokens) + (self._blocks is None)
            self._m_slots_busy.set(self._slots_in_use_locked())
        self._admitted_since_chunk[0] += 1
        self._admitted_since_chunk[1] += req._prefilled_tokens
        # admission segment: dispatch start → prefill program enqueued
        # (host-side admission machinery; the device part of prefill
        # lands in prefill_ms at harvest)
        req.admission_ms = (time.perf_counter() - req._dispatch_t) * 1e3
        self._flight_rec(
            "prefill", rid=req.rid, tenant=req.tenant, slot=slot,
            bucket=_bucket, tokens=req._prefilled_tokens,
            cached_tokens=req._saved_tokens,
        )
        self._inflight.put(("prefill", ep0, slot, req, first))
        self._schedule_insert(req, slot, ep0)

    def _device_splice_rows(self, blocks):
        """Device-resident rows for one splice unit (a tuple of cached
        host block trees), LRU-memoized on the blocks' object identity:
        a hot prefix — the pinned ``system_prefix`` above all — uploads
        host→device ONCE, then every later admission splices the
        resident copy. Each entry keeps the host tuples alive, so an
        ``id()`` key can never be recycled while its entry lives.
        Dispatcher thread only."""
        key = tuple(id(b) for b in blocks)
        hit = self._dev_splice.get(key)
        if hit is not None:
            self._dev_splice.move_to_end(key)
            return hit[1]
        host = blocks[0] if len(blocks) == 1 else _concat_rows(blocks)
        dev = jax.tree_util.tree_map(jnp.asarray, host)
        self._dev_splice[key] = (blocks, dev)
        while len(self._dev_splice) > self._dev_splice_cap:
            self._dev_splice.popitem(last=False)
        return dev

    def _schedule_insert(self, req: _Request, slot: int, epoch: int) -> None:
        """Dispatcher, right after a prefill dispatch: extract the
        slot's leading resident rows in ONE compiled dispatch, kick the
        async device→host copy, and queue the tree insert behind the
        in-flight readbacks — the harvester materializes the bytes once
        they are already local and splits them into blocks, so neither
        thread blocks on the transfer. Fully-matched prompts skip the
        extraction; the entry always carries the request so its lease is
        released only after the insert could build on live ancestors."""
        cache = self.prefix_cache
        if cache is None:
            return
        nb = len(req.prompt) // cache.block_size
        first_new = min(req._matched_blocks, nb)
        st = self._state  # one read: _recover may null it concurrently
        if first_new >= nb or st is None:
            rows = None  # nothing new to store — release-only entry
        else:
            # the bucket's leading rows of the slot, one compiled
            # dispatch per bucket: a row window, or the slot's blocks BY
            # TABLE ENTRY (uncovered tail entries gather the trash block
            # and are never inserted)
            n = self._bucket_for(len(req.prompt))
            ids = None
            if self.paged:
                with self._lock:
                    ids = self._table[slot, : n // self._kv_block_size].copy()
            rows = self._extract(st, jnp.int32(slot), _place(ids), n=n)
            for layer in rows:
                for buf in layer:
                    _start_host_copy(buf)
        self._inflight.put(("insert", epoch, req, first_new, rows))

    def _release_lease(self, req: _Request) -> None:
        """Unpin the request's matched cache blocks AND any resume pin
        (idempotent; error paths and the insert path may both get
        here). Entry ordering makes releasing both safe: an insert
        entry for admission N always processes before the preempt
        entry that would set a new resume lease."""
        lease, req._lease = req._lease, None
        if lease is not None:
            lease.release()
        self._release_resume_lease(req)
        if req._kv_event is not None:
            # prefill_export waits on this: the normal insert entry
            # lands here after attaching the request's KV blocks, and
            # every failure path lands here too — the export waiter
            # wakes either way (checking req.error), never hangs
            req._kv_event.set()

    def _release_resume_lease(self, req: _Request) -> None:
        """Drop the pin holding a preempted stream's evicted KV blocks
        in the host cache — once the resume admission has taken its
        own match lease over the same path, or on any terminal path
        (idempotent)."""
        lease, req._resume_lease = req._resume_lease, None
        if lease is not None:
            lease.release()

    def _fail_orphan(self, req: _Request, exc: BaseException) -> None:
        """Fail a request stranded in evict→resume limbo: between its
        preemption and its requeue it lives ONLY in the in-flight
        pipeline, so recovery and close cannot reach it through any
        occupant/queue structure — the preempt entry's failure arms
        must finish it or its waiter hangs forever. Its pool blocks
        were already released at eviction (generation-guarded against
        a concurrent pool reset)."""
        with self._lock:
            if req.event.is_set():
                return
            req.error = exc
        self._release_lease(req)
        self._m_errors.inc()
        if self._usage is not None:
            self._usage.record_drop(req.tenant, "error")
        self._flight_rec(
            "drop", rid=req.rid, tenant=req.tenant,
            cause=f"error:{type(exc).__name__}",
        )
        self._tracer.finish_request(req.rid)
        req.event.set()
        req.finish_stream()

    # ------------------------------------------------------------------ #
    # usage metering helpers (no-ops when usage=None)
    # ------------------------------------------------------------------ #

    def _program_cost(self, key: str, sig=None) -> float:
        """Cost-analysis FLOPs of one dispatch of a tracked program
        (0 when introspection is off or the program never compiled) —
        the per-dispatch numerator the ledger splits across tenants."""
        if self._programs is None:
            return 0.0
        return self._programs.cost(key, sig)[0]

    def _usage_kv_release(self, req: _Request) -> None:
        """Integrate the request's pool-block hold times into its
        tenant's KV block-seconds (idempotent: the stamp list drains).
        Called on every path that gives the blocks back — retirement,
        mid-admission drop, and recovery — so no hold window is left
        open for an abandoned or poisoned request."""
        if self._usage is None or not req._block_t0:
            req._block_t0 = []
            return
        now = time.monotonic()
        held = sum(now - t0 for t0 in req._block_t0)
        req._block_t0 = []
        self._usage.record_kv_block_seconds(req.tenant, held)

    # ------------------------------------------------------------------ #
    # paged-mode pool bookkeeping (engine lock held for all of these)
    # ------------------------------------------------------------------ #

    def _sweep_deferred_locked(self) -> None:
        """Free deferred block batches whose fence has passed: every
        decode chunk dispatched before the owning slot retired has been
        harvested, so no in-flight program can still write the rows."""
        if not self._deferred_free:
            return
        keep = []
        for fence, ids in self._deferred_free:
            if fence <= self._harvest_seq:
                self.kv_pool.give(ids)
            else:
                keep.append((fence, ids))
        self._deferred_free = keep

    def _take_covered_locked(self, req: _Request, slot: int,
                             bucket: int) -> np.ndarray:
        """Convert the leading ``ceil(true_len / block)`` of the
        request's reservation into concrete pool blocks, install them
        in the slot's table row, and return the scatter id vector
        ([bucket/block] int32, trash-padded) the prefill program
        consumes. The rest of the reservation converts lazily as
        decode fills rows (_grow_tables_locked)."""
        blk = self._kv_block_size
        nbb = bucket // blk
        covered = self.kv_pool.blocks_for_rows(len(req.prompt))
        ids = np.zeros(nbb, np.int32)
        self._table[slot, :] = 0
        t_take = time.monotonic() if self._usage is not None else 0.0
        for j in range(covered):
            bid = self.kv_pool.take()
            req._resv_blocks -= 1
            req._block_ids.append(bid)
            if self._usage is not None:
                req._block_t0.append(t_take)
            ids[j] = bid
            self._table[slot, j] = bid
        self._slot_covered[slot] = covered
        self._slot_rows[slot] = len(req.prompt)
        return ids

    def _grow_tables_locked(self) -> np.ndarray:
        """Grow every live slot's block table to cover the NEXT decode
        chunk's worst-case advance (``chunk_steps`` rows; by blocks a
        commit a forward, a block each, and behind them the two blocks'
        rows that a forward writes), drawing from
        each request's admission-time reservation — which is why growth
        can never fail — and return the table snapshot the chunk
        dispatch uploads. Rows past a request's reserved budget stay on
        the trash block: only overshoot (post-eos / post-budget device
        writes whose tokens the host discards) ever lands there."""
        used_rows = 0
        for slot, req in enumerate(self._occupant):
            if req is None:
                continue
            target_rows = min(
                self._slot_rows[slot] + self._chunk_advance + self._step_rows,
                req._rows_cap,
            )
            want = min(
                self.kv_pool.blocks_for_rows(target_rows),
                self._table_width,
            )
            while self._slot_covered[slot] < want and req._resv_blocks > 0:
                bid = self.kv_pool.take()
                req._resv_blocks -= 1
                req._block_ids.append(bid)
                if self._usage is not None:
                    req._block_t0.append(time.monotonic())
                self._table[slot, self._slot_covered[slot]] = bid
                self._slot_covered[slot] += 1
            used_rows += min(self._slot_rows[slot], req._rows_cap)
        self.kv_pool.note_used_rows(used_rows)
        return self._table.copy()

    def _release_blocks_locked(self, req: _Request,
                               slot: Optional[int] = None) -> None:
        """Retirement-path release: taken blocks go on the DEFERRED
        list fenced at the current dispatch seq (an in-flight chunk
        dispatched before this retirement may still write them — the
        free lands only after its harvest); the untaken reservation
        releases immediately (never in any table)."""
        self._usage_kv_release(req)
        ids, req._block_ids = list(req._block_ids), []
        unreserve, req._resv_blocks = req._resv_blocks, 0
        if slot is not None:
            self._table[slot, :] = 0
            self._slot_covered[slot] = 0
            self._slot_rows[slot] = 0
        if req._pool_gen != self.kv_pool.generation:
            return  # a recovery reset the pool under us: ids are stale
        if ids:
            self._deferred_free.append((self._dispatch_seq, ids))
        if unreserve:
            self.kv_pool.give([], unreserve=unreserve)
        self._sweep_deferred_locked()

    def _drop_blocks_now_locked(self, req: _Request) -> None:
        """Mid-admission release (the slot never became occupied, so
        every chunk dispatched so far carried ``active=False`` for it —
        its writes are trash-routed on device): immediate free."""
        self._usage_kv_release(req)
        ids, req._block_ids = list(req._block_ids), []
        unreserve, req._resv_blocks = req._resv_blocks, 0
        if req._pool_gen != self.kv_pool.generation:
            return  # a recovery reset the pool under us: ids are stale
        if ids or unreserve:
            self.kv_pool.give(ids, unreserve=unreserve)

    def _req_done(self, req: _Request, tok: int) -> bool:
        """The single stop predicate (shared by retirement and the
        harvest loop's chunk-splitting — one home so a future stop
        criterion cannot desync them)."""
        return (
            req.abandoned
            or (self.eos_id is not None and tok == self.eos_id)
            or len(req.tokens) >= req.max_new_tokens
        )

    def _observe_itl(self, req: _Request, now: float, n_tokens: int) -> None:
        """Harvester, lock held, perf plane on: one decode chunk's
        inter-token latency — harvest spacing since the previous
        harvested token batch, divided over this chunk's tokens. An
        unanchored request (anchor 0.0: first batch of a segment, or
        just resumed after preemption) only re-anchors, so neither the
        prefill gap nor the evict→resume gap ever counts as ITL and
        resume segments never double-count."""
        self._perf.note_tokens(n_tokens)
        anchor = req._itl_anchor
        req._itl_anchor = now
        if anchor <= 0.0:
            return
        gap_ms = (now - anchor) * 1e3
        self._h_itl[req.priority].observe(gap_ms / n_tokens)
        req._itl_sum_ms += gap_ms
        req._itl_n += n_tokens

    def _finish_if_done(self, slot: int, tok: int) -> bool:
        """Harvester thread, called with the lock held."""
        req = self._occupant[slot]
        if req is None:
            return True
        done = self._req_done(req, tok)
        if done:
            now = time.perf_counter()
            req.decode_ms = (now - req._prefill_end) * 1e3
            # decode_ms is wall time first-token→retirement, so it
            # includes harvest/queue gaps between chunks; the ITL
            # accumulators (chunk-spacing only, reset across
            # preemption) are the decode-lane-pure view
            itl_mean = req._itl_sum_ms / req._itl_n if req._itl_n else 0.0
            if not req.abandoned:
                # exemplar tagging (perf plane only): a top-bucket
                # observation keeps its rid, so GET /debug/tail can
                # hand the slowest recent requests to /debug/trace
                ex = req.rid if self._perf is not None else None
                self._h_queue.observe(req.queue_wait_ms, exemplar=ex)
                self._h_prefill.observe(req.prefill_ms, exemplar=ex)
                self._h_decode.observe(req.decode_ms, exemplar=ex)
                self._h_ttft.observe(req.ttft_ms, exemplar=ex)
                self._m_requests.inc()
                if self._perf is not None:
                    self._perf.observe_request(req.ttft_ms, itl_mean)
                # a successful completion proves the rebuilt state
                # serves: only CONSECUTIVE rebuild failures accumulate
                # toward the circuit breaker
                self._recovery_times.clear()
            else:
                self._m_abandoned.inc()
            self._occupant[slot] = None
            if self.paged:
                # taken blocks free behind the dispatch fence (chunks
                # already in flight may still write them); the untaken
                # reservation frees now
                self._release_blocks_locked(req, slot)
            self._m_slots_busy.set(self._slots_in_use_locked())
            self._tracer.record_span(req.rid, "harvest", self._harvest_t0, now)
            if self._blocks is not None:
                # the trajectory: which forward of its block decided each
                # served token (what a check rebuilds the block's states from)
                self._tracer.record_event(
                    req.rid, "decided_at", forwards=list(req.decided_at),
                )
            self._tracer.finish_request(req.rid)
            if self._usage is not None:
                if req.abandoned:
                    self._usage.record_drop(req.tenant, "abandoned")
                else:
                    self._usage.finish_request(
                        req.tenant, queue_ms=req.queue_wait_ms,
                        prefill_tokens=req._prefilled_tokens,
                        cached_tokens=req._saved_tokens,
                        priority=req.priority,
                        phase=self.phase,
                        version=self.model_version,
                    )
            self._flight_rec(
                "finish", rid=req.rid, tenant=req.tenant, slot=slot,
                tokens=len(req.tokens), abandoned=req.abandoned,
                # the per-request ledger split (docs/observability.md
                # "Serving goodput & tail attribution"): queue →
                # admission → prefill → decode segments + the
                # decode-lane-pure ITL rollup
                queue_ms=round(req.queue_wait_ms, 3),
                admission_ms=round(req.admission_ms, 3),
                prefill_ms=round(req.prefill_ms, 3),
                ttft_ms=round(req.ttft_ms, 3),
                decode_ms=round(req.decode_ms, 3),
                itl_mean_ms=round(itl_mean, 3),
                itl_tokens=req._itl_n,
            )
            req.event.set()
            req.finish_stream()
        return done

    def _process_entry(self, entry) -> None:
        """Account one readback's tokens (harvester thread). The blocking
        ``np.asarray`` happened outside the lock; entries arrive in
        dispatch order, so a slot's prefill token always lands before its
        decode tokens and before any reuse of the slot."""
        self._harvest_t0 = time.perf_counter()
        with self._lock:
            cur_epoch = self._epoch
        if entry[1] != cur_epoch:
            # poisoned-era readback: _recover already failed its
            # requests and the donated device buffers it references may
            # be invalid — never materialize them. An insert entry
            # still releases its lease (idempotent) so recovery can
            # never leak a prefix-cache pin, and a preempt entry must
            # FAIL its evicted stream (between eviction and requeue it
            # lives only here — recovery could not see it).
            if entry[0] == "insert":
                self._release_lease(entry[2])
            elif entry[0] == "preempt":
                self._fail_orphan(entry[2], RuntimeError(
                    "engine recovered while this stream was preempted; "
                    "its evicted device state belonged to the poisoned "
                    "era"
                ))
            return
        self._fire("engine.harvest")
        if entry[0] == "insert":
            # prompt blocks back into the radix tree: materialize the
            # (already-local, copy kicked at dispatch) host bytes, split
            # the contiguous row window into per-block OWNED copies
            # (`.copy()` — a view would pin the whole window in RAM
            # while charging only block bytes), and attach. A failed
            # insert must never fail the request — the same device error
            # would already have surfaced through the request's own
            # prefill readback, which precedes this entry.
            _, _, req, first_new, rows = entry
            try:
                if rows is not None and self.prefix_cache is not None:
                    blk = self.prefix_cache.block_size
                    nb = len(req.prompt) // blk
                    full = tuple(
                        tuple(np.asarray(buf) for buf in layer)
                        for layer in rows
                    )
                    if self.paged:
                        blocks = _host_blocks(full, first_new, nb)
                    else:
                        blocks = [
                            tuple(
                                tuple(
                                    buf[:, j * blk:(j + 1) * blk].copy()
                                    for buf in layer
                                )
                                for layer in full
                            )
                            for j in range(first_new, nb)
                        ]
                    self.prefix_cache.insert(req.prompt, first_new, blocks)
            except Exception as exc:
                logger.info(f"prefix-cache insert skipped: {exc!r}")
            finally:
                self._release_lease(req)
            return
        if entry[0] == "preempt":
            # a preempted stream's evicted KV lands in the host block
            # store, the path is pinned against LRU, and the stream
            # re-enters the waiting room at the FRONT of its queue —
            # the resume admission then splices these exact bytes back
            # (pointer swaps, exact token parity; docs/robustness.md
            # "Preemption & fairness"). FIFO entry order guarantees
            # the insert lands before the re-admission can match.
            _, _, req, nb, rows, resume_prompt, incl = entry
            cache = self.prefix_cache
            try:
                if rows is not None and cache is not None and nb > 0:
                    full = tuple(
                        tuple(np.asarray(buf) for buf in layer)
                        for layer in rows
                    )
                    cache.insert(
                        resume_prompt, 0, _host_blocks(full, 0, int(nb))
                    )
            except Exception as exc:
                # a failed save must not fail the stream: the resume
                # admission simply matches fewer blocks and recomputes
                logger.info(f"preempt KV save skipped: {exc!r}")
            if cache is not None:
                # eviction-target pinning: the saved path must survive
                # LRU pressure until the resume admission takes its
                # own match lease over it
                self._release_resume_lease(req)  # a prior preemption's
                req._resume_lease = cache.lease(resume_prompt)
            req.prompt = resume_prompt
            req._prompt_incl = incl
            req._matched_blocks = 0
            req._park_logged = False
            self._flight_rec(
                "resume", rid=req.rid, tenant=req.tenant,
                priority=req.priority, tokens=len(req.tokens),
                cached_blocks=int(nb),
            )
            self._room.put(req, front=True)
            self._g_queue_depth.set(self._room.qsize())
            return
        if entry[0] == "prefill":
            _, _, slot, req, first = entry
            with self._tracer.span(
                None, "engine.harvest_wait", kind="prefill", req=req.rid,
            ):
                tok = int(np.asarray(first))
            with self._tracer.span(
                None, "engine.harvest_process", kind="prefill", req=req.rid,
            ):
                self._process_prefill(slot, req, tok)
            return
        _, _, mask, gens, toks, dispatched, seq = entry
        with self._tracer.span(
            None, "engine.harvest_wait", kind="chunk", seq=seq,
        ):
            if isinstance(toks, tuple):  # the speculative chunk's outputs
                toks = tuple(np.asarray(x) for x in toks)
            else:
                toks = np.asarray(toks)
        with self._tracer.span(
            None, "engine.harvest_process", kind="chunk", seq=seq,
        ):
            if self.draft is not None:
                self._process_spec_chunk(mask, gens, toks, dispatched)
            elif self._blocks is not None:
                self._process_block_chunk(mask, gens, toks, dispatched, seq)
            else:
                self._process_chunk(mask, gens, toks, dispatched, seq)

    def _process_prefill(self, slot: int, req: _Request, tok: int) -> None:
        """Account a harvested prefill: the request's first token."""
        now = time.perf_counter()  # after the readback: prefill_ms
        with self._lock:           # includes its in-flight lag
            req.prefill_ms = (now - req._dispatch_t) * 1e3
            req._prefill_end = now
            self._tracer.record_span(
                req.rid, "prefill", req._dispatch_t, now,
                tokens=req._prefilled_tokens,
            )
            if self._blocks is not None:
                # by blocks a prefill yields no token (what came back is
                # the number of prompt entries held back for the first
                # block): TTFT and the ITL anchor are the first block's,
                # set where it is harvested
                if req.abandoned:
                    self._finish_if_done(slot, self.pad_id)
            else:
                if req.ttft_ms == 0.0:
                    # a RESUMED stream's first token already happened;
                    # its ttft must stay the first segment's
                    req.ttft_ms = (now - req.submitted) * 1e3
                # ITL anchor: the next decode chunk's harvest spacing
                # measures from this first token (re-anchored here on
                # resume too, so the evict→resume gap never counts)
                req._itl_anchor = now
                req.tokens.append(tok)
                req.emit([tok])
                if self._perf is not None:
                    self._perf.note_tokens(1)
                self._finish_if_done(slot, tok)
        if self._usage is not None:
            # the prefill's exclusive pipeline window (consecutive-
            # harvest spacing) + its dispatched programs' FLOPs,
            # billed wholly to the admitting tenant; the sampled
            # first token is that tenant's first served token
            device_s = max(
                0.0,
                now - max(req._dispatch_t, self._last_harvest_end),
            )
            self._last_harvest_end = now
            self._usage.attribute(
                {req.tenant: int(self._blocks is None)}, device_s=device_s,
                flops=req._attr_flops,
            )
            # drained: a resumed stream's next prefill segment
            # must not re-bill the first segment's programs
            req._attr_flops = 0.0

    def _process_chunk(self, mask, gens, toks, dispatched, seq) -> None:
        """Account one harvested decode chunk's tokens (``toks`` is on
        the host): emit, retire, sweep the deferred frees it fenced."""
        now = time.perf_counter()  # readback complete: the chunk landed
        self._h_harvest.observe((now - self._harvest_t0) * 1e3)
        tenant_tokens: dict = {}
        with self._lock:
            # slot-major (steps for different slots are independent): each
            # request's harvested tokens form ONE streamed chunk, emitted
            # before retirement so the stream's terminal sentinel follows
            # its final tokens
            for slot in np.flatnonzero(mask):
                req = self._occupant[slot]
                if req is None or gens[slot] != self._slot_gen[slot]:
                    continue  # stale: dispatched for a previous occupant
                chunk: List[int] = []
                for step_toks in toks:
                    tok = int(step_toks[slot])
                    req.tokens.append(tok)
                    chunk.append(tok)
                    if self._req_done(req, tok):
                        break
                self._tracer.record_span(
                    req.rid, f"decode-chunk[{req._chunk_i}]", dispatched, now,
                    tokens=len(chunk),
                )
                self._flight_rec(
                    "decode", rid=req.rid, tenant=req.tenant, slot=slot,
                    chunk=req._chunk_i, tokens=len(chunk),
                )
                req._chunk_i += 1
                req.emit(chunk)
                if self._perf is not None and chunk:
                    self._observe_itl(req, now, len(chunk))
                if self._usage is not None:
                    tenant_tokens[req.tenant] = (
                        tenant_tokens.get(req.tenant, 0) + len(chunk)
                    )
                self._finish_if_done(slot, chunk[-1])
            if self.paged:
                # this chunk (and by FIFO order every earlier one) has
                # been harvested: deferred frees fenced at or before it
                # are now safe — no in-flight program references them
                self._harvest_seq = max(self._harvest_seq, seq)
                self._sweep_deferred_locked()
        if self._usage is not None:
            # the chunk's exclusive pipeline window split by harvested-
            # token share; a chunk whose every slot went stale still
            # counts toward the unattributed totals (the identity
            # denominator stays honest under slot churn)
            device_s = max(
                0.0, now - max(dispatched, self._last_harvest_end)
            )
            self._last_harvest_end = now
            self._usage.attribute(
                tenant_tokens, device_s=device_s,
                flops=self._program_cost("engine.decode"),
                slot_steps=self.chunk_steps * self.slots,
            )

    def _process_block_chunk(self, mask, gens, outs, dispatched, seq) -> None:
        """Account one harvested chunk of a module that generates by
        blocks: per forward each live slot emitted a whole block's
        generated entries or nothing (``info``: programs.py
        ``block_chunk``). A request's tokens of one chunk form one
        streamed event, its first token is its first block's (TTFT), and
        the gap between two harvests that brought tokens is spread over
        the later one's (ITL, observed a burst). Budget and eos cut the
        emission here as in the plain path's ``_req_done`` walk."""
        toks, at, info = outs
        now = time.perf_counter()  # readback complete: the chunk landed
        self._h_harvest.observe((now - self._harvest_t0) * 1e3)
        tenant_tokens: dict = {}
        forwards = commits = fused = decided = emitted = 0
        with self._lock:
            for slot in np.flatnonzero(mask):
                req = self._occupant[slot]
                if req is None or gens[slot] != self._slot_gen[slot]:
                    continue  # stale: dispatched for a previous occupant
                chunk: List[int] = []
                n_fwd = n_commit = n_fused = 0
                finished = False
                for r in range(info.shape[0]):
                    n_emit, first, n_dec, kind = (int(x) for x in info[r, slot])
                    if kind == 0:
                        continue  # the slot ran nothing: done on the device
                    n_fwd += 1
                    n_commit += kind >> 1 & 1      # it closed the block before
                    n_fused += kind == 3           # and denoised the next
                    decided += n_dec
                    for i in range(first, first + n_emit):
                        tok = int(toks[r, slot, i])
                        req.tokens.append(tok)
                        req.decided_at.append(int(at[r, slot, i]))
                        chunk.append(tok)
                        if self._req_done(req, tok):
                            finished = True
                            break
                    if finished:
                        break
                forwards += n_fwd
                commits += n_commit
                fused += n_fused
                emitted += len(chunk)
                self._tracer.record_span(
                    req.rid, f"decode-chunk[{req._chunk_i}]", dispatched, now,
                    tokens=len(chunk), forwards=n_fwd, commits=n_commit,
                )
                self._flight_rec(
                    "decode", rid=req.rid, tenant=req.tenant, slot=slot,
                    chunk=req._chunk_i, tokens=len(chunk), forwards=n_fwd,
                )
                req._chunk_i += 1
                req.emit(chunk)
                if chunk and req.ttft_ms == 0.0:
                    req.ttft_ms = (now - req.submitted) * 1e3
                if self._perf is not None and chunk:
                    self._observe_itl(req, now, len(chunk))
                if self._usage is not None and chunk:
                    tenant_tokens[req.tenant] = (
                        tenant_tokens.get(req.tenant, 0) + len(chunk)
                    )
                if chunk:
                    self._finish_if_done(slot, chunk[-1])
                elif req.abandoned:
                    self._finish_if_done(
                        slot, req.tokens[-1] if req.tokens else self.pad_id
                    )
            self._harvest_seq = max(self._harvest_seq, seq)
            self._sweep_deferred_locked()
        if self._perf is not None:
            self._perf.note_blocks(
                forwards=forwards, commits=commits, fused_commits=fused, decided=decided, emitted=emitted,
            )
        if self._usage is not None:
            device_s = max(
                0.0, now - max(dispatched, self._last_harvest_end)
            )
            self._last_harvest_end = now
            self._usage.attribute(
                tenant_tokens, device_s=device_s,
                flops=self._program_cost("engine.decode"),
                slot_steps=self.chunk_steps * self.slots,
            )

    def _process_spec_chunk(self, mask, gens, outs, dispatched) -> None:
        """Account one speculative chunk's readback: per round, each slot
        contributed ``n_emit`` tokens (variable — acceptance-dependent)
        from its ``emit`` row; budget truncation happens here exactly
        like the plain path's per-token ``_req_done`` walk."""
        emit, n_emit, accepted = (np.asarray(x) for x in outs)
        now = time.perf_counter()  # after np.asarray: readback complete
        self._h_harvest.observe((now - self._harvest_t0) * 1e3)
        tenant_tokens: dict = {}
        with self._lock:
            for slot in np.flatnonzero(mask):
                req = self._occupant[slot]
                if req is None or gens[slot] != self._slot_gen[slot]:
                    continue
                chunk: List[int] = []
                finished = False
                for r in range(emit.shape[0]):
                    if n_emit[r, slot] > 0:
                        # acceptance stats count only rounds whose tokens
                        # were actually SERVED (inside the gens check and
                        # before the budget break) — stale-generation and
                        # post-retirement overshoot rounds would skew the
                        # /stats acceptance_rate
                        self._m_spec_rounds.inc()
                        self._m_spec_accepted.inc(int(accepted[r, slot]))
                    for i in range(int(n_emit[r, slot])):
                        tok = int(emit[r, slot, i])
                        req.tokens.append(tok)
                        chunk.append(tok)
                        if self._req_done(req, tok):
                            finished = True
                            break
                    if finished:
                        break
                self._tracer.record_span(
                    req.rid, f"decode-chunk[{req._chunk_i}]", dispatched, now,
                    tokens=len(chunk),
                )
                self._flight_rec(
                    "decode", rid=req.rid, tenant=req.tenant, slot=slot,
                    chunk=req._chunk_i, tokens=len(chunk),
                )
                req._chunk_i += 1
                req.emit(chunk)
                if self._perf is not None and chunk:
                    self._observe_itl(req, now, len(chunk))
                if self._usage is not None and chunk:
                    tenant_tokens[req.tenant] = (
                        tenant_tokens.get(req.tenant, 0) + len(chunk)
                    )
                if chunk:
                    self._finish_if_done(slot, chunk[-1])
                elif req.abandoned:
                    # a fully-idle readback (device marked the slot done
                    # before any round) still must retire an abandoned
                    # waiter
                    self._finish_if_done(
                        slot, req.tokens[-1] if req.tokens else self.pad_id
                    )
        if self._usage is not None:
            device_s = max(
                0.0, now - max(dispatched, self._last_harvest_end)
            )
            self._last_harvest_end = now
            self._usage.attribute(
                tenant_tokens, device_s=device_s,
                flops=self._program_cost("engine.decode"),
                slot_steps=self.chunk_steps * self.slots,
            )

    def _dispatch_chunk(self) -> bool:
        """Dispatch one decode chunk if the pipeline has a credit and any
        occupant still needs tokens beyond already-dispatched work."""
        if not self._chunk_credits.acquire(blocking=False):
            # pipeline_depth chunks already awaiting harvest: the poll
            # that follows is the dispatcher waiting for the chip
            self._no_credit = True
            return False
        seq = 0
        table_np = None
        with self._lock:
            mask = np.array([r is not None for r in self._occupant])
            needed = any(
                r is not None and r._expected < r.max_new_tokens
                for r in self._occupant
            )
            ep0 = self._epoch
            st = self._state
            proceed = bool(mask.any()) and needed and st is not None
            if proceed:
                # grow tables + snapshot + assign this chunk's fence seq
                # under ONE lock hold: a retirement racing this dispatch
                # fences its deferred frees at _dispatch_seq, which now
                # covers the snapshot we are about to launch — the
                # in-flight chunk can never write a recycled block
                # (contiguous engines have no fence; there the seq only
                # numbers the chunk for its spans)
                if self.paged:
                    table_np = self._grow_tables_locked()
                self._dispatch_seq += 1
                seq = self._dispatch_seq
        if not proceed:
            self._chunk_credits.release()
            return False
        self._enter_pass()
        with self._tracer.span(
            None, "engine.dispatch_chunk", seq=seq, live_slots=int(mask.sum()),
        ) as sp:
            self._launch_chunk(mask, st, ep0, table_np, seq)
        self._it_dispatch_s += sp.end_s - sp.start_s
        return True

    def _launch_chunk(self, mask, st, ep0, table_np, seq) -> None:
        """Enqueue the decode chunk that :meth:`_dispatch_chunk` decided
        on (it holds a pipeline credit) and hand its readback to the
        harvester."""
        t_dispatch = time.perf_counter()
        try:
            self._fire("engine.dispatch")
            with self._tracer.span(
                None, "engine.dispatch_chunk.enqueue", seq=seq,
            ) as sp:
                keys = jnp.stack(self._next_key(self.chunk_steps))
                new_state, toks = self._decode_chunk(
                    self._params, st, jnp.asarray(mask), _place(table_np),
                    keys,
                )
            self._it_enqueue_s += sp.end_s - sp.start_s
            for leaf in toks if isinstance(toks, tuple) else (toks,):
                _start_host_copy(leaf)
            self._h_dispatch.observe((time.perf_counter() - t_dispatch) * 1e3)
        except BaseException:
            # the credit is only released by the harvester for entries that
            # were actually enqueued — give it back or the pipeline wedges
            self._chunk_credits.release()
            raise
        with self._lock:
            if self._epoch != ep0:
                # _recover ran (harvester thread) mid-dispatch: new_state
                # derives from the invalidated buffers — discard it
                # (self._state stays the recovery's None) and drop the
                # readback; the requests it covered are already failed
                self._chunk_credits.release()
                return
            self._state = new_state
            for slot in np.flatnonzero(mask):
                if self._occupant[slot] is not None:
                    # the GUARANTEED emission per chunk (1 token/round in
                    # speculative mode — acceptance only adds more): an
                    # upper-bound here stops dispatching before enough
                    # tokens actually land at partial acceptance (hang,
                    # caught by test_spec_engine_matches_plain_greedy);
                    # over-dispatch at high acceptance is absorbed by the
                    # done mask + spare rows like any overshoot
                    occupant = self._occupant[slot]
                    if self._blocks is None:
                        occupant._expected += self.chunk_steps
                    else:
                        occupant._forwards += self.chunk_steps
                        occupant._expected = max(
                            occupant._expected, self._tokens_due(occupant)
                        )
                    if self.paged:
                        # host upper bound of the slot's device fill:
                        # next growth pass covers the following chunk
                        self._slot_rows[slot] = min(
                            self._slot_rows[slot] + self._chunk_advance,
                            self.cache_len,
                        )
            gens = tuple(self._slot_gen)
            self._m_chunks.inc()
            self._m_steps.inc(self.chunk_steps)
            occupied_now = int(mask.sum())
            self._m_occupied.inc(occupied_now * self.chunk_steps)
            if self._perf is not None:
                visible, selected = self._positions_read_locked(mask)
                # goodput ring: classify this pass (full batch /
                # padded slots / prefill-mix) + KV pool pressure
                admitted, prefill_tokens = self._admitted_since_chunk
                self._admitted_since_chunk = [0, 0]
                self._perf.note_pass(
                    occupied_now,
                    waiting=self._room.qsize(),
                    admitted=admitted, prefill_tokens=prefill_tokens,
                    prefill_mix=self._admission is not None,
                    kv_in_use=(
                        self.kv_pool.in_use
                        if self.kv_pool is not None else 0
                    ),
                    kv_capacity=(
                        self.kv_pool.capacity
                        if self.kv_pool is not None else 0
                    ),
                    kv_tokens=(
                        self.kv_pool.used_rows
                        if self.kv_pool is not None else 0
                    ),
                    visible_positions=visible, selected_positions=selected,
                )
        self._inflight.put(("chunk", ep0, mask, gens, toks, t_dispatch, seq))

    def _positions_read_locked(self, mask) -> Tuple[int, int]:
        """(visible, selected) of a module with a learned selection inside
        attention: the cached rows the chunk just launched lets its live
        sequences see, summed over its steps, and the smaller of each
        sequence's rows and the selection's ``topk``. **Reckoned, not
        read back**: from the host's upper bound of each slot's fill, as
        the block tables are grown from; what the device's attention
        fetched is held by tests (rows outside the picks may hold
        anything), not by this count. (0, 0) for every other module."""
        if self._index_topk is None or not self.paged:
            return 0, 0
        live = [s for s in np.flatnonzero(mask) if self._occupant[s] is not None]
        last = np.asarray([self._slot_rows[s] for s in live], np.int64)
        # the rows each live slot sees at each of the chunk's steps
        rows = np.maximum(last[:, None] - np.arange(self.chunk_steps)[None, ::-1], 0)
        return int(rows.sum()), int(np.minimum(rows, self._index_topk).sum())

    def _pop_request(self) -> Optional[_Request]:
        """Atomically dequeue a request and mark it as mid-admission, so
        bind()'s busy check never sees a gap where the request is neither
        queued nor occupying a slot."""
        self._fire("engine.dequeue")
        with self._lock:
            if None not in self._occupant:
                return None
            req = self._room.pop()
            if req is None:
                return None
            self._admitting += 1
        self._g_queue_depth.set(self._room.qsize())
        return req

    def _pop_bypass(self, parked: _Request) -> Optional[_Request]:
        """The PROMOTE path: while ``parked`` head-of-line-blocks its
        class on pool exhaustion, a STRICTLY higher-priority request
        may still admit past it (the waiting room's parked-lane gating
        releases nothing at or below the parked class) — without this,
        a premium request would wait out a bulk backlog's parked head
        in exactly the overload the scheduler exists for."""
        with self._lock:
            if None not in self._occupant:
                return None
            req = self._room.pop(
                above_rank=priority_rank(parked.priority)
            )
            if req is None:
                return None
            self._admitting += 1
        self._flight_rec(
            "promote", rid=req.rid, tenant=req.tenant,
            priority=req.priority, past=parked.rid,
            past_priority=parked.priority,
        )
        self._g_queue_depth.set(self._room.qsize())
        return req

    def _drop_admission(self, req: _Request, exc: BaseException) -> None:
        """Fail a request still mid-admission and release its count.
        Idempotent (keyed on the request event): the dispatcher's own
        error path and a concurrent ``_recover`` from the harvester must
        not double-release ``_admitting``."""
        with self._lock:
            if req.event.is_set():
                return
            req.error = exc
            self._admitting -= 1
            if self.paged:
                # the slot never became occupied, so every dispatched
                # chunk carried active=False for it (writes trash-routed
                # on device) — immediate free is safe
                self._drop_blocks_now_locked(req)
        self._release_lease(req)
        if req.abandoned:
            self._m_abandoned.inc()
            cause = "abandoned"
            if self._usage is not None:
                self._usage.record_drop(req.tenant, "abandoned")
        elif isinstance(exc, DeadlineExceeded):
            self._m_deadline_shed.inc()
            cause = "deadline_shed"
            if self._usage is not None:
                self._usage.record_deadline_shed(req.tenant)
        else:
            self._m_errors.inc()
            cause = f"error:{type(exc).__name__}"
            if self._usage is not None:
                self._usage.record_drop(req.tenant, "error")
        self._flight_rec("drop", rid=req.rid, tenant=req.tenant, cause=cause)
        self._tracer.finish_request(req.rid)
        req.event.set()
        req.finish_stream()

    # ------------------------------------------------------------------ #
    # preemption (docs/robustness.md "Preemption & fairness")
    # ------------------------------------------------------------------ #

    def _eligible_victims_locked(self) -> List:
        """Residents the scheduler may evict (lock held): prefill
        harvested (there is a token-exact resume point), waiter still
        listening, and the resume prompt — original prompt plus every
        harvested token — still fits an admission bucket (the splice
        path needs a ``[1, bucket]`` workspace)."""
        out = []
        for slot, r in enumerate(self._occupant):
            if r is None or r.abandoned or not r.tokens:
                continue
            if (
                len(r.prompt) + len(r.tokens) - r._prompt_incl
                > self.buckets[-1]
            ):
                continue
            out.append((slot, r))
        return out

    def _maybe_preempt(self, waiter: _Request) -> bool:
        """A parked (pool-exhausted) admission asks the scheduler to
        act: evict at most ONE strictly-lower-priority resident per
        dispatcher pass (gradual — each eviction frees blocks behind
        the dispatch fence, and the parked retry re-checks the pool
        every pass). Returns True when a victim was evicted."""
        if not self._preempt_enabled:
            return False
        with self._lock:
            # anti-cascade: blocks already freed onto the deferred
            # fence land as soon as the in-flight chunks harvest — if
            # they cover the waiter, a further eviction would thrash a
            # second victim for blocks that are already on their way
            pending = sum(len(ids) for _, ids in self._deferred_free)
            needed = self.kv_pool.blocks_for_rows(min(
                len(waiter.prompt) + waiter.max_new_tokens
                - len(waiter.tokens),
                self.cache_len,
            ))
            if self.kv_pool.available + pending >= needed:
                return False
            victim = self._sched.select_victim(
                waiter, self._eligible_victims_locked()
            )
        if victim is None:
            return False
        return self._preempt_victim(victim[0], victim[1], waiter)

    def _preempt_victim(
        self, slot: int, victim: _Request, waiter: _Request
    ) -> bool:
        """Evict ``victim`` from its slot so ``waiter`` can admit
        (dispatcher thread): gather the victim's finalized full KV
        blocks by table entry (the existing extract path — the async
        device→host copy starts now, the harvester materializes it),
        retire the slot with deferred-fence block frees (in-flight
        chunks may still write them), and hand the stream to the
        harvester's ``preempt`` entry, which stores the blocks in the
        host prefix cache and requeues the stream at the front of its
        queue. The resume admission splices the SAME bytes back, so
        the resumed stream's tokens are exactly its solo run's
        (chaos-tested in tests/unit/test_scheduler.py)."""
        t0 = time.perf_counter()
        blk = self._kv_block_size
        with self._lock:
            if self._occupant[slot] is not victim or self._state is None:
                return False
            ep0 = self._epoch
            st = self._state
            # only FULL blocks whose every row is covered by harvested
            # tokens are saved: rows past prompt + new-tokens[:-1] may
            # be written by in-flight chunks mid-extract (same block),
            # so the sub-block tail is recomputed at resume instead —
            # the same recompute the warm-partial-hit admission path
            # runs. A resumed victim's prompt already CONTAINS its
            # first _prompt_incl tokens, so only the tail since the
            # last resume counts as new rows.
            nb = min(
                (
                    len(victim.prompt)
                    + len(victim.tokens) - victim._prompt_incl - 1
                ) // blk,
                self._slot_covered[slot],
            )
            ids = self._table[slot, :nb].copy()
        rows = None
        if nb > 0:
            # dispatched on the dispatcher thread BEFORE any later
            # decode chunk, so donation order guarantees it reads the
            # pre-eviction pool (the _schedule_insert precedent)
            rows = self._extract(st, jnp.int32(slot), _place(ids), n=nb * blk)
            for layer in rows:
                for buf in layer:
                    _start_host_copy(buf)
        with self._lock:
            if self._epoch != ep0 or self._occupant[slot] is not victim:
                return False  # recovery/retirement raced: nothing evicted
            if (
                len(victim.prompt) + len(victim.tokens)
                - victim._prompt_incl > self.buckets[-1]
            ):
                # tokens harvested since the eligibility check pushed
                # the resume prompt past the largest bucket — evicting
                # now would fail the stream at re-admission (a caller-
                # visible error); leave it resident instead
                return False
            # stale-generation machinery: tokens from chunks already
            # in flight for this slot are discarded at harvest (they
            # are recomputed after resume), so the snapshot below is
            # the victim's final pre-eviction state
            self._slot_gen[slot] += 1
            self._occupant[slot] = None
            victim._preempts += 1
            victim._preempted_at = time.perf_counter()
            # unanchor ITL: the evict→resume gap is queueing, not
            # decode cadence — the resume prefill re-anchors
            victim._itl_anchor = 0.0
            resume_prompt = np.concatenate([
                victim.prompt,
                np.asarray(
                    victim.tokens[victim._prompt_incl:], np.int32
                ),
            ])
            incl = len(victim.tokens)
            freed = len(victim._block_ids)
            self._release_blocks_locked(victim, slot)
            self.kv_pool.note_preempted(freed)
            self._m_slots_busy.set(self._slots_in_use_locked())
        self._sched.record_preemption("priority")
        self._flight_rec(
            "preempt", rid=victim.rid, tenant=victim.tenant,
            priority=victim.priority, slot=slot, by=waiter.rid,
            by_priority=waiter.priority, blocks_saved=int(nb),
            blocks_freed=freed, tokens=len(victim.tokens),
        )
        self._tracer.record_span(
            victim.rid, f"preempt[{victim._preempts - 1}]", t0,
            time.perf_counter(), tokens=len(victim.tokens),
        )
        self._inflight.put(
            ("preempt", ep0, victim, nb, rows, resume_prompt, incl)
        )
        return True

    def _start_admission(self, req: _Request) -> None:
        """Dispatcher: begin admitting a dequeued request (counted in
        ``_admitting`` by ``_pop_request``). With a prefix cache, the
        longest cached block-prefix of the prompt is leased (pinned
        against eviction) and the admission becomes a block-granularity
        chunked one: the leading chunks are replaced by host-row
        splices, and only the uncovered suffix runs prefill programs.
        Otherwise short buckets prefill in one monolithic dispatch and
        buckets larger than ``prefill_chunk`` start a chunked admission
        whose lead chunks are dispatched one per loop pass, interleaved
        with decode chunks."""
        try:
            if req.abandoned:
                self._drop_admission(
                    req, TimeoutError("request abandoned before admission")
                )
                return
            if req.deadline is not None and time.perf_counter() > req.deadline:
                # shed at dequeue: an expired request must never consume
                # prefill (under overload that device time is exactly
                # what the live requests behind it need)
                waited_ms = (time.perf_counter() - req.submitted) * 1e3
                self._drop_admission(req, DeadlineExceeded(
                    f"request deadline expired while queued "
                    f"(waited {waited_ms:.0f} ms)",
                    deadline_ms=(req.deadline - req.submitted) * 1e3,
                ))
                return
            self._fire("engine.prefill")
            if self.paged and not req._block_ids and req._resv_blocks == 0:
                # reserve the WORST-CASE block count up front so table
                # growth can never fail mid-decode; a transiently full
                # pool PARKS the admission (retried every dispatcher
                # pass, FIFO preserved — nothing admits past it) until
                # retirements free blocks. Queue backlog behind a
                # parked admission sheds through max_queue_depth.
                # a RESUMED stream's prompt already contains its
                # harvested tokens, so it only decodes the remainder —
                # without the subtraction a resume could demand more
                # than the whole pool and park forever
                rows_cap = min(
                    len(req.prompt) + req.max_new_tokens - len(req.tokens)
                    + self._open_rows,  # by blocks the last block is written whole
                    self.cache_len,
                )
                needed = self.kv_pool.blocks_for_rows(rows_cap)
                with self._lock:
                    try:
                        # retries of a parked admission count neither a
                        # new alloc failure nor a new flight event —
                        # one pool-pressure incident per park
                        self.kv_pool.reserve(
                            needed, count_failure=not req._park_logged
                        )
                    except PoolExhausted as exc:
                        self._room.park(req)
                        if not req._park_logged:
                            req._park_logged = True
                            if self._perf is not None:
                                # a slot was free: the pool bound the batch
                                self._perf.note_parked()
                            resident = [
                                r for r in self._occupant if r is not None
                            ]
                            cand = (
                                min(resident, key=lambda r: r.submitted)
                                if resident else None
                            )
                            # post-hoc 429 analysis: distinguishes
                            # pool-full from queue-full, and names the
                            # oldest-resident candidate; the SCHEDULER
                            # acts on its own victim policy when a
                            # strictly lower-priority resident exists
                            # (docs/robustness.md)
                            self._flight_rec(
                                "pool_pressure", reason="alloc_fail",
                                rid=req.rid, priority=req.priority,
                                needed_blocks=exc.needed,
                                available_blocks=exc.available,
                                preempt_candidate=(
                                    cand.rid if cand is not None else None
                                ),
                                preempt_candidate_blocks=(
                                    len(cand._block_ids)
                                    if cand is not None else 0
                                ),
                            )
                        return
                    req._resv_blocks = needed
                    req._rows_cap = rows_cap
                    req._park_logged = False
                    req._pool_gen = self.kv_pool.generation
            # the resident state inits lazily inside _admit / the final
            # chunk of _advance_admission (NOT here: an unlocked write
            # would race a concurrent _recover's reset)
            cache, m_used = self.prefix_cache, 0
            bucket = self._bucket_for(len(req.prompt))
            chunk = self.prefill_chunk
            # cached-admission granularity: the cache block for
            # monolithic-class buckets, prefill_chunk for chunked ones —
            # a cached long prompt must never degrade its suffix to
            # block-sized programs (a small hit would then admit far
            # SLOWER than a miss). Buckets are lcm(block, chunk)-rounded
            # at construction, so unit-aligned starts are block-aligned.
            unit = None
            if cache is not None:
                unit = cache.block_size
                if chunk is not None and bucket > chunk:
                    # must stay block-representable AND chunk-aligned;
                    # == prefill_chunk whenever block divides it (the
                    # common case — same compiled shapes as a miss)
                    unit = math.lcm(unit, chunk)
                lease = cache.match(req.prompt)
                req._lease = lease
                req._matched_blocks = lease.n_blocks
                # the resume pin's job is done: the admission's own
                # match lease now covers the same path
                self._release_resume_lease(req)
                blk = cache.block_size
                # usable match: unit-quantized, and capped one token
                # short of the prompt — finish_prefill must run at
                # least the last real token to sample token 0 from it
                m_used = min(
                    lease.n_blocks, (len(req.prompt) - 1) // blk
                ) * blk // unit * unit
            # credited to the tokens-saved counter at admission
            # completion (_advance_admission), not here: a dropped or
            # abandoned admission saved nothing
            req._saved_tokens = m_used
            req._prefilled_tokens = len(req.prompt) - m_used
            if m_used == 0 and (chunk is None or bucket <= chunk):
                self._admit(req)
                with self._lock:
                    self._admitting -= 1
                return
            slot, bucket, padded = self._admission_preamble(req)
            # only the chunks covering the TRUE length run — a short
            # prompt routed into a long bucket pays for its own length
            # (and a cached admission only the uncovered suffix)
            chunk_use = unit if m_used else chunk
            if m_used:
                # group the matched blocks into unit-sized splice
                # entries (one device dispatch per unit, memoized
                # host→device via _dev_splice)
                g = unit // cache.block_size
                splice_rows = [
                    tuple(req._lease.rows[u * g:(u + 1) * g])
                    for u in range(m_used // unit)
                ]
            else:
                splice_rows = []
            n_chunks = -(-len(req.prompt) // chunk_use)
            pool_ids = None
            if self.paged:
                with self._lock:
                    pool_ids = self._take_covered_locked(req, slot, bucket)
            adm = _Admission(
                req=req, slot=slot, bucket=bucket, chunk=chunk_use,
                n_chunks=n_chunks, padded=padded,
                fresh=self._init_fresh(bucket=bucket),
                pool_ids=pool_ids,
                next_chunk=m_used // chunk_use,
                splice_rows=splice_rows,
            )
            with self._lock:
                self._admission = adm
        except BaseException as exc:
            with self._lock:
                self._admission = None
            self._drop_admission(req, exc)

    def _advance_admission(self, adm: _Admission) -> None:
        """Dispatch ONE step of the in-progress admission — a cached
        block splice, a lead prefill chunk, or the final chunk that
        finishes into the slot; decode chunks dispatch between calls, so
        resident slots never stall behind a long prompt's prefill.
        ``_recover``/``close`` may concurrently null ``_admission`` —
        every transition re-checks identity under the lock so the
        admission is completed or dropped exactly once."""
        req = adm.req
        try:
            if req.abandoned:
                with self._lock:
                    if self._admission is not adm:
                        return
                    self._admission = None
                self._drop_admission(
                    req, TimeoutError("request abandoned during admission")
                )
                return
            self._fire("engine.prefill")
            if adm.next_splice < len(adm.splice_rows):
                # cached-prefix unit: device-resident rows (memoized
                # host→device upload) spliced into the fresh cache in
                # place of the prefill program that would have
                # recomputed them
                i = adm.next_splice
                t0 = time.perf_counter()
                rows = self._device_splice_rows(adm.splice_rows[i])
                adm.fresh = self._splice_block(
                    adm.fresh, rows, jnp.int32(i * adm.chunk)
                )
                adm.next_splice += 1
                self._tracer.record_span(
                    req.rid, f"prefix-splice[{i}]", t0, time.perf_counter(),
                    tokens=adm.chunk,
                )
                return
            start = adm.next_chunk * adm.chunk
            toks = jnp.asarray(adm.padded[None, start: start + adm.chunk])
            if adm.next_chunk < adm.n_chunks - 1:
                t0 = time.perf_counter()
                with self._tracer.span(
                    req.rid, "admit.enqueue",
                    annotation="engine.admit.enqueue", program="prefill_step",
                ) as sp:
                    adm.fresh = self._prefill_step(
                        self._params, adm.fresh, toks, jnp.int32(start)
                    )
                self._it_enqueue_s += sp.end_s - sp.start_s
                if self._usage is not None:
                    req._attr_flops += self._program_cost(
                        "engine.prefill_chunk", tuple(toks.shape)
                    )
                self._tracer.record_span(
                    req.rid, f"prefill-chunk[{adm.next_chunk}]", t0,
                    time.perf_counter(), tokens=adm.chunk,
                )
                adm.next_chunk += 1
                return
            (key,) = self._next_key()
            with self._lock:
                ep0 = self._epoch
                st = self._state
                if self._admission is not adm:
                    # raced with _recover/close: the request was already
                    # failed and its count released — do not re-admit
                    return
            if st is None:
                # first admission ever, or a recovery dropped the
                # resident state while this admission was mid-flight but
                # BEFORE it was registered (so _recover could not drop
                # it): build it fresh and proceed — returning here
                # instead would strand the admission (never completed,
                # never dropped) and wedge the engine
                st = self._init_state()
            with self._tracer.span(
                req.rid, "admit.enqueue", annotation="engine.admit.enqueue",
                program="prefill_final",
            ) as sp:
                new_state, first = self._prefill_final(
                    self._params, st, adm.fresh, jnp.int32(adm.slot),
                    _place(adm.pool_ids), toks, jnp.int32(start), jnp.int32(len(req.prompt)), key,
                )
            self._it_enqueue_s += sp.end_s - sp.start_s
            _start_host_copy(first)
            if self._usage is not None:
                req._attr_flops += self._program_cost(
                    "engine.prefill_final", tuple(toks.shape)
                )
            with self._lock:
                if self._admission is not adm or self._epoch != ep0:
                    # raced with _recover/close mid-dispatch: the request
                    # was already failed, and new_state derives from the
                    # invalidated buffers — discard it (self._state stays
                    # the recovery's None)
                    return
                self._state = new_state
                self._admission = None
                self._occupant[adm.slot] = req
                self._slot_gen[adm.slot] += 1
                # resumed streams already hold harvested tokens;
                # dispatch accounting continues from them
                req._expected = len(req.tokens) + 1
                self._admitting -= 1
                self._m_slots_busy.set(self._slots_in_use_locked())
            self._admitted_since_chunk[0] += 1
            self._admitted_since_chunk[1] += req._prefilled_tokens
            # admission segment: dispatch start → final prefill chunk
            # enqueued (covers every interleaved lead chunk + splice)
            req.admission_ms = (
                (time.perf_counter() - req._dispatch_t) * 1e3
            )
            self._flight_rec(
                "prefill", rid=req.rid, tenant=req.tenant, slot=adm.slot,
                bucket=adm.bucket, tokens=req._prefilled_tokens,
                cached_tokens=req._saved_tokens, chunks=adm.n_chunks,
            )
            self._inflight.put(("prefill", ep0, adm.slot, req, first))
            self._schedule_insert(req, adm.slot, ep0)
            if self.prefix_cache is not None and req._saved_tokens:
                # the admission actually completed on spliced rows —
                # NOW the skipped prefill work is real
                self.prefix_cache.record_saved_tokens(req._saved_tokens)
        except BaseException as exc:
            with self._lock:
                if self._admission is adm:
                    self._admission = None
            self._drop_admission(req, exc)

    def _advance_admission_budgeted(self, adm: _Admission) -> None:
        """One dispatcher pass of admission work under the scheduler's
        stall-free mixing budget: with ``mix_prefill_tokens`` unset
        (default) exactly one admission step runs per pass — the
        historical cadence — else lead prefill chunks keep dispatching
        until the token budget is spent (splices are pointer swaps and
        never charge it), so long prompts admit faster while decode
        chunks still interleave every pass."""
        budget = self._mix_budget
        if budget is None:
            self._admit_step(self._advance_admission, adm, adm.req)
            return
        remaining = budget
        while self._admission is adm:
            was_splice = adm.next_splice < len(adm.splice_rows)
            self._admit_step(self._advance_admission, adm, adm.req)
            if not was_splice:
                remaining -= adm.chunk
                if remaining <= 0:
                    break

    def _run(self):
        """Dispatcher: admit queued requests into free slots and keep up
        to ``pipeline_depth`` decode chunks in flight. NEVER blocks on a
        readback — the harvester thread owns those: a readback costs a
        host↔device round trip, so overlapping dispatch with harvest
        is what keeps the chip busy.
        """
        t_iter = time.perf_counter()
        while not self._stop.is_set():
            try:
                progressed = False
                adm = self._admission
                if adm is not None:
                    # stall-free mixing (Sarathi lineage): up to the
                    # configured prefill token budget of admission
                    # steps per pass, then a decode chunk — resident
                    # slots keep streaming under any budget
                    self._enter_pass()
                    self._advance_admission_budgeted(adm)
                    progressed = True
                else:
                    # a parked admission (pool exhausted at
                    # reservation) retries FIRST; the waiting room
                    # only releases strictly-higher-priority requests
                    # past it, so FIFO-under-pressure survives within
                    # and below the parked class
                    req = None
                    with self._lock:
                        has_slot = None in self._occupant
                    if has_slot:
                        req = self._room.take_parked()
                    if req is None:
                        req = self._pop_request()
                    if req is not None:
                        self._enter_pass()
                        self._admit_step(self._start_admission, req, req)
                        if self._room.is_parked(req):
                            # pool exhausted: EVICTING a strictly
                            # lower-priority resident is progress;
                            # otherwise sleep and retry once
                            # retirements free blocks
                            progressed = self._maybe_preempt(req)
                            # promote: a strictly-higher-priority
                            # request may admit past the parked head
                            # (it may itself park — joining the lane —
                            # and preempt on its own behalf)
                            breq = self._pop_bypass(req)
                            if breq is not None:
                                self._admit_step(
                                    self._start_admission, breq, breq
                                )
                                if self._room.is_parked(breq):
                                    progressed = (
                                        self._maybe_preempt(breq)
                                        or progressed
                                    )
                                else:
                                    progressed = True
                        else:
                            progressed = True
                self._no_credit = False
                if self._dispatch_chunk():
                    progressed = True
                poll = None
                if not progressed:
                    # nothing admittable or dispatchable: arrivals and
                    # harvest-freed slots are picked up next pass (2 ms
                    # keeps the 1-core host responsive without spinning)
                    poll = "no_credit" if self._no_credit else "no_work"
                t_iter = self._end_iteration(t_iter, poll)
            except BaseException as exc:  # pragma: no cover - engine crash
                self._close_pass()
                self._recover(exc)

    # -- where the dispatcher's time goes (dispatcher thread only) ---------
    #
    # Every span below goes through the tracer's seam, so it is on the
    # profiler's clock while a session is open (docs/observability.md
    # "Host spans on the profiler's clock"); its perf_counter reads are
    # the ones the perf plane's ``dispatcher_s`` sums.

    def _enter_pass(self) -> None:
        """Open this iteration's ``engine.pass`` span at the first point
        where the iteration is certain to do work (an admission step or
        a chunk dispatch); an iteration that finds nothing has none."""
        if self._pass_span is None:
            with self._lock:
                free = self._occupant.count(None)
            self._pass_span = self._tracer.span(
                None, "engine.pass", waiting=self._room.qsize(),
                free_slots=free, live_slots=self.slots - free,
            )
            self._pass_span.__enter__()

    def _close_pass(self) -> None:
        span, self._pass_span = self._pass_span, None
        if span is not None:
            span.__exit__(None, None, None)

    def _end_iteration(self, t_iter: float, poll: Optional[str]) -> float:
        """Close the iteration that began at ``t_iter``: end its pass
        span, sleep the poll if it made no progress, and hand the perf
        plane the iteration's seconds by phase. Returns the next
        iteration's start."""
        self._close_pass()
        poll_s = 0.0
        if poll is not None:
            if (
                poll == "no_work" and self._perf is not None
                and self._engine_empty()
            ):
                # goodput ring: the device is parked this pass. A poll
                # with the pipeline full, or with residents waiting for
                # their last harvest, is not: the chip is busy
                self._perf.note_idle()
            with self._tracer.span(None, "engine.poll", reason=poll) as sp:
                time.sleep(0.002)
            poll_s = sp.end_s - sp.start_s
        now = time.perf_counter()
        if self._perf is not None:
            admit_s, dispatch_s = self._it_admit_s, self._it_dispatch_s
            self._perf.note_dispatcher(
                admit_s=admit_s, dispatch_s=dispatch_s,
                enqueue_s=self._it_enqueue_s, poll_s=poll_s,
                other_s=max(
                    0.0, now - t_iter - admit_s - dispatch_s - poll_s
                ),
                poll=poll,
            )
        self._it_admit_s = self._it_dispatch_s = self._it_enqueue_s = 0.0
        return now

    def _engine_empty(self) -> bool:
        """No resident, nothing queued or parked, nothing in flight."""
        with self._lock:
            if self._admission is not None or any(
                r is not None for r in self._occupant
            ):
                return False
        return self._room.empty() and self._inflight.empty()

    def _admit_step(self, step: Callable, arg, req: _Request) -> None:
        """One host-side admission step (``_start_admission(req)`` or
        ``_advance_admission(adm)``) under its ``engine.admit`` span,
        which is also the request's ``admit`` span."""
        with self._tracer.span(
            req.rid, "admit", annotation="engine.admit",
            bucket=self._bucket_for(len(req.prompt)),
            prompt_tokens=len(req.prompt), state_layers=self._state_layers,
            latent_layers=self._latent_layers, index_layers=self._index_layers,
        ) as sp:
            step(arg)
            sp.note(cached_tokens=req._saved_tokens)
            if self._blocks is not None:
                # the prompt entries that open the first block
                sp.note(held_back=len(req.prompt) % self._blocks.block_length)
            if self._room.is_parked(req):
                # pool exhausted: the admission is retried every pass;
                # only the try that gets through is the request's span
                sp.discard()
        self._it_admit_s += sp.end_s - sp.start_s

    def _harvest_loop(self):
        """Harvester: block on the oldest in-flight readback, account its
        tokens, retire finished requests, release the pipeline credit."""
        while not self._stop.is_set():
            try:
                entry = self._inflight.get(timeout=0.1)
            except queue.Empty:
                continue
            try:
                self._process_entry(entry)
            except BaseException as exc:  # pragma: no cover - engine crash
                self._recover(exc)
            finally:
                if entry[0] == "chunk":
                    self._chunk_credits.release()

    def _recover(self, exc: BaseException) -> None:
        """Engine supervision (replaces the old terminal ``_fail_all``):
        a failed device program fails ONLY the poisoned batch — the
        resident occupants and the in-progress admission, whose donated
        device state the error invalidated — then bumps the readback
        epoch (in-flight entries from the poisoned era are skipped at
        harvest, leases released) and drops the decode state so the
        next admission rebuilds it; queued requests were never touched
        and re-admit as survivors. Each recovery feeds the circuit
        breaker: ``breaker_threshold`` of them within
        ``breaker_window_s`` (with no successful completion in between)
        open it for ``breaker_cooldown_s``."""
        t0 = time.perf_counter()
        logger.info(
            f"decode engine error: {exc!r} — failing the poisoned batch "
            "and rebuilding decode state"
        )
        poisoned: List[str] = []
        with self._lock:
            adm, self._admission = self._admission, None
        if adm is not None:
            poisoned.append(adm.req.rid)
            self._drop_admission(adm.req, exc)
        with self._lock:
            self._epoch += 1
            for slot, req in enumerate(self._occupant):
                if req is not None:
                    poisoned.append(req.rid)
                    req.error = exc
                    self._m_errors.inc()
                    self._tracer.finish_request(req.rid)
                    self._release_lease(req)
                    if self._usage is not None:
                        # close the hold window and bill the drop before
                        # the pool bookkeeping is reset under it
                        self._usage_kv_release(req)
                        self._usage.record_drop(req.tenant, "error")
                    # pool bookkeeping resets wholesale below — zero the
                    # per-request fields so nothing double-frees
                    req._block_ids = []
                    req._resv_blocks = 0
                    req.event.set()
                    req.finish_stream()
                    self._occupant[slot] = None
            self._m_slots_busy.set(0)
            self._state = None
            if self.paged:
                # the device pool arrays died with the donated state;
                # the next admission's _init_state rebuilds them, so
                # host bookkeeping resets with them (in-flight poisoned
                # readbacks are epoch-skipped and write dead buffers)
                self.kv_pool.reset()
                self._table[:] = 0
                self._slot_covered = [0] * self.slots
                self._slot_rows = [0] * self.slots
                self._deferred_free = []
                self._harvest_seq = self._dispatch_seq
            self._m_recoveries.inc()
            now = time.monotonic()
            self._recovery_times.append(now)
            while (
                self._recovery_times
                and now - self._recovery_times[0] > self.breaker_window_s
            ):
                self._recovery_times.popleft()
            if len(self._recovery_times) >= self.breaker_threshold:
                self._breaker_open_until = now + self.breaker_cooldown_s
                self._g_breaker.set(1.0)
                logger.info(
                    f"engine circuit breaker OPEN: "
                    f"{len(self._recovery_times)} recoveries within "
                    f"{self.breaker_window_s}s; rejecting submissions "
                    f"for {self.breaker_cooldown_s}s"
                )
        # the recovery itself is a traceable event (spans are how the
        # PR-1 telemetry narrates a request timeline; recoveries get
        # their own synthetic timeline) — with the flight-recorder
        # snapshot of the poisoned requests' lifecycle attached, so the
        # postmortem names WHO died and what they were doing when the
        # device program failed
        span_args: dict = {
            "error": repr(exc)[:200], "poisoned": list(poisoned),
        }
        if self._flight is not None:
            self._flight_rec(
                "recovery", rids=list(poisoned), error=repr(exc)[:200],
            )
            span_args["flight"] = self._flight.snapshot(poisoned)
        rid = self._tracer.new_request("recovery")
        self._tracer.record_span(
            rid, "recover", t0, time.perf_counter(), **span_args
        )
        self._tracer.finish_request(rid)
