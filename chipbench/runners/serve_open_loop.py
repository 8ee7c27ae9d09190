"""Runner ``serve_open_loop``: open-loop HTTP + SSE load on the served model.

One process holds the chip: the program's engine and HTTP server run on
their threads and the load generator on its own, all on one clock. The
generator starts a ramp before the window at the window's rate; the requests
*due in the window* are the sample; they drain after it, outside it.
"""

from __future__ import annotations

import importlib
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import judge, loadgen, traffic, weights
from chipbench.yardstick import percentile, say


def request_times(records: List[dict]) -> Dict[str, List[float]]:
    """Per-request times in milliseconds, from the client's side, over the
    requests due in the window that finished without error."""
    out = {"ttft": [], "tpot": [], "per_token": []}
    for r in records:
        if not r["in_window"] or r["error"] or not r["t_tokens"]:
            continue
        n = len(r["tokens"])
        t_first, t_last = r["t_tokens"][0], r["t_tokens"][-1]
        out["ttft"].append((t_first - r["due"]) * 1e3)
        out["per_token"].append((t_last - r["due"]) * 1e3 / n)
        if n > 1:
            # tokens arrive in bursts of chunk_steps, so the unit is the
            # request's mean gap, not a single gap
            out["tpot"].append((t_last - t_first) * 1e3 / (n - 1))
    return out


def end_to_end(times: Dict[str, List[float]]) -> Dict[str, float]:
    def p(key: str, q: float) -> float:
        return percentile(times[key], q) if times[key] else float("nan")

    return {
        "tpot_ms_p50": p("tpot", 50), "tpot_ms_p95": p("tpot", 95),
        "req_ms_per_token_p50": p("per_token", 50),
        "ttft_ms_p50": p("ttft", 50), "ttft_ms_p95": p("ttft", 95),
    }


class Service:
    """The served model of one configuration, up for the life of a process:
    seeded weights, the program's engine behind its HTTP server, windows of
    load, and the reference's check of what a window served."""

    def __init__(self, cfg: dict, mix: dict, seed: int, marks: Optional[list] = None):
        import jax

        marks = marks if marks is not None else []
        self.cfg, self.mix = cfg, mix
        self.adapter = importlib.import_module(f"chipbench.adapters.{cfg['family']}")
        self.built = self.adapter.build(cfg)
        marks.append(("imports and module", time.perf_counter()))
        self.params = jax.block_until_ready(weights.make_tree(self.built["abstract_serve_params"](), seed))
        marks.append(("weights", time.perf_counter()))
        self.engine, self.app, self.host, self.port = self.adapter.start_service(self.built, cfg, self.params)
        self.chunk_steps, self.slots = self.engine.chunk_steps, self.engine.slots
        marks.append(("engine: compile or cache load, warm-up of its buckets", time.perf_counter()))
        # the HTTP path once per bucket, so that a window meets no first use
        warm = [
            {"due_s": 0.0, "prompt": [1] * b, "max_new_tokens": 9, "in_window": False}
            for b in cfg["serving"]["prompt_buckets"]
        ]
        bad = [r["error"] for r in loadgen.run_open_loop(self.host, self.port, warm)["records"] if r["error"]]
        if bad:
            self.close()
            raise SystemExit(f"chipbench: warm-up requests failed: {bad}")
        marks.append(("HTTP path warmed", time.perf_counter()))

    def reseed(self, seed: int) -> None:
        """New seeded weights in the old ones' buffers (the engine is idle)."""
        import jax

        # a 12 GB tree cannot exist twice on one chip: free the old one first
        # (the engine and the artifact are rebound to the new one below)
        for leaf in jax.tree_util.tree_leaves(self.params):
            leaf.delete()
        self.params = jax.block_until_ready(weights.make_tree(self.built["abstract_serve_params"](), seed))
        self.adapter.rebind(self.engine, self.app, self.params)

    def window(self, seed: int, seconds: float, *, rate: Optional[float] = None,
               hooks: Optional[List[tuple]] = None, memory_peak: Optional[Callable] = None) -> dict:
        mix = dict(self.mix, rate_per_s=rate) if rate is not None else self.mix
        requests = traffic.draw_requests(mix, seed, seconds, self.cfg["vocab_size"])
        self.engine.reset_stats()
        state: dict = {}

        def window_opens():
            if self.engine.perf is not None:
                self.engine.perf.reset()  # occupancy over the window, not the ramp

        def window_closes():
            state["occupancy"] = self.engine.perf.report() if self.engine.perf is not None else None
            state["peak"] = memory_peak() if memory_peak is not None else 0

        all_hooks = [(0.0, window_opens), (float(seconds), window_closes)] + list(hooks or [])
        result = loadgen.run_open_loop(
            self.host, self.port, requests, hooks=all_hooks, timeout=float(mix["drain_timeout_s"]),
        )
        records = result["records"]
        lag = [(r["sent"] - r["due"]) * 1e3 for r in records if r["sent"] == r["sent"]]
        in_window = [r for r in records if r["in_window"]]
        failed = [r for r in in_window if r["error"] or len(r["tokens"]) != r["n_asked"]]
        for r in failed[:5]:
            say(f"failed request: {r['error'] or 'token count'} ({len(r['tokens'])} of {r['n_asked']} tokens)")
        say(f"load generator: {len(records)} requests sent ({len(in_window)} due in the window) at "
            f"{mix['rate_per_s']} a second, send lag p50 {percentile(lag, 50):.3f} ms, "
            f"p99 {percentile(lag, 99):.3f} ms (limit {mix['max_send_lag_ms']} ms)")
        return dict(
            records=records, requests=requests, t_zero=result["t_zero"], window_s=float(seconds),
            attempted=len(in_window), failed=len(failed), send_lag_p99_ms=percentile(lag, 99),
            occupancy=state.get("occupancy"), peak=state.get("peak", 0), times=request_times(records),
        )

    def check(self, win: dict, seed: int, *, control: Optional[str] = None) -> dict:
        """The reference over a seeded sample of the window's finished
        requests, the longest among them: the widest gap by which a served
        token's logit lies below the reference's best (and, with ``control``,
        the gap of the tokens that the reference in that lower precision
        would put first)."""
        import jax

        records, requests = win["records"], win["requests"]
        done = [i for i, r in enumerate(records) if r["in_window"] and not r["error"] and r["tokens"]]
        if not done:
            nan = float("nan")
            return {"served": {"max": nan, "mean": nan}, "control": None, "tokens": 0}
        ref = importlib.import_module(f"chipbench.reference.{self.cfg['reference']}")
        rng = np.random.default_rng([int(seed), 0xC0DE])
        longest = max(done, key=lambda i: records[i]["n_prompt"] + len(records[i]["tokens"]))
        picks = [longest] + [done[int(j)] for j in rng.permutation(len(done))[: int(self.mix["check_requests"]) - 1]]
        samples = [{"prompt": requests[i]["prompt"], "tokens": records[i]["tokens"]} for i in dict.fromkeys(picks)]
        pad_to = self.cfg["serving"]["prompt_buckets"][-1] + self.cfg["serving"]["max_new_tokens"]
        with jax.default_matmul_precision("highest"):
            return judge.served_logit_gaps(
                lambda seq: ref.forward_layerwise(self.params, seq, self.cfg), samples, pad_to,
                control_forward=None if control is None else (
                    lambda seq: ref.forward_layerwise(self.params, seq, self.cfg, control)
                ),
            )

    def close(self) -> None:
        """Stop the server and the engine and free the engine's device state;
        the weights stay for the reference."""
        if self.app is not None:
            self.app.shutdown()
            self.engine.close()
            self.app = self.engine = None


def run(ctx: dict) -> dict:
    import jax

    cfg, mix, seed, seconds = ctx["config"], ctx["traffic"], ctx["seed"], ctx["seconds"]
    # engine spans and HTTP timelines of every finished request, only when traced
    timelines: List[tuple] = []
    if ctx["trace"]:
        from unionml_tpu import telemetry

        telemetry.get_tracer().add_listener(lambda rid, meta, spans: timelines.append((rid, meta, spans)))
    service = Service(cfg, mix, seed, ctx["marks"])
    try:
        compiles = ctx["compile_counter"]
        state = {"trace_t0": None, "trace_t1": None}

        def trace_starts():
            jax.profiler.start_trace(ctx["trace_dir"])
            state["trace_t0"] = time.perf_counter()

        def trace_stops():
            state["trace_t1"] = time.perf_counter()
            jax.profiler.stop_trace()

        hooks = [(0.0, lambda: state.update(c0=compiles())), (float(seconds), lambda: state.update(c1=compiles()))]
        if ctx["trace"]:
            hooks += [(float(mix["trace_from_s"]), trace_starts),
                      (float(mix["trace_from_s"]) + float(mix["trace_seconds"]), trace_stops)]
        win = service.window(seed, seconds, hooks=hooks, memory_peak=ctx["memory_peak"])
        chunk_steps, slots = service.chunk_steps, service.slots
    finally:
        service.close()
    # a traced run is not judged, and the profiler's own start and stop hold
    # the interpreter: there the lag is printed, not enforced
    if win["send_lag_p99_ms"] > float(mix["max_send_lag_ms"]) and not ctx["trace"]:
        raise SystemExit("chipbench: the load generator ran late; the run would measure the generator, not the server")
    compiles_in_window = state["c1"] - state["c0"]
    say(f"window: {compiles_in_window} compilations inside it")

    t_ref = time.perf_counter()
    gaps = service.check(win, seed)
    say(f"reference: {gaps['tokens']} served tokens checked in {time.perf_counter() - t_ref:.1f} s "
        "(not in setup_s, outside the window)")
    numbers = {
        "requests_not_served_in_full": {"value": float(win["failed"]), "limit": 0.0},
        "served_logit_gap_mean": {
            "value": gaps["served"]["mean"], "limit": cfg["correct"]["served_logit_gap_mean"]},
        "served_logit_gap_max": {
            "value": gaps["served"]["max"], "limit": cfg["correct"]["served_logit_gap_max"]},
    }
    e2e = end_to_end(win["times"])
    e2e["setup_s"] = win["t_zero"] - ctx["t_start"]
    say("client numbers, judged or not: " + json.dumps({k: round(v, 4) for k, v in e2e.items()}))
    return {
        "end_to_end": e2e, "attempted": win["attempted"], "failed": win["failed"],
        "numbers": numbers, "memory_peak_bytes": win["peak"],
        "records": win["records"], "requests": win["requests"], "times": win["times"],
        "timelines": timelines, "occupancy": win["occupancy"], "t_zero": win["t_zero"],
        "window_s": float(seconds), "compiles_in_window": compiles_in_window,
        "chunk_steps": chunk_steps, "slots": slots,
        "trace_dir": ctx["trace_dir"] if state["trace_t0"] is not None else None,
        "trace_host_window_s": None if state["trace_t0"] is None else state["trace_t1"] - state["trace_t0"],
    }
