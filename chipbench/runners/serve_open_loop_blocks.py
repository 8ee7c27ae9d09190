"""Runner ``serve_open_loop_blocks``: ``serve_open_loop``'s window, load
generator and record, with the check of a model that **generates by
blocks**.

``serve_open_loop.Service.check`` hands the reference ``prompt + tokens`` and
``judge.served_logit_gaps`` reads row ``len(prompt) - 1 + i`` for token ``i``:
a next-token alignment. A model that generates by diffusion over blocks
predicts the token *at* a position, from a state that held some of its
block's entries decided and the mask token at the others, and which state
that was is the engine's to say: the finished request's timeline carries
``decided_at``, for each served token the forward of its block that decided
it, and reaches this runner by ``X-Request-ID`` (so the tracer is listened to
in untraced runs too). :func:`served_block_logit_gaps` rebuilds each block's
state before each forward that decided something (decided entries as served,
the rest the mask token), has the reference run it against the *final* tokens
of every earlier block under the block-causal mask
(``reference.forward_states``: every state of a request in one pass), and
reads at each entry decided there how far the served token's logit lies below
the reference's best. ``numbers`` carries the three names the other served
cells use.

    python3 -m chipbench.runners.serve_open_loop_blocks --workload <name> --seeds 1,2,3 \\
        [--seconds 20] [--control int4] [--mask causal] [--control-seeds 3] [--stale-commit 1]

reads the compared numbers over several seeds in one process (new weights in
the old ones' buffers), and on the first ``--control-seeds`` of them the
controls': the reference in int4, the reference with a plainly causal mask
(each judged apart), and, with ``--stale-commit 1``, a program that keeps a
block's last denoising forward's rows. It sets nothing.
"""

from __future__ import annotations

import importlib
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench.runners.serve_open_loop import Service, end_to_end, request_times  # noqa: F401
from chipbench.yardstick import say


def block_states(sample: dict, gen: dict, span: int):
    """``(clean, copies, start)`` of one served request: the final tokens
    from position 0 to the end of the last block (the mask token at the
    entries past the asked length, which are never decided), and for each
    forward ``f`` of a block the tokens of the generated span (``start ..
    start + span - 1``, ``start`` the first generated block's) as they stood
    before it: the prompt's entries of the first block, the entries decided
    at an earlier forward as served, the mask token elsewhere."""
    bk, mask_id, steps = gen["block_length"], gen["mask_token_id"], gen["denoising_steps"]
    prompt, tokens, at = list(sample["prompt"]), list(sample["tokens"]), list(sample["decided_at"])
    start, stop = len(prompt) // bk * bk, len(prompt) + len(tokens)
    end = -(-stop // bk) * bk
    clean = prompt + tokens + [mask_id] * (end - stop)
    copies = np.full((steps, span), mask_id, np.int64)
    for f in range(steps):
        for p in range(start, end):
            if p < len(prompt) or (p < stop and at[p - len(prompt)] < f):
                copies[f, p - start] = clean[p]
    return clean, copies, start


def served_block_logit_gaps(forward_states: Callable, samples: List[dict], gen: dict, clean_len: int,
                            span: int, *, control_forward: Callable = None) -> dict:
    """For each sampled request ``{"prompt", "tokens", "decided_at"}`` run
    the reference once over its states and read, at every served token, how
    far its logit lies below the reference's best in the state it was
    decided from. With ``control_forward`` (one, or several by name) also
    read the gap of the token that each control puts first there.
    ``forward_states(clean [clean_len], copies [T, span], start) -> logits
    [T, span, vocab]``. Returns the widest and the mean gap of each, as
    ``judge.served_logit_gaps`` does (``control``: the sole control's;
    ``controls``: each by name)."""
    controls = control_forward if isinstance(control_forward, dict) else (
        {} if control_forward is None else {"control": control_forward})
    served, control = [], {name: [] for name in controls}
    for s in samples:
        clean, copies, start = block_states(s, gen, span)
        padded = np.zeros(clean_len, np.int64)
        padded[:len(clean)] = clean
        logits = np.asarray(forward_states(padded, copies, start))
        at = np.asarray(s["decided_at"], np.int64)
        where = len(s["prompt"]) - start + np.arange(len(s["tokens"]))
        rows = logits[at, where]
        best = rows.max(-1)
        served.append(best - rows[np.arange(len(rows)), np.asarray(s["tokens"])])
        for name, forward in controls.items():
            crows = np.asarray(forward(padded, copies, start))[at, where]
            control[name].append(best - rows[np.arange(len(rows)), crows.argmax(-1)])

    def stats(parts):
        if not parts:
            return None
        x = np.concatenate(parts)
        return {"max": float(x.max()), "mean": float(x.mean()), "p99": float(np.percentile(x, 99)),
                "share_over_half": float((x > 0.5).mean())}

    by_name = {name: stats(parts) for name, parts in control.items()}
    return {"served": stats(served), "control": by_name["control"] if list(by_name) == ["control"] else None,
            "controls": by_name, "tokens": int(sum(len(x) for x in served))}


def decided_at_by_http_rid(timelines: List[tuple]) -> Dict[str, List[int]]:
    """{X-Request-ID: the engine's ``decided_at`` of that request}, found
    through the engine timeline's parent span (as ``RunView
    .engine_spans_by_http_rid`` finds its spans)."""
    http = {meta.get("span_id"): rid for rid, meta, _ in timelines if meta.get("kind") == "http"}
    out = {}
    for _, meta, _ in timelines:
        rid = http.get(meta.get("parent_span_id"))
        for event in meta.get("events", []) if rid is not None else []:
            if event["name"] == "decided_at":
                out[rid] = list(event["args"]["forwards"])
    return out


class BlockService(Service):
    """``Service`` with the tracer listened to for the life of the process
    and the check of a model that generates by blocks."""

    def __init__(self, cfg: dict, mix: dict, seed: int, marks: Optional[list] = None):
        from unionml_tpu import telemetry

        self.timelines: List[tuple] = []
        self._listener = lambda rid, meta, spans: self.timelines.append((rid, meta, spans))
        telemetry.get_tracer().add_listener(self._listener)
        super().__init__(cfg, mix, seed, marks)

    def window(self, seed: int, seconds: float, **kw) -> dict:
        del self.timelines[:]       # the warm-up's, or the window's before
        return super().window(seed, seconds, **kw)

    def check(self, win: dict, seed: int, *, control: Optional[str] = None, mask: Optional[str] = None) -> dict:
        """The reference over a seeded sample of the window's finished
        requests, the longest among them. ``control`` / ``mask`` also read
        the gap of the tokens that the reference in that lower precision, and
        the reference under that mask, would put first (each apart)."""
        import jax

        records, requests = win["records"], win["requests"]
        trajectory = decided_at_by_http_rid(self.timelines)
        finished = [i for i, r in enumerate(records) if r["in_window"] and not r["error"] and r["tokens"]]
        done = [i for i in finished if len(trajectory.get(records[i]["rid"], ())) == len(records[i]["tokens"])]
        missing = len(finished) - len(done)
        if missing:
            say(f"reference: {missing} finished requests came without a trajectory as long as their stream")
        if not done:
            nan = float("nan")
            return {"served": {"max": nan, "mean": nan}, "control": None, "controls": {}, "tokens": 0, "missing": missing}
        ref = importlib.import_module(f"chipbench.reference.{self.cfg['reference']}")
        rng = np.random.default_rng([int(seed), 0xC0DE])
        longest = max(done, key=lambda i: records[i]["n_prompt"] + len(records[i]["tokens"]))
        picks = [longest] + [done[int(j)] for j in rng.permutation(len(done))[: int(self.mix["check_requests"]) - 1]]
        samples = [
            {"prompt": requests[i]["prompt"], "tokens": records[i]["tokens"],
             "decided_at": trajectory[records[i]["rid"]]}
            for i in dict.fromkeys(picks)
        ]
        gen, serving = self.cfg["generation"], self.cfg["serving"]
        bk = gen["block_length"]
        clean_len = -(-(serving["prompt_buckets"][-1] + serving["max_new_tokens"]) // bk) * bk
        span = serving["max_new_tokens"] // bk * bk + bk
        rows = clean_len + gen["denoising_steps"] * span
        pad_to = -(-rows // 256) * 256

        def states(**kw):
            return lambda clean, copies, start: ref.forward_states(
                self.params, clean, copies, start, self.cfg, pad_to=pad_to, **kw)

        others = {}
        if control is not None:
            others[control] = states(control=control)
        if mask is not None:
            others[mask] = states(mask=mask)
        with jax.default_matmul_precision("highest"):
            gaps = served_block_logit_gaps(states(), samples, gen, clean_len, span, control_forward=others or None)
        if len(others) == 1:
            gaps["control"] = next(iter(gaps["controls"].values()))
        gaps["missing"] = missing
        return gaps

    def close(self) -> None:
        if self.app is not None:
            from unionml_tpu import telemetry

            telemetry.get_tracer().remove_listener(self._listener)
        super().close()


def numbers_of(cfg: dict, win: dict, gaps: dict) -> dict:
    """The compared numbers of one window, each beside its limit: under the
    names the other served cells use. A finished request without a
    trajectory counts as not served in full."""
    return {
        "requests_not_served_in_full": {"value": float(win["failed"] + gaps.get("missing", 0)), "limit": 0.0},
        "served_logit_gap_mean": {
            "value": gaps["served"]["mean"], "limit": cfg["correct"]["served_logit_gap_mean"]},
        "served_logit_gap_max": {
            "value": gaps["served"]["max"], "limit": cfg["correct"]["served_logit_gap_max"]},
    }


def run(ctx: dict) -> dict:
    import jax

    cfg, mix, seed, seconds = ctx["config"], ctx["traffic"], ctx["seed"], ctx["seconds"]
    service = BlockService(cfg, mix, seed, ctx["marks"])
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
    e2e = end_to_end(win["times"])
    e2e["setup_s"] = win["t_zero"] - ctx["t_start"]
    say("client numbers, judged or not: " + json.dumps({k: round(v, 4) for k, v in e2e.items()}))
    return {
        "end_to_end": e2e, "attempted": win["attempted"], "failed": win["failed"],
        "numbers": numbers_of(cfg, win, gaps), "memory_peak_bytes": win["peak"],
        "records": win["records"], "requests": win["requests"], "times": win["times"],
        "timelines": list(service.timelines), "occupancy": win["occupancy"], "t_zero": win["t_zero"],
        "window_s": float(seconds), "compiles_in_window": compiles_in_window,
        "chunk_steps": chunk_steps, "slots": slots,
        "trace_dir": ctx["trace_dir"] if state["trace_t0"] is not None else None,
        "trace_host_window_s": None if state["trace_t0"] is None else state["trace_t1"] - state["trace_t0"],
    }


def main(argv=None) -> int:
    import argparse
    import sys

    from chipbench import judge
    from chipbench.run import find_devices, load_cell
    from chipbench.yardstick import ROOT

    ap = argparse.ArgumentParser(description="The compared numbers over several seeds, and the controls'.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default=None, help="also the reference in this precision (int4)")
    ap.add_argument("--mask", default=None, help="also the reference under this mask (causal)")
    ap.add_argument("--control-seeds", type=int, default=10 ** 6, help="the controls on the first N seeds only")
    ap.add_argument("--stale-commit", type=int, default=0,
                    help="1: the program keeps a block's last denoising forward's rows (wrong on purpose)")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    cell, cfg, mix = load_cell(bench, args.workload)
    first = find_devices(cell["chips"])[1][0]
    if args.stale_commit:
        from unionml_tpu.serving.engine import DecodeEngine

        DecodeEngine._block_stale_commit = True
    seeds = [int(s) for s in args.seeds.split(",")]
    service = BlockService(cfg, mix, seeds[0])
    try:
        for k, seed in enumerate(seeds):
            if k:
                service.reseed(seed)
            win = service.window(seed, args.seconds)
            with_controls = k < args.control_seeds
            gaps = service.check(
                win, seed, control=args.control if with_controls else None,
                mask=args.mask if with_controls else None,
            )
            say("calibrate: " + json.dumps(dict(
                seed=seed, device=f"{first.device_kind} ({first.platform})", stale_commit=args.stale_commit,
                attempted=win["attempted"], failed=win["failed"], **end_to_end(win["times"]), **gaps,
            )))
            say(f"calibrate: seed {seed}, the program through judge.verdict: "
                f"{judge.verdict(numbers_of(cfg, win, gaps))}")
            for name, stats in (gaps.get("controls") or {}).items():
                as_served = dict(gaps, served=stats)
                say(f"calibrate: seed {seed}, the control {name} through judge.verdict: "
                    f"{judge.verdict(numbers_of(cfg, win, as_served))}")
    finally:
        service.close()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
