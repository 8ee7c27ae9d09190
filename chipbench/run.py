"""One cell, once, in a new process:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell, its
configuration file and its traffic file by name, and hands them to the
runner of the traffic's kind. Fails without a TPU of a kind in
``chipbench/peaks.json``. This file knows no cell, configuration, traffic
mix or metric by name: those live in files of their own (README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can see it

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

from chipbench import xplane  # noqa: E402
from chipbench.yardstick import ROOT, load_peaks, percentile, say  # noqa: E402

CHECKOUT = ROOT.parent
OUT_DIR = CHECKOUT / ".chipbench_out"  # traces of traced runs; in .gitignore


class RunView:
    """What a per-layer reader may look at: the runner's record, the cell's
    files, the peaks, and the reduced device trace of a traced run."""

    def __init__(self, record: dict, config: dict, traffic: dict, peaks: dict):
        self.record, self.config, self.traffic, self.peaks = record, config, traffic, peaks
        self._trace: Optional[xplane.Trace] = None
        self._engine_spans: Optional[Dict[str, dict]] = None

    @property
    def trace(self) -> Optional[xplane.Trace]:
        if self._trace is None and self.record.get("trace_dir"):
            path = xplane.find_xplane(self.record["trace_dir"])
            if path is not None:
                self._trace = xplane.load(path)
        return self._trace

    def traced_window_s(self) -> float:
        return max(self.record.get("trace_host_window_s") or 0.0, self.trace.device_span_s())

    def device_idle_pct(self) -> Optional[float]:
        if self.trace is None or not self.trace.devices:
            return None
        return 100.0 * (1.0 - self.trace.busy_s() / self.traced_window_s())

    # ---- training

    def train_step_pattern(self) -> Optional[str]:
        """The name of the program that takes most of the traced device time."""
        if self.trace is None or not self.trace.devices:
            return None
        total: Dict[str, float] = {}
        for name, s, e in self.trace.devices[0].modules:
            total[name] = total.get(name, 0.0) + (e - s)
        return "^" + re.escape(max(total, key=total.get)) + "$" if total else None

    def train_step_s(self) -> Optional[float]:
        pattern = self.train_step_pattern()
        if pattern is None:
            return None
        return percentile([e - s for s, e in self.trace.module_runs(pattern)], 50)

    # ---- serving

    def decode_step_s(self) -> Optional[float]:
        if self.trace is None:
            return None
        runs = self.trace.module_runs(r"^jit_decode_chunk\(")
        if not runs:
            return None
        return percentile([e - s for s, e in runs], 50) / self.record["chunk_steps"]

    def engine_spans_by_http_rid(self) -> Dict[str, dict]:
        """{X-Request-ID: {span name: (start, end)}} of the engine's own
        timeline of each request, found through its parent span."""
        if self._engine_spans is None:
            http = {meta.get("span_id"): rid for rid, meta, _ in self.record["timelines"] if meta.get("kind") == "http"}
            out: Dict[str, dict] = {}
            for _, meta, spans in self.record["timelines"]:
                rid = http.get(meta.get("parent_span_id"))
                if rid is not None and meta.get("kind") != "http":
                    out[rid] = {s["name"]: (s["start_s"], s["end_s"]) for s in spans}
            self._engine_spans = out
        return self._engine_spans

    def mean_live_kv_tokens(self) -> float:
        """Cached positions resident on average over the window, from the
        client's records: each request holds its prompt from its first token
        to its last, and its output grows linearly between them."""
        t0 = self.record["t_zero"]
        t1 = t0 + self.record["window_s"]
        total = 0.0
        for r in self.record["records"]:
            if r["error"] or len(r["t_tokens"]) < 2:
                continue
            a, b = max(r["t_tokens"][0], t0), min(r["t_tokens"][-1], t1)
            if b > a:
                total += (b - a) * (r["n_prompt"] + len(r["tokens"]) / 2.0)
        return total / (t1 - t0)

    def prompt_lengths_prefilled_while_traced(self) -> List[int]:
        lo = self.record["t_zero"] + float(self.traffic["trace_from_s"])
        hi = lo + float(self.traffic["trace_seconds"])
        spans = self.engine_spans_by_http_rid()
        return [
            r["n_prompt"] for r in self.record["records"]
            if r.get("rid") in spans and "prefill" in spans[r["rid"]] and lo <= spans[r["rid"]]["prefill"][0] <= hi
        ]


def _load_reader(name: str):
    path = ROOT / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"chipbench: per-layer metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_layer_metric_{abs(hash(name))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(bench: dict, workload: str, files_root: Path = CHECKOUT):
    """(cell entry, configuration, traffic mix) of ``workload``, each found by
    the name that ``BENCHMARK.json`` gives."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((files_root / conf_entry["file"]).read_text())
    traffic_file = ROOT / "traffic" / f"{cell['traffic']}.json"
    if not traffic_file.is_file():
        traffic_file = files_root / "traffic" / f"{cell['traffic']}.json"
    return cell, config, json.loads(traffic_file.read_text())


def find_devices(chips: int, require_chip: bool = True):
    """(compile cache directory, JAX's devices). Without ``chips`` TPU chips of
    a kind in the peaks table this exits, and no result is printed."""
    # the program fixes its compile cache at <checkout>/.jax_cache unless the
    # environment names one; either way it is a fixed path that we hand it
    from unionml_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise SystemExit(f"chipbench: needs a TPU, JAX found platform {devices[0].platform!r}")
        if len(devices) < chips:
            raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX sees {len(devices)}")
        load_peaks(devices[0].device_kind)
    return cache_dir, devices


def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, files_root: Path = CHECKOUT) -> dict:
    """Run one cell and return its result line as a dict. ``require_chip``
    is false only in the benchmark's own tests, which rehearse on the CPU."""
    cell, config, mix = load_cell(bench, workload, files_root)
    cache_dir, devices = find_devices(cell["chips"], require_chip)
    first = devices[0]
    if require_chip:
        peaks = load_peaks(first.device_kind)
    else:
        peaks = next(iter(json.loads((ROOT / "peaks.json").read_text()).values()))
    import jax

    used = devices[: cell["chips"]]
    say(f"cell {workload}: config {cell['config']}, traffic {cell['traffic']} (kind {mix['kind']}), "
        f"seed {seed}, {seconds} s, trace {int(trace)}; {len(devices)} x {first.device_kind} "
        f"({first.platform}), jax {jax.__version__}, compile cache {cache_dir}")

    compiled = [0]

    def on_duration(event: str, duration: float, **_):
        if "backend_compile" in event or "cache_retrieval" in event:
            compiled[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def memory_peak() -> int:
        """Peak bytes on the fullest chip: the allocator's peak plus the peak
        reserved for the programs' temporaries, which the allocator's own
        peak does not count (PERF.md, section 2)."""
        peak = 0
        for d in used:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
        return peak

    trace_dir = OUT_DIR / "trace" / f"{workload}-{seed}"
    if trace:
        shutil.rmtree(OUT_DIR / "trace", ignore_errors=True)  # one trace is kept, the newest
        trace_dir.mkdir(parents=True, exist_ok=True)
    marks = [("process start", T_START), ("jax imported, devices found", time.perf_counter())]
    ctx = dict(
        config=config, traffic=mix, seed=seed, seconds=seconds, trace=trace, chips=cell["chips"],
        marks=marks, t_start=T_START, trace_dir=str(trace_dir),
        compile_counter=lambda: compiled[0], memory_peak=memory_peak,
    )
    runner = importlib.import_module(f"chipbench.runners.{mix['kind']}")
    record = runner.run(ctx)
    say(f"memory of {used[0]}: {used[0].memory_stats()}")

    for (_, t0), (what, t1) in zip(marks, marks[1:]):
        say(f"set-up: {t1 - t0:8.2f} s  {what}")
    say(f"set-up: {record['end_to_end']['setup_s']:8.2f} s  in all, from process start to the first measured request or step")

    from chipbench import judge

    correct = judge.verdict(record["numbers"])
    view = RunView(record, config, mix, peaks)
    metrics: Dict[str, dict] = {}
    if not trace:
        for m in bench["end_to_end"]:
            if _for_cell(m, workload):
                if m["name"] not in record["end_to_end"]:
                    raise SystemExit(f"chipbench: runner {mix['kind']} reports no {m['name']}")
                metrics[m["name"]] = {"value": record["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if _for_cell(m, workload):
                value = _load_reader(m["name"]).read(view)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {
        "platform": first.platform, "kind": first.device_kind, "count": cell["chips"],
        "memory_peak_bytes": record["memory_peak_bytes"],
    }
    line = {
        "correct": bool(correct), "attempted": record["attempted"], "failed": record["failed"],
        "metrics": metrics, "device": device, "compiles_in_window": record["compiles_in_window"],
    }
    if trace and view.trace is not None:
        device["busy_s"] = view.trace.busy_s()
        device["window_s"] = view.traced_window_s()
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in view.trace.op_totals(10)],
            "idle_gaps": [[n, s] for n, s in view.trace.idle_gaps(10)],
        }
    return line


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    line = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(line), flush=True)
    # daemon threads of the program (engine, HTTP server) are stopped by now;
    # leave without waiting for interpreter teardown of device buffers
    return 0


if __name__ == "__main__":
    sys.exit(main())
