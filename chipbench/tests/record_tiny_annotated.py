"""Records ``data/tiny_annotated_v5e.xplane.pb`` and, beside it,
``tiny_annotated_v5e.json`` (the engine's own request timelines and its perf
plane's report of the same seconds) on the chip:

    chiprun -- python3 -m chipbench.tests.record_tiny_annotated

A few passes of the tiny MoE engine (``data/configs/tiny-moe-int8.json``, two
slots) under a profiler session with the Python tracer off, so the file stays
small: four requests submitted together, so that two wait while two are
admitted, one admission a pass; then three steps of the tiny ViT through
``run_step_trainer``. What ``test_hostspans.py`` counts by hand in the result
is printed at the end. Fails without a TPU; the files land in ``chiprun_out/``
and are copied into ``data/`` by hand.
"""

from __future__ import annotations

import json
import shutil
import sys
import threading
from pathlib import Path

DATA = Path(__file__).parent / "data"
OUT = Path(__file__).resolve().parents[2] / "chiprun_out"
PROMPTS = [[7, 3, 9, 4, 2, 8], [5, 1, 6, 2, 9, 9, 3], [4, 4, 8, 1], [2, 7, 7, 5, 3, 1, 6, 8]]


def main() -> int:
    import jax

    from chipbench import hostspans, weights, xplane
    from chipbench.adapters import llama_decoder, vit
    from unionml_tpu import telemetry

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_tiny_annotated: needs a TPU")
    cfg = json.loads((DATA / "configs" / "tiny-moe-int8.json").read_text())
    cfg["serving"] = dict(cfg["serving"], slots=2, max_new_tokens=6, prompt_buckets=[16])
    built = llama_decoder.build(cfg)
    params = jax.block_until_ready(weights.make_tree(built["abstract_serve_params"](), 7))
    timelines = []
    telemetry.get_tracer().add_listener(lambda rid, meta, spans: timelines.append([rid, meta, spans]))
    engine, app, _host, _port = llama_decoder.start_service(built, cfg, params)
    trace_dir = OUT / "tiny_annotated_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    vit_cfg = json.loads((DATA / "configs" / "tiny-vit.json").read_text())
    vit_built = vit.build(vit_cfg)  # one step function for both calls: one compile
    train_three_steps(vit_cfg, vit_built)  # outside the session: the engine polls all through it
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        engine.perf.reset()
        threads = [
            threading.Thread(target=engine.generate, args=(params, [p]), kwargs={"max_new_tokens": 6})
            for p in PROMPTS
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        report = engine.perf.report()
        train_three_steps(vit_cfg, vit_built)
        jax.profiler.stop_trace()
    finally:
        app.shutdown()
        engine.close()
    path = xplane.find_xplane(str(trace_dir))
    shutil.copy(path, OUT / "tiny_annotated_v5e.xplane.pb")
    report.pop("watchdog")
    (OUT / "tiny_annotated_v5e.json").write_text(json.dumps({"timelines": timelines, "occupancy": report}))
    spans = hostspans.load(path)
    trace = xplane.load(path)
    print(json.dumps({
        "bytes": Path(path).stat().st_size,
        "annotations": {name: len(evs) for name, evs in sorted(spans.by_name.items())},
        "jit_prefill_runs": len(trace.module_runs(r"^jit_prefill\(")),
        "jit_decode_chunk_runs": len(trace.module_runs(r"^jit_decode_chunk\(")),
        "clock": hostspans.clock_offset(hostspans.clock_pairs(spans, timelines)),
        "report": report,
    }, indent=1))
    return 0


def train_three_steps(cfg: dict, built: dict) -> None:
    """Three steps of the tiny ViT through the loop behind ``Model.train()``."""
    import jax

    from chipbench import weights
    from unionml_tpu.execution import run_step_trainer

    state = built["make_state"](weights.make_tree(built["abstract_params"](), 7))
    pool = built["make_batches"](jax.random.key(7), 3, cfg["training"]["batch_per_chip"])
    batches = [built["take_batch"](pool, i) for i in range(3)]
    run_step_trainer(
        step_fn=built["step_fn"], state=state, features=iter(batches),
        batch_size=cfg["training"]["batch_per_chip"],
    )


if __name__ == "__main__":
    sys.exit(main())
