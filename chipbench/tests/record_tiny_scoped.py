"""Records ``data/tiny_scoped_v5e.xplane.pb`` on the chip:

    chiprun -- python3 -m chipbench.tests.record_tiny_scoped

What ``record_tiny_annotated.py`` traces (PR 25), of the program from PR 38
on, whose traced functions open ``jax.named_scope`` round the work no module
owns: two requests through the tiny MoE engine (``data/configs/tiny-moe-int8.json``,
two slots), then two steps of the tiny ViT through ``run_step_trainer``, under
a profiler session with the Python tracer off. ``test_opscopes.py`` pins
``sample`` / ``commit`` / ``router`` / ``optimizer`` against the file; what
it counts is printed at the end. Fails without a TPU; the file lands in
``chiprun_out/`` and is copied into ``data/`` by hand.
"""

from __future__ import annotations

import dataclasses
import json
import re
import shutil
import sys
import threading
import time
from pathlib import Path

from chipbench.tests.record_tiny_annotated import DATA, OUT, PROMPTS


def main() -> int:
    import jax

    from chipbench import opscopes, weights, xplane
    from chipbench.adapters import llama_decoder, vit
    from chipbench.tests.record_tiny_annotated import train_three_steps

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_tiny_scoped: needs a TPU")
    cfg = json.loads((DATA / "configs" / "tiny-moe-int8.json").read_text())
    cfg["serving"] = dict(cfg["serving"], slots=2, max_new_tokens=6, prompt_buckets=[16])
    built = llama_decoder.build(cfg)
    # the Pallas paged kernel copies whole 128-lane tiles (PR 26 on): a head
    # of 16 values is read by the plain gather, which compiles at any size
    module = built["serve_module"]
    built["serve_module"] = type(module)(dataclasses.replace(module.config, paged_impl="reference"))
    params = jax.block_until_ready(weights.make_tree(built["abstract_serve_params"](), 7))
    engine, app, _host, _port = llama_decoder.start_service(built, cfg, params)
    trace_dir = OUT / "tiny_scoped_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    vit_cfg = json.loads((DATA / "configs" / "tiny-vit.json").read_text())
    vit_built = vit.build(vit_cfg)
    train_three_steps(vit_cfg, vit_built)  # compiled outside the session
    engine.generate(params, [PROMPTS[0]], max_new_tokens=6)
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        threads = [
            threading.Thread(target=engine.generate, args=(params, [p]), kwargs={"max_new_tokens": 6})
            for p in PROMPTS[:2]
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        train_three_steps(vit_cfg, vit_built)
        jax.profiler.stop_trace()
    finally:
        app.shutdown()
        engine.close()
    path = xplane.find_xplane(str(trace_dir))
    shutil.copy(path, OUT / "tiny_scoped_v5e.xplane.pb")
    t0 = time.perf_counter()
    ops = opscopes.scoped_ops(path)["/device:TPU:0"]
    decode_s = time.perf_counter() - t0
    summary = {"bytes": Path(path).stat().st_size, "events": len(ops), "decode_s": decode_s, "programs": {}}
    for program in sorted({op.program for op in ops if op.program}):
        runs = opscopes.whole_runs(ops, "^" + re.escape(program) + "$")
        summary["programs"][program] = {"whole_runs": len(runs), "part_s": opscopes.part_seconds(runs)}
    summary["scopes"] = sorted({
        part for op in ops for part in opscopes.scope_path(op.tf_op)[:-1]
        if part in ("sample", "commit", "step_io", "router", "group_rows", "gather", "experts", "combine",
                    "loss", "optimizer")
    })
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
