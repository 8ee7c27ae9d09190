"""Read the numbers that ``correct`` compares, over many seeds in one process.

    python3 -m chipbench.calibrate --workload <name> --seeds 1,2,... --control-seeds 1,2,3 [--seconds 20]

For every seed the sound program's numbers; for the control seeds also the
control's: the plain reference put in the program's place and computed in
the nearest precision below the configuration's (fp8 for a bfloat16 step,
int4 for int8 weights). A limit is set between the sound runs' largest and
the control's smallest (PERF.md section 2); this tool only reads, it sets
nothing. One process, because set-up is most of a run.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import judge
from chipbench.yardstick import ROOT, say


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    from chipbench.run import find_devices, load_cell

    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    cell, cfg, mix = load_cell(bench, args.workload)
    first = find_devices(cell["chips"])[1][0]
    where = f"{first.device_kind} ({first.platform}) x {cell['chips']}"

    if mix["kind"] == "train_step_loop":
        from chipbench.runners.train_step_loop import Cell

        control = cfg["correct"]["control"]
        tc = Cell(cfg, mix, cell["chips"])
        for seed in seeds:
            batches = tc.make_batches(seed)
            state, got = tc.first_three(tc.make_state(seed), batches, seed)
            del state
            want = tc.reference(seed, batches)
            row = {k: v["value"] for k, v in judge.compare_training(got, want, cfg["correct"]).items()}
            say("calibrate: " + json.dumps(dict(seed=seed, who="program", losses=got["losses"], **row, device=where)))
            if seed in control_seeds:
                ctrl = tc.reference(seed, batches, control=control)
                row = {k: v["value"] for k, v in judge.compare_training(ctrl, want, cfg["correct"]).items()}
                say("calibrate: " + json.dumps(dict(seed=seed, who=f"control {control}", losses=ctrl["losses"], **row, device=where)))
    elif mix["kind"] == "serve_open_loop":
        from chipbench.runners.serve_open_loop import Service

        control = cfg["correct"]["control"]
        service = Service(cfg, mix, seeds[0])
        try:
            for k, seed in enumerate(seeds):
                if k:
                    service.reseed(seed)
                win = service.window(seed, args.seconds)
                gaps = service.check(win, seed, control=control if seed in control_seeds else None)
                say("calibrate: " + json.dumps(dict(
                    seed=seed, attempted=win["attempted"], failed=win["failed"], tokens=gaps["tokens"],
                    served=gaps["served"], control=control, control_gaps=gaps["control"], device=where,
                )))
        finally:
            service.close()
    else:
        raise SystemExit(f"chipbench: no calibration for traffic kind {mix['kind']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
