"""Find a serving cell's knee, once: several fixed rates in one process.

    python3 -m chipbench.sweep --workload <name> --seed <n> --seconds <s> --rates 4,6,8

For each rate one window of open-loop load; the backlog (requests due and
not yet finished) is read at thirds of the window. The knee is the highest
rate at which the backlog does not grow through the window; the cell's rate
is four fifths of it, written into the traffic file as a number. Results are
recorded in chipbench/README.md so that a later issue can find them again.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench.yardstick import ROOT, say


def backlog_at(records, t: float) -> int:
    due = sum(1 for r in records if r["due"] <= t)
    finished = sum(1 for r in records if r["t_tokens"] and r["t_tokens"][-1] <= t and not r["error"])
    return due - finished


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args(argv)
    from chipbench.run import find_devices, load_cell

    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    cell, cfg, mix = load_cell(bench, args.workload)
    first = find_devices(cell["chips"])[1][0]
    from chipbench.runners.serve_open_loop import Service, end_to_end

    service = Service(cfg, mix, args.seed)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            win = service.window(args.seed + k, args.seconds, rate=rate)
            t0, s = win["t_zero"], args.seconds
            logs = [backlog_at(win["records"], t0 + f * s) for f in (1 / 3, 2 / 3, 1.0)]
            occ = win["occupancy"] or {}
            row = dict(
                rate_per_s=rate, attempted=win["attempted"], failed=win["failed"],
                backlog_at_thirds=logs, occupancy_ratio=occ.get("occupancy_ratio"),
                send_lag_p99_ms=win["send_lag_p99_ms"], **end_to_end(win["times"]),
                device=f"{first.device_kind} ({first.platform})",
            )
            say("sweep: " + json.dumps(row))
    finally:
        service.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
