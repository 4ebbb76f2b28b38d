#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip: one full run of the cell
at each rate of a ladder, the raw points kept beside the cell's own file.

    python3 benchmark/sweep.py --workload <name> --seconds <s> --seed <n> <rate>...

The knee is the highest rate that the system holds for the window with no
growing order backlog: every order answered, the order queue no deeper at the
window's end than two frames, and the last third of the window's median fill
latency within 1.5 times the first third's. The cell's rate is then set by
hand in <base>/cells/<workload>.json as a share of the knee (PERF.md says which
share and why); a cell's run never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def holds(point: dict) -> bool:
    first, last = point["p50_first_third_ms"], point["p50_last_third_ms"]
    return (point["correct"] and point["failed"] == 0
            and point["order_backlog_frames_end"] <= 2
            and first is not None and last <= 1.5 * first)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None,
                    help="where the points go (default: beside the cell's "
                         "own file, <base>/cells/<workload>.sweep.json)")
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args(argv)
    points = []
    for i, rate in enumerate(args.rates):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               args.workload, "--seed", str(args.seed + i), "--seconds",
               str(args.seconds), "--trace", "0", "--rate", str(rate)]
        if args.rehearsal:
            cmd.append("--rehearsal")
        p = subprocess.run(cmd, capture_output=True, text=True)
        if p.returncode:
            print(p.stderr[-2000:], file=sys.stderr)
            return p.returncode
        lines = p.stdout.strip().splitlines()
        report = json.loads(
            next(ln for ln in lines if " report {" in " " + ln)
            .split("report ", 1)[1])
        last = json.loads(lines[-1])
        w = report["window"]
        b0, b1 = report["order_backlog_frames_start_end"]
        metrics = last.get("metrics", {})
        point = dict(
            rate_orders_per_s=rate, seed=args.seed + i, seconds=args.seconds,
            correct=last["correct"], attempted=last["attempted"],
            failed=last["failed"],
            p50_ms=metrics.get("fill_latency_p50_ms", {}).get("value"),
            p95_ms=w["fill_latency_p95_ms"],
            p99_ms=w["fill_latency_p99_ms"],
            p50_first_third_ms=w["fill_latency_p50_ms_first_third"],
            p50_last_third_ms=w["fill_latency_p50_ms_last_third"],
            order_backlog_frames_start=b0, order_backlog_frames_end=b1,
            order_backlog_slope_frames_per_s=(b1 - b0) / args.seconds,
            gen_late_p99_ms=w["gen_late_p99_ms"],
            events_per_order=report["events_per_order"],
        )
        point["holds"] = holds(point)
        points.append(point)
        print(json.dumps(point), flush=True)
    held = [p["rate_orders_per_s"] for p in points if p["holds"]]
    doc = dict(workload=args.workload, knee_orders_per_s=max(held, default=None),
               rule=__doc__.split("\n\n")[2].replace("\n", " "), points=points)
    out = args.out or os.path.join(HERE, "cells",
                                   args.workload + ".sweep.json")
    if not args.rehearsal:
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
    print(json.dumps(dict(knee_orders_per_s=doc["knee_orders_per_s"],
                          written=None if args.rehearsal else out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
