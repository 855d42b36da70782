#!/usr/bin/env python3
"""Rate sweep of a service cell on the chip, in one process: the cell's
traffic at each offered rate in turn, to find the knee (the highest rate
at which the queue does not grow over the window).

    python3 chipbench/tools/sweep.py --workload bbob-service-steady \
        --rates 1,2,3,4 --seconds 30 --seed 5

One JSON line per rate: jobs offered and done, completions per second,
the pending queue's depth at the window's quarters, the latency p50/p95
and the generator's lateness.  The benchmark's own runs never run this.
"""
import argparse
import dataclasses
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rows", type=int, default=0,
                    help="rows_per_island instead of the configuration's")
    args = ap.parse_args(argv)
    from bench import common, driver
    from bench.service import percentile
    cell = common.load_cell(args.workload)
    try:
        devices = common.start_jax(cell.chips)
    except common.NoChip as e:
        common.log(str(e))
        return 2
    if args.rows:
        cfg = dict(cell.config)
        cfg["system"] = dict(cfg["system"], rows_per_island=args.rows)
        cell = dataclasses.replace(cell, config=cfg)
    runner = driver.make_cell(cell, devices)
    runner.warm_up()
    for rate in [float(r) for r in args.rates.split(",")]:
        runner.traffic = dict(cell.traffic, rate_per_s=rate)
        win = runner.window(args.seconds, args.seed)
        lat = runner.latencies()
        depth = win["depth"]
        quarters = []
        for q in (0.25, 0.5, 0.75, 1.0):
            at = [d for t, d in depth if t <= q * args.seconds]
            quarters.append(at[-1] if at else 0)
        done = [j for j in runner.jobs if j.get("status") == "done"]
        in_window = [j for j in done if j["done_s"] <= args.seconds]
        print(json.dumps({
            "rate": rate, "rows": cell.config["system"]["rows_per_island"],
            "offered": len(runner.jobs), "done": len(done),
            "done_in_window_per_s": len(in_window) / args.seconds,
            "queue_depth_quarters": quarters,
            "p50_s": percentile(lat, 50), "p95_s": percentile(lat, 95),
            "late_p95_s": float(np.percentile(win["late"], 95))
            if win["late"] else 0.0,
            "run_s": win["run_s"], "steps": win["steps"]}), flush=True)
        runner.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
