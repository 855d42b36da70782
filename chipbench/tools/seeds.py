#!/usr/bin/env python3
"""Readings for the limits of a cell's comparison, on the chip, in one
process: the program on many seeds, then the float32 control on a few.

    python3 chipbench/tools/seeds.py --workload bbob40-ipop \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 51 \
        --fault-seeds 3

Each seed runs the cell's window for ``--seconds`` and its comparison;
one JSON line per seed gives every number compared and the window's
end-to-end metrics (set-up aside).  The control is the
program's own float32 path (``dtype: float32``), at the cell's size and
load.  Each line also gives the readings of the reference put in the
program's place one precision down on the same answers and states: its
float32 evaluation of the best points (``fitness_gap``), and its points
formed in float32 (``first_gen_gap``, ``segment_sample_gap``).  On the
first ``--fault-seeds`` seeds it gives ``cov_learning_gap`` with a faulty
reference in the program's place: the covariance kept, c_mu halved, the
rank-one update dropped.  The benchmark's own runs never run this.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def _scaled(**scale):
    """Reference parameters with some coefficients scaled."""
    from reference import cmaes_ref

    def params_for(n, lam):
        p = cmaes_ref.Params(n, lam)
        for k, v in scale.items():
            setattr(p, k, getattr(p, k) * v)
        return p
    return params_for


FAULTS = {"cov_kept": {"learn_C": False},
          "c_mu_halved": {"params_for": _scaled(c_mu=0.5)},
          "rank_one_dropped": {"params_for": _scaled(c_1=0.0)}}


def lower_precision(checks_mod, members, samples, faults: bool):
    """The readings of the reference put in the program's place."""
    import numpy as np
    out = {"fitness_gap_in_float32": checks_mod.fitness_gap(
               members, dtype="float32"),
           "first_gen_gap_points_float32": checks_mod.first_generation_gap(
               members, points=np.float32),
           "segment_sample_gap_points_float32": checks_mod.segment_sample_gap(
               samples, points=np.float32)}
    for name, fault in (FAULTS.items() if faults else ()):
        out["cov_learning_gap_" + name] = checks_mod.covariance_learning_gap(
            samples, **fault)
    return out


def readings(runner, cell, seeds, seconds, tag, fault_seeds=0):
    from bench import checks as checks_mod
    from bench import common
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        before = common.registry_snapshot()
        win = runner.window(seconds, seed)
        e2e = runner.end_to_end(
            win, common.registry_delta(before, common.registry_snapshot()))
        got = runner.compare(seed, cell.config["limits"])
        extra = {}
        members = getattr(runner, "last_members", None)
        if members:
            extra = lower_precision(checks_mod, members,
                                    getattr(runner, "last_samples", []),
                                    i < fault_seeds)
        print(json.dumps({"tag": tag, "seed": seed,
                          "window_s": win["window_s"], "end_to_end": e2e,
                          "attempted": runner.attempted_failed()[0],
                          "failed": runner.attempted_failed()[1],
                          "checks": {k: v["value"] for k, v in got.items()},
                          **extra,
                          "wall_s": time.perf_counter() - t0}), flush=True)
        runner.release()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault-seeds", type=int, default=0)
    args = ap.parse_args(argv)
    from bench import common, driver
    cell = common.load_cell(args.workload)
    try:
        devices = common.start_jax(cell.chips)
    except common.NoChip as e:
        common.log(str(e))
        return 2
    if args.seeds:
        runner = driver.make_cell(cell, devices)
        runner.warm_up()
        readings(runner, cell, args.seeds, args.seconds, "program",
                 args.fault_seeds)
    if args.control_seeds:
        cfg = dict(cell.config)
        cfg["system"] = dict(cfg["system"], dtype="float32")
        ctl = dataclasses.replace(cell, config=cfg)
        runner = driver.make_cell(ctl, devices)
        runner.warm_up()
        readings(runner, ctl, args.control_seeds, args.seconds, "control")
    return 0


if __name__ == "__main__":
    sys.exit(main())
