#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration file (``configs``) gives the system's settings and the limits
of the comparison; its traffic file (``chipbench/traffic/<traffic>.json``)
names the traffic's kind and parameters; each per-layer metric is read by
``chipbench/metrics/<metric>.py``.  The run finds the chip, warms up the
cell's programs (set-up), measures for ``--seconds``, compares what the
timed path produced with the plain reference (``chipbench/reference``),
and prints one JSON line last on standard output.  ``--trace 1`` records a
profiler trace of the window and reports the per-layer metrics instead of
the end-to-end ones.  Where JAX finds no TPU, or fewer chips than the cell
asks for, the run exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
# libtpu would otherwise write its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    from bench import common, driver
    cell = common.load_cell(args.workload)
    try:
        devices = common.start_jax(cell.chips)
    except common.NoChip as e:
        common.log(str(e))
        return 2
    out = driver.run_cell(cell, devices, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    print(out, flush=True)
    if os.path.isdir(common.RUN_DIR):
        shutil.rmtree(common.RUN_DIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
