"""One run of one cell: set-up, window, reading, comparison, result line."""
from __future__ import annotations

import os
import shutil
import sys
import time
from typing import Any, Dict

from bench import common, devtrace
from bench.common import log


def make_cell(cell, devices):
    kind = cell.traffic["kind"]
    if kind == "campaign":
        from bench.campaign import CampaignCell
        return CampaignCell(cell, devices)
    if kind == "service":
        from bench.service import ServiceCell
        return ServiceCell(cell, devices)
    raise ValueError(f"unknown traffic kind {kind!r}")


class Context:
    """What a per-layer metric reader is given."""

    def __init__(self, cell, runner, window, delta, trace, device):
        self.cell = cell
        self.runner = runner
        self.window = window
        self.counters = delta
        self.trace = trace
        self.device = device


def end_to_end(cell, runner, window, delta, setup_s) -> Dict[str, Any]:
    values = dict(runner.end_to_end(window, delta), setup_s=setup_s)
    out = {}
    for m in cell.end_to_end:
        v = values[m["name"]]
        out[m["name"]] = {"value": _finite(v), "unit": m["unit"]}
    return out


def _finite(v: float) -> float:
    """A failed job reads infinity, which no JSON number holds: it is
    written as 1e9 s, beyond any limit."""
    return v if v != float("inf") else 1e9


def run_cell(cell, devices, seed: int, seconds: float, trace: bool,
             t_start: float) -> str:
    counter = common.CompileCount()
    log(f"process start to the cell's set-up {time.perf_counter() - t_start:.3f}s")
    runner = make_cell(cell, devices)
    runner.warm_up()
    setup_s = time.perf_counter() - t_start
    at_setup = counter.mark()
    log(f"set-up {setup_s:.3f}s: {at_setup['compiles']} compiles "
        f"({at_setup['compile_s']:.2f}s), {at_setup['hits']} cache hits")
    before = common.registry_snapshot()
    tdir = os.path.join(common.RUN_DIR, f"trace-{os.getpid()}")
    shutil.rmtree(tdir, ignore_errors=True)
    tw = devtrace.TraceWindow(tdir if trace else None,
                              float(cell.traffic.get("trace_at_s", 0.0)),
                              float(cell.traffic.get("trace_s", 1.0)),
                              cell.traffic.get("trace_starts_at"))
    import jax
    jax.config.update("jax_log_compiles", True)     # names any compile here
    try:
        win = runner.window(seconds, seed, tw)
    finally:
        tw.close()
        jax.config.update("jax_log_compiles", False)
    delta = common.registry_delta(before, common.registry_snapshot())
    in_window = counter.since(at_setup)
    log(f"window: {in_window['compiles']} compiles, {in_window['hits']} "
        f"programs loaded from the cache")
    device = common.device_info(devices)
    breakdown = None
    reduced = None
    if trace:
        if tw.seconds is None:
            raise RuntimeError("the window ended before the trace started")
        cats = devtrace.categories_from_hlo(runner.traced_hlo())
        reduced = devtrace.reduce_dir(tdir, tw.seconds, len(devices), cats)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = devtrace.breakdown(reduced)
        log(f"trace: {tw.seconds:.3f}s traced, busy {reduced['busy_s']:.4f}s,"
            f" categories {reduced['categories']}")
    t_cmp = time.perf_counter()
    checks = runner.compare(seed, cell.config["limits"])
    correct = common.checks_pass(checks)
    log(f"comparison {time.perf_counter() - t_cmp:.2f}s: correct={correct}")
    attempted, failed = runner.attempted_failed()
    if trace:
        ctx = Context(cell, runner, win, delta, reduced, device)
        metrics = {}
        for m in cell.per_layer:
            v = common.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": _finite(v), "unit": m["unit"]}
    else:
        metrics = end_to_end(cell, runner, win, delta, setup_s)
    for k, v in metrics.items():
        log(f"metric {k} = {v['value']!r} {v['unit']}")
    common.print_checks(checks)
    sys.stderr.flush()
    line = common.result_line(
        correct, attempted, failed, metrics, device, checks, breakdown,
        extra={"setup_compiles": at_setup["compiles"],
               "window_compiles": in_window["compiles"]})
    runner.release()
    return line
