"""What every cell shares: the cell's files, the chip, the compile count,
the result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
RUN_DIR = os.path.join(ROOT, ".chipbench_runs")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``: its configuration file, its
    traffic file (``chipbench/traffic/<traffic>.json``) and the metrics
    that apply to it."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    traffic = _load_json(os.path.join(root, "chipbench", "traffic",
                                      w["traffic"] + ".json"))

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]),
                config=_load_json(os.path.join(root, cfg["file"])),
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def metric_reader(name: str):
    """The reader of per-layer metric ``name``: ``chipbench/metrics/<name>.py``
    with a ``read(ctx)`` that returns a number, or None where it found
    nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def start_jax(chips: int, require_tpu: bool = True):
    """x64 on, the devices checked, and the checkout's compile cache on.
    Raises ``NoChip`` (before any result is printed) where JAX finds no TPU
    or fewer chips than the cell asks for.  ``require_tpu=False`` is for
    the CPU rehearsals of the tests, which keep no compile cache."""
    import jax
    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    if not require_tpu:
        return devices[:chips]
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    # a fixed directory inside the checkout: the path is part of the key
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices[:chips]


class CompileCount:
    """Counts the programs JAX compiled and those it loaded from the
    persistent cache.  JAX reports a backend compile duration for both, and
    a cache hit for the loaded ones."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_kw):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _ev(self, event, **_kw):
        if event == self.HIT:
            self.hits += 1

    def mark(self) -> Dict[str, float]:
        return {"compiles": self.compiles - self.hits,
                "compile_s": self.compile_s, "hits": self.hits}

    def since(self, mark: Dict[str, float]) -> Dict[str, float]:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}


def device_info(devices, with_memory: bool = True) -> Dict[str, Any]:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if with_memory:
        peaks = []
        for d in devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        if peaks:
            out["memory_peak_bytes"] = max(peaks)
        limits = [int((d.memory_stats() or {}).get("bytes_limit", 0))
                  for d in devices]
        if any(limits):
            out["memory_limit_bytes"] = max(limits)
    return out


def registry_snapshot() -> Dict[tuple, Any]:
    """Counters and histogram (sum, count) pairs of the program's metrics
    registry, keyed by (name, labels)."""
    from repro import obs
    snap = {}
    for (name, labels), series in list(obs.metrics()._series.items()):
        if hasattr(series, "count"):
            snap[(name, labels)] = (series.sum, series.count)
        else:
            snap[(name, labels)] = series.value
    return snap


def registry_delta(before: Dict[tuple, Any], after: Dict[tuple, Any]
                   ) -> Dict[str, Any]:
    """Per metric name, summed over labels: the change between two
    snapshots (histograms as [sum, count])."""
    out: Dict[str, Any] = {}
    for key, v in after.items():
        name = key[0]
        b = before.get(key)
        if isinstance(v, tuple):
            b = b or (0.0, 0)
            s, c = out.get(name, (0.0, 0))
            out[name] = (s + v[0] - b[0], c + v[1] - b[1])
        else:
            out[name] = out.get(name, 0.0) + v - (b or 0.0)
    return out


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def checks_pass(checks: Dict[str, Dict[str, Any]]) -> bool:
    ok = True
    for v in checks.values():
        val = v["value"]
        ok &= val is not None and val == val and val <= v["limit"]
    return bool(ok)


def print_checks(checks: Dict[str, Dict[str, Any]]) -> None:
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                checks: Dict[str, Dict[str, Any]],
                breakdown: Optional[Dict[str, Any]] = None,
                extra: Optional[Dict[str, Any]] = None) -> str:
    """The last line of standard output; the compared numbers come last."""
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out.update(extra or {})
    # each number compared beside its limit, last; a reading of infinity
    # or NaN, which no JSON number holds, is written as its name
    out["checks"] = {k: {"value": _json_number(v["value"]),
                         "limit": v["limit"]}
                     for k, v in checks.items()}
    return json.dumps(out)


def _json_number(v):
    return v if v is None or math.isfinite(v) else str(v)
