"""Profiler traces: a short traced sub-window, and its reduction to the
device metrics.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it.  Device planes are named ``/device:TPU:<i>``; on each, the line "XLA
Ops" holds one event per operation run on the device (loops and the
operations inside them both appear, so operation events nest) and "XLA
Modules" one per program.  An operation event is named by its HLO text,
``%<instruction> = ...``; the compiled programs' HLO text maps each
instruction to the JAX operation it came from (``op_name`` metadata),
which gives each event a category: ``eigh``, ``cma_gen_update``,
``cma_gen_sample`` or ``other``.  From them:

* busy time: the union of the operation intervals of each device, averaged
  over the cell's devices; idle share = 1 − busy / traced window;
* device time of a category: the union of its events' intervals;
* device time of a program: the sum of its module events;
* the longest idle gaps, each named by the host event that covered most of
  it.

The emulated-float64 programs run close to a million device operations a
second, so a traced run records a short sub-window only.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SEGMENT_MODULE = r"run_one"
CATEGORIES = (("eigh", r"eigh"),
              ("cma_gen_update", r"_update_kernel|cma_gen_update"),
              ("cma_gen_sample", r"_sample_kernel|cma_gen_sample"))
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CONTAINERS = re.compile(r"^(while|conditional|call)\b")


class TraceWindow:
    """Starts the profiler at the first tick ``start_s`` or more into the
    window (of the kind ``starts_at``, where given) and stops it at the
    first tick ``length_s`` or more after that.  Ticks come at segment
    boundaries or service steps (kind "segment") and at campaign starts
    (kind "campaign"), so the traced span holds whole segments.  Writing
    the trace out takes tens of seconds; ``pause()`` hands that time to the
    caller once, to be left out of the window's clock."""

    def __init__(self, trace_dir: Optional[str], start_s: float = 0.0,
                 length_s: float = 0.0, starts_at: Optional[str] = None):
        self.dir, self.start_s, self.length_s = trace_dir, start_s, length_s
        self.starts_at = starts_at
        self.state = "idle" if trace_dir else "off"
        self.origin = self.t0 = self.t1 = None
        self.stop_s = 0.0

    def begin(self, now: float) -> None:
        self.origin = now

    def tick(self, now: float, kind: str = "segment") -> bool:
        """Advance; True while the profiler records."""
        import jax
        if (self.state == "idle" and now - self.origin >= self.start_s
                and self.starts_at in (None, kind)):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state, self.t0 = "on", time.perf_counter()
        elif self.state == "on" and now - self.t0 >= self.length_s:
            self.close()
        return self.state == "on"

    def close(self) -> None:
        if self.state == "on":
            import jax
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - self.t1
            self.state = "done"

    def pause(self) -> float:
        """Seconds spent writing the trace out, once."""
        out, self.stop_s = self.stop_s, 0.0
        return out

    @property
    def seconds(self) -> Optional[float]:
        return None if self.t1 is None else self.t1 - self.t0


def categories_from_hlo(texts: List[str]) -> Dict[str, str]:
    """Instruction name → category, from compiled programs' HLO text."""
    out: Dict[str, str] = {}
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            rest = m.group(2)
            names = " ".join(_OP_NAME.findall(rest))
            for cat, rx in CATEGORIES:
                if re.search(rx, names) or (cat != "eigh"
                                            and re.search(rx, rest)):
                    out[m.group(1)] = cat
                    break
    return out


def _instr(event_name: str) -> str:
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def _union(iv: np.ndarray) -> Tuple[float, List[Tuple[float, float]]]:
    """Total length of a union of [start, end) intervals and the gaps
    between its pieces."""
    if iv.size == 0:
        return 0.0, []
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new piece starts where an interval begins after every earlier end
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    piece_end = np.append(ends[np.nonzero(new)[0][1:] - 1], ends[-1])
    total = float(np.sum(piece_end - starts))
    gaps = list(zip(piece_end[:-1].tolist(), starts[1:].tolist()))
    return total, gaps


def reduce_events(devices: Dict[str, Dict[str, list]],
                  host: List[Tuple[str, float, float]], window_s: float,
                  categories: Optional[Dict[str, str]] = None
                  ) -> Dict[str, Any]:
    """The reduction on plain data: ``devices`` maps a device plane to
    {"ops": [(name, start_ns, dur_ns)], "modules": [...]}; ``host`` is
    [(name, start_ns, dur_ns)] of the host threads."""
    categories = categories or {}
    busy, gaps = [], []
    cat_s: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    for plane, lines in sorted(devices.items()):
        evs = lines["ops"]
        iv = np.asarray([(s, s + d) for _n, s, d in evs],
                        np.float64).reshape(-1, 2)
        b, g = _union(iv)
        busy.append(b * 1e-9)
        gaps += [(plane, a, c) for a, c in g]
        by_cat: Dict[str, list] = {}
        for (name, _s, d), row in zip(evs, iv):
            instr = _instr(name)
            cat = categories.get(instr, "other")
            by_cat.setdefault(cat, []).append(row)
            if not _CONTAINERS.match(instr):
                key = f"{instr} [{cat}]"
                ops[key] = ops.get(key, 0.0) + d * 1e-9
        for cat, rows in by_cat.items():
            cat_s[cat] = cat_s.get(cat, 0.0) + _union(np.asarray(rows))[0] * 1e-9
        for name, _s, d in lines["modules"]:
            modules[name] = modules.get(name, 0.0) + d * 1e-9
    n = max(len(devices), 1)
    gaps.sort(key=lambda x: x[1] - x[2])
    hs = np.asarray([(s, s + d) for _n, s, d in host],
                    np.float64).reshape(-1, 2)
    named = []
    for plane, a, c in gaps[:10]:
        what = "no host event"
        if hs.size:
            cover = np.minimum(hs[:, 1], c) - np.maximum(hs[:, 0], a)
            i = int(np.argmax(cover))
            if cover[i] > 0:
                what = host[i][0]
        named.append([f"{plane}: {what}", (c - a) * 1e-9])
    return {"busy_s": float(np.mean(busy)) if busy else 0.0,
            "window_s": float(window_s), "devices": len(devices),
            "categories": {k: v / n for k, v in cat_s.items()},
            "ops": {k: v / n for k, v in ops.items()},
            "modules": {k: v / n for k, v in modules.items()},
            "idle_gaps": named}


def reduce_dir(trace_dir: str, window_s: float, n_devices: int,
               categories: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Read the newest ``.xplane.pb`` (or ``.xplane.pb.gz``) under
    ``trace_dir`` and reduce it."""
    from jax.profiler import ProfileData
    files = sorted((f for pat in ("*.xplane.pb", "*.xplane.pb.gz")
                    for f in glob.glob(os.path.join(trace_dir, "**", pat),
                                       recursive=True)), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    if files[-1].endswith(".gz"):
        with gzip.open(files[-1]) as fh:
            pd = ProfileData.from_serialized_xspace(fh.read())
    else:
        pd = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = ("ops" if line.name == OPS_LINE else
                       "modules" if line.name == MODULES_LINE else None)
                if key:
                    lines[key] += [(_instr(e.name) if key == "ops" else e.name,
                                    e.start_ns, e.duration_ns)
                                   for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith(("main", "python")):
                    host += [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events]
    devices = dict(sorted(devices.items())[:n_devices])
    return reduce_events(devices, host, window_s, categories)


def category_seconds(reduced: Optional[Dict], cat: str) -> Optional[float]:
    if not reduced or not reduced["devices"]:
        return None
    return reduced["categories"].get(cat) or None


def module_seconds(reduced: Optional[Dict], pattern: str) -> Optional[float]:
    if not reduced or not reduced["devices"]:
        return None
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["modules"].items() if rx.search(k)) or None


def idle_percent(reduced: Optional[Dict]) -> Optional[float]:
    if not reduced or not reduced["devices"] or reduced["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def breakdown(reduced: Dict) -> Dict[str, list]:
    """The ten device operations (loops left out: they hold the others)
    that took most time, and the ten longest idle gaps by what the host was
    doing."""
    top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": reduced["idle_gaps"][:10]}
