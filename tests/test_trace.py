"""Causal tracing + flight recorder (src/repro/obs/trace.py, recorder.py).

Four things are pinned here:

* **tracer semantics** — explicit start/end spans on a monotonic clock,
  parent/trace-id chaining, the bounded ring (evictions counted), the
  context-manager/event sugar and the ``service_trace_*`` series;
* **read surfaces** — Chrome/Perfetto ``trace_event`` assembly routes
  job spans to async per-job tracks and island spans to per-(lane,
  island) lane tracks (schema-validated), the span JSONL round-trips
  through the crash-safe reader, and the ``--summarize`` digest
  (critical path per job, busy/blocked/idle per island) is exact on a
  synthetic trace;
* **the flight recorder** — a bounded last-K ring per island whose
  ``dump`` writes a ``postmortem-<island>-<boundary>.json`` carrying
  the timeline and the island's trace spans;
* **trace ↔ metrics reconciliation** — on real runs the spans agree
  with the counters: one ended "job" root per terminal lifecycle edge,
  span-derived busy seconds match the segment-wall histograms, and the
  PR-6 zero-new-device-syncs pin holds WITH tracing enabled
  (``jax.device_get`` count == boundary-pull observations).
"""
import contextlib
import glob
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from conftest import hermetic_subproc_env
from repro.core import bucketed
from repro.core.ipop import run_ipop
from repro.obs import registry as reg_mod
from repro.obs import trace as trace_mod
# NOTE: ``from repro.obs import recorder`` would bind the accessor
# FUNCTION (obs/__init__ re-exports it, shadowing the submodule) — import
# the module's names directly
from repro.obs.recorder import (FlightRecorder, recorder as _recorder,
                                set_recorder)
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import (Tracer, load_jsonl, summarize, validate_chrome)
from repro.service import CampaignRequest, CampaignServer

ROOT = Path(__file__).resolve().parents[1]

KW = dict(lam_start=8, kmax_exp=2)
TERMINAL = ("done", "rejected", "cancelled", "expired", "quarantined",
            "shed")


@pytest.fixture
def fresh_metrics():
    prev = reg_mod.set_metrics(MetricsRegistry())
    yield reg_mod.metrics()
    reg_mod.set_metrics(prev)


@pytest.fixture
def fresh_tracer():
    prev = trace_mod.set_tracer(Tracer())
    yield trace_mod.tracer()
    trace_mod.set_tracer(prev)


@pytest.fixture
def fresh_recorder():
    prev = set_recorder(FlightRecorder())
    yield _recorder()
    set_recorder(prev)


def series(reg, name):
    return {lkey: s for (n, lkey), s in reg._series.items() if n == name}


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------

def test_span_chain_and_series(fresh_metrics, fresh_tracer):
    tr = fresh_tracer
    root = tr.start("job", job=7)
    child = tr.start("queued", parent=root, job=7)
    assert child.trace_id == root.trace_id          # chained trace
    assert child.parent_id == root.span_id
    assert tr.active_count() == 2
    tr.end(child)
    tr.end(root, status="done", reason="")
    assert root.attrs["status"] == "done"           # end-attrs merge
    assert root.t1 >= root.t0 and root.dur >= 0
    assert [s.name for s in tr.finished()] == ["queued", "job"]
    assert tr.active_count() == 0
    # the tracer keeps no self-metrics of its own in the registry
    assert not any(n.startswith("service_trace_")
                   for n, _l in fresh_metrics._series)
    # wall anchor maps perf time to unix time
    assert abs(tr.unix(root.t0) - tr.epoch_unix
               - (root.t0 - tr.epoch_perf)) < 1e-6


def test_ring_is_bounded_and_evictions_counted(fresh_metrics, fresh_tracer):
    tr = Tracer(capacity=8)
    prev = trace_mod.set_tracer(tr)
    try:
        for i in range(20):
            tr.event("pull", island=0, boundary=i)
        spans = tr.finished()
        assert len(spans) == 8 and tr.dropped == 12
        assert [s.attrs["boundary"] for s in spans] == list(range(12, 20))
        assert fresh_metrics.counter(
            "service_trace_dropped_total").value == 12
    finally:
        trace_mod.set_tracer(prev)


def test_span_context_manager_and_event(fresh_metrics, fresh_tracer):
    tr = fresh_tracer
    with tr.span("dispatch", island=0, bucket=1) as s:
        s.attrs["hit"] = True
    assert s.t1 is not None and s.attrs["hit"] is True
    ev = tr.event("health", island=0, state="dead")
    assert ev.t1 == ev.t0 or ev.t1 > ev.t0          # instantaneous marker
    assert tr.active_count() == 0


def test_phase_is_a_span_inside_an_annotation_of_its_bare_name(
        fresh_tracer):
    tr = fresh_tracer
    entered = []

    @contextlib.contextmanager
    def annotation(name, **kw):
        entered.append((name, kw, tr.active_count()))
        try:
            yield
        finally:
            entered.append(("exit", name, tr.active_count()))

    prev = trace_mod.set_annotator(annotation)
    try:
        with tr.span("campaign") as root:
            with tr.phase("keys", root, island="all") as s:
                pass
            with pytest.raises(KeyError):
                with tr.phase("dispatch", root, bucket=0):
                    raise KeyError("stop")
    finally:
        trace_mod.set_annotator(prev)
    # only leaf phases are annotated, by bare name, inside their spans
    assert entered == [("keys", {}, 2), ("exit", "keys", 2),
                       ("dispatch", {}, 2), ("exit", "dispatch", 2)]
    assert s.parent_id == root.span_id and s.trace_id == root.trace_id
    assert s.attrs == {"island": "all"} and s.t1 >= s.t0
    stopped = {x.name: x.attrs.get("status") for x in tr.finished()}
    assert stopped == {"keys": None, "dispatch": "stopped",
                       "campaign": None}
    assert tr.active_count() == 0


# ---------------------------------------------------------------------------
# read surfaces: chrome export, jsonl round-trip, digest, CLI
# ---------------------------------------------------------------------------

def _emit_vertical(tr):
    """One job trace + one island lane + one host span."""
    root = tr.start("job", job=1, dim=4)
    q = tr.start("queued", parent=root, job=1)
    tr.end(q)
    r = tr.start("running", parent=root, job=1)
    with tr.span("pull", lane="L", island=0, boundary=0):
        pass
    with tr.span("dispatch", lane="L", island=0, bucket=0, boundary=0):
        pass
    with tr.span("snapshot"):                       # host track
        pass
    tr.end(r)
    tr.end(root, status="done", reason="")
    return root


def test_chrome_export_routes_tracks(fresh_metrics, fresh_tracer, tmp_path):
    tr = fresh_tracer
    root = _emit_vertical(tr)
    path = tmp_path / "trace.json"
    n = tr.export_chrome(str(path))
    obj = json.loads(path.read_text())
    assert len(obj["traceEvents"]) == n
    assert validate_chrome(obj) == []

    evs = obj["traceEvents"]
    # job spans: async b/e pairs on the jobs process, keyed by trace id
    pairs = [e for e in evs if e.get("ph") in ("b", "e")]
    assert pairs and all(e["pid"] == trace_mod.JOB_PID
                         and e["id"] == f"job:{root.trace_id:x}"
                         for e in pairs)
    assert {e["name"] for e in pairs} == {"job", "queued", "running"}
    # island spans: complete events on one lane-track per (lane, island)
    lanes = [e for e in evs if e.get("cat") == "island"]
    assert {e["name"] for e in lanes} == {"pull", "dispatch"}
    assert len({e["tid"] for e in lanes}) == 1      # same (lane, island)
    # host spans land on the host process; metadata names every track
    assert any(e.get("cat") == "host" and e["name"] == "snapshot"
               for e in evs)
    names = [e["args"]["name"] for e in evs if e["ph"] == "M"]
    assert {"host", "islands", "jobs"} <= set(names)
    assert any("island 0" in n for n in names)

    # the validator actually catches malformed events
    bad = {"traceEvents": [{"ph": "Q", "name": "x", "pid": 1},
                           {"ph": "X", "name": "y", "pid": 1, "ts": 0.0},
                           {"ph": "b", "name": "z", "pid": 1, "ts": 0.0}]}
    errs = validate_chrome(bad)
    assert len(errs) == 3


def test_jsonl_round_trip_and_torn_tail(fresh_metrics, fresh_tracer,
                                        tmp_path):
    tr = fresh_tracer
    _emit_vertical(tr)
    path = tmp_path / "spans.jsonl"
    n = tr.export_jsonl(str(path))
    spans = load_jsonl(str(path))
    assert len(spans) == n == len(tr.finished())
    assert spans[-1]["name"] == "job"
    assert spans[-1]["attrs"]["status"] == "done"
    # a torn final line (writer died mid-write) is tolerated...
    with open(path, "a") as fh:
        fh.write('{"trace_id": 1, "name": "tru')
    assert len(load_jsonl(str(path))) == n
    # ...corruption in the MIDDLE is real damage and must raise
    lines = path.read_text().splitlines()
    lines[0] = lines[0][:-5]
    path.write_text("\n".join(lines) + "\n")
    with pytest.raises(json.JSONDecodeError):
        load_jsonl(str(path))


def _sp(sid, name, t0, t1, parent=None, trace=None, **attrs):
    return {"trace_id": trace if trace is not None else sid,
            "span_id": sid, "parent_id": parent, "name": name,
            "t0": float(t0), "t1": float(t1), "dur_s": float(t1 - t0),
            "attrs": attrs}


def test_summarize_digest_is_exact():
    spans = [
        _sp(1, "job", 0.0, 10.0, job=9, status="done"),
        _sp(2, "queued", 0.0, 2.0, parent=1, trace=1, job=9),
        _sp(3, "running", 2.0, 10.0, parent=1, trace=1, job=9),
        # island 0: busy 3s (segment) + blocked 1s (pull), window 10s
        _sp(4, "segment", 0.0, 3.0, island=0, bucket=1),
        _sp(5, "pull", 3.0, 4.0, island=0, boundary=1),
        _sp(6, "health", 9.0, 10.0, island=0, state="alive"),  # neutral
        _sp(7, "orphan", 0.0, 1.0, parent=99),
    ]
    d = summarize(spans)
    assert d["spans"] == 7 and d["open_parents_missing"] == [99]
    (job,) = d["jobs"]
    assert job["job"] == 9 and job["status"] == "done"
    assert job["total_s"] == 10.0
    assert job["critical_path_s"] == pytest.approx(10.0)   # 2s + 8s
    assert job["phases"] == {"queued": 2.0, "running": 8.0}
    isl = d["islands"]["0"]
    assert isl["spans"] == 3
    assert isl["busy_s"] == pytest.approx(3.0)
    assert isl["blocked_s"] == pytest.approx(1.0)
    assert isl["busy_frac"] == pytest.approx(0.3)
    assert isl["blocked_frac"] == pytest.approx(0.1)
    assert isl["idle_frac"] == pytest.approx(0.6)


def test_summarize_counts_each_instant_once():
    spans = [
        # a segment span over its dispatch and wait phases counts once
        _sp(1, "segment", 0.0, 3.0, island="all", bucket=0),
        _sp(2, "dispatch", 0.0, 0.5, parent=1, trace=1, island="all"),
        _sp(3, "wait", 0.5, 3.0, parent=1, trace=1, island="all"),
        # a leaf segment (no counted children) is busy as before
        _sp(4, "segment", 3.0, 4.0, island="all", bucket=1),
        _sp(5, "pull", 4.0, 4.5, island="all", boundary=1),
        _sp(6, "schedule", 4.5, 4.6, island="all", boundary=1),
        _sp(7, "finish", 4.6, 5.0, island="all"),
    ]
    isl = summarize(spans)["islands"]["all"]
    assert isl["busy_s"] == pytest.approx(0.5 + 1.0 + 0.1 + 0.4)
    assert isl["blocked_s"] == pytest.approx(2.5 + 0.5)
    assert isl["idle_frac"] == pytest.approx(0.0, abs=1e-6)


def test_trace_cli_summarize_and_validate(fresh_metrics, fresh_tracer,
                                          tmp_path):
    tr = fresh_tracer
    _emit_vertical(tr)
    jsonl = tmp_path / "t.jsonl"
    chrome = tmp_path / "t.json"
    tr.export_jsonl(str(jsonl))
    tr.export_chrome(str(chrome))
    env = hermetic_subproc_env()
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs.trace", "--summarize", str(jsonl)],
        check=True, cwd=ROOT, env=env, capture_output=True, text=True)
    digest = json.loads(out.stdout)
    assert digest["jobs"][0]["job"] == 1 and "0" in digest["islands"]
    subprocess.run(
        [sys.executable, "-m", "repro.obs.trace", "--validate", str(chrome)],
        check=True, cwd=ROOT, env=env)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "?"}]}))
    r = subprocess.run(
        [sys.executable, "-m", "repro.obs.trace", "--validate", str(bad)],
        cwd=ROOT, env=env, capture_output=True, text=True)
    assert r.returncode == 1 and "unknown ph" in r.stderr


def test_schema_check_exits_nonzero_with_unified_diff(tmp_path):
    from repro.obs import schema as schema_mod
    env = hermetic_subproc_env()
    doc = tmp_path / "M.md"
    doc.write_text(f"# metrics\n\n{schema_mod.BEGIN_MARK}\n"
                   f"{schema_mod.END_MARK}\n")
    subprocess.run(
        [sys.executable, "-m", "repro.obs.schema", "--write", str(doc)],
        check=True, cwd=ROOT, env=env)
    subprocess.run(
        [sys.executable, "-m", "repro.obs.schema", "--check", str(doc)],
        check=True, cwd=ROOT, env=env)
    doc.write_text(doc.read_text().replace(
        "service_trace_dropped_total", "service_trace_dropt_total"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.obs.schema", "--check", str(doc)],
        cwd=ROOT, env=env, capture_output=True, text=True)
    assert r.returncode != 0
    assert "---" in r.stderr and "+++" in r.stderr and "@@" in r.stderr
    assert "-| `service_trace_dropt_total`" in r.stderr
    assert "+| `service_trace_dropped_total`" in r.stderr


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_recorder_ring_and_postmortem_dump(fresh_metrics, fresh_tracer,
                                           fresh_recorder, tmp_path):
    rec = fresh_recorder
    for b in range(20):
        rec.observe(0, b, wall=0.01, fevals=100 * b, grade="alive",
                    verdicts=[])
    rec.observe(1, 0, wall=0.02, fevals=5, grade="alive", verdicts=[])
    assert len(rec.last(0)) == rec.k == 16          # bounded per-island
    assert rec.last(0)[-1]["boundary"] == 19
    assert len(rec.last(1)) == 1                    # rings are per-island
    obs_n = {dict(lkey)["island"]: s.value
             for lkey, s in series(fresh_metrics,
                                   "obs_recorder_observations_total").items()}
    assert obs_n == {"0": 20, "1": 1}

    # island-attributed spans ride into the dump; other islands' don't
    tr = fresh_tracer
    tr.event("pull", island=0, boundary=19)
    tr.event("pull", island=1, boundary=0)
    rec.observe(0, 20, event="fault", grade="dead", reason="killed")
    pm = rec.dump(0, 20, "dead", extra={"reason": "killed"},
                  out_dir=str(tmp_path))
    path = tmp_path / "postmortem-0-20.json"
    assert path.exists() and pm["path"] == str(path)
    disk = json.loads(path.read_text())
    assert disk["island"] == 0 and disk["boundary"] == 20
    assert disk["trigger"] == "dead" and disk["extra"] == {"reason": "killed"}
    assert len(disk["timeline"]) == 16              # the last-K window
    assert disk["timeline"][-1]["event"] == "fault"
    assert [s["attrs"]["island"] for s in disk["spans"]] == [0]
    assert fresh_metrics.counter("obs_recorder_postmortems_total",
                                 trigger="dead").value == 1

    # without an out_dir the dump is in-memory only (the record still
    # returns so callers can attach it to reports)
    pm2 = rec.dump(1, 0, "quarantine")
    assert "path" not in pm2 and pm2["trigger"] == "quarantine"


# ---------------------------------------------------------------------------
# /statusz
# ---------------------------------------------------------------------------

def test_statusz_endpoint_and_server_snapshot(fresh_metrics, fresh_tracer):
    import urllib.error
    import urllib.request

    srv = CampaignServer(bbob_fids=(1, 8), max_budget=3000,
                         rows_per_island=2, **KW)
    srv.submit(CampaignRequest(dim=4, fid=1, budget=1000, seed=3))
    srv.step()
    httpd, port = reg_mod.start_metrics_server(fresh_metrics,
                                               status_fn=srv.statusz)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz") as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            status = json.loads(resp.read().decode())
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            assert resp.status == 200
    finally:
        httpd.shutdown()
    assert status["queue_depth"] == 0 and status["resident_jobs"] == 1
    assert status["boundary"] >= 1
    assert status["active_traces"] >= 1             # the job's root is open
    (lane,) = status["lanes"].values()
    (isl,) = lane["islands"].values()
    assert isl["health"] == "alive" and 0.0 < isl["occupancy"] <= 1.0
    assert isl["down"] is False

    # a metrics server WITHOUT a status_fn keeps /statusz a 404
    httpd2, port2 = reg_mod.start_metrics_server(fresh_metrics)
    try:
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port2}/statusz")
    finally:
        httpd2.shutdown()
    srv.drain()


# ---------------------------------------------------------------------------
# trace <-> metrics reconciliation on real runs
# ---------------------------------------------------------------------------

def test_bucketed_busy_fraction_reconciles_with_segment_wall(fresh_metrics,
                                                             fresh_tracer):
    run_ipop(lambda X: jnp.sum(X ** 2, axis=-1), 4, jax.random.PRNGKey(0),
             backend="bucketed", max_evals=3000, **KW)
    digest = summarize([s.to_json() for s in fresh_tracer.finished()])
    isl = digest["islands"]["all"]                  # drive_segments' island
    seg_wall = sum(h.sum for h in
                   series(fresh_metrics, "bucketed_segment_wall_s").values())
    sync_wall = fresh_metrics.histogram("bucketed_sync_s").sum
    wait_wall = sum(s.dur for s in fresh_tracer.finished()
                    if s.name == "wait")
    # span-derived busy/blocked seconds bracket the histogram walls: the
    # segment span counts once, through its busy dispatch and blocked wait
    # phases; the pulls are blocked; the rest is O(us) bookkeeping
    assert isl["busy_s"] == pytest.approx(seg_wall - wait_wall,
                                          rel=0.2, abs=0.05)
    assert isl["blocked_s"] == pytest.approx(sync_wall + wait_wall,
                                             rel=0.2, abs=0.05)
    assert isl["busy_frac"] + isl["blocked_frac"] + isl["idle_frac"] \
        == pytest.approx(1.0, abs=1e-3)
    n_segs = sum(s.value for s in
                 series(fresh_metrics, "bucketed_segments_total").values())
    assert sum(1 for s in fresh_tracer.finished()
               if s.name == "segment") == n_segs


def test_service_trace_reconciles_with_lifecycle_and_sync_pin(
        fresh_metrics, fresh_tracer, count_device_get):
    srv = CampaignServer(bbob_fids=(1, 8), max_budget=5000,
                         rows_per_island=2, **KW)
    t_a = srv.submit(CampaignRequest(dim=4, fid=8, budget=2000, seed=7))
    t_b = srv.submit(CampaignRequest(dim=4, fid=1, budget=1500, seed=3))
    srv.drain()
    assert t_a.done and t_b.done

    spans = fresh_tracer.finished()
    roots = [s for s in spans if s.name == "job"]
    terminal_edges = sum(
        s.value for lkey, s in
        series(fresh_metrics, "service_job_lifecycle_total").items()
        if dict(lkey)["to"] in TERMINAL)
    # EXACT reconciliation: one ended root span per terminal edge
    assert len(roots) == terminal_edges == 2
    assert {s.attrs["job"] for s in roots} == {t_a.job_id, t_b.job_id}
    assert all(s.attrs["status"] == "done" for s in roots)
    # every root chains queued -> running lifecycle children
    for r in roots:
        kids = [s for s in spans if s.parent_id == r.span_id]
        assert {"queued", "running"} <= {k.name for k in kids}
        assert all(k.trace_id == r.trace_id for k in kids)
    # job spans never carry island attrs (they must stay on job tracks)
    assert all("island" not in s.attrs for s in roots)

    # the PR-6 zero-new-device-syncs pin, re-asserted WITH tracing on:
    # every device_get is an observed boundary pull, and every pull span
    # is one histogram observation
    pulls = sum(h.count for h in
                series(fresh_metrics, "service_boundary_pull_s").values())
    assert count_device_get["n"] == pulls
    assert sum(1 for s in spans if s.name == "pull") == pulls
    # compile spans saw the warm cache or traced within the bound
    compiles = [s for s in spans if s.name == "compile"]
    assert compiles
    assert sum(1 for s in compiles if not s.attrs["hit"]) \
        <= (KW["kmax_exp"] + 1) * len(srv.lanes)
    # the ring holds every span of the run (nothing dropped)
    assert fresh_tracer.dropped == 0
    assert fresh_metrics.counter("service_trace_dropped_total").value == 0


# ---------------------------------------------------------------------------
# the campaign path's host phases, on the tracer and the profiler's clock
# ---------------------------------------------------------------------------

#: the leaf phases of ``run_campaign_bucketed``, each annotated
PHASES = ("keys", "instances", "init", "pull", "schedule", "dispatch",
          "wait", "finish")


@pytest.fixture(scope="module")
def small_engine():
    """n = 4, two fids, one instance; compiled once for the module."""
    eng = bucketed.BucketedLadderEngine(n=4, max_evals=3000, **KW)
    bucketed.run_campaign_bucketed(eng, fids=(1, 8), instances=(1,))
    return eng


def _campaign(eng):
    return bucketed.run_campaign_bucketed(eng, fids=(1, 8), instances=(1,),
                                          seed=3)


def test_campaign_phases_lie_on_the_profiler_host_line(
        small_engine, fresh_tracer, tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        _campaign(small_engine)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events
                       if e.name in PHASES + ("campaign", "segment")]
                if evs:
                    lines[line.name] = evs
    (name, evs), = lines.items()                    # one host line...
    assert name.startswith(("python", "main"))
    # ...holding only the leaf phases, by bare name, in the tracer's order
    leaves = sorted((s for s in fresh_tracer.finished() if s.name in PHASES),
                    key=lambda s: s.t0)
    assert [e[0] for e in evs] == [s.name for s in leaves]
    assert leaves[:3] == [s for s in leaves if s.name in
                          ("keys", "instances", "init")]
    assert leaves[-1].name == "finish"
    assert all(a[2] <= b[1] for a, b in zip(evs, evs[1:]))  # no overlap


def test_campaign_spans_share_one_trace_and_match_histograms(
        small_engine, fresh_metrics, fresh_tracer, count_device_get):
    _campaign(small_engine)
    spans = fresh_tracer.finished()
    (root,) = [s for s in spans if s.name == "campaign"]
    assert {s.trace_id for s in spans} == {root.trace_id}
    assert root.attrs == {"members": 2, "fids": [1, 8]}
    count = {n: sum(1 for s in spans if s.name == n)
             for n in PHASES + ("segment",)}
    sync = fresh_metrics.histogram("bucketed_sync_s")
    setup = fresh_metrics.histogram("bucketed_campaign_setup_s")
    finish = fresh_metrics.histogram("bucketed_campaign_finish_s")
    walls = series(fresh_metrics, "bucketed_segment_wall_s").values()
    # one pull per device sync (no new sync) and per histogram observation
    assert count["pull"] == sync.count == count_device_get["n"]
    assert count["schedule"] == count["pull"]
    assert count["segment"] == count["dispatch"] == count["wait"] \
        == sum(h.count for h in walls) == sync.count - 1
    assert count["keys"] == count["instances"] == count["init"] \
        == setup.count == 1
    assert count["finish"] == finish.count == 1
    # the histograms read the spans' own clock readings
    by = {n: [s for s in spans if s.name == n] for n in PHASES}
    assert sync.sum == pytest.approx(sum(s.dur for s in by["pull"]))
    assert setup.sum == pytest.approx(by["init"][0].t1 - by["keys"][0].t0)
    assert finish.sum == pytest.approx(by["finish"][0].dur)
    for s in spans:                                  # parents as documented
        want = ("segment" if s.name in ("dispatch", "wait") else
                None if s.name == "campaign" else "campaign")
        assert (s.parent_id and next(p.name for p in spans
                                     if p.span_id == s.parent_id)) == want


class _Stop(Exception):
    pass


def test_campaign_stopped_in_dispatch_closes_every_span(
        small_engine, fresh_metrics, fresh_tracer, monkeypatch):
    calls = {"n": 0}
    real = small_engine.segment_runner

    def stopping(k, fids, gens):
        fn = real(k, fids, gens)

        def run(*args):
            calls["n"] += 1
            if calls["n"] == 2:
                raise _Stop
            return fn(*args)
        return run

    monkeypatch.setattr(small_engine, "segment_runner", stopping)
    with pytest.raises(_Stop):
        _campaign(small_engine)
    assert fresh_tracer.active_count() == 0
    status = {s.name: s.attrs.get("status")
              for s in fresh_tracer.finished()}
    assert status["campaign"] == status["segment"] == status["dispatch"] \
        == "stopped"
    assert "finish" not in status
    assert fresh_metrics.histogram("bucketed_campaign_finish_s").count == 0
    assert fresh_metrics.histogram("bucketed_campaign_setup_s").count == 1
