"""Observability layer (src/repro/obs/) — PR 6.

Three things are pinned here:

* **registry semantics** — schema-validated emission (unknown name /
  wrong kind / wrong label set raise at the emission site), counter
  monotonicity, fixed log-bucket histograms, the JSONL sink and the
  prometheus-style text exposition / HTTP endpoint;
* **the instrumented vertical** — a tiny bucketed run and a 2-job
  service run emit exactly the series docs/METRICS.md documents, with
  values that reconcile against the engines' own accounting;
* **the zero-overhead contract** — instrumentation adds NO device syncs
  (``jax.device_get`` calls == boundary-pull observations; the pull is
  the tree's only call site), NO new segment programs, and leaves the
  PR-4 fused-generation HLO pins intact.
"""
import json
import subprocess
import sys
import urllib.error
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hermetic_subproc_env
from repro.core.ipop import run_ipop
from repro.obs import registry as reg_mod
from repro.obs import schema as schema_mod
from repro.obs.registry import MetricsRegistry
from repro.service import CampaignRequest, CampaignServer

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))           # benchmarks.* (repo-root package)

KW = dict(lam_start=8, kmax_exp=2)


@pytest.fixture
def fresh_metrics():
    """Swap in an empty process-wide registry; restore the previous one."""
    prev = reg_mod.set_metrics(MetricsRegistry())
    yield reg_mod.metrics()
    reg_mod.set_metrics(prev)


def series(reg, name):
    """{label-tuple: instrument} for one metric name."""
    return {lkey: s for (n, lkey), s in reg._series.items() if n == name}


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_instrument_semantics():
    reg = MetricsRegistry()
    c = reg.counter("service_jobs_total", event="submitted")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)                       # counters are monotone
    # same (name, labels) -> same series; different labels -> distinct
    assert reg.counter("service_jobs_total", event="submitted") is c
    assert reg.counter("service_jobs_total", event="completed") is not c

    g = reg.gauge("service_queue_depth")
    g.set(4)
    g.set(2)                            # gauges may go down
    assert g.value == 2.0

    h = reg.histogram("service_snapshot_s")
    assert h.buckets == schema_mod.TIME_BUCKETS_S
    h.observe(1e-6)                     # below the first edge
    h.observe(0.02)
    h.observe(5e4)                      # beyond the last edge -> +Inf bucket
    assert h.count == 3 and h.counts[0] == 1 and h.counts[-1] == 1
    assert h.sum == pytest.approx(1e-6 + 0.02 + 5e4)
    assert h.quantile(0.5) <= 0.0316228     # 0.02 lands in the <=10^-1.5 edge
    assert h.quantile(1.0) == float("inf")
    assert MetricsRegistry().histogram("service_snapshot_s").quantile(0.5) \
        is None


def test_emission_is_schema_validated():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        reg.counter("no_such_metric_total")
    with pytest.raises(TypeError):
        reg.gauge("service_jobs_total", event="submitted")   # it's a counter
    with pytest.raises(ValueError):
        reg.counter("service_jobs_total")                    # missing label
    with pytest.raises(ValueError):
        reg.counter("service_jobs_total", event="x", extra="y")


def test_schema_table_conventions():
    assert len(schema_mod.SPECS) == len(schema_mod.SCHEMA)
    for s in schema_mod.SCHEMA:
        assert s.name.split("_")[0] in ("bucketed", "mesh", "service",
                                        "fleet", "obs")
        if s.kind == schema_mod.COUNTER:
            assert s.name.endswith("_total"), s.name
        if s.kind == schema_mod.HISTOGRAM:
            # second-valued by default; eval-count histograms (fleet lost
            # work) carry the _evals suffix and explicit decade buckets
            if s.name.endswith("_evals"):
                assert s.unit == "evaluations", s.name
                assert s.buckets == schema_mod.EVAL_BUCKETS, s.name
            else:
                assert s.name.endswith("_s") and s.unit == "s", s.name
            assert list(s.buckets) == sorted(s.buckets) and s.buckets
        else:
            assert not s.buckets
    edges = schema_mod.log_buckets(1e-2, 1e1, per_decade=1)
    assert edges == (0.01, 0.1, 1.0, 10.0)


def test_jsonl_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("service_jobs_total", event="submitted").inc(3)
    reg.histogram("service_admission_wait_s").observe(0.5)
    path = tmp_path / "m.jsonl"
    reg.flush_jsonl(str(path))
    reg.counter("service_jobs_total", event="submitted").inc()
    reg.flush_jsonl(str(path))

    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [ln["seq"] for ln in lines] == [0, 1]
    assert all("unix_s" in ln for ln in lines)
    assert lines[-1]["metrics"] == reg.collect()
    jobs = [m for m in lines[0]["metrics"]
            if m["name"] == "service_jobs_total"]
    assert jobs == [{"name": "service_jobs_total", "type": "counter",
                     "labels": {"event": "submitted"}, "value": 3.0}]
    hist = [m for m in lines[0]["metrics"]
            if m["name"] == "service_admission_wait_s"][0]
    assert hist["count"] == 1 and hist["sum"] == 0.5
    assert sum(c for _le, c in hist["buckets"]) == 1


def test_text_exposition_and_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("service_jobs_total", event="submitted").inc(2)
    h = reg.histogram("service_boundary_pull_s", lane="d4.l8.k2.float64")
    h.observe(0.001)
    h.observe(0.002)
    txt = reg.render_text()
    assert '# TYPE service_jobs_total counter' in txt
    assert 'service_jobs_total{event="submitted"} 2' in txt
    # histogram buckets are CUMULATIVE and label-merged with le=
    assert 'le="+Inf"' in txt
    last = [ln for ln in txt.splitlines()
            if ln.startswith("service_boundary_pull_s_bucket")][-1]
    assert last.endswith(" 2")
    assert 'service_boundary_pull_s_count{lane="d4.l8.k2.float64"} 2' in txt

    httpd, port = reg_mod.start_metrics_server(reg)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            assert resp.status == 200
            assert resp.read().decode() == reg.render_text()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        httpd.shutdown()


def test_obs_package_is_jax_and_numpy_free():
    """The schema drift check (CI lint step) and the registry must not pay
    a jax/numpy import — pinned in a clean interpreter."""
    code = ("import sys, repro.obs.registry, repro.obs.schema, "
            "repro.obs.trace, repro.obs.recorder; "
            "assert 'jax' not in sys.modules, 'obs imported jax'; "
            "assert 'numpy' not in sys.modules, 'obs imported numpy'")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=hermetic_subproc_env())


def test_metrics_docs_match_schema():
    """docs/METRICS.md's generated table is current (the CI drift gate)."""
    assert schema_mod.check_file(str(ROOT / "docs" / "METRICS.md")), (
        "docs/METRICS.md is stale — regenerate with "
        "PYTHONPATH=src python -m repro.obs.schema --write docs/METRICS.md")


# ---------------------------------------------------------------------------
# the instrumented vertical + the zero-overhead contract
# ---------------------------------------------------------------------------

def test_bucketed_run_emits_series_without_new_syncs(fresh_metrics,
                                                     count_device_get):
    reg = fresh_metrics
    res = run_ipop(lambda X: jnp.sum(X ** 2, axis=-1), 4,
                   jax.random.PRNGKey(0), backend="bucketed",
                   max_evals=3000, **KW)

    # one sync observation per device_get: instrumentation added none
    syncs = reg.histogram("bucketed_sync_s")
    assert syncs.count > 0
    assert count_device_get["n"] == syncs.count

    # useful evals reconcile with the engine's own accounting
    useful = reg.counter("bucketed_useful_evals_total").value
    assert useful == res.total_fevals
    padded = sum(s.value for s in
                 series(reg, "bucketed_padded_evals_total").values())
    assert padded >= useful

    segs = series(reg, "bucketed_segments_total")
    assert segs and sum(s.value for s in segs.values()) == syncs.count - 1
    for lkey in segs:                   # per-bucket labels, k within range
        (_k, v), = lkey
        assert 0 <= int(v) <= KW["kmax_exp"]
    walls = series(reg, "bucketed_segment_wall_s")
    assert set(walls) == set(segs)
    assert sum(h.count for h in walls.values()) == syncs.count - 1
    assert all(s.value > 0 for s in
               series(reg, "bucketed_padded_evals_total").values())


def test_service_two_job_run_emits_documented_series(fresh_metrics,
                                                     count_device_get,
                                                     tmp_path):
    reg = fresh_metrics
    mpath = tmp_path / "rounds.jsonl"
    srv = CampaignServer(bbob_fids=(1, 8), max_budget=5000,
                         rows_per_island=2, metrics_out=str(mpath), **KW)
    t_a = srv.submit(CampaignRequest(dim=4, fid=8, budget=2000, seed=7))
    t_b = srv.submit(CampaignRequest(dim=4, fid=1, budget=1500, seed=3))
    srv.drain()
    assert t_a.done and t_b.done

    # lifecycle counters tell the 2-job story end to end
    jobs = {dict(lkey)["event"]: s.value
            for lkey, s in series(reg, "service_jobs_total").items()}
    assert jobs["submitted"] == jobs["admitted"] == jobs["completed"] == 2
    assert jobs.get("rejected", 0) == 0
    assert reg.histogram("service_admission_wait_s").count == 2
    assert reg.histogram("service_time_to_first_ticket_s").count == 2
    assert reg.histogram("service_time_to_completion_s").count == 2

    rounds = reg.counter("service_boundaries_total").value
    assert rounds > 0
    assert reg.gauge("service_queue_depth").value == 0      # drained
    occ = series(reg, "service_slot_occupancy")
    assert occ and all(0.0 <= g.value <= 1.0 for g in occ.values())
    hit_rate = reg.gauge("service_program_cache_hit_rate").value
    assert 0.0 <= hit_rate <= 1.0

    # no new syncs: every device_get is an observed boundary pull
    pulls = sum(h.count for h in
                series(reg, "service_boundary_pull_s").values())
    assert pulls > 0
    assert count_device_get["n"] == pulls

    # per-round JSONL flush: one line per service round, seq in order
    lines = [json.loads(ln) for ln in mpath.read_text().splitlines()]
    assert len(lines) == rounds
    assert [ln["seq"] for ln in lines] == list(range(len(lines)))

    # every emitted series is documented (and labeled as documented)
    for (name, lkey), _s in reg._series.items():
        spec = schema_mod.SPECS[name]
        assert tuple(sorted(dict(lkey))) == tuple(sorted(spec.labels))

    # zero new programs: the compile bound holds WITH instrumentation, and
    # another same-class job traces nothing new
    compiles = srv.segment_compiles()
    assert compiles <= (KW["kmax_exp"] + 1) * len(srv.lanes)
    t_c = srv.submit(CampaignRequest(dim=4, fid=1, budget=1200, seed=13))
    srv.drain()
    assert t_c.done
    assert srv.segment_compiles() == compiles


def test_fused_gen_hlo_pins_survive_instrumentation():
    """The PR-4 pin, re-run on top of the instrumented tree: exactly one
    gram-family (n, n+1) dot per generation, no separate (n, n) gram."""
    import test_fused_gen as tfg

    from repro.distributed import hlo_analyzer
    txt = tfg._scan_hlo("xla", T=10)
    assert hlo_analyzer.count_instrs(txt, tfg.DOT_N_NP1) == 10
    assert hlo_analyzer.count_instrs(txt, tfg.DOT_N_N) == 0


# ---------------------------------------------------------------------------
# soak-harness plumbing (pure pieces; the full soak runs in CI smoke)
# ---------------------------------------------------------------------------

def test_soak_slo_check_is_pure():
    from benchmarks.bench_service import _check_slo
    soak = {"latency_p99_s": 2.0, "evals_per_s": 1000.0}
    assert _check_slo(soak, None, None) == []
    assert _check_slo(soak, 5.0, 500.0) == []
    viol = _check_slo(soak, 1.0, 2000.0)
    assert len(viol) == 2
    assert "p99" in viol[0] and "evals/s" in viol[1]


def test_bench_json_sections_merge(tmp_path):
    from benchmarks.bench_service import _merge_out
    out = tmp_path / "BENCH_service.json"
    _merge_out(str(out), "service", {"p50": 1.0})
    _merge_out(str(out), "soak", {"latency_p99_s": 2.0})
    _merge_out(str(out), "service", {"p50": 3.0})       # overwrite one key
    data = json.loads(out.read_text())
    assert data == {"service": {"p50": 3.0}, "soak": {"latency_p99_s": 2.0}}
