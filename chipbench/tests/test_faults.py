"""The comparison that decides ``correct``, driven on the CPU at a small
size: a sound run passes, the float32 control and each planted fault of
the timed path fail.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

These tests skip the harness's look for a chip and run the rest of a run:
set-up, window, comparison.  The faults are planted in the program
underneath, before its programs are traced.
"""
import dataclasses
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from bench import common, driver  # noqa: E402

SMALL = {
    "bbob40-ipop": ({"n": 20, "fids": [1, 2, 10], "max_evals": 6000,
                     "kmax_exp": 2}, {"instances": 3, "warm_buckets": [0, 1]}),
    "bbob-service-steady": ({"lanes": [4, 6], "fids": [1, 8],
                             "max_budget": 3000, "rows_per_island": 4},
                            {"dims": [4, 6], "dim_weights": [1, 1],
                             "rate_per_s": 4.0}),
}
# the service cell is kept out of BENCHMARK.json for now; its harness is
# driven here from its configuration and traffic files
KEPT_OUT = {"bbob-service-steady": ("bbob-service", "service-steady")}


def _cell(name):
    if name not in KEPT_OUT:
        return common.load_cell(name)
    config, traffic = KEPT_OUT[name]

    def load(*parts):
        with open(os.path.join(BENCH, *parts)) as fh:
            return json.load(fh)
    return common.Cell(name=name, chips=1,
                       config=load("configs", config + ".json"),
                       traffic=load("traffic", traffic + ".json"),
                       end_to_end=[], per_layer=[])


def small_cell(name, **system):
    cell = _cell(name)
    sys_over, tr_over = SMALL[name]
    cfg = dict(cell.config)
    cfg["system"] = dict(cfg["system"], **sys_over, **system)
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, **tr_over))


def run(cell, seconds=6.0, seed=2 ** 31 + 12345):
    devices = common.start_jax(cell.chips, require_tpu=False)
    line = driver.run_cell(cell, devices, seed, seconds, False,
                           t_start=time.perf_counter())
    return json.loads(line)


def failing(out):
    """The checks that failed; a reading of infinity or NaN is written as
    its name."""
    return sorted(k for k, v in out["checks"].items()
                  if not isinstance(v["value"], (int, float))
                  or not v["value"] <= v["limit"])


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test traces its own programs (a planted fault must be in them)."""
    import jax
    jax.clear_caches()
    from repro.service import server
    server.clear_program_cache()
    yield
    jax.clear_caches()
    server.clear_program_cache()


@pytest.mark.parametrize("name", ["bbob40-ipop", "bbob-service-steady"])
def test_sound_run_is_correct(name):
    out = run(small_cell(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("name", ["bbob40-ipop", "bbob-service-steady"])
def test_float32_control_fails(name):
    out = run(small_cell(name, dtype="float32"))
    assert not out["correct"]
    assert "fitness_gap" in failing(out)


def _frozen(monkeypatch):
    """A step that returns its state unchanged (m, σ, C, B, D, paths), with
    only its bookkeeping moving on."""
    from repro.core import cmaes
    orig = cmaes._finish_update

    def frozen(cfg, params, state, *a, **k):
        new = orig(cfg, params, state, *a, **k)
        return new._replace(m=state.m, sigma=state.sigma, C=state.C,
                            B=state.B, D=state.D, p_sigma=state.p_sigma,
                            p_c=state.p_c)
    monkeypatch.setattr(cmaes, "_finish_update", frozen)


def _half_batch(monkeypatch):
    """Half of each population left out: its rows read +inf, and the
    ranks, weights and mean are taken over the rest."""
    import jax.numpy as jnp
    from repro.core import cmaes
    orig = cmaes.population_stats

    def half(fitness, x, params, lam_max):
        keep = jnp.arange(fitness.shape[0]) < params.lam // 2
        return orig(jnp.where(keep, fitness, jnp.inf), x, params, lam_max)
    monkeypatch.setattr(cmaes, "population_stats", half)


def _altered_answer(monkeypatch):
    """The generation's best f altered where it is produced."""
    from repro.core import cmaes
    orig = cmaes.population_stats

    def altered(fitness, x, params, lam_max):
        w, f_sorted, x_best, n_evals = orig(fitness, x, params, lam_max)
        return w, f_sorted.at[0].multiply(1.0 + 1e-6), x_best, n_evals
    monkeypatch.setattr(cmaes, "population_stats", altered)


@pytest.mark.parametrize("name", ["bbob40-ipop", "bbob-service-steady"])
@pytest.mark.parametrize("plant,caught_by", [
    (_frozen, "f1_hit_ratio"),
    (_half_batch, "first_gen_gap"),
    (_altered_answer, "fitness_gap"),
])
def test_planted_fault_fails(monkeypatch, name, plant, caught_by):
    plant(monkeypatch)
    out = run(small_cell(name))
    assert not out["correct"], out["checks"]
    assert caught_by in failing(out), out["checks"]


def _covariance_kept(monkeypatch):
    """The covariance update skipped: C stays as it was, m, σ and the paths
    move on."""
    from repro.core import cmaes
    orig = cmaes._finish_update

    def kept(cfg, params, state, f_sorted, x_best, n_evals, C_new, *rest):
        return orig(cfg, params, state, f_sorted, x_best, n_evals, state.C,
                    *rest)
    monkeypatch.setattr(cmaes, "_finish_update", kept)


def _coefficient(monkeypatch, name, scale):
    from repro.core import cmaes
    orig = cmaes.gen_coef

    def scaled(params, state):
        c = dict(orig(params, state))
        c[name] = c[name] * scale
        return c
    monkeypatch.setattr(cmaes, "gen_coef", scaled)


def _c_mu_halved(monkeypatch):
    """The rank-μ update at half its learning rate."""
    _coefficient(monkeypatch, "c_mu", 0.5)


def _rank_one_dropped(monkeypatch):
    """The rank-one update (c_1·p_c·p_cᵀ) left out."""
    _coefficient(monkeypatch, "c_1", 0.0)


def _points_in_float32(monkeypatch):
    """The points X = m + σ·Y formed in float32 and evaluated in float64."""
    import jax.numpy as jnp
    from repro.core import cmaes

    def rounded(fn):
        def sample(*a, **k):
            Y, X = fn(*a, **k)
            return Y, X.astype(jnp.float32).astype(X.dtype)
        return sample
    for op in ("gen_sample", "gen_sample_rng"):
        monkeypatch.setattr(cmaes.kops, op, rounded(getattr(cmaes.kops, op)))


@pytest.mark.parametrize("plant", [_covariance_kept, _c_mu_halved,
                                   _rank_one_dropped])
def test_planted_update_fault_fails(monkeypatch, plant):
    plant(monkeypatch)
    out = run(small_cell("bbob40-ipop"))
    assert not out["correct"], out["checks"]
    assert "cov_learning_gap" in failing(out), out["checks"]


def test_points_in_float32_fail(monkeypatch):
    _points_in_float32(monkeypatch)
    out = run(small_cell("bbob40-ipop"))
    assert not out["correct"], out["checks"]
    assert "best_x_float32_share" in failing(out), out["checks"]


def test_speculative_dispatch_keeps_only_the_segments_it_used(monkeypatch):
    """With the driver's double-buffered dispatch on, a speculative segment
    whose bucket missed is discarded by the driver and left out here."""
    from bench import campaign
    orig = campaign._engine_class

    def overlapped(watch):
        class Engine(orig(watch)):
            def __post_init__(self):
                super().__post_init__()
                self.overlap = True
        return Engine
    monkeypatch.setattr(campaign, "_engine_class", overlapped)
    cell = small_cell("bbob40-ipop")
    devices = common.start_jax(cell.chips, require_tpu=False)
    runner = driver.make_cell(cell, devices)
    runner.warm_up()
    runner.window(6.0, 2 ** 31 + 777)
    assert runner.engine.overlap
    dispatched = sum(len(c.dispatched) for c in runner.campaigns)
    kept = sum(len(c.segments) for c in runner.campaigns)
    assert kept < dispatched            # speculation missed at least once
    checks = runner.compare(2 ** 31 + 777, cell.config["limits"])
    assert common.checks_pass(checks), checks
