"""Rung-bucketed campaign engine (core/bucketed.py).

Covers the PR's acceptance bar: bucketed ↔ λ_max-padded trajectory
equivalence on the shared key schedule (f1/f8), ECDF-level equivalence when
the eigen cadence changes, compile-count ≤ number of rung buckets, the
budget-counter dtype fix under disabled x64, and the bucket-config
derivation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucketed, ladder
from repro.core.ipop import run_ipop
from repro.core.params import CMAConfig, bucket_config, make_params

KW = dict(n=4, lam_start=8, kmax_exp=2, max_evals=5000)


def _campaigns(policy="cover", seed=0, kw=KW, fids=(1, 8), runs=2,
               **extra):
    eng_p = ladder.LadderEngine(schedule="sequential", **kw, **extra)
    res_p = ladder.run_campaign(eng_p, fids=fids, instances=(1,), runs=runs,
                                seed=seed)
    eng_b = bucketed.BucketedLadderEngine(policy=policy, **kw, **extra)
    res_b = bucketed.run_campaign_bucketed(eng_b, fids=fids, instances=(1,),
                                           runs=runs, seed=seed)
    return res_p, res_b


# ---------------------------------------------------------------------------
# equivalence: bucketed segment driver == λ_max-padded engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["cover", "min"])
def test_bucketed_matches_padded_campaign(policy):
    """At eigen_interval == 1 (n=4 default) the per-generation arithmetic is
    identical; only per-shape XLA fusion rounding separates the programs —
    the same tolerance the host-loop baseline comparison carries."""
    res_p, res_b = _campaigns(policy)
    assert ladder.LadderEngine(schedule="sequential",
                               **KW).cfg.eigen_interval == 1

    np.testing.assert_array_equal(res_p.total_fevals, res_b.total_fevals)
    np.testing.assert_allclose(res_p.best_f, res_b.best_f,
                               rtol=1e-5, atol=1e-7)
    for b in range(len(res_p.members)):
        rp = res_p.trace.ran[b, :, 0]
        rb = res_b.trace.ran[b, :, 0]
        # identical per-member generation structure: rungs walked, gens per
        # rung, within-descent eval counters, stop reasons
        for field in ("k_idx", "gen", "fevals", "stop_reason", "stopped"):
            np.testing.assert_array_equal(
                getattr(res_p.trace, field)[b, :, 0][rp],
                getattr(res_b.trace, field)[b, :, 0][rb], err_msg=field)
        np.testing.assert_allclose(res_p.trace.best_f[b, :, 0][rp],
                                   res_b.trace.best_f[b, :, 0][rb],
                                   rtol=1e-5, atol=1e-7)


def test_bucketed_never_pays_lam_max_on_low_rungs():
    res_p, res_b = _campaigns("min")
    # the padded engine pays λ_max per executed generation; the bucketed
    # driver's padded spend must be strictly smaller on the same trajectory
    lam_max = (2 ** KW["kmax_exp"]) * KW["lam_start"]
    T = res_p.trace.ran.shape[1]
    padded_padded = len(res_p.members) * T * lam_max
    assert res_b.padded_evals < padded_padded
    assert res_b.padding_waste() < padded_padded / max(res_b.useful_evals, 1)
    # useful work is identical across engines (same trajectories)
    useful_p = int(np.sum(np.where(
        res_p.trace.ran, KW["lam_start"] * 2 ** res_p.trace.k_idx, 0)))
    assert useful_p == res_b.useful_evals


def test_compile_count_le_number_of_buckets():
    eng_b = bucketed.BucketedLadderEngine(**KW)
    res = bucketed.run_campaign_bucketed(eng_b, fids=(1, 8), instances=(1,),
                                         runs=2, seed=0)
    n_buckets = KW["kmax_exp"] + 1
    assert 1 <= res.compiles <= n_buckets
    # a second campaign with the same shapes reuses every cached executable
    res2 = bucketed.run_campaign_bucketed(eng_b, fids=(1, 8), instances=(1,),
                                          runs=2, seed=3)
    assert res2.compiles <= n_buckets


def test_campaign_rows_run_those_members_of_the_campaign():
    """``rows`` runs a slice of the campaign's member layout (as one mesh
    device holds it) on the same keys, instances and fid menu: each member
    follows the whole campaign's trajectory."""
    full = bucketed.run_campaign_bucketed(
        bucketed.BucketedLadderEngine(**KW), fids=(1, 8), runs=2, seed=0)
    rows = [1, 2]
    part = bucketed.run_campaign_bucketed(
        bucketed.BucketedLadderEngine(**KW), fids=(1, 8), runs=2, seed=0,
        rows=rows)
    assert part.members == [full.members[j] for j in rows]
    np.testing.assert_array_equal(part.f_opt, full.f_opt[rows])
    np.testing.assert_array_equal(part.total_fevals, full.total_fevals[rows])
    np.testing.assert_allclose(part.best_f, full.best_f[rows],
                               rtol=1e-5, atol=1e-7)
    assert part.compiles <= KW["kmax_exp"] + 1


def test_ecdf_equivalence_when_eigen_cadence_changes():
    """eigen_interval > 1: the nested scan's cadence is block-/segment-local
    rather than per-descent, so trajectories differ — but the engines must
    stay equivalent at the ECDF level (fraction of (member, target) pairs
    hit within the budget)."""
    kw = dict(n=8, lam_start=8, kmax_exp=1, max_evals=4000)
    res_p, res_b = _campaigns("cover", kw=kw, eigen_interval=4)
    targets = np.array([1e2, 1e0, 1e-4])
    hits_p = np.isfinite(res_p.hit_evals(targets)).mean(axis=0)
    hits_b = np.isfinite(res_b.hit_evals(targets)).mean(axis=0)
    B = len(res_p.members)
    assert np.all(np.abs(hits_p - hits_b) <= 1.0 / B + 1e-9)
    # sphere members must converge under both engines
    for (fid, _i, _r), ep, eb in zip(res_p.members,
                                     res_p.best_f - res_p.f_opt,
                                     res_b.best_f - res_b.f_opt):
        if fid == 1:
            assert ep < 1e-6 and eb < 1e-6
    # budget respected everywhere
    assert (res_b.total_fevals <= kw["max_evals"]).all()


def test_budget_below_one_generation_returns_empty_progress():
    """A budget that cannot pay for a single λ_start generation must yield
    the same empty-progress result as the padded ladder backend, not crash
    in the segment driver."""
    from repro.fitness import bbob
    inst = bbob.make_instance(1, 3, 1)
    fit = lambda X: bbob.evaluate(1, inst, X)
    kw = dict(lam_start=8, kmax_exp=1, max_evals=4)
    r_l = run_ipop(fit, 3, jax.random.PRNGKey(0), **kw)
    r_b = run_ipop(fit, 3, jax.random.PRNGKey(0), backend="bucketed", **kw)
    assert r_l.total_fevals == r_b.total_fevals == 0
    assert r_l.descents == r_b.descents == []

    eng = bucketed.BucketedLadderEngine(n=3, **kw)
    res = bucketed.run_campaign_bucketed(eng, fids=(1,), runs=2)
    assert res.useful_evals == 0 and res.segments == []
    assert res.trace.ran.shape[1] == 0          # zero-generation trace
    assert res.hit_evals(np.array([1e2])).shape == (2, 1)


def test_run_ipop_bucketed_backend_matches_ladder():
    from repro.fitness import bbob
    inst = bbob.make_instance(8, 4, 1)
    fit = lambda X: bbob.evaluate(8, inst, X)
    kw = dict(lam_start=8, kmax_exp=2, max_evals=4000)
    r_l = run_ipop(fit, 4, jax.random.PRNGKey(7), **kw)
    r_b = run_ipop(fit, 4, jax.random.PRNGKey(7), backend="bucketed", **kw)
    assert r_l.total_fevals == r_b.total_fevals
    assert len(r_l.descents) == len(r_b.descents)
    for dl, db in zip(r_l.descents, r_b.descents):
        assert dl.k_exp == db.k_exp and dl.lam == db.lam
        np.testing.assert_array_equal(dl.fevals, db.fevals)
        assert dl.stop_reason == db.stop_reason
    np.testing.assert_allclose(r_l.best_f, r_b.best_f, rtol=1e-5, atol=1e-7)


def test_overlap_driver_is_trajectory_identical():
    """Double-buffered dispatch (satellite): the speculative next-segment
    dispatch either lands (same bucket — its output IS what the unoverlapped
    driver would compute) or is discarded, so the two drivers must agree on
    every trace field; the host sync is recorded per segment and most
    boundaries keep the bucket (spec hits)."""
    eng = bucketed.BucketedLadderEngine(**KW)
    res = bucketed.run_campaign_bucketed(eng, fids=(1, 8), instances=(1,),
                                         runs=2, seed=0)
    eng_o = bucketed.BucketedLadderEngine(overlap=True, **KW)
    res_o = bucketed.run_campaign_bucketed(eng_o, fids=(1, 8), instances=(1,),
                                           runs=2, seed=0)
    np.testing.assert_array_equal(res.total_fevals, res_o.total_fevals)
    for field in ("ran", "k_idx", "gen", "fevals", "stop_reason", "stopped"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res.trace, field)),
            np.asarray(getattr(res_o.trace, field)), err_msg=field)
    np.testing.assert_allclose(res.trace.best_f, res_o.trace.best_f,
                               rtol=1e-12, atol=1e-12)
    # same bucket schedule, spec bookkeeping present, hits happen
    assert [s["bucket"] for s in res.segments] == \
        [s["bucket"] for s in res_o.segments]
    assert all("spec_hit" in s and "sync_s" in s for s in res_o.segments)
    if len(res_o.segments) > 1:
        assert any(s["spec_hit"] for s in res_o.segments)
    assert not any("spec_hit" in s for s in res.segments)
    assert res_o.compiles <= KW["kmax_exp"] + 1


# ---------------------------------------------------------------------------
# bucket configs (params.bucket_config)
# ---------------------------------------------------------------------------

def test_bucket_config_inherits_trajectory_knobs():
    cfg = CMAConfig(n=10, lam=128, lam_max=128, sigma0=2.5, tolfun=1e-9,
                    eigen_interval=7)
    cfg_b = bucket_config(cfg, 16)
    assert cfg_b.lam == cfg_b.lam_max == 16
    assert cfg_b.eigen_interval == 7 and cfg_b.tolfun == 1e-9
    assert cfg_b.hist_len == cfg.hist_len and cfg_b.sigma0 == cfg.sigma0
    # per-rung max_iter re-derives from the rung's own λ when auto
    assert cfg_b.max_iter == 100 + int(3000 * 10 / 16)
    with pytest.raises(ValueError):
        bucket_config(cfg, 256)
    # identical weight prefixes: a rung-1 descent padded to 16 or to 128
    p_wide = make_params(cfg, lam=16)
    p_narrow = make_params(cfg_b, lam=16)
    np.testing.assert_array_equal(np.asarray(p_wide.weights)[:16],
                                  np.asarray(p_narrow.weights))
    assert float(p_wide.mu_eff) == float(p_narrow.mu_eff)


# ---------------------------------------------------------------------------
# budget counter dtype under disabled x64 (satellite fix)
# ---------------------------------------------------------------------------

def test_budget_counter_respects_x64_availability():
    eng = ladder.LadderEngine(n=3, lam_start=4, kmax_exp=1, max_evals=2000)
    carry = eng.init_carry(jax.random.PRNGKey(0))
    assert carry.total_fevals.dtype == jnp.int64       # x64 on (conftest)

    with jax.enable_x64(False):
        eng32 = ladder.LadderEngine(n=3, lam_start=4, kmax_exp=1,
                                    max_evals=2000, dtype="float32")
        carry32 = eng32.init_carry(jax.random.PRNGKey(0))
        # explicit int32 — no silent downcast warning path
        assert carry32.total_fevals.dtype == jnp.int32
        # a budget that cannot fit the available counter is rejected up front
        # instead of silently wrapping negative mid-campaign
        with pytest.raises(ValueError, match="overflow"):
            ladder.LadderEngine(n=3, lam_start=4, kmax_exp=1,
                                max_evals=2 ** 31, dtype="float32")
        # smoke: a short non-x64 run works and respects the budget
        sphere = lambda X: jnp.sum(X ** 2, axis=-1)
        carry_f, _ = eng32.run(jax.random.PRNGKey(1), sphere, total_gens=40)
        assert int(carry_f.total_fevals) <= 2000
    # the same budget is fine with x64 on
    eng64 = ladder.LadderEngine(n=3, lam_start=4, kmax_exp=1,
                                max_evals=2 ** 31)
    assert eng64.init_carry(jax.random.PRNGKey(0)).total_fevals.dtype \
        == jnp.int64


# ---------------------------------------------------------------------------
# vectorized hit_evals (satellite)
# ---------------------------------------------------------------------------

def test_hit_evals_matches_reference_loop():
    eng = ladder.LadderEngine(schedule="sequential", **KW)
    res = ladder.run_campaign(eng, fids=(1, 8), instances=(1,), runs=2,
                              seed=0)
    targets = np.array([1e3, 1e0, 1e-5, 1e-9])
    got = res.hit_evals(targets)

    # reference: the former B×targets double loop
    gb = np.asarray(res.trace.global_best)
    fe = np.asarray(res.trace.total_fevals)
    want = np.full((gb.shape[0], len(targets)), np.inf)
    for b in range(gb.shape[0]):
        err = gb[b] - res.f_opt[b]
        for i, t in enumerate(targets):
            idx = np.nonzero(err <= t)[0]
            if idx.size:
                want[b, i] = fe[b, idx[0]]
    np.testing.assert_array_equal(got, want)
    assert got.shape == (len(res.members), len(targets))
