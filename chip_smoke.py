"""Smoke run of the system's main path on the TPU.

  python chip_smoke.py             # one chip: phases (a), (b), (c)
  python chip_smoke.py --chips 4   # four chips: the S1/S2 mesh phase only

(a) One generation, kernel against reference: ``ops.gen_sample`` and
    ``ops.gen_update`` under ``impl="pallas"`` (Mosaic) on 4 slots at
    n = 40 and n = 256, against ``ref.gen_sample`` / ``ref.fused_gen_update``
    in float64 on the host's CPU device, on the same inputs.
(b) The paper's campaign (ROADMAP W1): BBOB f{1,2,8,10,15,20} at n = 40,
    λ_start = 12, K_max = 2^4, through ``bucketed.run_campaign_bucketed``
    with the engine's defaults (``impl="auto"``, float64 state), then the
    same campaign under ``impl="xla"``.
(c) The campaign service: a handful of ``CampaignRequest``s (n ∈ {10, 40},
    mixed fids) through one ``CampaignServer``, each result compared with
    ``run_ipop(..., backend="bucketed")`` on the same explicit key.
(mesh) With ``--chips 4``: ``mesh_engine.run_campaign_mesh`` under S1
    (ordered) and S2 (concurrent) over four chips, against the bucketed
    single-device engine on the same keys, run on each chip's member slice
    (f1 and f8 at n = 40, λ_start = 12, K_max = 2^1, 4 runs; see MESH_KW).

Everything runs in this one process: a child would find the chip held.
The times printed are orientation for a smoke run, not measurements.  The
script exits 1 without a result line when JAX finds no TPU (or fewer chips
than ``--chips``), when the repository's ``src/`` is not beside it, or when
any phase fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

U32 = 2.0 ** -24            # unit roundoff of float32
W1_FIDS = (1, 2, 8, 10, 15, 20)
W1 = dict(n=40, lam_start=12, kmax_exp=4)
# per-member evaluation budget of the smoke campaign: 2,500·n.  f2 (the
# separable ellipsoid, condition 1e6) needs ~7e4 evaluations to reach 1e-8
# at n = 40 in float64; this leaves it room and keeps the run to minutes.
W1_BUDGET = 100_000
TARGET = 1e-8
# (dim, fid, budget, seed) of the service phase
SERVICE_JOBS = ((10, 1, 6000, 1), (10, 8, 8000, 2), (40, 1, 8000, 3),
                (40, 8, 10000, 4))
SERVICE_KW = dict(lam_start=12, kmax_exp=1)
# the four-chip phase: W1's n and λ_start, two of its fids, K_max cut to
# 2^1 and 4 runs.  S2 compiles every bucket program once per chip, and
# with W1's whole 6-fid menu (~50 s per program) that alone would be
# ~17 minutes of compiling on four chips.
MESH_FIDS = (1, 8)
MESH_KW = dict(n=40, lam_start=12, kmax_exp=1)
MESH_RUNS = 4
MESH_BUDGET = 20_000


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Sums XLA backend compile time, as JAX reports it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self):
        return self.seconds, self.count

    def since(self, mark) -> str:
        return (f"compile {self.seconds - mark[0]:.2f}s over "
                f"{self.count - mark[1]} programs")


def _bound_ratio(got, want, scale, terms: int) -> float:
    """max |got − want| / ((terms + 8)·u·scale), elementwise.

    ``scale`` is the sum of the magnitudes of the summands behind each
    element.  An f32 sum of ``terms`` products is within terms·u of it
    (the textbook dot-product bound, u = 2⁻²⁴); 8 more roundings cover the
    casts of inputs and coefficients to f32 and the epilogue's few ops.
    A ratio ≤ 1 passes."""
    import numpy as np
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    bound = (terms + 8) * U32 * np.asarray(scale, np.float64)
    ratio = np.where(bound > 0, err / np.where(bound > 0, bound, 1.0),
                     np.where(err > 0, np.inf, 0.0))
    return float(np.max(ratio))


def _gen_inputs(rng, S: int, lam: int, n: int):
    """One generation's state for S slots, drawn in float64: an SPD C with
    its eigenbasis, Hansen's default coefficients for (n, λ), slot 2 with a
    long p_σ (h_σ stalls), slot 3 parked (all-zero weights)."""
    import numpy as np

    from repro.core.params import CMAConfig, make_params

    A = rng.standard_normal((S, n, n))
    C = A @ A.transpose(0, 2, 1) / n + np.eye(n)
    evals, B = np.linalg.eigh(C)
    D = np.sqrt(evals)
    p = make_params(CMAConfig(n=n, lam=lam, dtype="float64"))
    w = np.tile(np.asarray(p.weights), (S, 1))
    w[3] = 0.0
    p_sigma = 0.1 * rng.standard_normal((S, n))
    p_sigma[2] *= 100.0
    coef = {f: np.full(S, float(getattr(p, f)))
            for f in ("c_sigma", "mu_eff", "c_c", "c_1", "c_mu", "chi_n")}
    coef["gen1"] = np.array([1.0, 2.0, 5.0, 50.0])
    return dict(m=rng.standard_normal((S, n)), sigma=rng.uniform(0.1, 1.0, S),
                B=B, D=D, Z=rng.standard_normal((S, lam, n)), C=C,
                p_sigma=p_sigma, p_c=0.1 * rng.standard_normal((S, n)),
                w=w, coef=coef)


def phase_kernels(clock, dims=(40, 256), lam: int = 48, S: int = 4) -> None:
    """(a) one generation through the Mosaic kernels vs the float64 ref."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.cma_gen import COEF_FIELDS

    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(0)
    for n in dims:
        g = _gen_inputs(rng, S, lam, n)
        tiers = (ops._gen_impl("pallas", n, jnp.float64, fits=ops._sample_fits),
                 ops._gen_impl("pallas", n, jnp.float64))
        log(f"(a) n={n} S={S} lam={lam}: impl sample={tiers[0]} "
            f"update={tiers[1]}")
        # the float64 reference, on the host CPU
        with jax.default_device(cpu):
            h = {k: jnp.asarray(v) for k, v in g.items() if k != "coef"}
            Yr, Xr = ref.gen_sample(h["m"], h["sigma"], h["B"], h["D"], h["Z"])
            Cr, psr, pcr, ywr = jax.vmap(ref.fused_gen_update)(
                h["C"], h["B"], h["D"], h["p_sigma"], h["p_c"], Yr, h["w"],
                *(jnp.asarray(g["coef"][f]) for f in COEF_FIELDS))
        Yr, Xr, Cr, psr, pcr, ywr = (np.asarray(a) for a in
                                     (Yr, Xr, Cr, psr, pcr, ywr))

        # the kernels on the chip, on the same inputs (the update takes the
        # reference Y, so its check sees only its own rounding)
        d = {k: jnp.asarray(v) for k, v in g.items() if k != "coef"}
        d["Y"] = jnp.asarray(Yr)
        coef = {k: jnp.asarray(v) for k, v in g["coef"].items()}

        def run():
            Y, X = ops.gen_sample(d["m"], d["sigma"], d["B"], d["D"], d["Z"],
                                  impl="pallas")
            out = ops.gen_update(d["C"], d["B"], d["D"], d["p_sigma"],
                                 d["p_c"], d["Y"], d["w"], coef,
                                 impl="pallas")
            return jax.block_until_ready((Y, X) + tuple(out))

        c0, t0 = clock.mark(), time.perf_counter()
        run()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        Yk, Xk, Ck, psk, pck, ywk = run()
        wall = time.perf_counter() - t0
        log(f"(a) n={n}: first call {first:.3f}s ("
            f"{clock.since(c0)}), second call {wall:.4f}s")

        # magnitudes behind each output element (see _bound_ratio)
        cf = g["coef"]
        aY = np.abs(g["Z"] * g["D"][:, None, :]) @ np.abs(
            g["B"]).transpose(0, 2, 1)
        aX = np.abs(g["m"])[:, None, :] + g["sigma"][:, None, None] * aY
        ays = np.sqrt(g["w"])[:, :, None] * np.abs(Yr)
        a_yw = np.einsum("sl,sln->sn", g["w"], np.abs(Yr))
        Binv = np.abs(g["B"]) / g["D"][:, None, :]
        a_wh = np.einsum("sij,skj,sk->si", Binv, np.abs(g["B"]), a_yw)
        g_ps = np.sqrt(cf["c_sigma"] * (2 - cf["c_sigma"]) * cf["mu_eff"])
        g_pc = np.sqrt(cf["c_c"] * (2 - cf["c_c"]) * cf["mu_eff"])
        a_ps = (np.abs((1 - cf["c_sigma"])[:, None] * g["p_sigma"])
                + g_ps[:, None] * a_wh)
        a_pc = np.abs((1 - cf["c_c"])[:, None] * g["p_c"]) \
            + g_pc[:, None] * a_yw
        decay = np.abs(1 - cf["c_1"] - cf["c_mu"]) + cf["c_1"]
        a_C = (decay[:, None, None] * np.abs(g["C"])
               + cf["c_mu"][:, None, None] * ays.transpose(0, 2, 1) @ ays
               + 2 * cf["c_1"][:, None, None] * a_pc[:, :, None]
               * a_pc[:, None, :])          # 2: both factors carry error
        # h_σ is a threshold test: kernel and ref must decide it the same
        # way, so the inputs must not sit on the threshold
        hd = np.sqrt(1 - (1 - cf["c_sigma"]) ** (2 * cf["gen1"]))
        margin = (np.linalg.norm(psr, axis=1) / hd / cf["chi_n"]
                  / (1.4 + 2.0 / (n + 1.0)))
        assert np.all(np.abs(margin - 1) > 1e-3), margin

        ratios = {
            "Y": _bound_ratio(Yk, Yr, aY, n),
            "X": _bound_ratio(Xk, Xr, aX, n),
            "y_w": _bound_ratio(ywk, ywr, a_yw, lam),
            "p_sigma": _bound_ratio(psk, psr, a_ps, lam + 2 * n),
            "p_c": _bound_ratio(pck, pcr, a_pc, lam),
            "C": _bound_ratio(Ck, Cr, a_C, lam),
        }
        maxerr = {"Y": float(np.max(np.abs(np.asarray(Yk) - Yr))),
                  "C": float(np.max(np.abs(np.asarray(Ck) - Cr)))}
        log(f"(a) n={n}: h_sigma margin {np.round(margin, 3).tolist()}; "
            f"max|err| Y={maxerr['Y']:.3e} C={maxerr['C']:.3e}; "
            "err/bound " + " ".join(f"{k}={v:.3f}" for k, v in ratios.items()))
        bad = [k for k, v in ratios.items() if not v <= 1.0]
        assert not bad, f"n={n}: outside the f32 bound: {bad}"
        assert tiers == ("pallas", "pallas"), tiers


def _check_campaign(res, budget: int, n_buckets: int, tag: str) -> None:
    import numpy as np
    err = res.best_f - res.f_opt
    assert res.compiles <= n_buckets, (tag, res.compiles)
    assert np.all(res.total_fevals <= budget), (tag, res.total_fevals)
    assert np.all(np.isfinite(res.best_f)), (tag, res.best_f)
    for (fid, _i, _r), e in zip(res.members, err):
        if fid in (1, 2):
            assert e <= TARGET, (tag, fid, e)


def phase_campaign(clock, fids=W1_FIDS, budget: int = W1_BUDGET,
                   kw=W1) -> None:
    """(b) the W1 campaign under impl="auto" and under impl="xla".

    The two tiers run in two threads: each compiles five ~50 s bucket
    programs on the host, and one tier's compiles overlap the other's
    device-bound segments (two compiles at once gain nothing)."""
    import concurrent.futures

    import jax.numpy as jnp
    import numpy as np

    from repro.core import bucketed
    from repro.kernels import ops

    def tier(impl):
        eng = bucketed.BucketedLadderEngine(max_evals=budget, impl=impl, **kw)
        t0 = time.perf_counter()
        res = bucketed.run_campaign_bucketed(eng, fids=fids, instances=(1,),
                                             runs=1, seed=0)
        return res, time.perf_counter() - t0

    c0, t0 = clock.mark(), time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(max_workers=2) as pool:
        runs = {impl: pool.submit(tier, impl) for impl in ("auto", "xla")}
        runs = {impl: f.result() for impl, f in runs.items()}
    log(f"(b) both tiers in {time.perf_counter() - t0:.2f}s "
        f"({clock.since(c0)})")
    failed = []
    for impl, (res, wall) in runs.items():
        tiers = (ops._gen_impl(impl, kw["n"], jnp.float64,
                               fits=ops._sample_fits),
                 ops._gen_impl(impl, kw["n"], jnp.float64))
        err = res.best_f - res.f_opt
        hits = res.hit_evals(np.array([TARGET]))[:, 0]
        log(f"(b) impl={impl} -> sample={tiers[0]} update={tiers[1]}: "
            f"wall {wall:.2f}s, compiles={res.compiles}, "
            f"segments={len(res.segments)}, useful evals={res.useful_evals}")
        for (fid, _i, _r), e, fe, h in zip(res.members, err,
                                          res.total_fevals, hits):
            log(f"(b) impl={impl} f{fid}: best-f_opt={e:.3e} fevals={fe} "
                f"evals to {TARGET:g}={h:g}")
        try:
            _check_campaign(res, budget, kw["kmax_exp"] + 1, impl)
            if impl == "auto":
                assert tiers == ("pallas", "pallas"), tiers
        except AssertionError as e:
            failed.append((impl, e))
    assert not failed, failed


def _flip_gens(n: int, lam: int) -> int:
    """Generations a borderline TolFun stop may move by between two
    differently compiled programs: TolFun reads the range of the best-f
    history over a window of 10 + ⌈30·n/λ⌉ generations, and rounding-level
    differences can only move the generation at which that range crosses
    the tolerance, never by more than one window."""
    return 10 + -(-30 * n // lam)


def _departure(a, b):
    """First generation (over the concatenated descents) at which two IPOP
    results' best-f traces differ beyond rounding; None when they agree."""
    import numpy as np
    fa = np.concatenate([d.best_f for d in a.descents])
    fb = np.concatenate([d.best_f for d in b.descents])
    L = min(len(fa), len(fb))
    off = ~np.isclose(fa[:L], fb[:L], rtol=1e-9, atol=0.0)
    if off.any():
        return int(np.argmax(off))
    return None if len(fa) == len(fb) else L


def phase_service(clock, jobs=SERVICE_JOBS, kw=SERVICE_KW) -> None:
    """(c) a handful of jobs through one CampaignServer on the chip.

    Each job is checked against ``run_ipop(backend="bucketed")`` on the
    same explicit key: every ticket done within its budget with a finite
    best f; the same first generation (same key, fid, instance and row);
    the same restart ladder on the common prefix of descents; and the f1
    jobs at the 1e-8 target in both.  Each job's best x, evaluated on the
    host CPU with the job's own fid and instance, must give its best f:
    a service that mixed up rows or slots after the first generation
    would report another row's point or value.  Bitwise parity with the
    reference is reported, not required: the service's island and the
    standalone run are programs of other shapes, and on the chip their
    float64 results part from the first generations on — under
    ``impl="xla"`` as under ``"auto"``, and at n = 6, where the covariance
    has no degenerate eigenspace, as well (PERF.md).  On the CPU, in
    float64, the n = 40 jobs part at generation 1–2 too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.ipop import run_ipop
    from repro.fitness import bbob
    from repro.service import CampaignRequest, CampaignServer

    cpu = jax.devices("cpu")[0]
    fids = tuple(sorted({f for _d, f, _b, _s in jobs}))
    srv = CampaignServer(bbob_fids=fids, max_budget=max(j[2] for j in jobs),
                         devices=jax.devices()[:1], **kw)
    c0, t0 = clock.mark(), time.perf_counter()
    tickets = [srv.submit(CampaignRequest(dim=d, fid=f, budget=b, seed=s,
                                          key=jax.random.PRNGKey(s)))
               for d, f, b, s in jobs]
    srv.drain()
    log(f"(c) service: {len(jobs)} jobs drained in "
        f"{time.perf_counter() - t0:.2f}s ("
        f"{clock.since(c0)}), segment programs="
        f"{srv.segment_compiles()}, lanes={len(srv.lanes)}")
    failed = []
    for t, (d, f, b, s) in zip(tickets, jobs):
        inst = bbob.make_instance(f, d, 1)
        c0 = clock.mark()
        ref = run_ipop(lambda X, _f=f, _i=inst: bbob.evaluate(_f, _i, X), d,
                       jax.random.PRNGKey(s), backend="bucketed",
                       max_evals=b, **kw)
        res = t.result
        if t.status != "done" or res is None:
            log(f"(c) job n={d} f{f}: status={t.status}")
            failed.append((d, f, s))
            continue
        ladder = [(x.k_exp, x.lam) for x in res.descents]
        ref_ladder = [(x.k_exp, x.lam) for x in ref.descents]
        L = min(len(ladder), len(ref_ladder))
        f_opt = float(inst.f_opt)
        err, ref_err = res.best_f - f_opt, ref.best_f - f_opt
        checks = {
            "budget": res.total_fevals <= b,
            "finite": bool(np.isfinite(res.best_f)),
            "gen0": bool(np.isclose(res.descents[0].best_f[0],
                                    ref.descents[0].best_f[0], rtol=1e-9)),
            "ladder": ladder[:L] == ref_ladder[:L],
            "target": f != 1 or max(err, ref_err) <= TARGET,
        }
        with jax.default_device(cpu):
            f_x = float(bbob.evaluate(
                f, bbob.make_instance(f, d, 1),
                jnp.asarray(res.best_x, jnp.float64)[None])[0])
        checks["own_point"] = bool(np.isclose(f_x, res.best_f, rtol=1e-9,
                                              atol=1e-9))
        dep = _departure(ref, res)
        log(f"(c) job n={d} f{f} budget={b}: fevals={res.total_fevals} "
            f"(ref {ref.total_fevals}) best-f_opt={err:.3e} "
            f"(ref {ref_err:.3e}) descents={len(ladder)} "
            f"(ref {len(ref_ladder)}) parity="
            f"{'exact' if dep is None else f'departs at gen {dep}'} "
            f"checks={checks} (ref {clock.since(c0)})")
        if not all(checks.values()):
            failed.append((d, f, s))
    assert not failed, f"service checks failed for {failed}"


def phase_mesh(clock, n_chips: int = 4, fids=MESH_FIDS, runs: int = MESH_RUNS,
               budget: int = MESH_BUDGET, kw=MESH_KW) -> None:
    """(mesh) S1 and S2 over n_chips devices vs the bucketed engine.

    The reference is the bucketed single-device engine on the same keys,
    run on each chip's member slice (``rows``): one program per chip of
    the batch shape a mesh device runs.  On the chip, float64 results
    depend on the program's batch shape: S1's 2-member programs ended the
    f8 members 0.7–6.6% apart in best f from one 8-member bucketed program
    on four TPU v5e chips, where on the CPU the two agree exactly (the
    early covariance's degenerate eigenspace turns rounding into another
    sampling basis, see phase_service).  Per member, against that
    reference: fevals within a TolFun flip, the same first generation,
    best f within 1e-5 (or both at target), and the f1 members at the
    1e-8 target."""
    import concurrent.futures

    import jax
    import numpy as np

    from repro.core import bucketed
    from repro.distributed import mesh_engine
    from repro.distributed.sharding import campaign_shardings
    from repro.launch.mesh import make_campaign_mesh

    devs = jax.devices()[:n_chips]
    mesh = make_campaign_mesh(devices=devs)
    n_buckets = kw["kmax_exp"] + 1
    B = len(fids) * runs
    assert B % n_chips == 0, (B, n_chips)
    Bl = B // n_chips

    def slice_ref(s):
        eng = bucketed.BucketedLadderEngine(max_evals=budget, **kw)
        with jax.default_device(devs[s]):
            return bucketed.run_campaign_bucketed(
                eng, fids=fids, runs=runs, seed=0,
                rows=range(s * Bl, (s + 1) * Bl))

    c0, t0 = clock.mark(), time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(max_workers=n_chips) as pool:
        refs = list(pool.map(slice_ref, range(n_chips)))
    log(f"(mesh) bucketed on each chip's {Bl}-member slice: wall "
        f"{time.perf_counter() - t0:.2f}s ({clock.since(c0)}), compiles="
        f"{[r.compiles for r in refs]}")
    for s, r in enumerate(refs):
        _check_campaign(r, budget, n_buckets, f"bucketed slice {s}")
    ref_best = np.concatenate([r.best_f for r in refs])
    ref_fev = np.concatenate([r.total_fevals for r in refs])
    ref_gen0 = np.concatenate([np.asarray(r.trace.global_best)[:, 0]
                               for r in refs])
    lam_max = kw["lam_start"] * 2 ** kw["kmax_exp"]
    flip = _flip_gens(kw["n"], kw["lam_start"]) * lam_max
    failed = []
    for strategy in ("ordered", "concurrent"):
        eng = mesh_engine.MeshCampaignEngine(strategy=strategy, mesh=mesh,
                                             max_evals=budget, **kw)
        # placement: the member batch split over n_chips distinct devices
        keys = np.zeros((B, 2), np.uint32)
        placed = jax.device_put(keys, campaign_shardings(keys, mesh, eng.axis))
        shard_devs = {s.device.id for s in placed.addressable_shards}
        c0, t0 = clock.mark(), time.perf_counter()
        res = mesh_engine.run_campaign_mesh(eng, fids=fids, runs=runs, seed=0)
        wall = time.perf_counter() - t0
        d_fev = np.abs(res.total_fevals - ref_fev)
        gen0 = np.isclose(np.asarray(res.trace.global_best)[:, 0], ref_gen0,
                          rtol=1e-9)
        close = np.isclose(res.best_f, ref_best, rtol=1e-5, atol=1e-7)
        hit = ((res.best_f - res.f_opt <= TARGET)
               & (ref_best - res.f_opt <= TARGET))
        f1_hit = np.array([fid != 1 for fid, _i, _r in res.members]) | hit
        islands = sum(1 for x in (res.shard_segments or []) if x)
        log(f"(mesh) {strategy} on {n_chips} chips {sorted(shard_devs)}: "
            f"wall {wall:.2f}s ({clock.since(c0)}), "
            f"compiles={res.compiles}, segments={len(res.segments)}, "
            f"islands={islands}, max|dfevals| vs reference="
            f"{int(d_fev.max())} (allowed {flip}), exact fevals="
            f"{int(np.sum(d_fev == 0))}/{B}, same first generation="
            f"{int(gen0.sum())}/{B}, best f within 1e-5 or both at target="
            f"{int(np.sum(close | hit))}/{B}")
        log(f"(mesh) {strategy} best f: {res.best_f.tolist()} "
            f"(reference {ref_best.tolist()})")
        try:
            assert len(shard_devs) == n_chips == eng.n_devices, shard_devs
            _check_campaign(res, budget, n_buckets, strategy)
            if strategy == "concurrent":
                assert islands == n_chips, islands
            assert np.all(d_fev <= flip), (strategy, d_fev)
            assert np.all(gen0), (strategy, gen0)
            assert np.all(close | hit), (strategy, close)
            assert np.all(f1_hit), (strategy, f1_hit)
        except AssertionError as e:
            failed.append(e)
    assert not failed, failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the S1/S2 mesh phase over four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    kind = devices[0].device_kind
    log("SMOKE RUN: a check that the main path runs and is right on the "
        "chip; its times are not measurements")
    log(f"device kind={kind!r} platform={platform} count={len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    clock = CompileClock()
    phases = ([("kernels", phase_kernels), ("campaign", phase_campaign),
               ("service", phase_service)] if args.chips == 1
              else [("mesh", lambda c: phase_mesh(c, n_chips=args.chips))])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(clock)
            status = "passed"
        except Exception:               # report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        log(f"phase {name} {status} in {time.perf_counter() - t0:.1f}s")
    log(f"total compile {clock.seconds:.1f}s over {clock.count} programs")
    if failed:
        log(f"failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
