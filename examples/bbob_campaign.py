"""BBOB campaign: the paper's §4 experiment at laptop scale.

The sequential-IPOP column now runs on the device-resident ladder engine
(core/ladder.py): every (function, run) member of the campaign is one batch
row of a single jitted/vmapped scanned program with in-place doubled-λ
restarts — one compile for the whole table.  K-Distributed runs all rungs
concurrently on the strategies collectives inside one jit
(``ladder.run_concurrent``); K-Replicated keeps its phase barriers.

  PYTHONPATH=src python examples/bbob_campaign.py [--fids 1,8,10] [--dim 10]
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import ladder
from repro.core.strategies import KReplicated
from repro.fitness import bbob
from repro.launch.compile_cache import enable_compile_cache

TARGETS = np.array([1e2, 1e1, 1e0, 1e-1, 1e-2])


def hits_from_trace(best_over_time, evals_over_time, f_opt):
    hits = np.full(len(TARGETS), np.inf)
    best = np.inf
    for bf, fe in zip(best_over_time, evals_over_time):
        best = min(best, bf)
        for i, t in enumerate(TARGETS):
            if np.isinf(hits[i]) and best - f_opt <= t:
                hits[i] = fe
    return hits


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fids", default="1,8,10")
    ap.add_argument("--dim", type=int, default=10)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--gens", type=int, default=120)
    ap.add_argument("--max-evals", type=int, default=60_000)
    ap.add_argument("--kmax", type=int, default=5)
    args = ap.parse_args()
    fids = [int(f) for f in args.fids.split(",")]

    # -- sequential IPOP: whole campaign = ONE jitted/vmapped ladder program --
    engine = ladder.LadderEngine(
        n=args.dim, lam_start=12, kmax_exp=args.kmax, schedule="sequential",
        max_evals=args.max_evals)
    camp = ladder.run_campaign(engine, fids=fids, instances=(1,), runs=1,
                               seed=1)
    seq_hits_all = camp.hit_evals(TARGETS)          # (B, targets)
    print(f"[campaign] {len(camp.members)} members, one ladder program, "
          f"compiles={camp.compiles}")

    print(f"{'f':>3} {'target':>8} {'seq-IPOP':>10} {'K-Dist':>10} "
          f"{'K-Rep':>10}   (evaluations to target)")
    for j, fid in enumerate(fids):
        inst = bbob.make_instance(fid, args.dim, 1)
        fit = lambda X: bbob.evaluate(fid, inst, X)  # noqa: B023
        f_opt = float(inst.f_opt)

        seq_hits = seq_hits_all[j]

        _, _, tr = ladder.run_concurrent(
            args.dim, args.devices, jax.random.PRNGKey(2), fit,
            total_gens=args.gens)
        kd_hits = hits_from_trace(tr["best_f"], tr["fevals"], f_opt)

        kr = KReplicated(n=args.dim, n_devices=args.devices)
        out = kr.run_sim(jax.random.PRNGKey(3), fit, phase_gens=args.gens,
                         max_evals=args.max_evals)
        bfs = np.concatenate([p["best_f"] for p in out["phases"]])
        fes = np.concatenate([p["fevals"] for p in out["phases"]])
        kr_hits = hits_from_trace(bfs, fes, f_opt)

        for i, t in enumerate(TARGETS):
            row = [seq_hits[i], kd_hits[i], kr_hits[i]]
            cells = [f"{v:10.0f}" if np.isfinite(v) else f"{'—':>10}"
                     for v in row]
            print(f"{fid:>3} {t:>8.0e} {cells[0]} {cells[1]} {cells[2]}")


if __name__ == "__main__":
    main()
