"""Mesh campaign engine scaling curve — S1 (ordered) vs S2 (concurrent).

Runs the same BBOB campaign through ``distributed/mesh_engine.py`` on
campaign meshes carved out of 1, 2, 4, ... virtual CPU devices (prefixes of
the ``--xla_force_host_platform_device_count`` fleet) for BOTH deployment
strategies, against the single-device bucketed driver as the baseline, and
writes the useful-evals/sec curve to ``BENCH_mesh.json`` (the CI artifact).

Virtual CPU devices share the machine's physical cores, so absolute
wall-clock does not scale the way the paper's Fugaku CMGs do — the curve's
value is (a) the per-strategy dispatch/synchronization overhead at each
device count on identical work, and (b) a smoke-level proof that both
strategies run, re-bucket and stay budget-correct on a real multi-device
mesh.  It runs in the calling process on the devices that process sees
(``--devices`` caps the curve, default all of them); on a CPU the virtual
fleet comes from the environment:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_mesh [--devices 8] [--dim 16]
"""
from __future__ import annotations

import argparse
import json
import sys


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None,
                    help="largest device count of the curve (default: all)")
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--fids", default="1,8")
    ap.add_argument("--runs", type=int, default=4)
    ap.add_argument("--lam-start", type=int, default=8)
    ap.add_argument("--kmax", type=int, default=3)
    ap.add_argument("--max-evals", type=int, default=8000)
    ap.add_argument("--eigen-interval", type=int, default=3)
    ap.add_argument("--out", default="BENCH_mesh.json")
    return ap


def main(argv=None):
    args = _parser().parse_args(argv)
    import time

    import jax

    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from repro.core import bucketed
    from repro.distributed import mesh_engine
    from repro.launch.mesh import make_campaign_mesh

    fids = [int(f) for f in args.fids.split(",")]
    kw = dict(n=args.dim, lam_start=args.lam_start, kmax_exp=args.kmax,
              max_evals=args.max_evals, eigen_interval=args.eigen_interval)
    devs = jax.devices()
    n_dev = len(devs) if args.devices is None else args.devices
    if n_dev > len(devs):
        raise SystemExit(
            f"--devices {n_dev}: only {len(devs)} local devices (for a CPU "
            f"rehearsal set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev})")
    counts = [d for d in (1, 2, 4, 8, 16, 32) if d <= n_dev]

    def timed(fn):
        fn()                                    # warm (compile) pass
        t0 = time.perf_counter()
        res = fn()
        return res, time.perf_counter() - t0

    # -- single-device bucketed baseline --------------------------------------
    eng_b = bucketed.BucketedLadderEngine(**kw)
    res_b, wall_b = timed(lambda: bucketed.run_campaign_bucketed(
        eng_b, fids=fids, instances=(1,), runs=args.runs, seed=1))
    baseline = {
        "wall_s": round(wall_b, 4),
        "useful_evals": res_b.useful_evals,
        "evals_per_s": round(res_b.useful_evals / max(wall_b, 1e-9), 1),
        "compiles": res_b.compiles,
    }

    # -- 1 → P device curve, both strategies ----------------------------------
    curve = {"ordered": [], "concurrent": []}
    for d in counts:
        mesh = make_campaign_mesh(devices=devs[:d])
        for strategy in ("ordered", "concurrent"):
            eng = mesh_engine.MeshCampaignEngine(strategy=strategy,
                                                 mesh=mesh, **kw)
            res, wall = timed(lambda: mesh_engine.run_campaign_mesh(
                eng, fids=fids, instances=(1,), runs=args.runs, seed=1))
            np.testing.assert_array_equal(res.total_fevals,
                                          res_b.total_fevals)
            curve[strategy].append({
                "devices": d,
                "wall_s": round(wall, 4),
                "useful_evals": res.useful_evals,
                "evals_per_s": round(res.useful_evals / max(wall, 1e-9), 1),
                "compiles": res.compiles,
                "segments": len(res.segments),
                "exchange_rounds": len(res.exchange),
                "padding_waste": round(res.padding_waste(), 3),
            })
            print(f"[bench_mesh] {strategy:10s} d={d}  wall={wall:.3f}s  "
                  f"{curve[strategy][-1]['evals_per_s']:.0f} evals/s",
                  flush=True)

    out = {
        "config": {
            "dim": args.dim, "fids": fids, "runs": args.runs,
            "lam_start": args.lam_start, "kmax_exp": args.kmax,
            "max_evals": args.max_evals,
            "eigen_interval": args.eigen_interval,
            "members": len(fids) * args.runs,
            "device_counts": counts,
            "note": "useful-evals/sec on identical work per cell; virtual "
                    "CPU devices share physical cores, so the curve "
                    "measures dispatch/synchronization overhead (S1 "
                    "barrier-per-segment vs S2 islands), not hardware "
                    "scaling",
        },
        "bucketed_baseline": baseline,
        "mesh": curve,
        "speedup_vs_bucketed": {
            s: {str(r["devices"]): round(
                r["evals_per_s"] / max(baseline["evals_per_s"], 1e-9), 3)
                for r in rows}
            for s, rows in curve.items()
        },
    }
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)
    print(json.dumps(out["speedup_vs_bucketed"], indent=2))
    print(f"[bench_mesh] wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
