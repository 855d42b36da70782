"""Benchmark aggregator — one section per paper table/figure + the roofline.

  PYTHONPATH=src python -m benchmarks.run [--full | --smoke]

Default sizes are CI-scale (single CPU core); --full widens dims/functions
to the paper's ranges (hours on this container, intended for real hardware).
--smoke runs the engine/kernel benchmarks only (a few minutes) and writes
the BENCH_kernels/BENCH_ladder/BENCH_bucketed/BENCH_mesh/BENCH_service
JSON artifacts for CI, every section in this one process.  The mesh
section covers as many devices as the process sees:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python benchmarks/run.py --smoke
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# allow `python benchmarks/run.py` without an editable install
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def section(title):
    print(f"\n=== {title} " + "=" * max(1, 60 - len(title)), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="ladder bench only; writes BENCH_ladder.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    t0 = time.time()

    if args.smoke:
        from benchmarks import (bench_kernels, bench_ladder, bench_mesh,
                                bench_service)
        section("Smoke — fused generation kernels vs PR-3 unfused op soup")
        # also writes the PR-7 residency A/B cells: sample_rng (in-kernel
        # counter stream vs host fold_in), resident_full_step_f1/f2
        # (eval-fused sample epilogue vs dispatched sample→eval chain) and
        # strategies_gram (KDistributed fused gram-family psum vs the PR-6
        # moments psum)
        bench_kernels.main(["--dims", "64,256,1024", "--gens", "40",
                            "--reps", "5", "--out", "BENCH_kernels.json"])
        section("Smoke — host-loop IPOP vs device-resident ladder")
        bench_ladder.main(["--dim", "6", "--fids", "1,8", "--runs", "2",
                           "--lam-start", "8", "--kmax", "2",
                           "--max-evals", "6000", "--out",
                           "BENCH_ladder.json"])
        section("Smoke — work-proportional campaigns (buckets + eigen blocks)")
        bench_ladder.main_bucketed(["--dim", "32", "--fids", "1,8",
                                    "--runs", "2", "--lam-start", "8",
                                    "--kmax", "4", "--max-evals", "20000",
                                    "--eigen-interval", "5", "--out",
                                    "BENCH_bucketed.json"])
        section("Smoke — mesh campaign engine, S1/S2 on 1→P devices")
        # in this process, on every device it sees (no child process may
        # need the chip); a CPU run gets its virtual fleet from XLA_FLAGS
        bench_mesh.main(["--dim", "8", "--fids", "1,8",
                         "--runs", "4", "--lam-start", "8", "--kmax", "2",
                         "--max-evals", "6000", "--eigen-interval", "3",
                         "--out", "BENCH_mesh.json"])
        section("Smoke — campaign service vs sequential per-job runs")
        bench_service.main(["--jobs", "6", "--dims", "4,6", "--fids", "1,8",
                            "--budget", "3000", "--lam-start", "8",
                            "--kmax", "2", "--out", "BENCH_service.json"])
        section("Smoke — service soak (sustained load, SLO-gated)")
        # merges a `soak` section into the same BENCH_service.json artifact;
        # the generous p99 bound is an is-it-alive gate on CI CPUs, not a
        # hardware claim
        rc = bench_service.main(["--soak", "--soak-jobs", "8",
                                 "--dims", "4,6", "--fids", "1,8",
                                 "--budget", "2000", "--lam-start", "8",
                                 "--kmax", "2", "--slo-p99-s", "300",
                                 "--out", "BENCH_service.json"])
        if rc:
            return rc
        print(f"\n[benchmarks.run] total {time.time() - t0:.1f}s")
        return 0

    from benchmarks import (bench_comm_share, bench_ecdf, bench_ladder,
                            bench_linalg, bench_popsize, bench_strategies,
                            roofline)

    section("Ladder engine — host-loop vs device-resident (BENCH_ladder.json)")
    if args.full:
        bench_ladder.main(["--dim", "40", "--fids", "1,8,15", "--runs", "3",
                           "--lam-start", "12", "--kmax", "4",
                           "--max-evals", "60000"])
    else:
        bench_ladder.main([])

    section("Fig.5/Table 1 — BLAS/GEMM linear-algebra rewrites")
    if args.full:
        bench_linalg.main(["--dims", "10,40,200,1000", "--ks", "1,256"])
    else:
        bench_linalg.main(["--dims", "10,40,200", "--ks", "1,16",
                           "--reps", "3"])

    section("Table 2 — strategy speedups over sequential IPOP (ERT model)")
    if args.full:
        bench_strategies.main(["--fids", "1,2,8,10,15,20", "--dim", "40",
                               "--devices", "512", "--cost-ms", "10",
                               "--runs", "5", "--gens", "400"])
    else:
        bench_strategies.main(["--fids", "1,8", "--dim", "10",
                               "--devices", "8", "--cost-ms", "1",
                               "--runs", "2", "--gens", "100",
                               "--max-evals", "25000"])

    section("Fig.8/Table 4 — ECDF over (function,target,run)")
    if args.full:
        bench_ecdf.main(["--fids", "1,2,8,10,15,20", "--dim", "40",
                         "--devices", "512", "--runs", "5"])
    else:
        bench_ecdf.main(["--fids", "1,8", "--dim", "10", "--devices", "8",
                         "--runs", "2", "--gens", "100",
                         "--max-evals", "25000"])

    section("Fig.9/Table 5 — best population size per (function,target)")
    if args.full:
        bench_popsize.main(["--fids", "1,7,8,15,17", "--dim", "40",
                            "--devices", "512", "--runs", "5",
                            "--gens", "400"])
    else:
        bench_popsize.main(["--fids", "1,8", "--dim", "10",
                            "--devices", "8", "--runs", "2",
                            "--gens", "100"])

    section("Fig.6 — comm/linalg share vs evaluation cost (CMA gen step)")
    bench_comm_share.main([])

    section("Roofline — single-pod baselines (from dry-run artifacts)")
    roofline.main(["--mesh", "pod"])

    section("Roofline — single-pod OPTIMIZED (flash + rowwise, §Perf)")
    roofline.main(["--mesh", "pod_opt"])

    section("Roofline — multi-pod (if artifacts present)")
    roofline.main(["--mesh", "multipod"])

    print(f"\n[benchmarks.run] total {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
