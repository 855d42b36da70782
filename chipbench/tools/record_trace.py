#!/usr/bin/env python3
"""Record a small profiler trace on the chip for the trace reduction's
test: a jitted step holding the fused update kernel (``cma_gen_update``,
Pallas) and a float64 ``eigh`` at n = 40, on two slots.

    python3 chipbench/tools/record_trace.py --out DIR

Writes ``DIR/step.xplane.pb`` and the step's compiled HLO text
``DIR/step.hlo.txt`` (the test reads them gzipped from
``chipbench/tests/data``).  The benchmark's own runs never run this.
"""
import argparse
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import common
    from repro.kernels import ops
    devices = common.start_jax(1)
    S, n, lam = 2, 40, 12
    rng = np.random.default_rng(0)
    A = rng.standard_normal((S, n, n))
    C = A @ A.transpose(0, 2, 1) / n + np.eye(n)
    ev, B = np.linalg.eigh(C)
    w = np.zeros((S, lam))
    w[:, :6] = 1.0 / 6.0
    coef = {"c_sigma": 0.3, "mu_eff": 3.2, "c_c": 0.1, "c_1": 0.001,
            "c_mu": 0.002, "chi_n": 6.2, "gen1": 3.0}
    args_dev = jax.device_put(
        (C, B, np.sqrt(ev), 0.1 * rng.standard_normal((S, n)),
         0.1 * rng.standard_normal((S, n)),
         rng.standard_normal((S, lam, n)), w), devices[0])

    @jax.jit
    def step(C, B, D, ps, pc, Y, w):
        out = ops.gen_update(C, B, D, ps, pc, Y, w, coef, impl="pallas")
        return out, jnp.linalg.eigh(out[0])

    jax.block_until_ready(step(*args_dev))
    tdir = os.path.join(common.RUN_DIR, "record")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        jax.block_until_ready(step(*args_dev))
    jax.profiler.stop_trace()
    f = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True))[-1]
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(f, os.path.join(args.out, "step.xplane.pb"))
    with open(os.path.join(args.out, "step.hlo.txt"), "w") as fh:
        fh.write(step.lower(*args_dev).compile().as_text())
    print(os.path.getsize(f))
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
