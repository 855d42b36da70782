"""Quickstart: the paper's technique in 40 lines.

Runs the K-Distributed parallel IPOP-CMA-ES (paper §3.2.3) on a BBOB
function with 8 simulated devices, then the sequential IPOP-CMA-ES baseline
(paper Alg. 2), and prints the ERT-style comparison.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core.ipop import run_ipop
from repro.core.strategies import KDistributed
from repro.fitness import bbob
from repro.launch.compile_cache import enable_compile_cache

FID, DIM, DEVICES = 8, 10, 8         # Rosenbrock, the paper's dims start at 10


def main():
    enable_compile_cache()
    inst = bbob.make_instance(FID, DIM, instance=1)
    fitness = lambda X: bbob.evaluate(FID, inst, X)
    f_opt = float(inst.f_opt)

    print(f"BBOB f{FID} ({bbob.NAMES[FID]}), dim {DIM}")

    # --- K-Distributed: all population sizes at once (paper Fig. 4) --------
    kd = KDistributed(n=DIM, n_devices=DEVICES)
    carry, trace = kd.run_sim(jax.random.PRNGKey(0), fitness, total_gens=150)
    kd_err = float(carry.best_f) - f_opt
    kd_evals = int(np.sum(carry.fevals))
    print(f"K-Distributed ({kd.n_descents} concurrent descents, "
          f"K=1..{2 ** kd.kmax_exp}): error {kd_err:.3e} "
          f"in {kd_evals} evaluations")

    # --- sequential IPOP baseline (paper Alg. 2) ----------------------------
    res = run_ipop(fitness, DIM, jax.random.PRNGKey(1),
                   max_evals=kd_evals)    # same evaluation budget
    print(f"Sequential IPOP:  error {res.best_f - f_opt:.3e} "
          f"in {res.total_fevals} evaluations")
    print("(same budget; K-Distributed additionally finishes "
          f"~{DEVICES}x faster in wall-clock on {DEVICES} devices)")


if __name__ == "__main__":
    main()
