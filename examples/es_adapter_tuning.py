"""IPOP-CMA-ES over a real neural-network objective (paper §4.1's expensive-
evaluation regime, on this repo's own LM substrate).

A reduced qwen2-family model is trained for a few steps, then CMA-ES tunes a
34-dimensional adapter (per-layer output gains + head scales) to minimize
validation cross-entropy — fitness = one forward pass per candidate, the
kind of seconds-per-evaluation blackbox the paper targets (§4.1).

  PYTHONPATH=src python examples/es_adapter_tuning.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.core import cmaes
from repro.core.params import CMAConfig, make_params
from repro.data.pipeline import SyntheticTokens
from repro.fitness.nn_fitness import make_nn_fitness
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm


def main():
    enable_compile_cache()
    cfg = smoke_config("qwen2-0.5b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    data = SyntheticTokens(cfg, seq_len=32, global_batch=4, seed=1)
    val_batch = {k: jnp.asarray(v) for k, v in data.batch_at(999).items()}

    fitness, space = make_nn_fitness(cfg, params, val_batch)
    print(f"adapter dim n = {space.dim}; "
          f"baseline val CE = {float(fitness(jnp.zeros((1, space.dim)))[0]):.4f}")

    cma_cfg = CMAConfig(n=space.dim, lam=12, sigma0=0.5, dtype="float64")
    cma_params = make_params(cma_cfg)
    final = cmaes.run(cma_cfg, cma_params,
                      lambda X: fitness(X).astype(jnp.float64),
                      jax.random.PRNGKey(2),
                      x0=jnp.zeros((space.dim,)), max_gens=25)
    print(f"after {int(final.fevals)} NN evaluations: "
          f"best val CE = {float(final.best_f):.4f} "
          f"(Δ = {float(final.best_f) - float(fitness(jnp.zeros((1, space.dim)))[0]):+.4f})")
    print("best gains (first 8):",
          np.round(np.asarray(final.best_x[:8]), 3))


if __name__ == "__main__":
    main()
