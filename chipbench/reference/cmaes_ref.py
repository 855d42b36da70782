"""Plain float64 CMA-ES reference on the host (Hansen's defaults).

A straightforward numpy implementation of one CMA-ES descent, with the key
schedule of the system under test written out again so that the reference
draws the same normal vectors for the same (member key, incarnation,
generation, row):

    kd   = fold_in(fold_in(member_key, slot=0), incarnation)
    k_init, k_x0 = split(kd)
    x0   = uniform(k_x0, (n,), float64, lo, hi)
    z_r  = normal(fold_in(fold_in(kd, generation), r), (n,), float64)

It imports nothing of the program.  The random draws use ``jax.random`` on
the host's CPU device; the algebra is numpy.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import numpy as np

from reference import bbob_ref


def _jax():
    import jax
    return jax


@functools.lru_cache(maxsize=None)
def _draws(n: int, rows: int):
    """Jitted CPU draws of one generation: (x0, z) from (member key,
    incarnation, generation)."""
    jax = _jax()
    import jax.numpy as jnp

    def f(key, inc, gen, lo, hi):
        kd = jax.random.fold_in(jax.random.fold_in(key, 0), inc)
        _k_init, k_x0 = jax.random.split(kd)
        x0 = jax.random.uniform(k_x0, (n,), jnp.float64, lo, hi)
        kg = jax.random.fold_in(kd, gen)
        ks = jax.vmap(jax.random.fold_in, (None, 0))(
            kg, jnp.arange(rows, dtype=jnp.uint32))
        z = jax.vmap(lambda k: jax.random.normal(k, (n,), jnp.float64))(ks)
        return x0, z
    return jax.jit(f, device=bbob_ref.cpu())


def draws(key, n: int, rows: int, incarnation: int, generation: int,
          domain=(-5.0, 5.0)):
    """(x0, Z) in float64 numpy: the incarnation's initial mean and the
    generation's ``rows`` normal vectors."""
    jax = _jax()
    import jax.numpy as jnp
    with jax.default_device(bbob_ref.cpu()):
        k = jnp.asarray(np.asarray(key, np.uint32))
        x0, z = _draws(n, rows)(k, np.int32(incarnation), np.int32(generation),
                                float(domain[0]), float(domain[1]))
    return np.asarray(x0, np.float64), np.asarray(z, np.float64)


class Params:
    """Hansen's default strategy parameters for (n, λ)."""

    def __init__(self, n: int, lam: int):
        self.n, self.lam = n, lam
        self.mu = lam // 2
        w = np.log((lam + 1.0) / 2.0) - np.log(np.arange(1, self.mu + 1))
        self.w = w / np.sum(w)
        self.mu_eff = 1.0 / np.sum(self.w ** 2)
        me = self.mu_eff
        self.c_sigma = (me + 2.0) / (n + me + 5.0)
        self.d_sigma = (1.0 + 2.0 * max(0.0, np.sqrt((me - 1.0) / (n + 1.0))
                                        - 1.0) + self.c_sigma)
        self.c_c = (4.0 + me / n) / (n + 4.0 + 2.0 * me / n)
        self.c_1 = 2.0 / ((n + 1.3) ** 2 + me)
        self.c_mu = min(1.0 - self.c_1, 2.0 * (me - 2.0 + 1.0 / me)
                        / ((n + 2.0) ** 2 + me))
        self.chi_n = np.sqrt(n) * (1.0 - 1.0 / (4.0 * n)
                                   + 1.0 / (21.0 * n ** 2))


def _points(x0, sigma, Y, points=np.float64) -> np.ndarray:
    """x0 + σ·Y formed in ``points`` and returned in float64: float32 is
    the reading of points formed one precision down."""
    x0, Y = np.asarray(x0, points), np.asarray(Y, points)
    return (x0[None, :] + points(sigma) * Y).astype(np.float64)


def first_generation(key, ins: bbob_ref.Instance, lam: int, incarnation: int,
                     sigma0: float, domain=(-5.0, 5.0),
                     points=np.float64) -> float:
    """Best f of an incarnation's first generation: λ points
    x0 + σ0·z_r with C = I."""
    x0, Z = draws(key, ins.n, lam, incarnation, 0, domain)
    return float(np.min(bbob_ref.evaluate(ins, _points(x0, sigma0, Z,
                                                       points))))


def generation_from_state(key, ins: bbob_ref.Instance, lam: int,
                          incarnation: int, generation: int, m, sigma, B,
                          D, points=np.float64) -> float:
    """Best f of one generation sampled from a given state:
    x_r = m + σ·B·(D∘z_r)."""
    _x0, Z = draws(key, ins.n, lam, incarnation, generation)
    Y = (Z * np.asarray(D, np.float64)[None, :]) @ np.asarray(B, np.float64).T
    return float(np.min(bbob_ref.evaluate(
        ins, _points(np.asarray(m, np.float64), float(sigma), Y, points))))


def descent(key, ins: bbob_ref.Instance, lam: int, sigma0: float,
            domain=(-5.0, 5.0), incarnation: int = 0,
            fitness: Optional[Callable] = None,
            params_for: Callable[[int, int], Params] = Params,
            learn_C: bool = True):
    """One plain CMA-ES descent of a member's incarnation from its own key:
    yields (evaluations, best f so far, C) after each generation.  Its
    trajectory shares the first generation with the program's and then
    goes its own way (another eigenbasis, other rounding), so what can be
    compared is how fast the algorithm gets on, not the path.
    ``params_for`` (n, λ) → parameters, and ``learn_C`` = False, put a
    planted fault in the reference's place."""
    fitness = fitness or (lambda X: bbob_ref.evaluate(ins, X))
    n = ins.n
    p = params_for(n, lam)
    m, _Z = draws(key, n, 1, incarnation, 0, domain)
    sigma = float(sigma0)
    C = np.eye(n)
    B, D = np.eye(n), np.ones(n)
    ps, pc = np.zeros(n), np.zeros(n)
    best = np.inf
    evals = 0
    gen = 0
    while True:
        _x0, Z = draws(key, n, lam, incarnation, gen, domain)
        Y = (Z * D[None, :]) @ B.T
        X = m[None, :] + sigma * Y
        f = fitness(X)
        evals += lam
        order = np.argsort(f, kind="stable")
        best = min(best, float(f[order[0]]))
        yw = p.w @ Y[order[:p.mu]]
        ps = ((1.0 - p.c_sigma) * ps + np.sqrt(p.c_sigma * (2.0 - p.c_sigma)
                                               * p.mu_eff)
              * (B @ ((B.T @ yw) / D)))
        h = (np.linalg.norm(ps) / np.sqrt(1.0 - (1.0 - p.c_sigma)
                                          ** (2.0 * (gen + 1))) / p.chi_n
             < 1.4 + 2.0 / (n + 1.0))
        pc = (1.0 - p.c_c) * pc + h * np.sqrt(p.c_c * (2.0 - p.c_c)
                                              * p.mu_eff) * yw
        if learn_C:
            Ysel = Y[order[:p.mu]]
            gram = (Ysel * p.w[:, None]).T @ Ysel
            decay = (1.0 - p.c_1 - p.c_mu
                     + (1.0 - h) * p.c_1 * p.c_c * (2.0 - p.c_c))
            C = decay * C + p.c_mu * gram + p.c_1 * np.outer(pc, pc)
            C = 0.5 * (C + C.T)
        m = m + sigma * yw
        sigma *= np.exp((p.c_sigma / p.d_sigma)
                        * (np.linalg.norm(ps) / p.chi_n - 1.0))
        kth = min(lam // 4 + 1, lam - 1)
        if f[order[0]] == f[order[kth]]:
            sigma *= np.exp(0.2 + p.c_sigma / p.d_sigma)
        ev, B = np.linalg.eigh(C)
        D = np.sqrt(np.maximum(ev, 1e-300))
        gen += 1
        yield evals, best, C


def descent_hit(key, ins: bbob_ref.Instance, lam: int, sigma0: float,
                target: float, max_evals: int, domain=(-5.0, 5.0),
                fitness: Optional[Callable] = None) -> Optional[int]:
    """Evaluations after which incarnation 0's plain descent first has its
    best f at or below ``target`` (None if not within ``max_evals``)."""
    for evals, best, _C in descent(key, ins, lam, sigma0, domain,
                                   fitness=fitness):
        if evals > max_evals:
            return None
        if best <= target:
            return evals


def covariance_after(key, ins: bbob_ref.Instance, lam: int, incarnation: int,
                     sigma0: float, generations: int, domain=(-5.0, 5.0),
                     **fault) -> np.ndarray:
    """C of the incarnation's plain descent after ``generations``
    generations (``generations`` ≥ 1)."""
    for gen, (_e, _b, C) in enumerate(
            descent(key, ins, lam, sigma0, domain, incarnation, **fault), 1):
        if gen >= generations:
            return C
