"""BBOB noiseless test suite (f1–f24) in JAX.

Faithful to the function definitions of Hansen, Finck, Ros & Auger,
"Real-Parameter Black-Box Optimization Benchmarking 2009: Noiseless Functions
Definitions" (RR-6829, INRIA) — the benchmark the paper evaluates on.

Instances are seeded (x_opt, rotations R/Q, Gallagher peak sets are drawn from
a PRNG keyed by (fid, dim, instance)); they follow the published definitions
but are not bit-identical to COCO's instance-id derivation (DESIGN.md §8.3).

Every evaluator is pure jnp over a batch: ``evaluate(fid, inst, X) -> (batch,)``
so it jit/vmap/shard_maps cleanly — this is what the strategies shard across
the mesh (the paper's 'scatter the λ evaluations', §3.2.1).
"""
from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SEARCH_DOMAIN = (-5.0, 5.0)

#: fids whose evaluation is fully separable AFTER the x_opt shift — i.e.
#: expressible as Σᵢ scaleᵢ·g(xᵢ − shiftᵢ)² with an elementwise g — and
#: therefore fusable into the sample kernel's epilogue (f(X) computed while
#: X is still in registers; X never stored).  f1 sphere (g = identity) and
#: f2 ellipsoid (g = t_osz, the f10-style 10^{6i/(n−1)} conditioning
#: WITHOUT f10's rotation).  Rotated fids (f10 itself: `@ R.T`) are not
#: separable and take the dispatched two-program path.
FUSABLE_FIDS = (1, 2)

GROUPS = {  # paper §4.1: the five BBOB difficulty groups
    "separable": (1, 2, 3, 4, 5),
    "low_conditioning": (6, 7, 8, 9),
    "high_conditioning": (10, 11, 12, 13, 14),
    "multimodal_adequate": (15, 16, 17, 18, 19),
    "multimodal_weak": (20, 21, 22, 23, 24),
}

NAMES = {
    1: "Sphere", 2: "Ellipsoidal", 3: "Rastrigin", 4: "BucheRastrigin",
    5: "LinearSlope", 6: "AttractiveSector", 7: "StepEllipsoidal",
    8: "Rosenbrock", 9: "RosenbrockRotated", 10: "EllipsoidalRotated",
    11: "Discus", 12: "BentCigar", 13: "SharpRidge", 14: "DifferentPowers",
    15: "RastriginRotated", 16: "Weierstrass", 17: "SchaffersF7",
    18: "SchaffersF7Ill", 19: "GriewankRosenbrock", 20: "Schwefel",
    21: "Gallagher101", 22: "Gallagher21", 23: "Katsuura", 24: "LunacekBiRastrigin",
}


class BBOBInstance(NamedTuple):
    fid: jnp.ndarray      # () int32 (informational)
    x_opt: jnp.ndarray    # (n,) location encoding of the optimum (see per-f use)
    f_opt: jnp.ndarray    # ()
    R: jnp.ndarray        # (n, n) orthogonal
    Q: jnp.ndarray        # (n, n) orthogonal
    peaks_y: jnp.ndarray  # (m, n) Gallagher peak locations (else (1, n) zeros)
    peaks_w: jnp.ndarray  # (m,)
    peaks_c: jnp.ndarray  # (m, n) per-peak diagonal scalings (already permuted)


# ---------------------------------------------------------------------------
# transforms (RR-6829 §0)
# ---------------------------------------------------------------------------

def t_osz(x):
    xhat = jnp.where(x != 0.0, jnp.log(jnp.abs(jnp.where(x != 0.0, x, 1.0))), 0.0)
    # constants in x's dtype: a weak-typed where() would be f64 under x64,
    # which the t_osz in the eval-fused Pallas epilogue cannot hold
    dt = x.dtype
    c1 = jnp.where(x > 0.0, jnp.asarray(10.0, dt), jnp.asarray(5.5, dt))
    c2 = jnp.where(x > 0.0, jnp.asarray(7.9, dt), jnp.asarray(3.1, dt))
    return jnp.sign(x) * jnp.exp(
        xhat + 0.049 * (jnp.sin(c1 * xhat) + jnp.sin(c2 * xhat)))


def t_asy(x, beta):
    n = x.shape[-1]
    idx = jnp.arange(n) / jnp.maximum(n - 1.0, 1.0)
    expo = 1.0 + beta * idx * jnp.sqrt(jnp.maximum(x, 0.0))
    return jnp.where(x > 0.0, jnp.maximum(x, 0.0) ** expo, x)


def lam_alpha(alpha, n, dtype=jnp.float64):
    idx = jnp.arange(n, dtype=dtype) / jnp.maximum(n - 1.0, 1.0)
    return jnp.asarray(alpha, dtype) ** (0.5 * idx)


def f_pen(x):
    return jnp.sum(jnp.maximum(0.0, jnp.abs(x) - 5.0) ** 2, axis=-1)


def _orth(key, n, dtype=jnp.float64):
    a = jax.random.normal(key, (n, n), dtype)
    q, r = jnp.linalg.qr(a)
    return q * jnp.sign(jnp.diagonal(r))[None, :]


# ---------------------------------------------------------------------------
# instance factory
# ---------------------------------------------------------------------------

def make_instance(fid: int, n: int, instance: int = 0,
                  dtype=jnp.float64) -> BBOBInstance:
    key = jax.random.PRNGKey(np.uint32(1_000_003 * fid + 97 * n + instance))
    k_xopt, k_fopt, k_R, k_Q, k_peaks, k_w, k_alpha, k_sign = jax.random.split(key, 8)

    x_opt = jax.random.uniform(k_xopt, (n,), dtype, -4.0, 4.0)
    if fid == 5:       # optimum at a ±5 corner
        x_opt = 5.0 * jnp.sign(jax.random.normal(k_sign, (n,), dtype) + 1e-12)
    elif fid == 20:    # x_opt = 4.2096874633/2 · ±1
        x_opt = (4.2096874633 / 2.0) * jnp.sign(
            jax.random.normal(k_sign, (n,), dtype) + 1e-12)
    elif fid == 24:    # x_opt = (μ0/2)·±1
        x_opt = (2.5 / 2.0) * jnp.sign(
            jax.random.normal(k_sign, (n,), dtype) + 1e-12)
    elif fid in (8,):  # plain Rosenbrock: x_opt free in [-3, 3] (z=1 shift)
        x_opt = jax.random.uniform(k_xopt, (n,), dtype, -3.0, 3.0)

    f_opt = jnp.round(jax.random.uniform(k_fopt, (), dtype, -100.0, 100.0), 2)
    R = _orth(k_R, n, dtype)
    Q = _orth(k_Q, n, dtype)

    if fid == 9:       # optimum implied by z = c·R·x + 1/2 == 1
        c = max(1.0, np.sqrt(n) / 8.0)
        x_opt = R.T @ (jnp.full((n,), 0.5 / c, dtype))
    elif fid == 19:    # z = c·R·x + 0.5 == 1
        c = max(1.0, np.sqrt(n) / 8.0)
        x_opt = R.T @ (jnp.full((n,), 0.5 / c, dtype))

    # Gallagher peak sets (f21: 101 peaks, f22: 21 peaks)
    if fid in (21, 22):
        m = 101 if fid == 21 else 21
        span = 4.0 if fid == 21 else 3.92
        base = 1000.0 if fid == 21 else 1000.0 ** 2
        y = jax.random.uniform(k_peaks, (m, n), dtype, -4.9, 4.9)
        y = y.at[0].set(jax.random.uniform(k_xopt, (n,), dtype, -span, span))
        x_opt = y[0]
        w = jnp.concatenate([
            jnp.asarray([10.0], dtype),
            1.1 + 8.0 * jnp.arange(m - 1, dtype=dtype) / (m - 2.0),
        ])
        # per-peak condition numbers: random permutation of 1000^{2j/(m-2)}
        j = jax.random.permutation(k_alpha, m - 1)
        alphas = jnp.concatenate([
            jnp.asarray([base], dtype),
            1000.0 ** (2.0 * j.astype(dtype) / jnp.maximum(m - 2.0, 1.0)),
        ])
        idx = jnp.arange(n, dtype=dtype) / jnp.maximum(n - 1.0, 1.0)
        diag = alphas[:, None] ** (0.5 * idx[None, :]) / (alphas[:, None] ** 0.25)
        peaks_y, peaks_w, peaks_c = y, w, diag
    else:
        peaks_y = jnp.zeros((1, n), dtype)
        peaks_w = jnp.zeros((1,), dtype)
        peaks_c = jnp.ones((1, n), dtype)

    return BBOBInstance(
        fid=jnp.asarray(fid, jnp.int32), x_opt=x_opt, f_opt=f_opt, R=R, Q=Q,
        peaks_y=peaks_y, peaks_w=peaks_w, peaks_c=peaks_c)


# ---------------------------------------------------------------------------
# the 24 functions — X: (batch, n) → (batch,) raw value; f_opt added by caller
# ---------------------------------------------------------------------------

def _f01(inst, X):
    z = X - inst.x_opt
    return jnp.sum(z ** 2, -1)


def _ell_scale(n: int, dtype) -> jnp.ndarray:
    """The ellipsoid axis weights 10^(6·i/(n−1)), host-computed so the SAME
    literal constant is embedded in every program that needs them.  (XLA's
    compiled/folded ``pow`` differs from the eager one by ulps; sharing the
    literal is what makes the eval-fused f2 bit-identical to the dispatched
    ``_f02``.)"""
    return jnp.asarray(
        np.power(10.0, 6.0 * np.arange(n) / max(n - 1.0, 1.0)), dtype)


def _f02(inst, X):
    n = X.shape[-1]
    z = t_osz(X - inst.x_opt)
    return jnp.sum(_ell_scale(n, X.dtype) * z ** 2, -1)


def _f03(inst, X):
    n = X.shape[-1]
    z = lam_alpha(10.0, n, X.dtype) * t_asy(t_osz(X - inst.x_opt), 0.2)
    return 10.0 * (n - jnp.sum(jnp.cos(2 * jnp.pi * z), -1)) + jnp.sum(z ** 2, -1)


def _f04(inst, X):
    n = X.shape[-1]
    t = t_osz(X - inst.x_opt)
    s = 10.0 ** (0.5 * jnp.arange(n) / max(n - 1.0, 1.0))
    odd = (jnp.arange(n) % 2) == 0      # 1-based odd indices
    s = jnp.where(odd & (t > 0), 10.0 * s, s)
    z = s * t
    return (10.0 * (n - jnp.sum(jnp.cos(2 * jnp.pi * z), -1))
            + jnp.sum(z ** 2, -1) + 100.0 * f_pen(X))


def _f05(inst, X):
    n = X.shape[-1]
    s = jnp.sign(inst.x_opt) * 10.0 ** (jnp.arange(n) / max(n - 1.0, 1.0))
    z = jnp.where(X * inst.x_opt < 25.0, X, inst.x_opt)
    return jnp.sum(5.0 * jnp.abs(s) - s * z, -1)


def _f06(inst, X):
    z = (X - inst.x_opt) @ inst.R.T * lam_alpha(10.0, X.shape[-1], X.dtype)
    z = z @ inst.Q.T
    # sector: s_i = 100 where z_i·x_opt_i > 0 (RR-6829 uses raw x_opt_i)
    s = jnp.where(z * inst.x_opt > 0, 100.0, 1.0)
    val = jnp.sum((s * z) ** 2, -1)
    return t_osz(val) ** 0.9


def _f07(inst, X):
    n = X.shape[-1]
    zhat = (X - inst.x_opt) @ inst.R.T * lam_alpha(10.0, n, X.dtype)
    ztil = jnp.where(jnp.abs(zhat) > 0.5,
                     jnp.floor(0.5 + zhat),
                     jnp.floor(0.5 + 10.0 * zhat) / 10.0)
    z = ztil @ inst.Q.T
    scale = 10.0 ** (2.0 * jnp.arange(n) / max(n - 1.0, 1.0))
    body = 0.1 * jnp.maximum(jnp.abs(zhat[..., 0]) / 1e4,
                             jnp.sum(scale * z ** 2, -1))
    return body + f_pen(X)


def _f08(inst, X):
    n = X.shape[-1]
    c = max(1.0, np.sqrt(n) / 8.0)
    z = c * (X - inst.x_opt) + 1.0
    return jnp.sum(100.0 * (z[..., :-1] ** 2 - z[..., 1:]) ** 2
                   + (z[..., :-1] - 1.0) ** 2, -1)


def _f09(inst, X):
    n = X.shape[-1]
    c = max(1.0, np.sqrt(n) / 8.0)
    z = c * (X @ inst.R.T) + 0.5
    return jnp.sum(100.0 * (z[..., :-1] ** 2 - z[..., 1:]) ** 2
                   + (z[..., :-1] - 1.0) ** 2, -1)


def _f10(inst, X):
    n = X.shape[-1]
    z = t_osz((X - inst.x_opt) @ inst.R.T)
    scale = 10.0 ** (6.0 * jnp.arange(n) / max(n - 1.0, 1.0))
    return jnp.sum(scale * z ** 2, -1)


def _f11(inst, X):
    z = t_osz((X - inst.x_opt) @ inst.R.T)
    return 1e6 * z[..., 0] ** 2 + jnp.sum(z[..., 1:] ** 2, -1)


def _f12(inst, X):
    z = t_asy((X - inst.x_opt) @ inst.R.T, 0.5) @ inst.R.T
    return z[..., 0] ** 2 + 1e6 * jnp.sum(z[..., 1:] ** 2, -1)


def _f13(inst, X):
    z = ((X - inst.x_opt) @ inst.R.T * lam_alpha(10.0, X.shape[-1], X.dtype)) @ inst.Q.T
    return z[..., 0] ** 2 + 100.0 * jnp.sqrt(jnp.sum(z[..., 1:] ** 2, -1))


def _f14(inst, X):
    n = X.shape[-1]
    z = (X - inst.x_opt) @ inst.R.T
    expo = 2.0 + 4.0 * jnp.arange(n) / max(n - 1.0, 1.0)
    return jnp.sqrt(jnp.sum(jnp.abs(z) ** expo, -1))


def _f15(inst, X):
    n = X.shape[-1]
    z = t_asy(t_osz((X - inst.x_opt) @ inst.R.T), 0.2) @ inst.Q.T
    z = (z * lam_alpha(10.0, n, X.dtype)) @ inst.R.T
    return 10.0 * (n - jnp.sum(jnp.cos(2 * jnp.pi * z), -1)) + jnp.sum(z ** 2, -1)


def _f16(inst, X):
    n = X.shape[-1]
    z = t_osz((X - inst.x_opt) @ inst.R.T) @ inst.Q.T
    z = (z * lam_alpha(0.01, n, X.dtype)) @ inst.R.T
    k = jnp.arange(12, dtype=X.dtype)
    halfk = 0.5 ** k
    threek = 3.0 ** k
    f0 = jnp.sum(halfk * jnp.cos(jnp.pi * threek))
    inner = jnp.sum(halfk[None, None, :] * jnp.cos(
        2 * jnp.pi * threek[None, None, :] * (z[..., None] + 0.5)), -1)
    return 10.0 * (jnp.mean(inner, -1) - f0) ** 3 + (10.0 / n) * f_pen(X)


def _schaffers(inst, X, alpha):
    n = X.shape[-1]
    z = t_asy((X - inst.x_opt) @ inst.R.T, 0.5) @ inst.Q.T
    z = z * lam_alpha(alpha, n, X.dtype)
    s = jnp.sqrt(z[..., :-1] ** 2 + z[..., 1:] ** 2)
    val = jnp.mean(jnp.sqrt(s) * (1.0 + jnp.sin(50.0 * s ** 0.2) ** 2), -1) ** 2
    return val + 10.0 * f_pen(X)


def _f17(inst, X):
    return _schaffers(inst, X, 10.0)


def _f18(inst, X):
    return _schaffers(inst, X, 1000.0)


def _f19(inst, X):
    n = X.shape[-1]
    c = max(1.0, np.sqrt(n) / 8.0)
    z = c * (X @ inst.R.T) + 0.5
    s = 100.0 * (z[..., :-1] ** 2 - z[..., 1:]) ** 2 + (z[..., :-1] - 1.0) ** 2
    return (10.0 / (n - 1.0)) * jnp.sum(s / 4000.0 - jnp.cos(s), -1) + 10.0


def _f20(inst, X):
    n = X.shape[-1]
    ones_pm = 2.0 * jnp.sign(inst.x_opt)     # ±2 pattern from x_opt signs
    xhat = ones_pm * X
    xo = 2.0 * jnp.abs(inst.x_opt)
    zhat = jnp.concatenate([
        xhat[..., :1],
        xhat[..., 1:] + 0.25 * (xhat[..., :-1] - xo[:-1]),
    ], -1)
    z = 100.0 * (lam_alpha(10.0, n, X.dtype) * (zhat - xo) + xo)
    body = -jnp.mean(z * jnp.sin(jnp.sqrt(jnp.abs(z))), -1) / 100.0
    return body + 4.189828872724339 + 100.0 * f_pen(z / 100.0)


def _gallagher(inst, X):
    n = X.shape[-1]
    d = (X @ inst.R.T)[:, None, :] - (inst.peaks_y @ inst.R.T)[None, :, :]
    quad = jnp.sum(d * d * inst.peaks_c[None, :, :], -1)      # (batch, m)
    vals = inst.peaks_w[None, :] * jnp.exp(-quad / (2.0 * n))
    best = jnp.max(vals, -1)
    return t_osz(10.0 - best) ** 2 + f_pen(X)


def _f21(inst, X):
    return _gallagher(inst, X)


def _f22(inst, X):
    return _gallagher(inst, X)


def _f23(inst, X):
    n = X.shape[-1]
    z = ((X - inst.x_opt) @ inst.R.T * lam_alpha(100.0, n, X.dtype)) @ inst.Q.T
    j = 2.0 ** jnp.arange(1, 33, dtype=X.dtype)
    zj = z[..., None] * j                                  # (batch, n, 32)
    frac = jnp.abs(zj - jnp.round(zj)) / j
    inner = 1.0 + (jnp.arange(1, n + 1, dtype=X.dtype))[None, :] * jnp.sum(frac, -1)
    prod = jnp.prod(inner ** (10.0 / n ** 1.2), -1)
    return (10.0 / n ** 2) * prod - 10.0 / n ** 2 + f_pen(X)


def _f24(inst, X):
    n = X.shape[-1]
    mu0 = 2.5
    s = 1.0 - 1.0 / (2.0 * np.sqrt(n + 20.0) - 8.2)
    mu1 = -np.sqrt((mu0 ** 2 - 1.0) / s)
    xhat = 2.0 * jnp.sign(inst.x_opt) * X
    z = ((xhat - mu0) @ inst.R.T * lam_alpha(100.0, n, X.dtype)) @ inst.Q.T
    term1 = jnp.sum((xhat - mu0) ** 2, -1)
    term2 = n + s * jnp.sum((xhat - mu1) ** 2, -1)
    ras = 10.0 * (n - jnp.sum(jnp.cos(2 * jnp.pi * z), -1))
    return jnp.minimum(term1, term2) + ras + 1e4 * f_pen(X)


_EVALS = {1: _f01, 2: _f02, 3: _f03, 4: _f04, 5: _f05, 6: _f06, 7: _f07,
          8: _f08, 9: _f09, 10: _f10, 11: _f11, 12: _f12, 13: _f13, 14: _f14,
          15: _f15, 16: _f16, 17: _f17, 18: _f18, 19: _f19, 20: _f20,
          21: _f21, 22: _f22, 23: _f23, 24: _f24}


def evaluate(fid: int, inst: BBOBInstance, X: jnp.ndarray) -> jnp.ndarray:
    """Batch evaluation f(X) (absolute value, i.e. f_opt included)."""
    X = jnp.atleast_2d(X)
    return _EVALS[fid](inst, X) + inst.f_opt


def make_fitness(fid: int, n: int, instance: int = 0, dtype=jnp.float64):
    """Returns (fitness_fn, inst): fitness_fn(X) -> (batch,) closed over inst."""
    inst = make_instance(fid, n, instance, dtype)
    def fn(X):
        return evaluate(fid, inst, X)
    return fn, inst


# ---------------------------------------------------------------------------
# stacked campaigns — traced-fid dispatch over a batch of instances
# ---------------------------------------------------------------------------

def pad_instance(inst: BBOBInstance, m_max: int) -> BBOBInstance:
    """Pad the Gallagher peak set to ``m_max`` rows so heterogeneous instances
    stack into one pytree.  Padding peaks carry weight 0 and therefore never
    win the max in ``_gallagher`` (real peaks have weight ≥ 1.1)."""
    m, n = inst.peaks_y.shape
    if m >= m_max:
        return inst
    pad = m_max - m
    dt = inst.peaks_y.dtype
    return inst._replace(
        peaks_y=jnp.concatenate([inst.peaks_y, jnp.zeros((pad, n), dt)]),
        peaks_w=jnp.concatenate([inst.peaks_w, jnp.zeros((pad,), dt)]),
        peaks_c=jnp.concatenate([inst.peaks_c, jnp.ones((pad, n), dt)]),
    )


def stack_instances(instances: list[BBOBInstance]) -> BBOBInstance:
    """Stack instances along a leading batch axis (peaks padded to a common m)."""
    m_max = max(int(i.peaks_y.shape[0]) for i in instances)
    padded = [pad_instance(i, m_max) for i in instances]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def evaluate_dynamic(inst: BBOBInstance, X: jnp.ndarray,
                     branch_fids: tuple = tuple(range(1, 25))) -> jnp.ndarray:
    """``evaluate`` with a *traced* function id (``inst.fid``).

    Dispatch is a ``lax.switch`` over ``branch_fids`` — pass the (static) set
    of fids actually present in a campaign to keep the compiled program small.
    Under ``vmap`` a batched switch index evaluates every branch and selects,
    so the per-point cost is len(branch_fids)×; with the per-campaign fid set
    that is the price of running heterogeneous functions in one program.
    """
    branch_fids = tuple(branch_fids)
    branches = [lambda i, x, f=f: _EVALS[f](i, x) for f in branch_fids]
    fid_tab = jnp.asarray(branch_fids, jnp.int32)
    match = fid_tab == inst.fid.astype(jnp.int32)
    idx = jnp.argmax(match)
    val = jax.lax.switch(idx, branches, inst, X) + inst.f_opt
    # a fid outside branch_fids would silently dispatch to branch 0 (argmax of
    # all-False is 0); the fid is traced so we cannot raise — poison instead
    return jnp.where(jnp.any(match), val, jnp.nan)


# ---------------------------------------------------------------------------
# separable-fid eval fusion (the sample kernel's fitness epilogue)
# ---------------------------------------------------------------------------

class SepCoeffs(NamedTuple):
    """Per-instance coefficients of a separable fid: f(X) = Σᵢ scaleᵢ·
    g(Xᵢ − shiftᵢ)² + f_opt with g selected by ``mode`` (0 = identity,
    1 = t_osz).  Pure data — rides kernel calls (SMEM scalars + two (n,)
    rows) and program-cache keys never see the values."""
    scale: jnp.ndarray     # (n,)
    shift: jnp.ndarray     # (n,) — x_opt
    f_opt: jnp.ndarray     # ()
    mode: jnp.ndarray      # () int32: 0 identity, 1 t_osz
    valid: jnp.ndarray     # () bool: fid ∈ branch_fids (else poison NaN)


def separable_coeffs(inst: BBOBInstance, branch_fids: tuple) -> SepCoeffs:
    """SepCoeffs for a (traced-fid) instance over a fusable static fid menu.

    The per-fid scale/mode tables are selected by the same argmax-match
    index ``evaluate_dynamic`` dispatches on, so a stacked campaign keeps
    its fid a row operand; a fid outside ``branch_fids`` poisons to NaN
    exactly like the dispatched path.
    """
    branch_fids = tuple(branch_fids)
    assert all(f in FUSABLE_FIDS for f in branch_fids), branch_fids
    n = inst.x_opt.shape[-1]
    dt = inst.x_opt.dtype
    scale_tab = {1: jnp.ones((n,), dt), 2: _ell_scale(n, dt)}
    mode_tab = {1: 0, 2: 1}
    fid_tab = jnp.asarray(branch_fids, jnp.int32)
    match = fid_tab == inst.fid.astype(jnp.int32)
    idx = jnp.argmax(match)
    return SepCoeffs(
        scale=jnp.stack([scale_tab[f] for f in branch_fids])[idx],
        shift=inst.x_opt,
        f_opt=inst.f_opt,
        mode=jnp.asarray([mode_tab[f] for f in branch_fids],
                         jnp.int32)[idx],
        valid=jnp.any(match))


def separable_eval(X: jnp.ndarray, sep: SepCoeffs) -> jnp.ndarray:
    """Evaluate a separable fid from its coefficients — bit-identical to the
    dispatched ``evaluate_dynamic`` on the same X (same elementwise chain,
    same last-axis reduce; ×1.0 and +0.0 are IEEE-exact)."""
    t = X - sep.shift[..., None, :]
    tg = jnp.where(sep.mode[..., None, None] == 1, t_osz(t), t)
    val = jnp.sum(sep.scale[..., None, :] * tg ** 2, -1) + sep.f_opt[..., None]
    return jnp.where(sep.valid[..., None], val, jnp.nan)


def eval_fusion_enabled() -> bool:
    """Env toggle (``REPRO_EVAL_FUSION=0`` disables) — read at TRACE time,
    like ``REPRO_KERNEL_IMPL``: export before the first engine call, and
    mind that cached programs keep the setting they were traced with (the
    engines' program-cache keys include it)."""
    return os.environ.get("REPRO_EVAL_FUSION", "1").strip() != "0"


def fusable_fitness(inst: BBOBInstance, branch_fids: tuple, fn):
    """Wrap a campaign fitness closure with its separable coefficients when
    the WHOLE static fid menu is fusable (and fusion is enabled) — the
    engines detect the ``.sep`` attribute and route sampling through the
    eval-fused kernels; any non-fusable fid in the menu, or the env kill
    switch, returns ``fn`` unchanged (two-program fallback)."""
    branch_fids = tuple(branch_fids)
    if (not branch_fids or not eval_fusion_enabled()
            or any(f not in FUSABLE_FIDS for f in branch_fids)):
        return fn
    from repro.core.eval_dispatch import FusableEval
    return FusableEval(fn, separable_coeffs(inst, branch_fids))


def evaluate_stacked(fid_array: jnp.ndarray, inst_params: BBOBInstance,
                     X: jnp.ndarray,
                     branch_fids: tuple = tuple(range(1, 25))) -> jnp.ndarray:
    """Batched campaign evaluation: one program over stacked instances.

    ``fid_array``: (B,) int32; ``inst_params``: BBOBInstance with (B, ...)
    leaves (see ``stack_instances``); ``X``: (B, batch, n).  Returns
    (B, batch) absolute fitness values.
    """
    def one(fid, inst, x):
        return evaluate_dynamic(inst._replace(fid=fid), x, branch_fids)
    return jax.vmap(one)(fid_array.astype(jnp.int32), inst_params, X)
