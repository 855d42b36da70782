"""Plain float64 BBOB reference on the host: instances and functions.

The benchmark's own copy of the noiseless functions that its configurations
use (f1, f2, f8, f10, f15, f20; Hansen, Finck, Ros & Auger, RR-6829), with
the program's seeded instance recipe: x_opt, f_opt, R and Q are drawn from a
threefry key of (fid, n, instance).  It imports nothing of the program.  The
draws use ``jax.random`` on the host's CPU device, the rest is numpy in
float64.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

FIDS = (1, 2, 8, 10, 15, 20)


class Instance(NamedTuple):
    fid: int
    n: int
    x_opt: np.ndarray
    f_opt: float
    R: np.ndarray
    Q: np.ndarray


def cpu():
    import jax
    return jax.devices("cpu")[0]


def _orth(a: np.ndarray) -> np.ndarray:
    q, r = np.linalg.qr(a)
    return q * np.sign(np.diagonal(r))[None, :]


@functools.lru_cache(maxsize=None)
def instance(fid: int, n: int, inst: int) -> Instance:
    """The seeded instance (fid, n, inst): the key is 1,000,003·fid + 97·n
    + inst, split eight ways (x_opt, f_opt, R, Q, three unused, sign)."""
    if fid not in FIDS:
        raise ValueError(f"f{fid} has no reference here")
    import jax
    import jax.numpy as jnp
    with jax.default_device(cpu()):
        key = jax.random.PRNGKey(np.uint32(1_000_003 * fid + 97 * n + inst))
        k_xopt, k_fopt, k_R, k_Q, _p, _w, _a, k_sign = jax.random.split(key, 8)
        f64 = jnp.float64
        if fid == 8:
            x_opt = jax.random.uniform(k_xopt, (n,), f64, -3.0, 3.0)
        elif fid == 20:
            x_opt = (4.2096874633 / 2.0) * jnp.sign(
                jax.random.normal(k_sign, (n,), f64) + 1e-12)
        else:
            x_opt = jax.random.uniform(k_xopt, (n,), f64, -4.0, 4.0)
        f_opt = jnp.round(jax.random.uniform(k_fopt, (), f64, -100.0, 100.0), 2)
        a_R = jax.random.normal(k_R, (n, n), f64)
        a_Q = jax.random.normal(k_Q, (n, n), f64)
    return Instance(fid, n, np.asarray(x_opt, np.float64), float(f_opt),
                    _orth(np.asarray(a_R)), _orth(np.asarray(a_Q)))


def _t_osz(x):
    nz = x != 0.0
    xhat = np.where(nz, np.log(np.abs(np.where(nz, x, 1.0))), 0.0)
    c1 = np.where(x > 0.0, 10.0, 5.5)
    c2 = np.where(x > 0.0, 7.9, 3.1)
    return np.sign(x) * np.exp(xhat + 0.049 * (np.sin(c1 * xhat)
                                               + np.sin(c2 * xhat)))


def _t_asy(x, beta):
    n = x.shape[-1]
    idx = np.arange(n) / max(n - 1.0, 1.0)
    expo = 1.0 + beta * idx * np.sqrt(np.maximum(x, 0.0))
    return np.where(x > 0.0, np.maximum(x, 0.0) ** expo, x)


def _lam_alpha(alpha, n):
    return alpha ** (0.5 * np.arange(n) / max(n - 1.0, 1.0))


def _ell(n):
    return np.power(10.0, 6.0 * np.arange(n) / max(n - 1.0, 1.0))


def _f_pen(x):
    return np.sum(np.maximum(0.0, np.abs(x) - 5.0) ** 2, -1)


def _raw(ins: Instance, X: np.ndarray) -> np.ndarray:
    n, fid = ins.n, ins.fid
    if fid == 1:
        return np.sum((X - ins.x_opt) ** 2, -1)
    if fid == 2:
        return np.sum(_ell(n) * _t_osz(X - ins.x_opt) ** 2, -1)
    if fid == 8:
        c = max(1.0, np.sqrt(n) / 8.0)
        z = c * (X - ins.x_opt) + 1.0
        return np.sum(100.0 * (z[..., :-1] ** 2 - z[..., 1:]) ** 2
                      + (z[..., :-1] - 1.0) ** 2, -1)
    if fid == 10:
        z = _t_osz((X - ins.x_opt) @ ins.R.T)
        return np.sum(_ell(n) * z ** 2, -1)
    if fid == 15:
        z = _t_asy(_t_osz((X - ins.x_opt) @ ins.R.T), 0.2) @ ins.Q.T
        z = (z * _lam_alpha(10.0, n)) @ ins.R.T
        return (10.0 * (n - np.sum(np.cos(2 * np.pi * z), -1))
                + np.sum(z ** 2, -1))
    if fid == 20:
        xhat = 2.0 * np.sign(ins.x_opt) * X
        xo = 2.0 * np.abs(ins.x_opt)
        zhat = np.concatenate(
            [xhat[..., :1], xhat[..., 1:] + 0.25 * (xhat[..., :-1] - xo[:-1])],
            -1)
        z = 100.0 * (_lam_alpha(10.0, n) * (zhat - xo) + xo)
        body = -np.mean(z * np.sin(np.sqrt(np.abs(z))), -1) / 100.0
        return body + 4.189828872724339 + 100.0 * _f_pen(z / 100.0)
    raise ValueError(fid)


def ellipsoid_hessian(ins: Instance) -> np.ndarray:
    """The Hessian of f2 (separable) or f10 (rotated) at the optimum, with
    T_osz taken as the identity (it bends each axis by at most ±10%):
    diag(10^(6i/(n−1))), rotated by R for f10.  Condition number 1e6."""
    ell = _ell(ins.n)
    if ins.fid == 2:
        return np.diag(ell)
    if ins.fid == 10:
        return ins.R.T @ (ell[:, None] * ins.R)
    raise ValueError(f"f{ins.fid} is not an ellipsoid")


def evaluate(ins: Instance, X, dtype=np.float64) -> np.ndarray:
    """f(X) with f_opt included, X of shape (rows, n) or (n,).  ``dtype``
    float32 computes the same in float32 (X and the instance rounded)."""
    X = np.atleast_2d(np.asarray(X, dtype))
    if dtype != np.float64:
        ins = ins._replace(x_opt=ins.x_opt.astype(dtype),
                           R=ins.R.astype(dtype), Q=ins.Q.astype(dtype))
    return _raw(ins, X) + np.asarray(ins.f_opt, dtype)
