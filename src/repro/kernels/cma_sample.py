"""Pallas TPU kernel: batched CMA-ES sampling   X = M + σ·(B·diag(D))·Z.

The paper (§3.1) rewrites the per-point sampling (eq. 1) as one Level-3 BLAS
GEMM over the whole population.  On TPU the analogous move is an MXU-tiled
matmul; this kernel additionally fuses the diag(D) scaling (a VPU multiply on
the loaded Z tile — zero extra HBM traffic) and the `m + σ·(·)` epilogue that
BLAS required separate axpy-style passes for.

Layout:  out[l, j] = m[j] + σ · Σ_k Z[l, k]·D[k]·B[j, k]
Grid: (lam/bl, n/bj, n/bk) — k innermost so each output tile accumulates in
VMEM across the contraction; epilogue applied on the last k step.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import jax.numpy as jnp

from repro.kernels.cma_gen import _block, _dot, _kernel_dtype, _pad, _smem


def _kernel(coef_ref, z_ref, d_ref, b_ref, m_ref, x_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[...].astype(jnp.float32)          # (bl, bk)
    d = d_ref[...].astype(jnp.float32)          # (1, bk)
    b = b_ref[...].astype(jnp.float32)          # (bj, bk)
    acc_ref[...] += _dot(z * d, b, ((1,), (1,)))

    @pl.when(k == n_k - 1)
    def _epilogue():
        sigma = coef_ref[0]
        m = m_ref[...].astype(jnp.float32)       # (1, bj)
        x_ref[...] = (m + sigma * acc_ref[...]).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bl", "bj", "bk", "interpret"))
def cma_sample(m: jnp.ndarray, sigma: jnp.ndarray, B: jnp.ndarray,
               D: jnp.ndarray, Z: jnp.ndarray, *, bl: int = 128, bj: int = 128,
               bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """X = m + σ·(B·diag(D))·Z, row convention (lam, n).  Pads to block shape."""
    lam, n = Z.shape
    dt = Z.dtype
    kdt = _kernel_dtype(dt, interpret)
    bl = min(bl, max(8, lam))
    bj = min(bj, n)
    bk = min(bk, n)
    pl_lam = -(-lam // bl) * bl
    pl_n = -(-n // bj) * bj
    pk_n = -(-n // bk) * bk
    if pl_n != pk_n:
        pl_n = pk_n = max(pl_n, pk_n)
    Zp = _pad(Z, (pl_lam, pk_n), kdt)
    Bp = _pad(B, (pl_n, pk_n), kdt)
    Dp = _pad(D[None], (1, pk_n), kdt)       # vectors as rows: a 1-D block
    Mp = _pad(m[None], (1, pl_n), kdt)       # misses XLA's 1-D tiling
    coef = jnp.asarray([sigma], jnp.float32)

    n_l, n_j, n_k = pl_lam // bl, pl_n // bj, pk_n // bk
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(n_l, n_j, n_k),
        in_specs=[
            _smem((1,)),                                         # coef (1,)
            _block((bl, bk), lambda l, j, k: (l, k)),      # Z
            _block((1, bk), lambda l, j, k: (0, k)),       # D
            _block((bj, bk), lambda l, j, k: (j, k)),      # B
            _block((1, bj), lambda l, j, k: (0, j)),       # m
        ],
        out_specs=_block((bl, bj), lambda l, j, k: (l, j)),
        out_shape=jax.ShapeDtypeStruct((pl_lam, pl_n), kdt),
        scratch_shapes=[pltpu.VMEM((bl, bj), jnp.float32)],
        interpret=interpret,
    )(coef, Zp, Dp, Bp, Mp)
    return out[:lam, :n].astype(dt)
