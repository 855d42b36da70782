"""Pallas TPU kernel: fused CMA-ES covariance adaptation (paper eq. 3).

    C' = decay·C + c_μ · Σ_i w_i·yᵢyᵢᵀ + c₁·p_c p_cᵀ

The paper's key linear-algebra contribution is rewriting the λ rank-one
covariance updates as ONE rank-λ GEMM (A·B with A = [yᵢ], B = [w_i·yᵢᵀ]) so a
Level-3 BLAS can be used.  The TPU-native version tiles that GEMM onto the MXU
and — unlike the dgemm + two scaled-add passes the paper's C code needs —
fuses the decay term and the rank-one p_c p_cᵀ term into the output epilogue,
so C is read and written exactly once from HBM.

Layout: out[i, j] = decay·C[i,j] + c_μ·Σ_k w[k]·Y[k,i]·Y[k,j] + c₁·pc[i]·pc[j]
Grid: (n/bi, n/bj, λ/bk) — k innermost, accumulation in a VMEM scratch tile.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import jax.numpy as jnp

from repro.kernels.cma_gen import _block, _dot, _kernel_dtype, _pad, _smem


def _kernel(coef_ref, yi_ref, yj_ref, w_ref, c_ref, pci_ref, pcj_ref,
            out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    yi = yi_ref[...].astype(jnp.float32)        # (bk, bi)
    yj = yj_ref[...].astype(jnp.float32)        # (bk, bj)
    w = w_ref[...].astype(jnp.float32)          # (bk, 1)
    # (bi, bj) += Yᵢᵀ · diag(w) · Yⱼ — one MXU contraction per k-step
    acc_ref[...] += _dot(yi, yj * w, ((0,), (0,)))

    @pl.when(k == n_k - 1)
    def _epilogue():
        decay, c_mu, c_1 = coef_ref[0], coef_ref[1], coef_ref[2]
        c = c_ref[...].astype(jnp.float32)       # (bi, bj)
        pci = pci_ref[...].astype(jnp.float32)   # (bi, 1)
        pcj = pcj_ref[...].astype(jnp.float32)   # (1, bj)
        out = decay * c + c_mu * acc_ref[...] + c_1 * pci * pcj
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bk", "interpret"))
def cma_rank_mu_update(C: jnp.ndarray, Y: jnp.ndarray, w: jnp.ndarray,
                       p_c: jnp.ndarray, decay, c_mu, c_1, *, bi: int = 128,
                       bj: int = 128, bk: int = 128,
                       interpret: bool = False) -> jnp.ndarray:
    """Fused covariance adaptation.  Y: (λ, n) rows are yᵢ; w: (λ,) rank weights."""
    lam, n = Y.shape
    dt = C.dtype
    kdt = _kernel_dtype(dt, interpret)
    bi = min(bi, n)
    bj = min(bj, n)
    bk = min(bk, max(8, lam))
    p_n_i = -(-n // bi) * bi
    p_n_j = -(-n // bj) * bj
    p_n = max(p_n_i, p_n_j)
    p_lam = -(-lam // bk) * bk
    Yp = _pad(Y, (p_lam, p_n), kdt)
    # vectors as columns/rows: a 1-D block misses XLA's 1-D tiling
    wp = _pad(w[:, None], (p_lam, 1), kdt)              # zero weight ⇒ no effect
    Cp = _pad(C, (p_n, p_n), kdt)
    pc_col = _pad(p_c[:, None], (p_n, 1), kdt)
    pc_row = _pad(p_c[None], (1, p_n), kdt)
    coef = jnp.stack([jnp.asarray(decay, jnp.float32),
                      jnp.asarray(c_mu, jnp.float32),
                      jnp.asarray(c_1, jnp.float32)])

    n_i, n_j, n_k = p_n // bi, p_n // bj, p_lam // bk
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(n_i, n_j, n_k),
        in_specs=[
            _smem((3,)),                                          # coef (3,)
            _block((bk, bi), lambda i, j, k: (k, i)),       # Y (rows i)
            _block((bk, bj), lambda i, j, k: (k, j)),       # Y (rows j)
            _block((bk, 1), lambda i, j, k: (k, 0)),        # w
            _block((bi, bj), lambda i, j, k: (i, j)),       # C
            _block((bi, 1), lambda i, j, k: (i, 0)),        # p_c rows
            _block((1, bj), lambda i, j, k: (0, j)),        # p_c cols
        ],
        out_specs=_block((bi, bj), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((p_n, p_n), kdt),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
    )(coef, Yp, Yp, wp, Cp, pc_col, pc_row)
    return out[:n, :n].astype(dt)
