"""Pallas TPU megakernels: one fused CMA-ES generation, slot-batched.

The paper's §3.1 rewrites the CMA-ES hot spots as Level-3 BLAS; PR 2/3
made every λ-proportional cost work-proportional, leaving the
λ-independent O(n²) per-generation state update as the dominant per-step
cost at large n.  These kernels take that update Pallas-native END TO END:

* ``cma_gen_sample`` — fused sampling emitting BOTH ``Y = Z·diag(D)·Bᵀ``
  and ``X = m + σ·Y`` in one pass (the separate-op path writes Y to HBM,
  reads it back, and writes X; here the epilogue reuses the accumulator
  tile while it is still in VMEM).
* ``cma_gen_update`` — the update megakernel: rank-μ gram, weighted-mean
  GEMV, evolution-path recursions (including the h_σ stall test), the
  ``decay·C + c_μ·G + c₁·p_c'p_c'ᵀ`` epilogue, and the whitened-step GEMV
  ``C^{-1/2}·y_w = B·diag(1/D)·Bᵀ·y_w`` — so C, B and D are each read
  from HBM exactly ONCE per generation instead of once per op.  The gram
  accumulates as ``(√w·Y)ᵀ(√w·Y)``, which keeps C' symmetric by
  construction — the unfused path's ``0.5·(C + Cᵀ)`` repair pass (the
  memory-bound transpose-add that dominates the update at large n) has no
  counterpart here at all (see ref.fused_gen_update).

Both kernels are **slot-batched**: every input carries a leading slot (or
member) axis that maps onto the leading grid dimension, so the stacked-slot
ladder programs (core/ladder.py::slots_gen_step) invoke ONE kernel for all
slots instead of vmapping a per-slot kernel (whose batching rule would
re-trace and rely on vmap lowering — the dead corner PR 4 removes).
Inactive/parked slots ride through with all-zero weights: the gram,
``y_w`` and p_c/p_σ pulls they contribute are zero, and the engine's
``ran``/``stop`` tree-select discards their outputs — the repo-wide
zero-weight masking convention, now honored in-kernel.

Geometry: grid ``(S, n_k)`` with λ chunked over ``n_k`` and whole-(n,n)
C/B tiles per slot.  The λ-contraction accumulates in a VMEM scratch tile
across the ``n_k`` steps; the epilogue (everything after the gram) runs on
the last λ chunk.  Whole-matrix tiles bound the kernel to roughly
n ≤ 768 in f32 on a 16 MB-VMEM core (4 n² tiles live: C, B, C', gram
accumulator) — comfortably past the paper's n = 1000 BBOB ceiling in
bf16/f16 state and past every config this repo ships in f32.  Off-TPU the
kernels execute in interpret mode (correctness oracle only; the XLA ref
``kernels/ref.py::fused_gen_update`` is the production CPU path).

Mosaic constraints the layouts below honour:

* every index map returns int32 (``_block``, ``_smem``) — under x64 a
  literal ``0`` is an i64, which Mosaic cannot return;
* a per-slot vector is an ``(S, 1, n)`` array with ``(1, 1, n)`` blocks,
  a per-row column an ``(S, λ, 1)`` one, and a per-slot scalar lives in
  SMEM — the last two block dims must each be (8, 128)-aligned or span
  the whole axis, which a ``(1, n)`` block of an ``(S, n)`` array only
  does at S = 1;
* no 64-bit types: compiled kernels take and return f32 for f64 state
  (``_kernel_dtype``).
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import jax.numpy as jnp

# per-slot scalar coefficients of the update megakernel, in SMEM layout
# order (see ops.gen_update for the packing)
COEF_FIELDS = ("c_sigma", "mu_eff", "c_c", "c_1", "c_mu", "chi_n", "gen1")


def _round_block(n: int, cap: int = 128) -> int:
    """Block edge for an axis of size n: 8-aligned, capped at the MXU edge."""
    return min(cap, -(-max(n, 1) // 8) * 8)


def _block(shape, index_map) -> pl.BlockSpec:
    """A VMEM block whose index map returns int32 block indices."""
    return pl.BlockSpec(
        shape, lambda *g: tuple(jnp.int32(i) for i in index_map(*g)))


def _smem(shape) -> pl.BlockSpec:
    """The whole (small) array in SMEM, with an explicit int32 index map —
    the implicit whole-array map returns i64 zeros under x64."""
    zeros = (np.int32(0),) * len(shape)
    return pl.BlockSpec(tuple(shape), lambda *_: zeros,
                        memory_space=pltpu.SMEM)


def _kernel_dtype(dtype, interpret: bool):
    """I/O dtype of a kernel for state of ``dtype``.  Mosaic has no 64-bit
    types, so a compiled kernel takes and returns f32 for f64 state; the
    kernels compute in f32 whatever the state dtype, so for their f32
    results this changes nothing.  Interpret mode keeps the state dtype:
    the CPU oracle tests pin the f64 RNG stream and eval epilogue."""
    if not interpret and jnp.dtype(dtype).itemsize == 8:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(dtype)


def _dot(a, b, contract):
    """f32 MXU contraction of ``a`` and ``b`` over ``contract`` (a pair of
    axis tuples) at full f32 precision, the kernels' stated arithmetic —
    never single bf16 passes."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _pad(a, shape, dtype):
    """Zero-pad ``a`` at the end of each axis to ``shape``, cast to dtype."""
    return jnp.pad(a.astype(dtype), [(0, t - d) for d, t in zip(a.shape, shape)])


def _fold_vmap(kernel_call, *args):
    """``kernel_call(*args)``, where every argument and result carries the
    leading slot axis, with a vmap over it folded INTO that axis: a campaign
    that vmaps its members over a slot-batched kernel runs one kernel over
    B·S slots.  (Pallas's own batching rule would add a grid axis whose
    blocks break the TPU tiling rule for the per-slot vectors and SMEM
    scalars.)  Slots are independent, so the fold changes no result."""
    @jax.custom_batching.custom_vmap
    def call(*a):
        return kernel_call(*a)

    @call.def_vmap
    def _rule(axis_size, in_batched, *a):
        a = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
             for x, b in zip(a, in_batched)]
        out = call(*(x.reshape((-1,) + x.shape[2:]) for x in a))
        out = jax.tree_util.tree_map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return call(*(jnp.asarray(x) for x in args))


# ---------------------------------------------------------------------------
# fused sample kernels
# ---------------------------------------------------------------------------
#
# One parametrized factory covers the plain kernel and the residency
# variants:
#
#   rng=True         Z is drawn IN the kernel via the portable threefry2x32
#                    counter stream (kernels/ref.py — plain jnp uint32 ops,
#                    so the same code lowers under Mosaic AND interpret
#                    mode), seeded per slot from the row base key with
#                    counter (row << 16) | col.  The host-shaped fold_in
#                    stream and the HBM-resident (S, λ, n) Z tile both
#                    disappear from the sampled path.
#   fused_eval=True  the separable-fid fitness (bbob.SepCoeffs) is computed
#                    in the epilogue while X = m + σ·Y is still in
#                    registers: the kernel emits (Y, F) and X never exists
#                    in HBM.
#
# ``pltpu.prng_random_bits`` (the hardware PRNG) has no interpret/CPU
# lowering on this jax, so the threefry stream is the portable default;
# the hw path stays available behind ``rng_bits="hw"`` for TPU-only runs
# (seeded per (slot, row-block) from the same seeds — a DIFFERENT stream,
# gated out of every parity test off-TPU).

def _make_sample_kernel(*, n_k: int, bl: int, bn: int, np_: int, n_true: int,
                        rng: bool, fused_eval: bool, rng_bits: str = "counter",
                        z_dtype=None):
    from repro.kernels import ref as _ref

    def body(*refs):
        it = iter(refs)
        sigma_ref = next(it)
        seeds_ref = next(it) if rng else None
        z_ref = None if rng else next(it)
        d_ref, b_ref, m_ref = next(it), next(it), next(it)
        if fused_eval:
            scale_ref, shift_ref, fopt_ref = next(it), next(it), next(it)
            mode_ref, valid_ref = next(it), next(it)
        y_ref, out2_ref, acc_ref = next(it), next(it), next(it)

        s, l, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if rng and rng_bits == "hw":
            # TPU hardware PRNG: per-(slot, row-block) seed keeps each
            # grid step's draw independent of every other step's.
            pltpu.prng_seed(seeds_ref[s, 0], seeds_ref[s, 1], l, k)
            bits = pltpu.prng_random_bits((bl, bn))
            z = _ref._bits_to_unit(bits.astype(jnp.uint32), jnp.float32)
            z2 = _ref._bits_to_unit(
                pltpu.prng_random_bits((bl, bn)).astype(jnp.uint32),
                jnp.float32)
            two_pi = jnp.float32(2.0 * 3.14159265358979323846)
            z = jnp.sqrt(jnp.float32(-2.0) * jnp.log1p(-z)) * jnp.cos(
                two_pi * z2)
        elif rng:
            rows = (jax.lax.broadcasted_iota(jnp.uint32, (bl, bn), 0)
                    + (l * bl).astype(jnp.uint32))
            cols = (jax.lax.broadcasted_iota(jnp.uint32, (bl, bn), 1)
                    + (k * bn).astype(jnp.uint32))
            z = _ref.threefry_normal(seeds_ref[s, 0], seeds_ref[s, 1],
                                     rows, cols, z_dtype).astype(jnp.float32)
        else:
            z = z_ref[0].astype(jnp.float32)        # (bl, bn)
        d = d_ref[0].astype(jnp.float32)            # (1, bn)
        b = b_ref[0].astype(jnp.float32)            # (np, bn)
        acc_ref[...] += _dot(z * d, b, ((1,), (1,)))

        @pl.when(k == n_k - 1)
        def _epilogue():
            sigma = sigma_ref[s]
            m = m_ref[0].astype(jnp.float32)        # (1, np)
            y = acc_ref[...]
            y_ref[0] = y.astype(y_ref.dtype)
            x = m + sigma * y                       # (bl, np) — in registers
            if not fused_eval:
                out2_ref[0] = x.astype(out2_ref.dtype)
                return
            from repro.fitness import bbob as _bbob
            # the eval chain runs in the OUTPUT dtype on the f32-computed x
            # — exactly the values the two-program path would hand the
            # dispatched menu.  (On TPU the output dtype is f32 anyway; the
            # state-dtype chain is what keeps the f64 interpret tier at ref
            # precision, e.g. + f_opt must not round to f32.)
            dt = out2_ref.dtype
            xe = x.astype(dt)
            t = xe - shift_ref[0]
            tg = jnp.where(mode_ref[s] == 1, _bbob.t_osz(t), t)
            # padding cols: scale is zero-padded, but guard the transform
            # output anyway (0·NaN would poison the row sum)
            colm = jax.lax.broadcasted_iota(jnp.int32, (bl, np_), 1) < n_true
            tg = jnp.where(colm, tg, jnp.zeros((), dt))
            fv = jnp.sum(scale_ref[0] * tg * tg, axis=1, keepdims=True) \
                + fopt_ref[0]                       # (bl, 1)
            fv = jnp.where(valid_ref[s] == 1, fv, jnp.asarray(jnp.nan, dt))
            out2_ref[0] = fv.astype(dt)

    return body


def _sample_call(m, sigma, B, D, *, Z=None, seeds=None, sep=None,
                 lam=None, bl=128, bn=128, interpret=False,
                 rng_bits: str = "counter"):
    """Shared pad/spec plumbing of the sample kernels.  Returns (Y, X)
    without ``sep`` and (Y, F) with it."""
    rng = seeds is not None
    fused_eval = sep is not None
    n = m.shape[-1]
    lam = Z.shape[-2] if Z is not None else int(lam)
    dt = m.dtype
    kdt = _kernel_dtype(dt, interpret)
    bl = _round_block(lam, bl)
    bn = _round_block(n, bn)
    lp = -(-lam // bl) * bl
    np_ = -(-n // bn) * bn
    n_l, n_k = lp // bl, np_ // bn

    def call(m, sigma, B, D, zs, *sep_arrays):
        S = m.shape[0]

        def vec(a):                                 # (S, n) -> (S, 1, np)
            return _pad(jnp.reshape(a, (S, 1, n)), (S, 1, np_), kdt)

        in_specs = [_smem((S,))]                                # sigma
        args = [sigma.astype(jnp.float32)]
        if rng:
            in_specs.append(_smem((S, 2)))                      # seeds
            args.append(zs.astype(jnp.uint32))
        else:
            in_specs.append(_block((1, bl, bn), lambda s, l, k: (s, l, k)))
            args.append(_pad(zs, (S, lp, np_), kdt))
        row = _block((1, 1, np_), lambda s, l, k: (s, 0, 0))
        in_specs += [
            _block((1, 1, bn), lambda s, l, k: (s, 0, k)),      # D
            _block((1, np_, bn), lambda s, l, k: (s, 0, k)),    # B
            row,                                                # m
        ]
        args += [vec(D), _pad(B, (S, np_, np_), kdt), vec(m)]
        if fused_eval:
            scale, shift, fopt, mode, valid = sep_arrays
            in_specs += [row, row,
                         _block((1, 1, 1), lambda s, l, k: (s, 0, 0)),  # f_opt
                         _smem((S,)),                                   # mode
                         _smem((S,))]                                   # valid
            args += [vec(scale), vec(shift),
                     fopt.reshape(S, 1, 1).astype(kdt),
                     mode.astype(jnp.int32), valid.astype(jnp.int32)]

        y_spec = _block((1, bl, np_), lambda s, l, k: (s, l, 0))
        if fused_eval:
            out_specs = (y_spec, _block((1, bl, 1), lambda s, l, k: (s, l, 0)))
            out_shape = (jax.ShapeDtypeStruct((S, lp, np_), kdt),
                         jax.ShapeDtypeStruct((S, lp, 1), kdt))
        else:
            out_specs = (y_spec, y_spec)
            out_shape = (jax.ShapeDtypeStruct((S, lp, np_), kdt),
                         jax.ShapeDtypeStruct((S, lp, np_), kdt))

        kernel = _make_sample_kernel(n_k=n_k, bl=bl, bn=bn, np_=np_,
                                     n_true=n, rng=rng, fused_eval=fused_eval,
                                     rng_bits=rng_bits, z_dtype=kdt)
        Y, out2 = pl.pallas_call(
            kernel, grid=(S, n_l, n_k), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bl, np_), jnp.float32)],
            interpret=interpret,
        )(*args)
        Y = Y[:, :lam, :n].astype(dt)
        if fused_eval:
            return Y, out2[:, :lam, 0].astype(dt)
        return Y, out2[:, :lam, :n].astype(dt)

    return _fold_vmap(call, m, sigma, B, D, seeds if rng else Z,
                      *(sep if fused_eval else ()))


@functools.partial(jax.jit, static_argnames=("bl", "bn", "interpret"))
def cma_gen_sample(m: jnp.ndarray, sigma: jnp.ndarray, B: jnp.ndarray,
                   D: jnp.ndarray, Z: jnp.ndarray, *, bl: int = 128,
                   bn: int = 128, interpret: bool = False):
    """Slot-batched fused sampling.  All inputs carry a leading slot axis:
    m (S,n), sigma (S,), B (S,n,n), D (S,n), Z (S,lam,n).  Returns
    (Y, X), each (S, lam, n)."""
    return _sample_call(m, sigma, B, D, Z=Z, bl=bl, bn=bn,
                        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("lam", "bl", "bn", "interpret",
                                    "rng_bits"))
def cma_gen_sample_rng(m, sigma, B, D, seeds, *, lam: int, bl: int = 128,
                       bn: int = 128, interpret: bool = False,
                       rng_bits: str = "counter"):
    """Fused sampling with in-kernel RNG: per-slot ``seeds`` (S, 2) uint32
    replace the (S, lam, n) Z operand.  Returns (Y, X), each (S, lam, n).
    Oracle: ``ref.gen_sample_rng`` (bit-exact Z stream by construction)."""
    return _sample_call(m, sigma, B, D, seeds=seeds, lam=lam, bl=bl, bn=bn,
                        interpret=interpret, rng_bits=rng_bits)


@functools.partial(jax.jit, static_argnames=("bl", "bn", "interpret"))
def cma_gen_sample_eval(m, sigma, B, D, Z, scale, shift, fopt, mode, valid,
                        *, bl: int = 128, bn: int = 128,
                        interpret: bool = False):
    """Eval-fused sampling: the separable fid (per-slot SepCoeffs rows
    ``scale``/``shift`` (S, n), scalars ``fopt``/``mode``/``valid`` (S,))
    is evaluated in the epilogue; returns (Y, F) — X never leaves VMEM.
    Oracle: ``ref.gen_sample_eval``."""
    return _sample_call(m, sigma, B, D, Z=Z,
                        sep=(scale, shift, fopt, mode, valid),
                        bl=bl, bn=bn, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("lam", "bl", "bn", "interpret",
                                    "rng_bits"))
def cma_gen_sample_rng_eval(m, sigma, B, D, seeds, scale, shift, fopt, mode,
                            valid, *, lam: int, bl: int = 128, bn: int = 128,
                            interpret: bool = False,
                            rng_bits: str = "counter"):
    """The full residency kernel: seeds → (Y, F).  No host RNG stream, no
    HBM Z, no HBM X — one kernel in, one kernel out per generation."""
    return _sample_call(m, sigma, B, D, seeds=seeds,
                        sep=(scale, shift, fopt, mode, valid), lam=lam,
                        bl=bl, bn=bn, interpret=interpret, rng_bits=rng_bits)


def _z_kernel(seeds_ref, z_ref, *, bl: int, bn: int, z_dtype):
    from repro.kernels import ref as _ref
    s, l, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows = (jax.lax.broadcasted_iota(jnp.uint32, (bl, bn), 0)
            + (l * bl).astype(jnp.uint32))
    cols = (jax.lax.broadcasted_iota(jnp.uint32, (bl, bn), 1)
            + (k * bn).astype(jnp.uint32))
    z_ref[0] = _ref.threefry_normal(seeds_ref[s, 0], seeds_ref[s, 1],
                                    rows, cols, z_dtype).astype(z_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("lam", "n", "dtype", "bl", "bn",
                                    "interpret"))
def cma_sample_z_rng(seeds, *, lam: int, n: int, dtype, bl: int = 128,
                     bn: int = 128, interpret: bool = False):
    """Materialize the in-kernel Z stream — the parity surface the bit-exact
    kernel↔ref tests compare (``ref.sample_z_rng``), and the compile probe
    target for ``ops._rng_kernel_supported``."""
    seeds = jnp.asarray(seeds, jnp.uint32)
    S = seeds.shape[0]
    kdt = _kernel_dtype(dtype, interpret)
    bl = _round_block(lam, bl)
    bn = _round_block(n, bn)
    lp, np_ = -(-lam // bl) * bl, -(-n // bn) * bn
    Z = pl.pallas_call(
        functools.partial(_z_kernel, bl=bl, bn=bn, z_dtype=kdt),
        grid=(S, lp // bl, np_ // bn),
        in_specs=[_smem((S, 2))],
        out_specs=_block((1, bl, bn), lambda s, l, k: (s, l, k)),
        out_shape=jax.ShapeDtypeStruct((S, lp, np_), kdt),
        interpret=interpret,
    )(seeds)
    return Z[:, :lam, :n].astype(dtype)


# ---------------------------------------------------------------------------
# update megakernel
# ---------------------------------------------------------------------------

def _update_kernel(coef_ref, y_ref, w_ref, c_ref, b_ref, d_ref, psig_ref,
                   pc_ref, cn_ref, psn_ref, pcn_ref, yw_ref, acc_g, acc_yw,
                   *, n_k: int, n_true: int):
    s, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_yw[...] = jnp.zeros_like(acc_yw)

    y = y_ref[0].astype(jnp.float32)            # (bk, np)
    wv = w_ref[0].astype(jnp.float32)           # (bk, 1)
    ys = jnp.sqrt(wv) * y
    # (np, np) += Y_sᵀ·Y_s — the rank-μ gram chunk on the MXU; the √w
    # factoring keeps the accumulated gram (and C') symmetric by
    # construction, so no 0.5·(C + Cᵀ) repair pass exists anywhere
    acc_g[...] += _dot(ys, ys, ((0,), (0,)))
    acc_yw[...] += jnp.sum(wv * y, axis=0, keepdims=True)  # (1, np)

    @pl.when(k == n_k - 1)
    def _epilogue():
        c_sig, mu_eff = coef_ref[s, 0], coef_ref[s, 1]
        c_c, c_1 = coef_ref[s, 2], coef_ref[s, 3]
        c_mu, chi_n, h_denom = coef_ref[s, 4], coef_ref[s, 5], coef_ref[s, 6]

        b = b_ref[0].astype(jnp.float32)        # (np, np)
        d = d_ref[0].astype(jnp.float32)        # (1, np)
        psig = psig_ref[0].astype(jnp.float32)  # (1, np)
        pc = pc_ref[0].astype(jnp.float32)      # (1, np)
        yw = acc_yw[...]                        # (1, np)

        # whitened step: (y_wᵀ·B / D) · Bᵀ, padded D rows guarded by the max
        t = _dot(yw, b, ((1,), (0,))) / jnp.maximum(d, 1e-30)
        whiten = _dot(t, b, ((1,), (1,)))

        ps_new = (1.0 - c_sig) * psig + jnp.sqrt(
            c_sig * (2.0 - c_sig) * mu_eff) * whiten
        ps_norm = jnp.sqrt(jnp.sum(ps_new * ps_new))
        h_sigma = (ps_norm / h_denom / chi_n
                   < 1.4 + 2.0 / (n_true + 1.0)).astype(jnp.float32)
        pc_new = (1.0 - c_c) * pc + h_sigma * jnp.sqrt(
            c_c * (2.0 - c_c) * mu_eff) * yw
        decay = 1.0 - c_1 - c_mu + (1.0 - h_sigma) * c_1 * c_c * (2.0 - c_c)
        # p_c'·p_c'ᵀ as a K = 1 contraction: the row vector never needs a
        # relayout into a column
        outer = _dot(pc_new, pc_new, ((0,), (0,)))

        c_old = c_ref[0].astype(jnp.float32)    # (np, np)
        c_new = decay * c_old + c_mu * acc_g[...] + c_1 * outer

        cn_ref[0] = c_new.astype(cn_ref.dtype)
        psn_ref[0] = ps_new.astype(psn_ref.dtype)
        pcn_ref[0] = pc_new.astype(pcn_ref.dtype)
        yw_ref[0] = yw.astype(yw_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "interpret"))
def cma_gen_update(C: jnp.ndarray, B: jnp.ndarray, D: jnp.ndarray,
                   p_sigma: jnp.ndarray, p_c: jnp.ndarray, Y: jnp.ndarray,
                   w: jnp.ndarray, coef: jnp.ndarray, *, bk: int = 128,
                   bn: int = 128, interpret: bool = False):
    """Slot-batched fused generation update (oracle: ref.fused_gen_update).

    Shapes (S = slots): C/B (S,n,n); D/p_sigma/p_c (S,n); Y (S,lam,n);
    w (S,lam); coef (S, len(COEF_FIELDS)) f32 per-slot scalars.  Returns
    ``(C_new, p_sigma_new, p_c_new, y_w)``.
    """
    lam, n = Y.shape[-2:]
    dt = C.dtype
    kdt = _kernel_dtype(dt, interpret)
    bk = _round_block(lam, bk)
    bn = _round_block(n, bn)
    lp = -(-lam // bk) * bk
    np_ = -(-n // bn) * bn
    n_k = lp // bk

    def call(C, B, D, p_sigma, p_c, Y, w, coef):
        S = C.shape[0]

        def vec(a):                                 # (S, n) -> (S, 1, np)
            return _pad(jnp.reshape(a, (S, 1, n)), (S, 1, np_), kdt)

        coef = coef.astype(jnp.float32)
        # the h_σ denominator √(1 − (1 − c_σ)^(2·gen)) is a per-slot
        # scalar: computed here, because Mosaic has no powf
        c_sig, gen1 = coef[:, 0], coef[:, 6]
        coef = coef.at[:, 6].set(jnp.sqrt(1.0 - (1.0 - c_sig) ** (2.0 * gen1)))

        mat = _block((1, np_, np_), lambda s, k: (s, 0, 0))
        row = _block((1, 1, np_), lambda s, k: (s, 0, 0))
        C_new, ps_new, pc_new, y_w = pl.pallas_call(
            functools.partial(_update_kernel, n_k=n_k, n_true=n),
            grid=(S, n_k),
            in_specs=[
                _smem((S, len(COEF_FIELDS))),                   # coef
                _block((1, bk, np_), lambda s, k: (s, k, 0)),   # Y
                _block((1, bk, 1), lambda s, k: (s, k, 0)),     # w
                mat,                                            # C
                mat,                                            # B
                row,                                            # D
                row,                                            # p_sigma
                row,                                            # p_c
            ],
            out_specs=(mat, row, row, row),
            out_shape=(jax.ShapeDtypeStruct((S, np_, np_), kdt),
                       jax.ShapeDtypeStruct((S, 1, np_), kdt),
                       jax.ShapeDtypeStruct((S, 1, np_), kdt),
                       jax.ShapeDtypeStruct((S, 1, np_), kdt)),
            scratch_shapes=[pltpu.VMEM((np_, np_), jnp.float32),
                            pltpu.VMEM((1, np_), jnp.float32)],
            interpret=interpret,
        )(coef, _pad(Y, (S, lp, np_), kdt),
          _pad(jnp.reshape(w, (S, lam, 1)), (S, lp, 1), kdt),  # zero w: inert
          _pad(C, (S, np_, np_), kdt), _pad(B, (S, np_, np_), kdt),
          vec(D), vec(p_sigma), vec(p_c))
        return (C_new[:, :n, :n].astype(dt), ps_new[:, 0, :n].astype(dt),
                pc_new[:, 0, :n].astype(dt), y_w[:, 0, :n].astype(dt))

    return _fold_vmap(call, C, B, D, p_sigma, p_c, Y, w, coef)
