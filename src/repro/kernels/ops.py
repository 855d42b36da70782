"""Jitted wrappers that route each hot-spot op to its Pallas kernel or jnp ref.

``impl`` semantics (used across core/ and models/):
  * ``"xla"``     — pure-jnp reference path (ref.py), with the generation
                    step FUSED (ref.fused_gen_update / ref.gen_sample —
                    one gram-family dot per generation).  Default on CPU:
                    XLA already lowers these GEMMs well, and Mosaic kernels
                    cannot compile for the CPU backend.
  * ``"xla_unfused"`` — the pre-PR-4 jnp op soup (separate gram / combine /
                    whiten calls).  Kept as the measured regression baseline
                    (benchmarks/bench_kernels.py) and for trajectory A/B
                    tests; at the per-op level it behaves exactly like
                    ``"xla"``.
  * ``"pallas"``  — the Pallas kernels, compiled by Mosaic (TPU) or executed
                    in interpret mode elsewhere (correctness-equivalent,
                    slow — the interpret path exists for the equivalence
                    tests, not for production CPU runs).
  * ``"pallas_rng"`` — ``"pallas"`` plus in-kernel RNG for the generation
                    sample: Z is drawn inside ``cma_gen_sample_rng`` from a
                    portable threefry2x32 counter stream seeded per slot,
                    so the host-shaped ``fold_in`` stream and the HBM Z
                    operand disappear.  A DIFFERENT (but still
                    counter-based, prefix-stable) stream from the default
                    row-keyed one — trajectories are not comparable across
                    tiers, which is why ``"auto"`` never selects it.  Off
                    TPU the sample falls back to the XLA threefry ref — the
                    bit-exact same stream, so the fallback never changes a
                    trajectory; on TPU a kernel that fails to compile
                    raises.
  * ``"auto"``    — "pallas" on TPU backends, "xla" otherwise.  Never
                    resolves to "pallas_rng": switching the RNG stream is
                    a trajectory-level decision the caller must make
                    explicitly.

``REPRO_KERNEL_IMPL`` (env) overrides the caller's choice globally — handy
for A/B runs of a whole campaign without threading a flag through every
engine config.  It is consulted at TRACE time, so export it before the
first engine call of the process; already-compiled programs keep the impl
they were traced with (tests/conftest.py scrubs it so the suite stays
hermetic).  Unknown values, from either source, raise immediately.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cma_gen import (COEF_FIELDS, cma_gen_sample,
                                   cma_gen_sample_eval, cma_gen_sample_rng,
                                   cma_gen_sample_rng_eval, cma_gen_update,
                                   cma_sample_z_rng)
from repro.kernels.cma_sample import cma_sample
from repro.kernels.cma_update import cma_rank_mu_update

IMPL_CHOICES = ("auto", "xla", "xla_unfused", "pallas", "pallas_rng")


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # cached: jax.default_backend() initializes the backend and takes a
    # platform lock — re-querying it inside every traced op call added
    # measurable per-trace overhead.  The backend cannot change after the
    # first jax computation in a process, so one probe is authoritative.
    return jax.default_backend() == "tpu"


def validate_impl(impl: str) -> str:
    """Membership check without resolution — for config/entry validation."""
    if impl not in IMPL_CHOICES:
        raise ValueError(
            f"unknown impl {impl!r}; expected one of {IMPL_CHOICES}")
    return impl


def resolve_impl(impl: str) -> str:
    validate_impl(impl)             # caller typos raise even under override
    env = os.environ.get("REPRO_KERNEL_IMPL", "").strip()
    if env:
        impl = validate_impl(env)
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    return impl


def use_fused(impl: str) -> bool:
    """Static dispatch for the generation step: fused path unless the caller
    explicitly pinned the pre-PR-4 op soup."""
    return resolve_impl(impl) != "xla_unfused"


def _kernel_tier(impl: str) -> bool:
    """True for every resolved tier that routes through the Pallas kernels
    ("pallas_rng" is "pallas" plus the in-kernel sample RNG — all non-sample
    ops treat the two identically)."""
    return impl in ("pallas", "pallas_rng")


@functools.lru_cache(maxsize=1)
def _rng_kernel_supported() -> bool:
    """One-shot probe: does the in-kernel RNG sample kernel compile and run
    on this backend?  On TPU it makes a tiny real call and lets a Mosaic
    error propagate — a kernel that does not compile is a fault to see,
    not a reason to switch streams in silence.  Everywhere else the answer
    is a static False: the XLA threefry ref IS the bit-exact same stream,
    so the CPU fallback never changes a trajectory and interpret-mode
    kernels stay a test-only surface (they are orders of magnitude too
    slow for production CPU runs)."""
    if not _on_tpu():
        return False
    seeds = jnp.zeros((1, 2), jnp.uint32)
    jax.block_until_ready(
        cma_sample_z_rng(seeds, lam=8, n=128, dtype=jnp.float32))
    return True


def sample_transform(B, D, Z, impl: str = "auto"):
    """Y = Z·diag(D)·Bᵀ (lam, n)."""
    impl = resolve_impl(impl)
    if not _kernel_tier(impl):
        return ref.sample_transform(B, D, Z)
    zero = jnp.zeros((B.shape[0],), Z.dtype)
    one = jnp.ones((), Z.dtype)
    return cma_sample(zero, one, B, D, Z, interpret=not _on_tpu())


def sample_points(m, sigma, B, D, Z, impl: str = "auto"):
    """X = M + σ·B·diag(D)·Z (lam, n) — fused kernel when impl=pallas."""
    impl = resolve_impl(impl)
    if not _kernel_tier(impl):
        return ref.sample_points(m, sigma, B, D, Z)
    return cma_sample(m, sigma, B, D, Z, interpret=not _on_tpu())


def rank_mu_gram(Y, w, impl: str = "auto"):
    """Σ wᵢ yᵢyᵢᵀ — the paper's rank-λ GEMM (eq. 3)."""
    impl = resolve_impl(impl)
    if not _kernel_tier(impl):
        return ref.rank_mu_gram(Y, w)
    n = Y.shape[1]
    zeros = jnp.zeros((n, n), Y.dtype)
    zvec = jnp.zeros((n,), Y.dtype)
    return cma_rank_mu_update(zeros, Y, w, zvec, 0.0, 1.0, 0.0,
                              interpret=not _on_tpu())


# ---------------------------------------------------------------------------
# fused generation step (kernels/cma_gen.py ↔ ref.gen_sample/fused_gen_update)
# ---------------------------------------------------------------------------

def _stacked(*arrays):
    """Add a singleton slot axis to per-slot arrays (kernels are slot-batched)."""
    return tuple(a[None] for a in arrays)


def _megakernel_fits(n: int, dtype) -> bool:
    """VMEM-fit check for the whole-(n,n)-tile update megakernel: ~4 f32
    n² tiles (C, B, gram accumulator, C') plus the dtype-width C/B input
    tiles must fit a 16 MB core."""
    itemsize = jnp.dtype(dtype).itemsize
    tile_bytes = n * n * (4 * 4 + 2 * itemsize)
    return tile_bytes <= 12 * 1024 * 1024


def _sample_fits(n: int, dtype) -> bool:
    """The fused sample kernel only holds chunked tiles — a (np, bn) B
    slab plus three (bl, np) row blocks — so its bound is far looser than
    the update megakernel's whole-matrix one."""
    itemsize = jnp.dtype(dtype).itemsize
    bn = bl = 128
    tile_bytes = n * bn * (4 + itemsize) + 3 * bl * n * (4 + itemsize)
    return tile_bytes <= 12 * 1024 * 1024


def _gen_impl(impl: str, n: int, dtype, fits=_megakernel_fits) -> str:
    """Dispatch for the fused generation ops.  ``"auto"`` silently falls
    back to the fused XLA ref when the kernel's tiles cannot fit VMEM
    instead of failing in Mosaic; an EXPLICIT pallas request — from the
    caller or from the ``REPRO_KERNEL_IMPL`` override — is honored (and
    fails loudly) so kernel work at larger n stays drivable."""
    resolved = resolve_impl(impl)
    env = os.environ.get("REPRO_KERNEL_IMPL", "").strip()
    requested = env if env else impl
    if resolved == "pallas" and requested == "auto" and not fits(n, dtype):
        return "xla"
    return resolved


def _sep_slots(sep, S: int, n: int, dtype):
    """Broadcast a ``bbob.SepCoeffs`` (shared by all slots of a run, or
    already per-slot) to the kernel's per-slot layout."""
    return (jnp.broadcast_to(jnp.asarray(sep.scale, dtype), (S, n)),
            jnp.broadcast_to(jnp.asarray(sep.shift, dtype), (S, n)),
            jnp.broadcast_to(jnp.asarray(sep.f_opt, dtype), (S,)),
            jnp.broadcast_to(jnp.asarray(sep.mode, jnp.int32), (S,)),
            jnp.broadcast_to(jnp.asarray(sep.valid), (S,)))


def _kernel_sample(m, sigma, B, D, zs, *, lam=None, sep=None):
    """The sample kernels behind the four ``gen_sample*`` ops: ``zs`` is Z,
    or the per-slot seeds when ``lam`` is given (in-kernel RNG).  Returns
    (Y, X), or (Y, F) with ``sep``.  Per-slot arrays get a singleton slot
    axis for the kernel.

    Float64 state takes Y from the kernel and forms X = m + σ·Y (and the
    separable fitness) in float64.  The kernels' own X is f32, a point of
    the f32 lattice: its spacing near |x| ≈ 3 (2.4e-7) caps what a float64
    campaign can reach (BBOB f2 at n = 40 stalled near 2e-8 on the chip).
    The O(λn) axpy keeps the float64 mean's precision; the O(λn²) sampling
    GEMM stays in the kernel.  The kernel's f32 X is still written and
    dropped: a Y-only variant of the kernel (one output) inside the S1
    shard_map segment loses its Mosaic config in the TPU compiler's x64
    rewriter, and the v5e compile refuses it."""
    rng = lam is not None
    batched = B.ndim == 3
    if not batched:
        m, B, D, zs = _stacked(m, B, D, zs)
        sigma = jnp.asarray(sigma)[None]
    kw = dict(interpret=not _on_tpu(), **({"lam": lam} if rng else {}))
    if jnp.dtype(m.dtype).itemsize > 4:
        from repro.fitness import bbob
        kernel = cma_gen_sample_rng if rng else cma_gen_sample
        Y, _ = kernel(m, sigma, B, D, zs, **kw)
        X = m[:, None, :] + jnp.asarray(sigma)[:, None, None] * Y
        out = (Y, X if sep is None else bbob.separable_eval(X, sep))
    elif sep is None:
        kernel = cma_gen_sample_rng if rng else cma_gen_sample
        out = kernel(m, sigma, B, D, zs, **kw)
    else:
        kernel = cma_gen_sample_rng_eval if rng else cma_gen_sample_eval
        out = kernel(m, sigma, B, D, zs,
                     *_sep_slots(sep, B.shape[0], B.shape[-1], m.dtype), **kw)
    return tuple(out) if batched else tuple(o[0] for o in out)


def gen_sample(m, sigma, B, D, Z, impl: str = "auto"):
    """Fused sampling: (Y, X) in one pass.

    Slot-batched when ``Z`` carries a leading slot axis (ndim == 3) — the
    stacked-slot ladder programs call this ONCE for all slots; per-slot
    arrays are accepted too (a singleton slot axis is added for the kernel).
    For float64 state the kernel tier forms X in float64 (``_kernel_sample``).
    """
    impl = _gen_impl(impl, Z.shape[-1], Z.dtype, fits=_sample_fits)
    if not _kernel_tier(impl):
        return ref.gen_sample(m, sigma, B, D, Z)
    return _kernel_sample(m, sigma, B, D, Z)


def gen_sample_rng(m, sigma, B, D, seeds, lam: int, impl: str = "auto"):
    """Fused sampling with the in-kernel threefry counter stream: per-slot
    ``seeds`` (S, 2) uint32 replace the (S, lam, n) Z operand, so nothing
    host-shaped (and no HBM Z) exists on the sampled path.  Returns (Y, X).

    The Mosaic kernel runs only when the resolved tier is ``"pallas_rng"``
    AND the one-shot backend probe passes; every other combination takes
    ``ref.gen_sample_rng`` — the bit-exact same stream under jit, so the
    fallback is trajectory-invisible.  Slot-batched like ``gen_sample``.
    """
    impl = _gen_impl(impl, B.shape[-1], B.dtype, fits=_sample_fits)
    if impl == "pallas_rng" and _rng_kernel_supported():
        return _kernel_sample(m, sigma, B, D, seeds, lam=lam)
    return ref.gen_sample_rng(m, sigma, B, D, seeds, lam)


def gen_sample_eval(m, sigma, B, D, Z, sep, impl: str = "auto"):
    """Eval-fused sampling for separable fids: returns (Y, F) with the
    fitness computed in the sample epilogue — X never materializes in HBM
    (for f32 state; float64 state evaluates its float64 X,
    ``_kernel_sample``).  ``sep`` is a ``bbob.SepCoeffs``; on the XLA tiers
    the same algebra runs as ``ref.gen_sample_eval`` (bit-identical to the
    dispatched ``evaluate_dynamic`` on the same X)."""
    impl = _gen_impl(impl, Z.shape[-1], Z.dtype, fits=_sample_fits)
    if not _kernel_tier(impl):
        return ref.gen_sample_eval(m, sigma, B, D, Z, sep)
    return _kernel_sample(m, sigma, B, D, Z, sep=sep)


def gen_sample_rng_eval(m, sigma, B, D, seeds, lam: int, sep,
                        impl: str = "auto"):
    """The full residency path: seeds → (Y, F) in one kernel — in-kernel
    RNG plus eval-fused epilogue.  Kernel only under a probed
    ``"pallas_rng"``; otherwise the XLA threefry ref with the fused
    separable eval (same stream, same fitness algebra)."""
    impl = _gen_impl(impl, B.shape[-1], B.dtype, fits=_sample_fits)
    if impl == "pallas_rng" and _rng_kernel_supported():
        return _kernel_sample(m, sigma, B, D, seeds, lam=lam, sep=sep)
    return ref.gen_sample_rng_eval(m, sigma, B, D, seeds, lam, sep)


def gen_update(C, B, D, p_sigma, p_c, Y, w, coef, impl: str = "auto"):
    """Fused O(n²) generation update — C/B/D read from HBM once.

    ``coef`` is a dict-like of per-slot scalars with the fields named in
    ``cma_gen.COEF_FIELDS`` (``gen1`` = 1-based generation counter as a
    float).  Slot-batched when ``C`` carries a leading slot axis; returns
    ``(C_new, p_sigma_new, p_c_new, y_w)`` with matching batching.

    The megakernel computes in f32 regardless of the state dtype (the MXU
    has no f64 path; compiled for TPU, f64 operands cross the kernel
    boundary as f32 and the outputs are cast back); f64 campaigns that
    need strict double-precision trajectories should pin ``impl="xla"``.
    Under ``impl="auto"``, problems whose whole-matrix tiles exceed VMEM
    fall back to the fused XLA ref (``_megakernel_fits``).
    """
    impl = _gen_impl(impl, C.shape[-1], C.dtype)
    if not _kernel_tier(impl):
        fn = ref.fused_gen_update
        args = (coef["c_sigma"], coef["mu_eff"], coef["c_c"], coef["c_1"],
                coef["c_mu"], coef["chi_n"], coef["gen1"])
        if C.ndim == 3:
            return jax.vmap(fn)(C, B, D, p_sigma, p_c, Y, w, *args)
        return fn(C, B, D, p_sigma, p_c, Y, w, *args)
    batched = C.ndim == 3
    if not batched:
        C, B, Y = (a[None] for a in (C, B, Y))
        D, p_sigma, p_c, w = (a[None] for a in (D, p_sigma, p_c, w))
    cs = jnp.stack([jnp.broadcast_to(
        jnp.asarray(coef[f], jnp.float32), C.shape[:1])
        for f in COEF_FIELDS], axis=1)
    out = cma_gen_update(C, B, D, p_sigma, p_c, Y, w, cs,
                         interpret=not _on_tpu())
    return out if batched else tuple(o[0] for o in out)


def covariance_combine(C, gram, p_c, decay, c_mu, c_1, impl: str = "auto"):
    """decay·C + c_μ·gram + c₁·p_c p_cᵀ (cheap epilogue; always jnp).

    The fused path (kernel computing gram+epilogue in one pass) is
    ``rank_mu_update`` below — used when the caller still has Y at hand.
    """
    return ref.covariance_combine(C, gram, p_c, decay, c_mu, c_1)


def rank_mu_update(C, Y, w, p_c, decay, c_mu, c_1, impl: str = "auto"):
    """Fully fused covariance adaptation: one HBM read+write of C."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.rank_mu_update(C, Y, w, p_c, decay, c_mu, c_1)
    return cma_rank_mu_update(C, Y, w, p_c, decay, c_mu, c_1,
                              interpret=not _on_tpu())


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "auto"):
    """GQA flash attention (see kernels/flash_attention.py)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.flash_attention(q, k, v, causal=causal, window=window)
    from repro.kernels.flash_attention import flash_attention as fa
    return fa(q, k, v, causal=causal, window=window, interpret=not _on_tpu())


def wkv6(r, k, v, logw, u, impl: str = "auto"):
    """Chunked RWKV-6 WKV (see kernels/rwkv6_wkv.py)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.wkv6(r, k, v, logw, u)
    from repro.kernels.rwkv6_wkv import wkv6_forward
    return wkv6_forward(r, k, v, logw, u, interpret=not _on_tpu())
