"""Sharded evaluation dispatch — the TPU analogue of the paper's MPI
scatter/gather of the λ function evaluations (§3.2.1).

Differences from the paper (DESIGN.md §2):
  * points are sampled device-locally (identical distribution, zero scatter
    traffic) instead of centrally sampled + scattered;
  * fitnesses are exchanged with one small ``all_gather``;
  * straggler mitigation: an evaluation may be reported as failed/late by the
    ``valid`` mask — it enters the rank computation as +inf, receives zero
    recombination weight and the remaining weights are renormalized.  This is
    the ES analogue of gradient-skipping and costs no synchronization.

All functions here are written from the *per-device view* and are agnostic to
how that view is produced: ``shard_map`` on a real mesh, or nested ``vmap``
with the same axis names (the simulation path used by unit tests — bit-exact
same program).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


AxisNames = Sequence[str]


class FusableEval:
    """A fitness closure that ALSO carries the separable coefficients of its
    (traced-fid) evaluation — the per-fid ``fusable`` capability flag of the
    dispatch menu, in object form.

    Calling it behaves exactly like the wrapped closure (the two-program
    fallback, and what every non-fused engine path keeps using); engines
    that can fuse (``ladder._slots_fused_update``) detect the ``.sep``
    payload via ``getattr(fitness_fn, "sep", None)`` and route sampling
    through the eval-fused kernel ops instead, so X never materializes.
    Built by ``bbob.fusable_fitness`` — only when the whole static fid menu
    is separable.
    """

    __slots__ = ("fn", "sep")

    def __init__(self, fn, sep):
        self.fn = fn
        self.sep = sep

    def __call__(self, X):
        return self.fn(X)


def shard_map_compat(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def flat_index(axes: AxisNames) -> jnp.ndarray:
    """Linearized device index over (possibly multiple) named axes."""
    return jax.lax.axis_index(tuple(axes))


def axis_size(axes: AxisNames) -> int:
    out = 1
    for a in axes:
        out *= int(jax.lax.axis_size(a))
    return out


def all_gather_flat(x: jnp.ndarray, axes: AxisNames) -> jnp.ndarray:
    """all_gather over (possibly multiple) named axes, flattened to one
    leading dim of size P in row-major (= ``flat_index``) order."""
    y = x
    for a in reversed(tuple(axes)):
        y = jax.lax.all_gather(y, a)
    return y.reshape((-1,) + x.shape)


def local_ranks(f_local: jnp.ndarray, f_all_flat: jnp.ndarray,
                my_flat_base: jnp.ndarray) -> jnp.ndarray:
    """Rank of each local fitness among a (masked) flat fitness vector.

    ``f_all_flat`` holds the full descent population's fitnesses with
    non-members / failed evaluations set to +inf.  Ties are broken by the
    global slot index so the ranking is a strict total order (matching a
    centralized argsort).
    """
    lam_local = f_local.shape[0]
    my_idx = my_flat_base + jnp.arange(lam_local)
    all_idx = jnp.arange(f_all_flat.shape[0])
    smaller = f_all_flat[None, :] < f_local[:, None]
    tie = (f_all_flat[None, :] == f_local[:, None]) & (
        all_idx[None, :] < my_idx[:, None])
    finite = jnp.isfinite(f_all_flat)[None, :]
    return jnp.sum((smaller | tie) & finite, axis=1)


def masked_fitness(f: jnp.ndarray, valid: jnp.ndarray | None) -> jnp.ndarray:
    """Apply the straggler/failure mask: invalid evaluations rank last."""
    if valid is None:
        return f
    return jnp.where(valid, f, jnp.inf)
