"""The comparison that decides ``correct``: what the timed path produced,
member by member, against the plain float64 reference on the host.

A member is one IPOP-CMA-ES run (a campaign member or a service job): its
key, objective, the descents of its restart ladder as the program recorded
them generation by generation, and its answer (best x, best f).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from reference import bbob_ref, cmaes_ref

TARGET = 1e-8           # the f1 target, f − f_opt (the configurations')
ELLIPSOIDS = (2, 10)    # condition 1e6: only a learned C makes headway
MIN_LEARNING_GENS = 256  # a descent's C has learned about a decade by then


@dataclasses.dataclass
class Descent:
    k: int                  # rung; λ = 2^k·λ_start; also the incarnation
    lam: int
    gens: np.ndarray        # (T,) generation within the descent, 1-based
    fevals: np.ndarray      # (T,) evaluations within the descent
    best_f: np.ndarray      # (T,) best f so far within the descent


@dataclasses.dataclass
class Member:
    key: np.ndarray         # (2,) uint32 base key
    fid: int
    inst: int
    n: int
    lam_start: int
    kmax: int
    budget: int
    sigma0: float
    domain: tuple           # the search box x0 is drawn in
    descents: List[Descent]
    best_x: np.ndarray
    best_f: float
    total_fevals: int
    finished: bool          # the program is done with this member

    @property
    def ref(self) -> bbob_ref.Instance:
        return bbob_ref.instance(self.fid, self.n, self.inst)

    def hit_evals(self, target: float) -> Optional[int]:
        """Evaluations after which the member's best f first lay at or
        below ``target`` (None: not yet)."""
        base = 0
        for d in self.descents:
            at = np.nonzero(d.best_f <= target)[0]
            if at.size:
                return int(base + d.fevals[at[0]])
            base += int(d.fevals[-1]) if d.fevals.size else 0
        return None


def descents_from_rows(ran, k_idx, gen, fevals, best_f,
                       lam_start: int) -> List[Descent]:
    """Slice one member's per-generation rows (time order) into descents,
    one per rung, the way the ladder records them."""
    ran = np.asarray(ran, bool)
    out = []
    k_idx = np.asarray(k_idx)
    for k in sorted(set(int(x) for x in k_idx[ran])):
        idx = np.nonzero(ran & (k_idx == k))[0]
        out.append(Descent(k=k, lam=(2 ** k) * lam_start,
                           gens=np.asarray(gen)[idx].astype(np.int64),
                           fevals=np.asarray(fevals)[idx].astype(np.int64),
                           best_f=np.asarray(best_f)[idx].astype(np.float64)))
    return out


def search_box(cfg) -> tuple:
    """(domain, σ0) of a configuration: σ0 = sigma0_frac × the box's width."""
    lo, hi = (float(x) for x in cfg["domain"])
    return (lo, hi), float(cfg["sigma0_frac"]) * (hi - lo)


def _rel(a: float, b: float) -> float:
    if not (np.isfinite(a) and np.isfinite(b)):
        return float("inf")
    return abs(a - b) / max(1.0, abs(b))


def fitness_gap(members: Sequence[Member], dtype: str = "float64"
                ) -> Optional[float]:
    """Widest gap between a member's reported best f and its best x
    evaluated by the reference, relative to max(1, |f|).  With ``dtype``
    float32 the reported f is replaced by the reference's own float32
    evaluation: the reading of the reference put in the program's place
    one precision down."""
    gaps = []
    for m in members:
        if not m.descents:
            continue
        want = float(bbob_ref.evaluate(m.ref, m.best_x)[0])
        got = (m.best_f if dtype == "float64" else
               float(bbob_ref.evaluate(m.ref, m.best_x, np.float32)[0]))
        gaps.append(_rel(got, want))
    return max(gaps) if gaps else None


def first_generation_gap(members: Sequence[Member],
                         points=np.float64) -> Optional[float]:
    """Every descent's first generation: the best f the program recorded
    against the best of the reference's λ points x0 + σ0·z from the same
    key, incarnation and rung (C = I there, so no eigenbasis enters).
    ``points`` float32 reads the reference's own points formed in float32
    against its float64 ones instead."""
    gaps = []
    for m in members:
        for d in m.descents:
            ref = cmaes_ref.first_generation(m.key, m.ref, d.lam, d.k,
                                             m.sigma0, m.domain)
            got = (float(d.best_f[0]) if points is np.float64 else
                   cmaes_ref.first_generation(m.key, m.ref, d.lam, d.k,
                                              m.sigma0, m.domain, points))
            gaps.append(_rel(got, ref))
    return max(gaps) if gaps else None


def ladder_violations(members: Sequence[Member]) -> int:
    """Breaks of the restart ladder's bookkeeping: rungs 0, 1, 2, … in
    order, λ = 2^k·λ_start evaluations per generation, generations
    1, 2, … within a descent, best f never rising within a descent, the
    member's evaluations the sum of its descents' and within its budget."""
    bad = 0
    for m in members:
        total = 0
        for j, d in enumerate(m.descents):
            T = d.gens.size
            bad += int(d.k != j) + int(d.lam != (2 ** d.k) * m.lam_start)
            bad += int(np.sum(d.gens != np.arange(1, T + 1)))
            bad += int(np.sum(d.fevals != d.lam * d.gens))
            bad += int(np.sum(np.diff(d.best_f) > 0))
            total += int(d.fevals[-1]) if T else 0
        bad += int(len(m.descents) > m.kmax + 1)
        bad += int(total != m.total_fevals) + int(total > m.budget)
    return bad


def f1_hit_ratio(members: Sequence[Member]) -> Optional[float]:
    """For the f1 members: evaluations the program took to reach
    f_opt + 1e-8, over those a plain CMA-ES descent from the same key took.
    A finished member that never got there reads infinity; an unfinished
    one is left out until it has spent twice the reference's count."""
    ratios = []
    for m in members:
        if m.fid != 1:
            continue
        ref = cmaes_ref.descent_hit(m.key, m.ref, m.lam_start, m.sigma0,
                                    m.ref.f_opt + TARGET, m.budget, m.domain)
        if ref is None:
            continue
        got = m.hit_evals(m.ref.f_opt + TARGET)
        if got is not None:
            ratios.append(got / ref)
        elif m.finished or m.total_fevals >= 2 * ref:
            ratios.append(float("inf"))
    return max(ratios) if ratios else None


@dataclasses.dataclass
class StateSample:
    """One member's state at a segment boundary and what the program
    recorded for the next generation."""
    member: Member
    k: int
    incarnation: int
    gen: int
    m: np.ndarray
    sigma: float
    C: np.ndarray
    B: np.ndarray
    D: np.ndarray
    best_before: float      # the descent's best f at the boundary
    best_after: Optional[float]  # the recorded best after the next generation
    final: bool = False     # the campaign's last boundary


def segment_sample_gap(samples: Sequence[StateSample],
                       points=np.float64) -> Optional[float]:
    """The first generation after a boundary, sampled from the boundary
    state as the program holds it (x = m + σ·B·(D∘z)): the best f the
    program recorded against min(best before, the reference's best of
    those λ points).  ``points`` float32 reads the reference's own points
    formed in float32 in the program's place."""
    gaps = []
    for s in samples:
        if s.best_after is None:
            continue
        lam = (2 ** s.k) * s.member.lam_start
        args = (s.member.key, s.member.ref, lam, s.incarnation, s.gen, s.m,
                s.sigma, s.B, s.D)
        ref = cmaes_ref.generation_from_state(*args)
        got = (s.best_after if points is np.float64 else
               min(s.best_before,
                   cmaes_ref.generation_from_state(*args, points=points)))
        gaps.append(_rel(got, min(s.best_before, ref)))
    return max(gaps) if gaps else None


def eigen_residual(samples: Sequence[StateSample]) -> Optional[float]:
    """max ‖C·B − B·diag(D²)‖_F / ‖C‖_F over the boundary states."""
    out = []
    for s in samples:
        C, B, D = (np.asarray(a, np.float64) for a in (s.C, s.B, s.D))
        out.append(np.linalg.norm(C @ B - B * (D ** 2)[None, :])
                   / np.linalg.norm(C))
    return float(max(out)) if out else None


def eigen_orthogonality(samples: Sequence[StateSample]) -> Optional[float]:
    """max |BᵀB − I| over the boundary states."""
    out = [float(np.max(np.abs(np.asarray(s.B, np.float64).T
                               @ np.asarray(s.B, np.float64)
                               - np.eye(s.B.shape[0])))) for s in samples]
    return max(out) if out else None


def best_x_float32_share(members: Sequence[Member]) -> Optional[float]:
    """Share of the members' best-x coordinates that a float32 holds
    exactly.  Points formed as m + σ·Y in float64 almost never land on a
    float32 (2^-29 a coordinate); points formed in float32 always do."""
    xs = [np.asarray(m.best_x, np.float64) for m in members if m.descents]
    if not xs:
        return None
    x = np.concatenate(xs)
    return float(np.mean(x.astype(np.float32).astype(np.float64) == x))


def learned_decades(ins: bbob_ref.Instance, C: np.ndarray) -> float:
    """Decades of the ellipsoid's condition number that C has taken out:
    log10 κ(H) − log10 κ(C^½·H·C^½) (0 for C = I; −inf for a singular
    C)."""
    H = bbob_ref.ellipsoid_hessian(ins)
    e, V = np.linalg.eigh(0.5 * (C + C.T))
    S = (V * np.sqrt(np.maximum(e, 0.0))[None, :]) @ V.T
    ev = np.linalg.eigvalsh(S @ H @ S)
    eh = np.linalg.eigvalsh(H)
    with np.errstate(divide="ignore"):
        return float(np.log10(eh[-1] / eh[0]) - np.log10(ev[-1] / ev[0]))


def covariance_learning_gap(samples: Sequence[StateSample],
                            **fault) -> Optional[float]:
    """How far the covariance update learned, against the reference:
    |1 − Σ learned(program's C) / Σ learned(reference's C)| over the f2
    and f10 members at each campaign's last boundary whose descent has run
    ``MIN_LEARNING_GENS`` generations or more.  The reference is the plain
    descent of the same key, incarnation and λ, run as many generations.
    The trajectories part after the first generation, so each member's
    learning differs by chance; the sum over the members is steady.
    ``fault`` (``params_for``, ``learn_C``: see ``cmaes_ref.descent``)
    puts a faulty reference in the program's place."""
    got = want = 0.0
    for s in samples:
        m = s.member
        if not s.final or m.fid not in ELLIPSOIDS or s.gen < MIN_LEARNING_GENS:
            continue
        lam = (2 ** s.k) * m.lam_start
        ref = cmaes_ref.covariance_after(m.key, m.ref, lam, s.incarnation,
                                         m.sigma0, s.gen, m.domain)
        C = (np.asarray(s.C, np.float64) if not fault else
             cmaes_ref.covariance_after(m.key, m.ref, lam, s.incarnation,
                                        m.sigma0, s.gen, m.domain, **fault))
        got += learned_decades(m.ref, C)
        want += learned_decades(m.ref, ref)
    return abs(1.0 - got / want) if want > 0 else None


def evaluate(members: Sequence[Member], samples: Sequence[StateSample],
             limits: Dict[str, Dict[str, float]]) -> Dict[str, Dict]:
    """Each number compared, with its limit, for the checks the
    configuration names."""
    fns = {
        "fitness_gap": lambda: fitness_gap(members),
        "first_gen_gap": lambda: first_generation_gap(members),
        "ladder_violations": lambda: float(ladder_violations(members)),
        "f1_hit_ratio": lambda: f1_hit_ratio(members),
        "cov_learning_gap": lambda: covariance_learning_gap(samples),
        "best_x_float32_share": lambda: best_x_float32_share(members),
        "segment_sample_gap": lambda: segment_sample_gap(samples),
        "eigen_residual": lambda: eigen_residual(samples),
        "eigen_orthogonality": lambda: eigen_orthogonality(samples),
    }
    return {name: {"value": fns[name](), "limit": float(lim["limit"])}
            for name, lim in limits.items()}
