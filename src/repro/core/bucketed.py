"""Rung-bucketed campaign execution — work proportional to the active rung.

The λ_max-padded ladder engine (core/ladder.py) compiles ONE program whose
every generation samples, evaluates and Gram-reduces λ_max points even when
the live rung needs only λ_start — a 2^kmax× (16× at paper defaults)
overcount of sampling, evaluation and rank-μ GEMM work on the first rung,
and a λ_max-padded masked tail once a member's ladder is exhausted.  This
module replaces the single program with a small FAMILY of per-rung-bucket
programs plus a host-side segment driver:

* **Bucket programs** — bucket k pads to λ_k = 2^k·λ_start
  (``params.bucket_config``) and carries the rung-0..k parameter stack
  padded to that width.  Each program runs a fixed-length *segment*
  (``seg_blocks`` eigen blocks of the nested scan — see
  ``ladder.scan_eigen_blocks``) over the FULL campaign batch, jitted and
  vmapped exactly like ``LadderEngine.campaign_runner``; shapes are cached,
  so the whole campaign compiles at most once per bucket
  (``compiles ≤ kmax_exp+1``, asserted in tests/test_bucketed.py).
* **Parking** — inside a bucket-k program, members whose rung index exceeds
  k (their in-place restart outgrew the bucket) or whose ladder
  retired/budget died are parked: ``ran=False``, state frozen
  (``slots_gen_step(bucket_cap=k)``).
* **Segment driver** (``run_campaign_bucketed``) — between device-resident
  segments it pulls only the (B,) rung indices / active flags, re-buckets
  members as their rungs advance (members only move up, so it always runs
  the lowest occupied bucket next), and stops as soon as every member has
  retired or exhausted its budget — no λ_max-padded masked tail.

Trajectory equivalence with the padded engine holds when the eigen cadence
is unchanged (``eigen_interval == 1``): sampling is row-keyed
(``cmaes.sample_population``), so a member sees the identical z-stream, rank
weights and Gram reductions no matter which bucket executes it — while each
bucket pays RNG proportional to its own width, not λ_max's.  The compiled
programs differ in shape, so XLA's fusion choices leave ~1e-13 seed noise
that chaos can amplify late in a descent — the same tolerance the host-loop
baseline comparison carries (tests/test_ladder.py).  With
``eigen_interval > 1`` the nested-scan eigen cadence is segment-local rather
than campaign-global and the engines are ECDF-equivalent instead
(tests/test_bucketed.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ladder
from repro.core.params import bucket_config, default_max_iter, ladder_params
from repro.fitness import bbob

# host phases (``obs.Tracer.phase``) also land on the JAX profiler's trace
obs.set_annotator(jax.profiler.TraceAnnotation)


@dataclasses.dataclass
class BucketedLadderEngine:
    """Per-rung-bucket compiled programs over a shared ladder state.

    Mirrors ``LadderEngine``'s sequential schedule (one slot walking the
    rungs) and its key schedule exactly; ``schedule="concurrent"`` keeps all
    rungs live at once and therefore has no narrow bucket to exploit.
    """

    n: int
    lam_start: int = 12
    kmax_exp: int = 4
    max_evals: int = 200_000
    domain: Tuple[float, float] = (-5.0, 5.0)
    sigma0_frac: float = 0.25
    impl: str = "auto"                  # kernel dispatch — see kernels/ops.py
    dtype: str = "float64"
    eigen_interval: Optional[int] = None
    seg_blocks: Optional[int] = None    # segment length cap in eigen blocks
    policy: str = "cover"               # "cover" | "min" (see run_campaign_bucketed)
    overlap: bool = False               # double-buffered segment dispatch

    def __post_init__(self):
        if self.policy not in ("cover", "min"):
            raise ValueError(f"unknown policy {self.policy!r}")
        if self.seg_blocks is None and self.policy == "cover":
            # cover tracks the max live rung, so segments must stay short
            # enough for the covering bucket to follow climbers between
            # host syncs (a sync is ~ms; a 64-block segment is ~100ms)
            self.seg_blocks = 64
        # the λ_max-padded engine supplies cfg/sparams/key schedule/init —
        # buckets only narrow the padding.
        self.full = ladder.LadderEngine(
            n=self.n, lam_start=self.lam_start, kmax_exp=self.kmax_exp,
            schedule="sequential", max_evals=self.max_evals,
            domain=self.domain, sigma0_frac=self.sigma0_frac, impl=self.impl,
            dtype=self.dtype, eigen_interval=self.eigen_interval)
        self.lam_max = self.full.lam_max
        self.interval = int(self.full.cfg.eigen_interval)
        self.bucket_cfgs = []
        self.bucket_sparams = []
        for k in range(self.kmax_exp + 1):
            lam_k = (2 ** k) * self.lam_start
            cfg_k = bucket_config(self.full.cfg, lam_k)
            self.bucket_cfgs.append(cfg_k)
            self.bucket_sparams.append(
                ladder_params(cfg_k, self.lam_start, k))
        self._runner_cache: dict = {}
        self._init_runner = jax.jit(jax.vmap(self.full.init_carry))

    # -- sizing ---------------------------------------------------------------
    def bucket_seg_gens(self, k: int, need_gens: Optional[int] = None) -> int:
        """Segment length (generations) of bucket k: whole eigen blocks,
        capped by what a rung-k descent can possibly still run — its MaxIter
        allowance, the budget's generation count at λ_k, and (when the
        driver knows it) the cohort's actual remaining-budget need.  The
        block count is rounded UP to a power of two so segment shapes come
        from a tiny menu and the jit cache stays hot across campaigns."""
        lam_k = (2 ** k) * self.lam_start
        most = max(1, self.max_evals // lam_k)
        if self.policy == "min":
            # a bucket-k cohort is all ON rung k, so its descents cannot
            # outlive rung k's own MaxIter allowance; under "cover" lower-rung
            # members keep walking the ladder inside the same segment
            most = min(most, default_max_iter(self.n, lam_k))
        if need_gens is not None:
            most = min(most, max(1, int(need_gens)))
        blocks = -(-most // self.interval)
        blocks = 1 << (blocks - 1).bit_length()          # next power of two
        if self.seg_blocks is not None:
            blocks = min(blocks, max(1, int(self.seg_blocks)))
        return blocks * self.interval

    def init_carry(self, base_key: jax.Array) -> ladder.LadderCarry:
        return self.full.init_carry(base_key)

    # -- one bucket segment as a pure scanned program --------------------------
    def segment_scan(self, k: int, base_key: jax.Array, fitness_fn: Callable,
                     carry: ladder.LadderCarry, seg_gens: int,
                     max_evals=None,
                     ) -> Tuple[ladder.LadderCarry, ladder.LadderTrace]:
        """``max_evals`` overrides the engine budget for this member — it may
        be a *traced* scalar, which is how the campaign service runs
        heterogeneous per-job budgets through one compiled bucket program
        (service/server.py vmaps it as a per-row operand)."""
        cfg_k = self.bucket_cfgs[k]
        sparams_k = self.bucket_sparams[k]
        budget = self.max_evals if max_evals is None else max_evals

        def step_fn(c, eigen):
            return ladder.slots_gen_step(
                cfg_k, sparams_k, c, base_key, fitness_fn,
                max_evals=budget, kmax_exp=self.kmax_exp,
                schedule="sequential", domain=self.domain, impl=self.impl,
                eigen=eigen, bucket_cap=k)

        return ladder.scan_eigen_blocks(step_fn, carry, self.interval,
                                        int(seg_gens) // self.interval)

    def segment_runner(self, k: int, branch_fids: Tuple[int, ...],
                       seg_gens: int):
        """Jitted vmapped segment program, cached per (bucket, length, fids)
        — plus the trace-time eval-fusion toggle (``REPRO_EVAL_FUSION``)."""
        key = (int(k), int(seg_gens), tuple(branch_fids),
               bbob.eval_fusion_enabled())
        if key not in self._runner_cache:
            def run_one(base_key, inst, carry):
                def fit(X):
                    return bbob.evaluate_dynamic(inst, X, branch_fids)
                return self.segment_scan(
                    k, base_key, bbob.fusable_fitness(inst, branch_fids, fit),
                    carry, seg_gens)
            self._runner_cache[key] = jax.jit(jax.vmap(run_one))
        return self._runner_cache[key]

    def compiles(self) -> int:
        total = 0
        for fn in self._runner_cache.values():
            cs = getattr(fn, "_cache_size", None)
            total += int(cs()) if callable(cs) else 1
        return total


@dataclasses.dataclass
class BucketedCampaignResult(ladder.CampaignResult):
    """Campaign result plus the driver's per-bucket execution record.

    ``trace`` concatenates the per-segment traces along time: each member's
    generations appear in its own chronological order (the driver runs one
    bucket at a time and members only move upward), with parked steps as
    ``ran=False`` rows — every ``CampaignResult`` consumer (``hit_evals``,
    the ipop slicer) already masks on ``ran``.
    """

    segments: List[dict] = dataclasses.field(default_factory=list)
    bucket_wall_s: Dict[int, float] = dataclasses.field(default_factory=dict)
    useful_evals: int = 0
    padded_evals: int = 0

    def padding_waste(self) -> float:
        """Padded-to-useful evaluation ratio actually paid on device."""
        return self.padded_evals / max(self.useful_evals, 1)


def _useful_evals_per_rung(trace: ladder.LadderTrace, lam_start: int,
                           kmax_exp: int) -> Dict[int, int]:
    """Σ over executed generations of that generation's true λ, keyed by rung."""
    ran = np.asarray(trace.ran)
    k_idx = np.asarray(trace.k_idx)
    out = {}
    for k in range(kmax_exp + 1):
        gens_k = int(np.sum(ran & (k_idx == k)))
        out[k] = gens_k * (2 ** k) * lam_start
    return out


def padding_report(trace: ladder.LadderTrace, lam_start: int, kmax_exp: int,
                   padded_lam: int) -> dict:
    """Padded-vs-useful evaluation accounting of a fixed-width campaign trace.

    Every (member, step, slot) cell of a ``padded_lam``-wide program pays
    ``padded_lam`` evaluation rows on device (masked tail steps included);
    the useful count is each executed generation's true rung λ.  Returns
    per-rung useful counts plus the overall waste ratio — the number the
    rung-bucketed driver exists to shrink (benchmarks/bench_ladder.py).
    """
    useful = _useful_evals_per_rung(trace, lam_start, kmax_exp)
    padded = int(np.asarray(trace.ran).size) * int(padded_lam)
    total_useful = int(sum(useful.values()))
    return {
        "useful_evals": total_useful,
        "padded_evals": padded,
        "waste": round(padded / max(total_useful, 1), 3),
        "useful_per_rung": {str(k): v for k, v in useful.items()},
    }


def pull_schedule(carry: ladder.LadderCarry):
    """The driver's per-segment host sync: ONE batched transfer of the four
    scheduling arrays — (B,) rung indices, active flags, budget counters and
    member bests — instead of four separate blocking ``np.asarray`` pulls
    (each of which paid its own device round-trip).  Returns 1-d np arrays.

    The mesh engine substitutes a ``process_allgather``-based puller with the
    same signature (distributed/mesh_engine.py), so the re-bucketing loop is
    identical on one device and on a sharded campaign mesh.
    """
    k_idx, active, fevals, best_f = jax.device_get(
        (carry.k_idx[..., 0], carry.active[..., 0],
         carry.total_fevals, carry.best_f))
    return (np.atleast_1d(k_idx), np.atleast_1d(active),
            np.atleast_1d(fevals), np.atleast_1d(best_f))


def next_bucket(engine: BucketedLadderEngine, k_idx: np.ndarray,
                active: np.ndarray, fevals: np.ndarray,
                seg_len: Dict[int, int], budgets=None):
    """One re-bucketing decision — THE scheduling invariant shared by
    ``drive_segments``, the mesh engine's per-island loops
    (distributed/mesh_engine.py) and the campaign service's lane boundaries
    (service/server.py), so the three can never silently diverge.

    Returns ``(live, k)`` with ``k is None`` when no member can pay for
    another generation.  Policy ``"min"`` picks the narrowest occupied rung
    (members only move up the ladder, so the lowest occupied bucket is
    work-conserving — least padded rows); ``"cover"`` picks the widest LIVE
    rung (every live member executes every step, fewest total scan steps —
    best on host-dispatch-bound backends).  On a bucket's first open its
    segment length is sized for what the cohort can still possibly run and
    recorded in ``seg_len`` (in place) — ONE length per bucket keeps
    ``compiles ≤ #buckets``.

    ``budgets`` (optional (B,) array) replaces the engine-wide ``max_evals``
    with per-member budgets — the host mirror of the traced budget operand
    the service threads through ``segment_scan``; the liveness rule here must
    match the device-side gate in ``ladder.slots_gen_step`` exactly.
    """
    cap = engine.max_evals if budgets is None else np.asarray(budgets)
    lam_cur = engine.lam_start * (2 ** k_idx)
    live = active & (fevals + lam_cur <= cap)
    if not live.any():
        return live, None
    if engine.policy == "min":
        k = int(k_idx[live].min())
    else:
        k = int(k_idx[live].max())
    if k not in seg_len:
        cohort = live if engine.policy == "cover" else live & (k_idx == k)
        need = int(np.max((cap - fevals)[cohort] // lam_cur[cohort]))
        seg_len[k] = engine.bucket_seg_gens(k, need_gens=need)
    return live, k


def drive_segments(engine: BucketedLadderEngine, carry: ladder.LadderCarry,
                   dispatch: Callable, max_segments: int = 10_000,
                   pull: Optional[Callable] = None, budgets=None,
                   overlap: Optional[bool] = None, supervisor=None,
                   parent: Optional[obs.Span] = None):
    """The host-side re-bucketing loop shared by campaign and single runs.

    ``dispatch(k, seg_gens, carry) -> (carry, trace)`` runs one jitted
    segment of bucket ``k``.  Between segments only the (B,) rung indices,
    active flags, budget counters and member bests cross the device boundary
    — one batched ``pull`` (default ``pull_schedule``; the mesh engine passes
    a ``process_allgather`` variant); per-segment traces stay device-resident
    (``concat_traces`` gathers them).  Returns ``(carry, seg_traces,
    segments, bucket_wall)``.

    ``overlap`` (default ``engine.overlap``) double-buffers the carries: the
    next segment is dispatched SPECULATIVELY with the previous bucket before
    the blocking re-bucketing ``pull``, so jax's async dispatch chains it
    behind the running segment and the host sync drops off the device's
    critical path.  Members only move up the ladder and most boundaries keep
    the bucket, so the speculation usually lands (``spec_hit`` per segment
    record); when the bucket changes the speculative output is discarded —
    it never touches the accepted carry, so trajectories are bit-identical
    to the unoverlapped driver (the in-device budget/active gates make a
    mispredicted segment run its members exactly as the right bucket would,
    or park them).  ``dispatch`` must not block on its own outputs for
    overlap to help (the mesh S1 driver forces its psum scalars, so it pins
    ``overlap=False``).

    Observability: each boundary is a chain of host phases
    (``obs.Tracer.phase``, children of ``parent``): ``pull``, ``schedule``
    (re-bucketing and bookkeeping), then a ``segment`` span over its
    ``dispatch`` and (unoverlapped) ``wait`` phases.  The loop emits the
    ``bucketed_*`` series of ``repro.obs.schema`` — segment wall, boundary
    sync (each the span's own duration), speculative-dispatch hit/miss,
    useful vs padded evaluations — from values that are ALREADY host-side
    here (the pull's np arrays and the spans' clock readings), so
    instrumentation adds no device syncs and no recompiles (guarded in
    tests/test_obs.py).

    ``supervisor`` (a ``repro.fleet`` ``IslandSupervisor``) adds fleet
    supervision at three host-side points: a per-boundary snapshot/recovery
    hook (restoring the carry and truncating the trace list on a death
    verdict — replay regenerates the lost segments identically, since the
    carry is the complete state and sampling is row-keyed prefix-stable),
    a supervised pull (corruption retries + health grading), and a
    pre-dispatch delay hook.  When ``supervisor is None`` (the default)
    each hook site is one host ``if`` — no extra device syncs, no extra
    programs (pinned in tests/test_obs.py and tests/test_fleet.py).
    """
    pull = pull_schedule if pull is None else pull
    overlap = bool(engine.overlap) if overlap is None else bool(overlap)
    reg = obs.metrics()
    tr = obs.tracer()
    seg_traces: List[ladder.LadderTrace] = []
    segments: List[dict] = []
    bucket_wall: Dict[int, float] = {}
    seg_len: Dict[int, int] = {}        # one segment length per bucket/campaign
    k_prev: Optional[int] = None
    fev_prev: Optional[float] = None    # pulled-budget sum at the last boundary

    for b in range(max_segments):
        if supervisor is not None:
            carry, keep, recovered = supervisor.segment_boundary(
                b, carry, len(seg_traces))
            if recovered:
                # replay from the restored snapshot: drop post-snapshot
                # traces and forget the stale speculation/progress anchors
                del seg_traces[keep:]
                del segments[keep:]
                k_prev = None
                fev_prev = None
        spec = None
        if overlap and k_prev is not None:
            # double-buffered carry: enqueue the likely next segment before
            # the host blocks on the schedule pull
            with tr.phase("dispatch", parent, island="all", boundary=b,
                          speculative=True):
                if supervisor is not None:
                    supervisor.before_dispatch(0, b)
                spec = dispatch(k_prev, seg_len[k_prev], carry)
        with tr.phase("pull", parent, island="all", boundary=b) as pull_span:
            if supervisor is not None:
                k_idx, active, fevals, best_f = supervisor.pull(
                    0, b, lambda: pull(carry))
            else:
                k_idx, active, fevals, best_f = pull(carry)
        sync_s = pull_span.dur
        with tr.phase("schedule", parent, island="all", boundary=b):
            reg.histogram("bucketed_sync_s").observe(sync_s)
            fev_sum = float(np.sum(fevals))
            if fev_prev is not None:
                reg.counter("bucketed_useful_evals_total").inc(
                    max(0.0, fev_sum - fev_prev))
            fev_prev = fev_sum
            if segments:
                # the pull reflects the PREVIOUS segment's result — attach
                # its post-segment best there (finite by then; None keeps
                # the record strict-JSON-safe on the pathological all-inf
                # fitness)
                gb = float(best_f.min())
                segments[-1]["global_best"] = gb if np.isfinite(gb) else None
            _live, k = next_bucket(engine, k_idx, active, fevals, seg_len,
                                   budgets=budgets)
        if k is None:
            break
        hit = spec is not None and k == k_prev
        with tr.span("segment", parent, island="all", bucket=int(k),
                     boundary=b) as seg_span:
            with tr.phase("dispatch", seg_span, island="all", bucket=int(k),
                          boundary=b):
                if hit:
                    carry, seg_tr = spec
                else:
                    if supervisor is not None:
                        supervisor.before_dispatch(0, b)
                    carry, seg_tr = dispatch(k, seg_len[k], carry)
                seg_traces.append(seg_tr)   # device-resident until the end
                if spec is not None:
                    reg.counter("bucketed_spec_dispatch_total",
                                outcome="hit" if hit else "miss").inc()
                reg.counter("bucketed_segments_total", bucket=k).inc()
                reg.counter("bucketed_padded_evals_total", bucket=k).inc(
                    int(np.size(k_idx)) * seg_len[k] * (2 ** k)
                    * engine.lam_start)
            if not overlap:
                with tr.phase("wait", seg_span, island="all", boundary=b):
                    jax.block_until_ready(carry.total_fevals)
            seg_span.attrs["spec"] = ("hit" if hit else
                                      "miss" if spec is not None else "sync")
        wall = seg_span.dur
        reg.histogram("bucketed_segment_wall_s", bucket=k).observe(wall)
        seg = {"bucket": k, "gens": seg_len[k], "wall_s": round(wall, 5)}
        if overlap:
            # wall_s is dispatch-only here (no block); the host-blocked time
            # rides the pull instead
            seg["sync_s"] = round(sync_s, 5)
            seg["spec_hit"] = hit
        segments.append(seg)
        bucket_wall[k] = bucket_wall.get(k, 0.0) + wall + \
            (sync_s if overlap else 0.0)
        k_prev = k
    else:
        raise RuntimeError("segment driver did not converge "
                           f"within {max_segments} segments")
    return carry, seg_traces, segments, bucket_wall


def concat_traces(seg_traces: List[ladder.LadderTrace],
                  carry: ladder.LadderCarry,
                  time_axis: int = 1) -> ladder.LadderTrace:
    """Gather ``drive_segments``' per-segment traces to the host, joined
    along ``time_axis`` (1 for vmapped campaigns whose leaves are
    (B, T, ...), 0 for a single run's (T, ...))."""
    if not seg_traces:
        # nothing could run (e.g. max_evals below one λ_start generation):
        # return a zero-length trace shaped like the padded engine's, so
        # every consumer sees the same empty-progress result
        return _empty_trace(carry, time_axis)
    return jax.tree_util.tree_map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs],
                                   axis=time_axis),
        *seg_traces)


def _empty_trace(carry: ladder.LadderCarry, time_axis: int) -> ladder.LadderTrace:
    """Zero-generation LadderTrace with the batch/slot layout of ``carry``."""
    k = np.asarray(carry.k_idx)                       # (B, S) or (S,)
    slot = k.shape[:time_axis] + (0,) + k.shape[time_axis:]
    glob = k.shape[:time_axis] + (0,)
    dt = np.asarray(carry.best_f).dtype
    return ladder.LadderTrace(
        ran=np.zeros(slot, bool),
        k_idx=np.zeros(slot, np.int32),
        gen=np.zeros(slot, np.int32),
        fevals=np.zeros(slot, np.asarray(carry.states.fevals).dtype),
        best_f=np.zeros(slot, dt),
        stop_reason=np.zeros(slot, np.int32),
        stopped=np.zeros(slot, bool),
        total_fevals=np.zeros(glob, np.asarray(carry.total_fevals).dtype),
        global_best=np.zeros(glob, dt))


def run_bucketed_single(engine: BucketedLadderEngine, base_key: jax.Array,
                        fitness_fn: Callable,
                        max_segments: int = 10_000, supervisor=None):
    """One (un-vmapped) problem through the segment driver — the bucketed
    backend behind ``ipop.run_ipop``.  Returns ``(carry, trace)`` shaped like
    ``LadderEngine.run``'s output (trace leaves (T, S)).

    Runners are cached per call, not on the engine: the fitness closure is
    baked in at trace time, so an engine-level cache would silently replay a
    previous call's fitness.
    """
    carry = jax.jit(engine.init_carry)(base_key)
    cache: Dict[Tuple[int, int], Callable] = {}

    def dispatch(k, seg_gens, c):
        ck = (k, seg_gens)
        if ck not in cache:
            def run_seg(bk, cc, _k=k, _g=seg_gens):
                return engine.segment_scan(_k, bk, fitness_fn, cc, _g)
            cache[ck] = jax.jit(run_seg)
        return cache[ck](base_key, c)

    carry, seg_traces, _segs, _walls = drive_segments(
        engine, carry, dispatch, max_segments, supervisor=supervisor)
    return carry, concat_traces(seg_traces, carry, time_axis=0)


def run_campaign_bucketed(engine: BucketedLadderEngine, fids,
                          instances=(1,), runs: int = 1, seed: int = 0,
                          max_segments: int = 10_000,
                          rows: Optional[Sequence[int]] = None,
                          ) -> BucketedCampaignResult:
    """Run a whole BBOB campaign through the rung-bucketed segment driver.

    Same member layout, instance stacking and key schedule as
    ``ladder.run_campaign`` — the two are trajectory-equivalent (bit-exact
    arithmetic per generation at ``eigen_interval == 1``, modulo per-shape
    XLA fusion rounding); this driver just never pays λ_max padding on a
    λ_start rung and stops as soon as the whole cohort is done.

    ``rows`` runs only those members of the layout, with their own keys and
    instances and the whole campaign's fitness menu: e.g. the slice one
    mesh device holds, in a program of that slice's batch shape.

    Host phases (``obs.Tracer.phase``) under one ``campaign`` root span:
    ``keys``, ``instances``, ``init``, then ``drive_segments``' per
    boundary, then ``finish``.  An exception (a caller's stop raised in a
    dispatch) ends every open span, the root with ``status="stopped"``.
    """
    fids = tuple(fids)
    layout = [(f, i, r) for f in fids for i in instances for r in range(runs)]
    if rows is not None:
        rows = [int(j) for j in rows]
    members = layout if rows is None else [layout[j] for j in rows]
    reg = obs.metrics()
    tr = obs.tracer()
    with tr.span("campaign", members=len(members), fids=list(fids)) as root:
        with tr.phase("keys", root, island="all") as keys_span:
            base = jax.random.PRNGKey(seed)
            keys = jnp.stack([jax.random.fold_in(base, j)
                              for j in range(len(layout))])
            if rows is not None:
                keys = keys[np.asarray(rows)]
        with tr.phase("instances", root, island="all"):
            insts = [bbob.make_instance(f, engine.n, i, engine.full.cfg.jdtype)
                     for (f, i, _r) in members]
            stacked = bbob.stack_instances(insts)
        with tr.phase("init", root, island="all") as init_span:
            carry = engine._init_runner(keys)
        reg.histogram("bucketed_campaign_setup_s").observe(
            init_span.t1 - keys_span.t0)
        branch_fids = tuple(sorted(set(fids)))
        fused_menu = (bbob.eval_fusion_enabled()
                      and all(f in bbob.FUSABLE_FIDS for f in branch_fids))

        def dispatch(k, seg_gens, c):
            runner = engine.segment_runner(k, branch_fids, seg_gens)
            if fused_menu:
                # whole-menu-separable segments run the eval-fused sample
                # epilogue — count their generations (host-known statics
                # only: no device sync, no recompile)
                reg.counter("bucketed_eval_fused_generations_total").inc(
                    int(seg_gens))
            return runner(keys, stacked, c)

        carry, seg_traces, segments, bucket_wall = drive_segments(
            engine, carry, dispatch, max_segments, parent=root)
        with tr.phase("finish", root, island="all") as finish_span:
            trace = concat_traces(seg_traces, carry)
            lam_start, kmax = engine.lam_start, engine.kmax_exp
            useful = _useful_evals_per_rung(trace, lam_start, kmax)
            B = len(members)
            padded = sum(B * s["gens"] * (2 ** s["bucket"]) * lam_start
                         for s in segments)
            result = BucketedCampaignResult(
                members=members,
                f_opt=np.asarray([i.f_opt for i in insts], np.float64),
                best_f=np.asarray(carry.best_f),
                best_x=np.asarray(carry.best_x),
                total_fevals=np.asarray(carry.total_fevals),
                trace=trace,
                compiles=engine.compiles(),
                segments=segments,
                bucket_wall_s={k: round(v, 5) for k, v in bucket_wall.items()},
                useful_evals=int(sum(useful.values())),
                padded_evals=int(padded))
        reg.histogram("bucketed_campaign_finish_s").observe(finish_span.dur)
    return result
