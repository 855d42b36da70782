"""Mesh campaign engine — the paper's two deployment strategies (§4–5).

The rung-bucketed engine (core/bucketed.py) is mesh-ready in shape: a small
family of fixed per-bucket programs, host syncs only between segments.  This
module deploys it across a device mesh, implementing both of the paper's
strategies for running many IPOP-CMA-ES searches on a large machine:

* ``strategy="ordered"`` (S1 — sequential order): the campaign members
  shard over a 1-d ``("camp",)`` mesh axis and EVERY segment runs as one
  ``shard_map`` program over the whole mesh — all shards advance through the
  same global segment schedule with a barrier per segment (the host pull
  that re-buckets), preserving the bucketed driver's sequential rung
  ordering exactly.  Inside the program each device vmaps
  ``BucketedLadderEngine.segment_scan`` over its member slice; the budget
  and best-f scalars are reduced (a ``psum``, and the min of an
  ``all_gather``) to replicated values, so every shard (and the host) sees
  the campaign-global values.
* ``strategy="concurrent"`` (S2 — the paper's winner): each device is an
  island owning a contiguous member slice and drives its OWN budget-adaptive
  segment schedule — the host round-robins over islands, dispatching each
  one's next bucket program asynchronously (dispatch returns before the
  segment finishes, so islands genuinely overlap); between segments the
  islands exchange only the global best/budget scalars.  No barrier: a
  shard whose members finished stops paying for the stragglers' schedule,
  which is exactly where the paper's S2 wins super-linearly.  With
  ``stop_at`` set, the global-best exchange also retires every island as
  soon as any island reaches the target (S2's early-sharing win; off by
  default to keep strict equivalence with the single-device driver).

Compilation stays bounded by the bucket family: segment lengths are fixed
once per (campaign, bucket), so the ordered path holds ``compiles ≤
#buckets`` at the jit-cache level, and the concurrent path traces at most
one program per bucket (each island then holds its device's executable copy
of that same traced program — copies, not new programs; ``compiles()``
counts traced programs).

Equivalence with ``backend="bucketed"`` (tests/mesh_check.py, run on 8
virtual CPU devices): member trajectories depend only on their own
(slot, incarnation, generation) key schedule and row-keyed sampling, never
on which shard or segment executed them — so at ``eigen_interval == 1``
both strategies are trajectory-equivalent to the single-device driver
(modulo per-shape XLA fusion rounding, the tolerance every engine pair here
carries), and at ``eigen_interval > 1`` (segment-local eigen cadence, and
shard-local segment cuts under S2) they are ECDF-equivalent.

Between segments the scheduling arrays ride ONE
``multihost_utils.process_allgather`` call (``k_idx``, ``active``,
``total_fevals``, ``best_f`` as a single tree) — multi-process ready, and on
a single process exactly the batched ``device_get`` the bucketed driver
uses.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np

from repro import obs
from repro.core import bucketed, ladder
from repro.core.eval_dispatch import shard_map_compat
from repro.distributed.sharding import campaign_shardings
from repro.fitness import bbob
from repro.launch.mesh import make_campaign_mesh


def _finite_or_none(x: float):
    """Strict-JSON-safe scalar for logged records (json.dump emits a bare
    ``Infinity`` token otherwise): None until a best exists."""
    x = float(x)
    return x if np.isfinite(x) else None


# ---------------------------------------------------------------------------
# island program cache (S2)
# ---------------------------------------------------------------------------
# S2 island bring-up used to be O(buckets·P) *per driver call*: `island_runner`
# cached its jitted programs on the engine instance (or, for baked-in fitness
# closures, in a dict that died with the call), so every new campaign, every
# `run_mesh_single`, and every round of a long-lived service re-traced the
# same bucket programs.  Executables are now keyed here, at module level, by a
# compilation-cache key — everything that determines the compiled program:
# the bucket's full CMAConfig (shape + trajectory knobs), the engine's ladder
# geometry/budget/impl, the segment length, the fitness identity (the static
# BBOB fid set, or the closure OBJECT for generic runs — keying by object
# removes the stale-closure hazard that forced the per-call caches), and the
# mesh's device fingerprint.  Per-island dispatch therefore reuses ONE traced
# program per bucket for the life of the process; the per-device executables
# XLA still wants live inside that single callable's jit cache and fill
# lazily, only for islands that actually run the bucket.  The campaign
# service's segment programs ride the same class (service/server.py).


def _contains_callable(x) -> bool:
    return callable(x) or (isinstance(x, tuple)
                           and any(_contains_callable(i) for i in x))


class ProgramCache:
    """Process-wide compiled-program cache with closure-aware eviction.

    Entries whose key embeds a callable (a fitness closure, a service
    registry) keep that closure — and everything its cells capture — alive;
    unbounded, a long-lived process that builds a fresh closure per call
    would leak one traced program per (closure, bucket) forever.  Those
    entries are therefore capped at ``max_closure_entries`` with FIFO
    eviction (evicting a live program only costs a re-trace on its next
    use); purely-static keys (BBOB fid sets + config scalars) are bounded by
    the configuration space and never evicted.
    """

    def __init__(self, max_closure_entries: int = 64):
        self.max_closure_entries = int(max_closure_entries)
        self._programs: Dict[tuple, Callable] = {}
        self.stats = {"traces": 0, "hits": 0}

    def get(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        fn = self._programs.get(key)
        if fn is not None:
            self.stats["hits"] += 1
            return fn
        fn = build()
        self._programs[key] = fn
        self.stats["traces"] += 1
        if _contains_callable(key):
            closure_keys = [k for k in self._programs
                            if _contains_callable(k)]
            for k in closure_keys[:max(0, len(closure_keys)
                                       - self.max_closure_entries)]:
                del self._programs[k]
        return fn

    def snapshot(self) -> dict:
        return {"programs": len(self._programs), **self.stats}

    def clear(self):
        self._programs.clear()
        self.stats.update(traces=0, hits=0)


_ISLAND_CACHE = ProgramCache()


def island_program_key(eng: bucketed.BucketedLadderEngine, k: int,
                       seg_gens: int, branch_fids: Tuple[int, ...],
                       fitness_fn: Optional[Callable], devices) -> tuple:
    """Compilation-cache key of one island segment program (bucket shape +
    mesh) — hashable because ``CMAConfig`` is a frozen dataclass of scalars."""
    fit_id = tuple(branch_fids) if fitness_fn is None else fitness_fn
    return (eng.bucket_cfgs[k], eng.lam_start, eng.kmax_exp, eng.max_evals,
            tuple(eng.domain), eng.impl, int(k), int(seg_gens), fit_id,
            bbob.eval_fusion_enabled(),
            tuple((d.platform, d.id) for d in devices))


def island_cache_stats() -> dict:
    """{"programs": live cached programs, "traces": total traced,
    "hits": cache hits} — island bring-up is O(buckets) iff ``traces`` stops
    growing across campaigns (asserted in tests/test_mesh_engine.py)."""
    return _ISLAND_CACHE.snapshot()


def clear_island_program_cache():
    """Drop all cached island programs (tests; also frees the engines the
    program closures keep alive)."""
    _ISLAND_CACHE.clear()


def pull_schedule_allgather(carry: ladder.LadderCarry):
    """Mesh variant of ``bucketed.pull_schedule``: the four scheduling arrays
    cross the device boundary as ONE ``process_allgather`` of a single tree
    (global views of the sharded members), not four blocking per-array
    ``np.asarray`` pulls.  Single-process this is the same batched
    ``device_get``; multi-process it is the collective the ROADMAP named."""
    from jax.experimental import multihost_utils

    k_idx, active, fevals, best_f = multihost_utils.process_allgather(
        (carry.k_idx[..., 0], carry.active[..., 0],
         carry.total_fevals, carry.best_f), tiled=True)
    return (np.atleast_1d(k_idx), np.atleast_1d(active),
            np.atleast_1d(fevals), np.atleast_1d(best_f))


@dataclasses.dataclass
class MeshCampaignEngine:
    """Bucketed-ladder campaigns sharded over a ``("camp",)`` device mesh.

    Wraps a ``BucketedLadderEngine`` (which owns the bucket configs, segment
    sizing and single-device semantics); this engine only decides WHERE each
    segment program runs and how shards synchronize — per the two paper
    strategies above.  ``mesh`` defaults to all local devices
    (``launch.mesh.make_campaign_mesh``).
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
    seg_blocks: Optional[int] = None
    policy: str = "cover"
    strategy: str = "ordered"           # "ordered" (S1) | "concurrent" (S2)
    mesh: Optional[object] = None       # jax.sharding.Mesh over axis "camp"
    axis: str = "camp"
    stop_at: Optional[float] = None     # S2 early-stop on the shared best
    overlap: bool = True                # S1 speculative double-buffered
                                        # dispatch (exchange scalars fold
                                        # lazily at the boundary pull)

    def __post_init__(self):
        if self.strategy not in ("ordered", "concurrent"):
            raise ValueError(f"unknown strategy {self.strategy!r}")
        self.bucketed = bucketed.BucketedLadderEngine(
            n=self.n, lam_start=self.lam_start, kmax_exp=self.kmax_exp,
            max_evals=self.max_evals, domain=self.domain,
            sigma0_frac=self.sigma0_frac, impl=self.impl, dtype=self.dtype,
            eigen_interval=self.eigen_interval, seg_blocks=self.seg_blocks,
            policy=self.policy)
        if self.mesh is None:
            self.mesh = make_campaign_mesh()
        self.n_devices = int(self.mesh.devices.size)
        self._runner_cache: dict = {}
        self._island_keys: set = set()

    # -- segment programs -----------------------------------------------------
    def _seg_fn(self, k: int, seg_gens: int, branch_fids: Tuple[int, ...],
                fitness_fn: Optional[Callable]):
        """The vmapped local segment body shared by both strategies: one
        device's member slice through ``segment_scan``.  With ``branch_fids``
        the fitness is the stacked-instance BBOB dispatch (campaigns); with
        ``fitness_fn`` it is a baked-in closure (generic single runs)."""
        eng = self.bucketed
        if fitness_fn is None:
            def run_one(base_key, inst, c):
                def fit(X):
                    return bbob.evaluate_dynamic(inst, X, branch_fids)
                return eng.segment_scan(
                    k, base_key, bbob.fusable_fitness(inst, branch_fids, fit),
                    c, seg_gens)
            return jax.vmap(run_one)

        def run_one(base_key, c):
            return eng.segment_scan(k, base_key, fitness_fn, c, seg_gens)
        return jax.vmap(run_one)

    def ordered_runner(self, k: int, seg_gens: int,
                       branch_fids: Tuple[int, ...] = (),
                       fitness_fn: Optional[Callable] = None,
                       cache: Optional[dict] = None):
        """One S1 segment as a ``shard_map`` program over the whole mesh:
        member batch sharded over ``axis``, budget/best scalars reduced to
        replicated outputs.  Cached per (bucket, length, fids) —
        jit-cache size 1 per entry, so ``compiles ≤ #buckets`` holds at the
        executable level (asserted in tests/mesh_check.py)."""
        cache = self._runner_cache if cache is None else cache
        key = ("ordered", int(k), int(seg_gens), tuple(branch_fids),
               bbob.eval_fusion_enabled())
        if key not in cache:
            axis = self.axis
            vmapped = self._seg_fn(k, seg_gens, branch_fids, fitness_fn)
            n_args = 2 if fitness_fn is not None else 3

            def local_seg(*args):
                c, tr = vmapped(*args)
                g_fev = jax.lax.psum(jnp.sum(c.total_fevals), axis)
                # min of the gathered per-shard bests: the TPU lowers only
                # sum all-reduces of f64, so a pmin of it does not compile
                g_best = jnp.min(jax.lax.all_gather(jnp.min(c.best_f), axis))
                return c, tr, g_fev, g_best

            fn = shard_map_compat(
                local_seg, mesh=self.mesh,
                in_specs=(P(axis),) * n_args,
                out_specs=(P(axis), P(axis), P(), P()))
            # explicit in/out shardings pin the jit cache key: without them a
            # 1-device mesh canonicalizes P(axis) outputs to P(), and feeding
            # a segment's carry back in recompiles the same bucket program
            sh_c = jax.sharding.NamedSharding(self.mesh, P(axis))
            sh_r = jax.sharding.NamedSharding(self.mesh, P())
            cache[key] = jax.jit(fn, in_shardings=(sh_c,) * n_args,
                                 out_shardings=(sh_c, sh_c, sh_r, sh_r))
        return cache[key]

    def island_runner(self, k: int, seg_gens: int,
                      branch_fids: Tuple[int, ...] = (),
                      fitness_fn: Optional[Callable] = None):
        """One S2 segment as a plain jitted program over one island's member
        slice; dispatching it on inputs committed to island ``s``'s device
        runs it there, asynchronously.  Programs come from the module-level
        compilation-cache (``island_program_key``): one traced program per
        (bucket shape, mesh) reused across islands, campaigns and engine
        instances — island bring-up is O(buckets), not O(buckets·calls)."""
        key = island_program_key(self.bucketed, k, seg_gens, branch_fids,
                                 fitness_fn, self.mesh.devices.flat)
        traces0 = _ISLAND_CACHE.stats["traces"]
        with obs.tracer().span("compile", key=f"island.k{k}.g{seg_gens}") \
                as sp:
            fn = _ISLAND_CACHE.get(key, lambda: jax.jit(
                self._seg_fn(k, seg_gens, branch_fids, fitness_fn)))
            sp.attrs["hit"] = _ISLAND_CACHE.stats["traces"] == traces0
        self._island_keys.add(key)
        return fn

    def compiles(self) -> int:
        """Distinct segment programs this engine used: jit-cache entries for
        ordered runners (always 1 each — same shardings every call), one per
        island program key (counted as used even on a module-cache hit, so
        the ``compiles ≤ #buckets`` bound stays meaningful per campaign;
        process-wide reuse shows up in ``island_cache_stats`` instead)."""
        total = len(self._island_keys)
        for key, fn in self._runner_cache.items():
            if key[0] == "ordered":
                cs = getattr(fn, "_cache_size", None)
                total += int(cs()) if callable(cs) else 1
        return total

    # -- member layout --------------------------------------------------------
    def pad_batch(self, keys: jax.Array, carry: ladder.LadderCarry,
                  insts=None):
        """Pad the member batch to a multiple of the mesh size with inert
        rows: ``active=False`` from the start, so they never run a
        generation, spend budget, or win a pmin — results slice back to the
        real members.  Returns (keys, carry, insts, B_real, B_pad)."""
        B = int(keys.shape[0])
        P_n = self.n_devices
        B_pad = -(-B // P_n) * P_n
        if B_pad != B:
            pad = B_pad - B
            pad_keys = jnp.stack([jax.random.fold_in(keys[-1], 1 + j)
                                  for j in range(pad)])
            keys = jnp.concatenate([keys, pad_keys])
            carry = jax.tree_util.tree_map(
                lambda a: jnp.concatenate(
                    [a, jnp.repeat(a[-1:], pad, axis=0)]), carry)
            if insts is not None:
                insts = jax.tree_util.tree_map(
                    lambda a: jnp.concatenate(
                        [a, jnp.repeat(a[-1:], pad, axis=0)]), insts)
        active = jnp.asarray(carry.active)
        mask = (jnp.arange(B_pad) < B)[:, None]
        carry = carry._replace(active=active & mask)
        return keys, carry, insts, B, B_pad

    # -- drivers --------------------------------------------------------------
    def _drive_ordered(self, keys, insts, carry, branch_fids, fitness_fn,
                       max_segments: int, supervisor=None):
        """S1: the bucketed re-bucketing loop verbatim (``drive_segments``),
        with shard_map dispatch and the allgather pull.

        The psum'd exchange scalars are folded LAZILY: ``dispatch`` leaves
        them device-resident (keyed by the segment's output carry) and the
        boundary pull — which already blocks on that same segment's carry —
        folds the matching entry afterwards, when the values are guaranteed
        ready and ``int()`` costs a ready-buffer read instead of a device
        round-trip.  With nothing in ``dispatch`` blocking on its own
        outputs, S1 runs the bucketed driver's speculative double-buffered
        dispatch (``engine.overlap``, default on): trajectories are
        bit-identical (a mispredicted segment's output — and its pending
        exchange entry — is discarded without ever being forced), and each
        accepted segment still produces exactly one exchange record."""
        shd = campaign_shardings(keys, self.mesh, self.axis)
        keys = jax.device_put(keys, shd)
        carry = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, shd), carry)
        if insts is not None:
            insts = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, shd), insts)
        local_cache = None if fitness_fn is None else {}
        exchange: List[dict] = []
        reg = obs.metrics()
        # pending exchange scalars, matched to the accepted carry by object
        # identity (holding the array also pins its id against reuse)
        inflight: List[tuple] = []

        def dispatch(k, seg_gens, c):
            runner = self.ordered_runner(k, seg_gens, branch_fids,
                                         fitness_fn, cache=local_cache)
            args = (keys, c) if insts is None else (keys, insts, c)
            # no island attr on purpose: drive_segments already covers this
            # wall with its island="all" dispatch phase — a second island-
            # attributed span would double-count busy time in the digest
            sp = obs.tracer().start("dispatch", strategy="ordered",
                                    bucket=int(k))
            t0 = time.perf_counter()
            c, tr, g_fev, g_best = runner(*args)
            obs.tracer().end(sp)
            reg.histogram("mesh_island_dispatch_s", strategy="ordered",
                          island="all").observe(time.perf_counter() - t0)
            inflight.append((c.total_fevals, int(k), g_fev, g_best))
            return c, tr

        def pull(c):
            res = pull_schedule_allgather(c)
            for i, (arr, k, g_fev, g_best) in enumerate(inflight):
                if arr is c.total_fevals:
                    t0 = time.perf_counter()
                    exchange.append({
                        "bucket": k, "global_fevals": int(g_fev),
                        "global_best": _finite_or_none(g_best)})
                    reg.histogram("mesh_exchange_s", strategy="ordered"
                                  ).observe(time.perf_counter() - t0)
                    reg.counter("mesh_exchange_rounds_total",
                                strategy="ordered").inc()
                    # anything dispatched before the accepted segment can
                    # never be pulled again — mispredicted spec entries drop
                    del inflight[:i + 1]
                    break
            return res

        # every accepted segment is folded: the loop always pulls the carry
        # it just accepted before deciding whether another bucket exists
        # (a supervisor sees S1 as ONE island — its failure domain is the
        # whole mesh program, so recovery restarts the whole-batch carry)
        carry, seg_traces, segments, bucket_wall = bucketed.drive_segments(
            self.bucketed, carry, dispatch, max_segments, pull=pull,
            overlap=self.overlap, supervisor=supervisor)
        trace = bucketed.concat_traces(seg_traces, carry)
        return carry, trace, segments, bucket_wall, exchange, None

    def _drive_concurrent(self, keys, insts, carry, branch_fids, fitness_fn,
                          max_segments: int, supervisor=None):
        """S2: one island per device, each with its own re-bucketing loop;
        the host round-robins dispatches (async — islands overlap) and folds
        the per-island budget/best scalars into the shared campaign view.

        ``supervisor`` (``repro.fleet``) supervises each island: periodic
        host snapshots of shard state, kill/delay/corrupt fault application,
        health grading of the per-island pulls, and recovery by replay —
        a killed shard's snapshot is device_put onto a surviving device and
        re-driven (identical trajectories: shard state is complete and
        sampling row-keyed).  ``None`` (default) costs one host ``if`` per
        hook site."""
        eng = self.bucketed
        devs = list(self.mesh.devices.flat)
        P_n = len(devs)
        B_pad = int(keys.shape[0])
        Bl = B_pad // P_n

        shards = []
        for s, dev in enumerate(devs):
            sl = slice(s * Bl, (s + 1) * Bl)

            def put(a, _sl=sl, _dev=dev):
                return jax.device_put(a[_sl], _dev)

            shards.append({
                "keys": put(keys),
                "insts": None if insts is None
                else jax.tree_util.tree_map(put, insts),
                "carry": jax.tree_util.tree_map(put, carry),
                "traces": [], "segments": [], "done": False,
                "best": np.inf, "fevals": 0,
            })

        seg_len: Dict[int, int] = {}    # shared per bucket: compiles ≤ #buckets
        bucket_wall: Dict[int, float] = {}
        exchange: List[dict] = []
        reg = obs.metrics()
        if supervisor is not None:
            supervisor.mesh_init(shards, devs)
        for rnd in range(max_segments):
            if supervisor is not None:
                supervisor.mesh_round(rnd, shards, devs)
            dispatched = retired = finished = 0
            for s, sh in enumerate(shards):
                if sh["done"]:
                    continue
                blk = obs.tracer().start("block", island=s, boundary=rnd)
                t0 = time.perf_counter()
                if supervisor is not None:
                    k_idx, active, fevals, best_f = supervisor.pull(
                        s, rnd,
                        lambda _c=sh["carry"]: bucketed.pull_schedule(_c))
                else:
                    k_idx, active, fevals, best_f = bucketed.pull_schedule(
                        sh["carry"])             # blocks on THIS island only
                obs.tracer().end(blk)
                reg.histogram("mesh_island_block_s",
                              island=s).observe(time.perf_counter() - t0)
                sh["best"] = float(best_f.min())
                sh["fevals"] = int(fevals.sum())
                if self.stop_at is not None and \
                        min(x["best"] for x in shards) <= self.stop_at:
                    # the shared best already meets the target: this island
                    # (and, as their turns come, every other) retires instead
                    # of dispatching another segment — S2's early sharing
                    sh["done"] = True
                    retired += 1
                    reg.counter("mesh_retirements_total",
                                reason="target").inc()
                    continue
                # shard-local re-bucketing: the same decision the
                # single-device driver makes, over this island's slice only
                _live, k = bucketed.next_bucket(eng, k_idx, active, fevals,
                                                seg_len)
                if k is None:
                    sh["done"] = True
                    finished += 1
                    reg.counter("mesh_retirements_total",
                                reason="exhausted").inc()
                    continue
                runner = self.island_runner(k, seg_len[k], branch_fids,
                                            fitness_fn)
                args = (sh["keys"], sh["carry"]) if sh["insts"] is None \
                    else (sh["keys"], sh["insts"], sh["carry"])
                if supervisor is not None:
                    supervisor.before_dispatch(s, rnd)
                dsp = obs.tracer().start("dispatch", island=s,
                                         bucket=int(k), boundary=rnd)
                t0 = time.perf_counter()
                sh["carry"], tr = runner(*args)   # async: no block here
                wall = time.perf_counter() - t0
                obs.tracer().end(dsp)
                reg.histogram("mesh_island_dispatch_s",
                              strategy="concurrent",
                              island=s).observe(wall)
                sh["traces"].append(tr)
                sh["segments"].append({"shard": s, "bucket": k,
                                       "gens": seg_len[k],
                                       "dispatch_s": round(wall, 5)})
                bucket_wall[k] = bucket_wall.get(k, 0.0) + wall
                dispatched += 1
            # -- the only cross-island traffic: two scalars ----------------
            if dispatched or retired or finished:
                t0 = time.perf_counter()
                entry = {"round": rnd,
                         "global_best": _finite_or_none(
                             min(sh["best"] for sh in shards)),
                         "global_fevals": sum(sh["fevals"] for sh in shards)}
                if retired:
                    entry["stopped_early"] = True
                exchange.append(entry)
                reg.histogram("mesh_exchange_s", strategy="concurrent"
                              ).observe(time.perf_counter() - t0)
                reg.counter("mesh_exchange_rounds_total",
                            strategy="concurrent").inc()
            if not dispatched and all(sh["done"] for sh in shards):
                break
        else:
            raise RuntimeError("island driver did not converge "
                               f"within {max_segments} rounds")

        # -- assemble the global (B_pad, T_max, ...) trace --------------------
        shard_traces = []
        for sh in shards:
            if sh["traces"]:
                tr = jax.tree_util.tree_map(
                    lambda *xs: np.concatenate(
                        [np.asarray(x) for x in xs], axis=1), *sh["traces"])
            else:
                tr = bucketed._empty_trace(
                    jax.tree_util.tree_map(np.asarray, sh["carry"]),
                    time_axis=1)
            shard_traces.append(tr)
        T_max = max(tr.ran.shape[1] for tr in shard_traces)
        trace = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0),
            *[_pad_time(tr, T_max) for tr in shard_traces])
        carry = jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
            *[sh["carry"] for sh in shards])
        segments = [seg for sh in shards for seg in sh["segments"]]
        return carry, trace, segments, bucket_wall, exchange, \
            [sh["segments"] for sh in shards]


def _pad_time(tr: ladder.LadderTrace, T: int) -> ladder.LadderTrace:
    """Pad a shard trace to ``T`` generations along axis 1 with inert rows:
    ``ran=False`` steps (every consumer masks on ``ran``), edge-extended
    budget/best accumulators so ``hit_evals`` stays monotone."""
    t = tr.ran.shape[1]
    if t == T:
        return tr

    def cpad(a, fill):
        pw = [(0, 0)] * a.ndim
        pw[1] = (0, T - t)
        return np.pad(a, pw, constant_values=fill)

    def epad(a, fill):
        if t == 0:
            return cpad(a, fill)
        pw = [(0, 0)] * a.ndim
        pw[1] = (0, T - t)
        return np.pad(a, pw, mode="edge")

    return ladder.LadderTrace(
        ran=cpad(tr.ran, False), k_idx=cpad(tr.k_idx, 0),
        gen=cpad(tr.gen, 0), fevals=cpad(tr.fevals, 0),
        best_f=cpad(tr.best_f, np.inf), stop_reason=cpad(tr.stop_reason, 0),
        stopped=cpad(tr.stopped, False),
        total_fevals=epad(tr.total_fevals, 0),
        global_best=epad(tr.global_best, np.inf))


@dataclasses.dataclass
class MeshCampaignResult(bucketed.BucketedCampaignResult):
    """Bucketed campaign result plus the mesh deployment record."""

    strategy: str = "ordered"
    n_devices: int = 1
    exchange: List[dict] = dataclasses.field(default_factory=list)
    shard_segments: Optional[List[List[dict]]] = None


def run_campaign_mesh(engine: MeshCampaignEngine, fids, instances=(1,),
                      runs: int = 1, seed: int = 0,
                      max_segments: int = 10_000,
                      supervisor=None) -> MeshCampaignResult:
    """Run a whole BBOB campaign through the mesh engine — same member
    layout, instance stacking and key schedule as ``run_campaign_bucketed``
    (and therefore the λ_max-padded engine), with the batch padded to the
    mesh with inert members and deployed per ``engine.strategy``."""
    eng = engine.bucketed
    fids = tuple(fids)
    members = [(f, i, r) for f in fids for i in instances for r in range(runs)]
    insts = [bbob.make_instance(f, engine.n, i, eng.full.cfg.jdtype)
             for (f, i, _r) in members]
    stacked = bbob.stack_instances(insts)
    branch_fids = tuple(sorted(set(fids)))

    base = jax.random.PRNGKey(seed)
    keys = jnp.stack([jax.random.fold_in(base, j)
                      for j in range(len(members))])
    carry = eng._init_runner(keys)
    keys, carry, stacked, B, _B_pad = engine.pad_batch(keys, carry, stacked)

    drive = (engine._drive_ordered if engine.strategy == "ordered"
             else engine._drive_concurrent)
    carry, trace, segments, bucket_wall, exchange, shard_segments = drive(
        keys, stacked, carry, branch_fids, None, max_segments,
        supervisor=supervisor)

    sl = lambda a: np.asarray(a)[:B]
    trace = jax.tree_util.tree_map(sl, trace)
    useful = bucketed._useful_evals_per_rung(trace, eng.lam_start,
                                             eng.kmax_exp)
    rows = {"ordered": _B_pad, "concurrent": _B_pad // engine.n_devices}
    padded = sum(rows[engine.strategy] * s["gens"]
                 * (2 ** s["bucket"]) * eng.lam_start for s in segments)
    return MeshCampaignResult(
        members=members,
        f_opt=np.asarray([i.f_opt for i in insts], np.float64),
        best_f=sl(carry.best_f),
        best_x=sl(carry.best_x),
        total_fevals=sl(carry.total_fevals),
        trace=trace,
        compiles=engine.compiles(),
        segments=segments,
        bucket_wall_s={k: round(v, 5) for k, v in bucket_wall.items()},
        useful_evals=int(sum(useful.values())),
        padded_evals=int(padded),
        strategy=engine.strategy,
        n_devices=engine.n_devices,
        exchange=exchange,
        shard_segments=shard_segments)


def run_mesh_single(engine: MeshCampaignEngine, base_key: jax.Array,
                    fitness_fn: Callable, max_segments: int = 10_000,
                    supervisor=None):
    """One (un-vmapped) problem through the mesh engine — the ``mesh``
    backend behind ``ipop.run_ipop``.  The single member rides shard 0; the
    other shards carry inert padding rows.  Returns ``(carry, trace)`` with
    the single-run layout (trace leaves (T, S)) of ``run_bucketed_single``.

    Ordered runners are cached per call (the fitness closure is baked in at
    trace time — same reasoning as ``run_bucketed_single``); island runners
    ride the module-level program cache, which keys by the closure OBJECT and
    therefore can never replay a previous call's fitness.
    """
    keys = base_key[None]
    carry = engine.bucketed._init_runner(keys)
    keys, carry, _, _B, _B_pad = engine.pad_batch(keys, carry, None)
    drive = (engine._drive_ordered if engine.strategy == "ordered"
             else engine._drive_concurrent)
    carry, trace, _segs, _walls, _exch, _ss = drive(
        keys, None, carry, (), fitness_fn, max_segments,
        supervisor=supervisor)
    one = lambda a: np.asarray(a)[0]
    return (jax.tree_util.tree_map(one, carry),
            jax.tree_util.tree_map(one, trace))


# ---------------------------------------------------------------------------
# dry-run / roofline hook
# ---------------------------------------------------------------------------

def lower_ordered_segment(engine: MeshCampaignEngine, fid: int = 8,
                          seg_blocks: int = 1):
    """Lower (no execute, no real buffers) one S1 shard_map segment of the
    widest bucket over ``engine.mesh`` with one member per device — the
    mesh-engine cell of the dry-run/roofline harness (launch/dryrun.py).

    Returns ``(lowered, meta)`` with the bucket/segment geometry; the caller
    compiles and feeds the HLO to ``hlo_analyzer.analyze``.
    """
    eng = engine.bucketed
    k = engine.kmax_exp
    seg_gens = int(seg_blocks) * eng.interval
    B = engine.n_devices
    runner = engine.ordered_runner(k, seg_gens, (fid,), cache={})

    inst = bbob.make_instance(fid, engine.n, 1, eng.full.cfg.jdtype)
    stacked1 = bbob.stack_instances([inst])
    insts_abs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((B,) + a.shape[1:], a.dtype), stacked1)
    keys_abs = jax.ShapeDtypeStruct((B, 2), jnp.uint32)
    carry_abs = jax.eval_shape(
        jax.vmap(eng.full.init_carry),
        jax.ShapeDtypeStruct((B, 2), jnp.uint32))
    lowered = runner.lower(keys_abs, insts_abs, carry_abs)
    meta = {"bucket": k, "lam_bucket": (2 ** k) * engine.lam_start,
            "seg_gens": seg_gens, "members": B,
            "n_devices": engine.n_devices, "strategy": "ordered"}
    return lowered, meta
