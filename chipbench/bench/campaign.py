"""Closed-loop campaign traffic: whole IPOP campaigns back to back.

Each campaign is the configuration's BBOB menu × the traffic's instances ×
runs, run through ``bucketed.run_campaign_bucketed`` with a key drawn from
``--seed`` and the campaign's index.  The window closes at
the first segment boundary at or after ``--seconds``: the campaign then in
flight stops there, and the window's evaluations are the members' own,
summed from the budget counters pulled at the boundaries.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import checks as checks_mod
from bench.common import log


class StopWindow(Exception):
    """Raised at the first segment boundary past the window's end."""


@dataclasses.dataclass
class Segment:
    k: int
    gens: int
    fn: Any                  # the jitted segment program
    args: tuple              # what it was given (device-resident)
    traced: bool = False     # ran while the profiler recorded
    out: Any = None          # (carry, trace), device-resident

    @property
    def carry_in(self):
        return self.args[-1]


@dataclasses.dataclass
class Campaign:
    seed: int
    keys: Any
    members: list
    dispatched: List[Segment] = dataclasses.field(default_factory=list)
    stopped_on: Any = None   # the carry the window stopped the campaign on
    result: Any = None

    @property
    def segments(self) -> List[Segment]:
        """The segments the driver kept, in order.  Each kept segment runs
        on the carry the previous one returned; where several were
        dispatched on one carry (a speculative segment whose bucket missed,
        then the right one), the last is the one kept.  The chain ends at
        the driver's count of segments, or at the carry the window stopped
        on."""
        kept: List[Segment] = []
        if not self.dispatched:
            return kept
        carry = self.dispatched[0].carry_in
        count = (len(self.result.segments) if self.result is not None
                 else None)
        while carry is not self.stopped_on and (count is None
                                                or len(kept) < count):
            on = [s for s in self.dispatched if s.carry_in is carry]
            if not on:
                break
            kept.append(on[-1])
            carry = on[-1].out[0]
        return kept


def campaign_seed(seed: int, index: int) -> int:
    """A 32-bit key seed for campaign ``index`` of run ``seed``."""
    return int(np.random.SeedSequence([int(seed) % 2 ** 63, index])
               .generate_state(1)[0])


class Watch:
    """Wraps each segment program: records what it was given and returned,
    moves the trace window on, and stops the campaign at the first boundary
    past the deadline."""

    def __init__(self):
        self.deadline: Optional[float] = None
        self.stopped_at: Optional[float] = None
        self.campaign: Optional[Campaign] = None
        self.trace = None

    def wrap(self, fn, k: int, gens: int):
        def run(*args):
            now = time.perf_counter()
            if self.deadline is not None and now >= self.deadline:
                self.stopped_at = now
                if self.campaign is not None:
                    self.campaign.stopped_on = args[-1]
                raise StopWindow
            seg = Segment(k=k, gens=gens, fn=fn, args=args)
            if self.trace is not None:
                seg.traced = self.trace.tick(now)
                self.deadline += self.trace.pause()
            seg.out = fn(*args)
            if self.campaign is not None:
                self.campaign.dispatched.append(seg)
            return seg.out
        return run

    def campaign_start(self) -> None:
        """A campaign boundary: the traced sub-window may open here, so
        that it holds the campaign's keys, instances and init."""
        if self.trace is not None:
            self.trace.tick(time.perf_counter(), "campaign")


def _engine_class(watch: Watch):
    from repro.core import bucketed

    class Engine(bucketed.BucketedLadderEngine):
        def segment_runner(self, k, branch_fids, seg_gens):
            return watch.wrap(super().segment_runner(k, branch_fids, seg_gens),
                              k, seg_gens)
    return Engine


def _layout(cfg, traffic):
    fids = tuple(traffic.get("fids", cfg["fids"]))
    instances = tuple(range(1, int(traffic["instances"]) + 1))
    runs = int(traffic.get("runs", 1))
    return fids, instances, runs


def _keys(seed: int, count: int):
    """The members' base keys, as ``run_campaign_bucketed`` derives them
    (computed on the host's CPU device)."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        base = jax.random.PRNGKey(seed)
        return np.stack([np.asarray(jax.random.fold_in(base, j))
                         for j in range(count)])


class CampaignCell:
    """Set-up, window and comparison of a campaign cell."""

    def __init__(self, cell, devices):
        self.cell = cell
        self.cfg = dict(cell.config["system"])
        self.traffic = cell.traffic
        self.devices = devices
        self.watch = Watch()
        self.fids, self.instances, self.runs = _layout(self.cfg, self.traffic)
        self.members = [(f, i, r) for f in self.fids for i in self.instances
                        for r in range(self.runs)]
        self.campaigns: List[Campaign] = []
        c = self.cfg
        self.engine = _engine_class(self.watch)(
            n=int(c["n"]), lam_start=int(c["lam_start"]),
            kmax_exp=int(c["kmax_exp"]), max_evals=int(c["max_evals"]),
            impl=c["impl"], dtype=c["dtype"], domain=tuple(c["domain"]),
            sigma0_frac=float(c["sigma0_frac"]))

    # -- one campaign through the system's entry point ----------------------
    def _campaign(self, seed: int) -> Campaign:
        camp = Campaign(seed=seed, keys=None, members=self.members)
        self.watch.campaign = camp
        self.campaigns.append(camp)
        self.watch.campaign_start()
        from repro.core import bucketed
        camp.result = bucketed.run_campaign_bucketed(
            self.engine, fids=self.fids, instances=self.instances,
            runs=self.runs, seed=seed)
        return camp

    def warm_up(self) -> None:
        """Compile (or load from the cache) every program the window runs:
        the campaign's init, its instances, and the segment programs of the
        buckets the traffic file lists, at the length the engine gives a
        bucket that opens with its whole budget ahead, each run once on a
        fresh carry."""
        import jax
        import jax.numpy as jnp

        from repro.core import bucketed
        from repro.fitness import bbob
        eng = self.engine
        t0 = time.perf_counter()
        # the campaign's own key path, on the default device
        base = jax.random.PRNGKey(campaign_seed(0, 0))
        keys = jnp.stack([jax.random.fold_in(base, j)
                          for j in range(len(self.members))])
        insts = [bbob.make_instance(f, eng.n, i, eng.full.cfg.jdtype)
                 for (f, i, _r) in self.members]
        stacked = bbob.stack_instances(insts)
        carry = jax.block_until_ready(eng._init_runner(keys))
        log(f"warm-up: keys, {len(insts)} instances and init "
            f"{time.perf_counter() - t0:.3f}s")
        branch = tuple(sorted(set(self.fids)))
        for k in self.traffic["warm_buckets"]:
            t0 = time.perf_counter()
            seg = eng.bucket_seg_gens(int(k))
            out, _tr = eng.segment_runner(int(k), branch, seg)(
                keys, stacked, carry)
            bucketed.pull_schedule(out)
            log(f"warm-up: bucket {k}, {seg} generations "
                f"{time.perf_counter() - t0:.3f}s")
        self.campaigns.clear()

    def window(self, seconds: float, seed: int, trace=None) -> Dict[str, Any]:
        """Campaigns back to back until the first boundary past
        ``seconds``; returns the window's length and campaigns.  ``trace``
        (a ``devtrace.TraceWindow``) is moved on at each boundary."""
        t0 = time.perf_counter()
        self.watch.deadline = t0 + float(seconds)
        self.watch.stopped_at = None
        self.watch.trace = trace
        if trace is not None:
            trace.begin(t0)
        index = 0
        try:
            while True:
                self._campaign(campaign_seed(seed, index))
                index += 1
                if time.perf_counter() >= self.watch.deadline:
                    self.watch.stopped_at = time.perf_counter()
                    break
        except StopWindow:
            pass
        finally:
            self.watch.deadline = None
            self.watch.campaign = None
            self.watch.trace = None
        t1 = self.watch.stopped_at or time.perf_counter()
        segs = [s for c in self.campaigns for s in c.segments]
        gens = sum(s.gens for s in segs)
        log(f"window {t1 - t0:.3f}s: {len(self.campaigns)} campaign(s), "
            f"{len(segs)} segments, {gens} generations, programs "
            f"{sorted({(s.k, s.gens) for s in segs})}")
        return {"window_s": t1 - t0, "generations": gens,
                "traced_generations": sum(s.gens for s in segs if s.traced),
                "campaigns": len(self.campaigns)}

    @staticmethod
    def end_to_end(win, delta) -> Dict[str, float]:
        """Members' own evaluations over the window."""
        useful = float(delta.get("bucketed_useful_evals_total", 0.0))
        return {"evals_per_s": useful / win["window_s"]}

    def traced_hlo(self):
        """HLO text of the segment programs that ran while the profiler
        recorded (from the compile cache, after the window)."""
        import jax
        seen, texts = set(), []
        for c in self.campaigns:
            for s in c.dispatched:
                if s.traced and (s.k, s.gens) not in seen:
                    seen.add((s.k, s.gens))
                    shapes = jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        s.args)
                    texts.append(s.fn.lower(*shapes).compile().as_text())
        return texts

    # -- after the window -----------------------------------------------------
    def update_work(self):
        """(operations, bytes) of ``cma_gen_update`` in the traced
        segments, at each running member's own n and λ (no padding, no
        parked rows)."""
        from bench.peaks import cma_gen_update_work
        ops = nbytes = 0.0
        n = int(self.cfg["n"])
        lam0 = int(self.cfg["lam_start"])
        for c in self.campaigns:
            for s in c.segments:
                if not s.traced:
                    continue
                _carry, tr = s.out
                ran = np.asarray(tr.ran).reshape(-1)
                k = np.asarray(tr.k_idx).reshape(-1)[ran]
                for kk, cnt in zip(*np.unique(k, return_counts=True)):
                    o, b = cma_gen_update_work(n, lam0 * 2 ** int(kk))
                    ops += o * cnt
                    nbytes += b * cnt
        return ops, nbytes

    def _members(self, camp: Campaign, carry, rows) -> List:
        c = self.cfg
        domain, sigma0 = checks_mod.search_box(c)
        fin = camp.result is not None
        out = []
        best_x = np.asarray(carry.best_x)
        best_f = np.asarray(carry.best_f)
        total = np.asarray(carry.total_fevals)
        for j, (fid, inst, _r) in enumerate(camp.members):
            descents = checks_mod.descents_from_rows(
                rows["ran"][j, :, 0], rows["k_idx"][j, :, 0],
                rows["gen"][j, :, 0], rows["fevals"][j, :, 0],
                rows["best_f"][j, :, 0], int(c["lam_start"]))
            out.append(checks_mod.Member(
                key=camp.keys[j], fid=fid, inst=inst, n=int(c["n"]),
                lam_start=int(c["lam_start"]), kmax=int(c["kmax_exp"]),
                budget=int(c["max_evals"]), sigma0=sigma0, domain=domain,
                descents=descents,
                best_x=best_x[j], best_f=float(best_f[j]),
                total_fevals=int(total[j]), finished=fin))
        return out

    def compare(self, seed: int, limits) -> Dict[str, Dict]:
        """Every member of every campaign in the window, and a sample of
        segment boundaries drawn from the seed, against the reference."""
        rng = np.random.default_rng([int(seed) % 2 ** 63, 7])
        members, samples = [], []
        per_campaign = int(self.traffic.get("boundary_samples", 4))
        for camp in self.campaigns:
            segments = camp.segments
            if not segments:
                continue
            camp.keys = _keys(camp.seed, len(camp.members))
            trs = [s.out[1] for s in segments]
            rows = {f: np.concatenate([np.asarray(getattr(t, f)) for t in trs],
                                      axis=1)
                    for f in ("ran", "k_idx", "gen", "fevals", "best_f")}
            final = segments[-1].out[0]
            mem = self._members(camp, final, rows)
            members += mem
            # boundary states: the final one, and the starts of a sample of
            # segments (whose first generation the trace records)
            picks = rng.choice(len(segments),
                               size=min(per_campaign, len(segments)),
                               replace=False)
            offsets = np.cumsum([0] + [s.gens for s in segments])
            for p in sorted(int(x) for x in picks):
                samples += self._boundary(mem, segments[p].carry_in,
                                          rows, int(offsets[p]))
            samples += self._boundary(mem, final, rows, None)
        log(f"comparing {len(members)} members, "
            f"{sum(len(m.descents) for m in members)} descents, "
            f"{len(samples)} boundary states")
        self.last_members, self.last_samples = members, samples
        return checks_mod.evaluate(members, samples, limits)

    def _boundary(self, members, carry, rows, t: Optional[int]):
        st = carry.states
        arr = {f: np.asarray(getattr(st, f)) for f in
               ("m", "sigma", "C", "B", "D", "best_f", "gen")}
        k_idx = np.asarray(carry.k_idx)
        inc = np.asarray(carry.incarnation)
        out = []
        for j, mem in enumerate(members):
            after = None
            if t is not None:
                if t >= rows["ran"].shape[1] or not rows["ran"][j, t, 0]:
                    continue
                after = float(rows["best_f"][j, t, 0])
            out.append(checks_mod.StateSample(
                member=mem, k=int(k_idx[j, 0]), incarnation=int(inc[j, 0]),
                gen=int(arr["gen"][j, 0]), m=arr["m"][j, 0],
                sigma=float(arr["sigma"][j, 0]), C=arr["C"][j, 0],
                B=arr["B"][j, 0], D=arr["D"][j, 0],
                best_before=float(arr["best_f"][j, 0]), best_after=after,
                final=t is None))
        return out

    def attempted_failed(self):
        n = sum(len(c.members) for c in self.campaigns if c.dispatched)
        return n, 0

    def release(self):
        self.campaigns.clear()

