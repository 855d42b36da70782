"""Open-loop service traffic: jobs arrive by a Poisson clock at a fixed
rate and are submitted to one ``CampaignServer`` when they fall due.

The harness drives ``CampaignServer.step`` itself and submits, between
steps, every job whose due time has passed.  Each job is timed from its
due time to the step after which its ticket is done, on the harness's
clock; the generator's lateness (submit − due) is reported beside it.
Arrivals run for ``--seconds``; then no more are made, and the run
drains for at most ``drain_s``.  A job not done by then, or ended in any
other state, has failed.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from bench import checks as checks_mod
from bench.common import log
from reference import bbob_ref


def arrivals(seed: int, seconds: float, traffic: Dict[str, Any],
             cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The open-loop schedule.  Every seed gets the same work in another
    order: N = round(``rate_per_s``·seconds) jobs whose arrival times are N
    sorted uniform draws over [0, seconds) (a Poisson process given its
    count); the jobs split over the lanes by their weights, and within a
    lane cycle through the fid menu and the ``instances`` range.  The seed
    draws the arrival times, shuffles the jobs over them and draws each
    job's key.  Budget ``budget_per_dim``·n, target f_opt +
    ``target_gap``."""
    rng = np.random.default_rng([int(seed) % 2 ** 63, 11])
    total = int(round(float(traffic["rate_per_s"]) * seconds))
    weights = np.asarray(traffic["dim_weights"], np.float64)
    counts = np.floor(total * weights / weights.sum()).astype(int)
    counts[0] += total - counts.sum()
    fids = [int(f) for f in cfg["fids"]]
    lo, hi = traffic["instances"]
    work = [(int(n), fids[j % len(fids)], lo + (j // len(fids)) % (hi - lo + 1))
            for n, c in zip(traffic["dims"], counts) for j in range(c)]
    rng.shuffle(work)
    due = np.sort(rng.uniform(0.0, seconds, total))
    return [{"due": float(at), "dim": n, "fid": fid, "instance": inst,
             "budget": int(traffic["budget_per_dim"]) * n,
             "seed": int(rng.integers(0, 2 ** 31)),
             "target": bbob_ref.instance(fid, n, inst).f_opt
             + float(traffic["target_gap"])}
            for at, (n, fid, inst) in zip(due, work)]


class ServiceCell:
    """Set-up, window and comparison of a service cell."""

    def __init__(self, cell, devices):
        from repro.service import CampaignServer
        self.cell = cell
        self.cfg = dict(cell.config["system"])
        self.traffic = cell.traffic
        self.devices = devices
        c = self.cfg
        self.server = CampaignServer(
            bbob_fids=tuple(c["fids"]), lam_start=int(c["lam_start"]),
            kmax_exp=int(c["kmax_exp"]), dtype=c["dtype"], impl=c["impl"],
            domain=tuple(c["domain"]), sigma0_frac=float(c["sigma0_frac"]),
            max_budget=int(c["max_budget"]),
            rows_per_island=int(c["rows_per_island"]),
            max_pending=int(c.get("max_pending", 4096)),
            devices=devices[:1])
        self.jobs: List[Dict[str, Any]] = []

    def _request(self, job):
        from repro.service import CampaignRequest
        return CampaignRequest(dim=job["dim"], fid=job["fid"],
                               instance=job["instance"], budget=job["budget"],
                               seed=job["seed"], target=job["target"])

    def warm_up(self) -> None:
        """Every program the window runs: the instances of each (fid, n),
        admission and retirement in each lane, and each lane's segment
        programs for every bucket, at the length the engine gives a bucket
        that opens with its whole budget ahead."""
        from repro.fitness import bbob
        from repro.service import server as server_mod
        lo, hi = self.traffic["instances"]
        for n in self.traffic["dims"]:
            for fid in self.cfg["fids"]:
                bbob.make_instance(int(fid), int(n), 1)
                for inst in range(lo, hi + 1):      # the client's targets
                    bbob_ref.instance(int(fid), int(n), inst)
        warm = []
        for n in self.traffic["dims"]:
            for j, fid in enumerate(self.cfg["fids"]):
                job = {"dim": int(n), "fid": int(fid), "instance": 1,
                       "budget": int(self.traffic["budget_per_dim"]) * int(n),
                       "seed": 1000 + j,
                       "target": bbob_ref.instance(int(fid), int(n), 1).f_opt
                       + float(self.traffic["target_gap"])}
                warm.append(self.server.submit(self._request(job)))
        self.server.drain()
        for lane in self.server.lanes.values():
            for k in range(int(self.cfg["kmax_exp"]) + 1):
                seg = lane.engine.bucket_seg_gens(k)
                for isl in lane.islands:
                    a = isl.arrays
                    out, _tr = lane.runner(k, seg)(
                        a["keys"], a["fn_idx"], a["budgets"], a["insts"],
                        a["carry"])
                    server_mod.bucketed.pull_schedule(out)
        for t in warm:
            self.server.release_ticket(t.job_id)
        log(f"warm-up: {len(warm)} jobs, statuses "
            f"{sorted({t.status for t in warm})}, lanes "
            f"{len(self.server.lanes)}, programs "
            f"{self.server.segment_compiles()}")

    def window(self, seconds: float, seed: int, trace=None) -> Dict[str, Any]:
        """Arrivals for ``seconds``, then the drain.  ``trace`` (a
        ``devtrace.TraceWindow``) is moved on after each server step."""
        srv = self.server
        sched = arrivals(seed, seconds, self.traffic, self.cfg)
        drain_s = float(self.traffic["drain_s"])
        t0 = time.perf_counter()
        if trace is not None:
            trace.begin(t0)
        live: Dict[int, Dict[str, Any]] = {}
        nxt = 0
        depth = []
        steps = 0
        end_arrivals = t0 + seconds
        while True:
            now = time.perf_counter()
            while nxt < len(sched) and t0 + sched[nxt]["due"] <= now:
                job = dict(sched[nxt])
                job["submit_s"] = now - t0
                job["ticket"] = srv.submit(self._request(job))
                live[job["ticket"].job_id] = job
                self.jobs.append(job)
                nxt += 1
            if nxt >= len(sched) and not live:
                break
            if now > end_arrivals + drain_s:
                break
            if not live:
                time.sleep(min(0.002, max(0.0, t0 + sched[nxt]["due"] - now)))
                continue
            srv.step()
            steps += 1
            if trace is not None:
                trace.tick(time.perf_counter())
                paused = trace.pause()      # the arrival clock stands still
                t0 += paused
                end_arrivals += paused
            after = time.perf_counter() - t0
            depth.append((after, len(srv.queue)))
            for jid, job in list(live.items()):
                t = job["ticket"]
                if "first_update_s" not in job and t.updates:
                    job["first_update_s"] = after
                if t.terminal:
                    job["done_s"] = after
                    job["status"] = t.status
                    del live[jid]
        t_end = time.perf_counter() - t0
        for job in live.values():
            job["status"] = "unfinished"
        late = [j["submit_s"] - j["due"] for j in self.jobs]
        log(f"window: {len(self.jobs)} jobs due in {seconds}s, "
            f"{sum(j.get('status') == 'done' for j in self.jobs)} done, "
            f"run ended at {t_end:.2f}s after {steps} steps; generator "
            f"lateness p50 {np.median(late) if late else 0:.4f}s, max "
            f"{max(late) if late else 0:.4f}s; queue depth max "
            f"{max((d for _t, d in depth), default=0)}")
        return {"window_s": float(seconds), "run_s": t_end, "depth": depth,
                "late": late, "steps": steps}

    def end_to_end(self, win, delta) -> Dict[str, float]:
        lat = self.latencies()
        return {"job_p50_s": percentile(lat, 50),
                "job_p95_s": percentile(lat, 95)}

    def traced_hlo(self):
        return []

    def latencies(self) -> np.ndarray:
        """Due → done per job; failed jobs read infinity."""
        return np.asarray([j["done_s"] - j["due"]
                           if j.get("status") == "done" else np.inf
                           for j in self.jobs], np.float64)

    def first_updates(self) -> np.ndarray:
        return np.asarray([j["first_update_s"] - j["due"]
                           if "first_update_s" in j else np.inf
                           for j in self.jobs], np.float64)

    def attempted_failed(self):
        return len(self.jobs), sum(j.get("status") != "done"
                                   for j in self.jobs)

    def compare(self, seed: int, limits) -> Dict[str, Dict]:
        import jax
        cpu = bbob_ref.cpu()
        c = self.cfg
        domain, sigma0 = checks_mod.search_box(c)
        members = []
        for job in self.jobs:
            if job.get("status") != "done":
                continue
            res = job["ticket"].result
            with jax.default_device(cpu):
                key = np.asarray(jax.random.PRNGKey(job["seed"]), np.uint32)
            descents = [checks_mod.Descent(
                k=int(d.k_exp), lam=int(d.lam),
                gens=np.asarray(d.gens, np.int64),
                fevals=np.asarray(d.fevals, np.int64),
                best_f=np.asarray(d.best_f, np.float64))
                for d in res.descents]
            members.append(checks_mod.Member(
                key=key, fid=job["fid"], inst=job["instance"], n=job["dim"],
                lam_start=int(c["lam_start"]), kmax=int(c["kmax_exp"]),
                budget=job["budget"], sigma0=sigma0, domain=domain,
                descents=descents,
                best_x=np.asarray(res.best_x), best_f=float(res.best_f),
                total_fevals=int(res.total_fevals), finished=True))
        log(f"comparing {len(members)} jobs, "
            f"{sum(len(m.descents) for m in members)} descents")
        self.last_members = members
        return checks_mod.evaluate(members, [], limits)

    def release(self):
        self.jobs.clear()


def percentile(x: np.ndarray, q: float) -> float:
    """Nearest-rank percentile; a failed job (infinity) counts as missing
    every limit."""
    if x.size == 0:
        return float("nan")
    return float(np.percentile(x, q, method="higher"))
