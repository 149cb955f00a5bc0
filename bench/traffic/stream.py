"""Traffic kind ``stream``: a DVFS manager querying the service from a
serving loop. An open loop submits (job, telemetry) requests to
``DVFSService.submit`` at their due times, whatever the service's state.

The requests are the service's request stream (``gen.request_stream``),
drawn from the seed; the due times are exponential gaps at the mix's
``rate_per_s`` (``gen.arrival_offsets``), the same set of gaps for every
seed in a seeded order, spanning the window.

End-to-end: ``jobs_per_s``, the jobs completed over the seconds from the
first due time to the last completion; ``job_p95_ms``, the 95th percentile
(nearest rank) over all jobs of the time from a job's due time to the
resolution of its future, report included; a job that fails or never
comes counts as missing.

Correctness: every job resolves; the jobs of a sample drawn from the seed
are replayed by the reference (``check.py``), both mechanisms of each,
and their reported ED^2P ratio is recomputed from the replay.
"""
from __future__ import annotations

import math
import threading
import time
from concurrent.futures import wait
from typing import List

import numpy as np

from bench import check as CHK
from bench import gen
from bench import program as PRG

END_TO_END = ("jobs_per_s", "job_p95_ms", "setup_s")


def program_names(cfg: dict, mix: dict) -> List[str]:
    """The programs a cell of this kind draws from its library."""
    return list(mix["workloads"])


class Runner:
    def __init__(self, cfg: dict, mix: dict, library, n_dev: int):
        self.cfg, self.mix, self.library, self.n_dev = cfg, mix, library, n_dev
        self.attempted = self.failed = 0
        self.svc = None
        self.jobs: List[dict] = []
        self.late: List[float] = []

    def _requests(self, n: int, seed: int):
        m = self.mix
        return list(gen.request_stream(n, seed, m["workloads"], m["epoch_us"],
                                       m["objectives"],
                                       m["steps_per_request"]))

    def setup(self, seed: int) -> None:
        from repro.dvfs_runtime.service import DVFSService
        svc = self.cfg["service"]
        self.seed = seed
        self.arrays = PRG.program_arrays(self.library, self.cfg,
                                         program_names(self.cfg, self.mix))
        self.progs = PRG.programs(self.arrays)
        self.sim = PRG.sim_config(self.cfg, seed)
        self.svc = DVFSService(
            self.sim, svc["mechanism"], svc["baseline"],
            max_batch=svc["max_batch"], depth=svc["depth"],
            coalesce_s=svc["coalesce_s"], with_reports=svc["with_reports"],
            n_dev=self.n_dev)
        # warm-up at the window's one batch shape: two full batches, so
        # both in-flight slots and the per-job report path have run
        warm = self._requests(2 * svc["max_batch"], seed ^ 0x3A3A)
        futs = [self.svc.submit(self.progs[w], ax, tel) for w, ax, tel in warm]
        for f in futs:
            f.result(timeout=600)

    def trace_seconds(self, seconds: float) -> float:
        return min(seconds, self.mix["trace_seconds"])

    def window(self, seconds: float, span) -> dict:
        n = int(round(self.mix["rate_per_s"] * seconds))
        reqs = self._requests(n, self.seed)
        due = gen.arrival_offsets(n, seconds, self.seed)
        done_at = [math.inf] * n
        lock = threading.Lock()

        def stamp(i):
            def cb(_):
                with lock:
                    done_at[i] = time.perf_counter()
            return cb

        futs = []
        t0 = time.perf_counter()
        for i, (w, ax, tel) in enumerate(reqs):
            target = t0 + due[i]
            with span("generator"):
                pause = target - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
            self.late.append(time.perf_counter() - target)
            with span("submit"):
                fut = self.svc.submit(self.progs[w], ax, tel)
            fut.add_done_callback(stamp(i))
            futs.append(fut)
        with span("collect"):
            wait(futs, timeout=max(0.0, t0 + seconds + 60.0
                                   - time.perf_counter()))
        lat = []
        for i, (f, (w, ax, tel)) in enumerate(zip(futs, reqs)):
            ok = f.done() and f.exception() is None
            res = f.result() if ok else None
            with lock:
                t_done = done_at[i]
            lat.append(t_done - (t0 + due[i]) if ok else math.inf)
            self.jobs.append({"w": w, "axes": ax, "res": res, "lat": lat[-1]})
        self.attempted = n
        self.failed = sum(1 for x in lat if math.isinf(x))
        finished = [done_at[i] for i in range(n) if not math.isinf(lat[i])]
        elapsed = (max(finished) if finished else math.inf) - t0
        rank = max(1, math.ceil(0.95 * n))
        return {"jobs_per_s": (len(finished) / elapsed, "jobs/s"),
                "job_p95_ms": (sorted(lat)[rank - 1] * 1e3, "ms")}

    def notes(self) -> List[str]:
        late = np.asarray(self.late) * 1e3
        return [f"generator lateness ms: median {np.median(late):.4f}, "
                f"p99 {np.percentile(late, 99):.4f}, max {late.max():.4f} "
                f"over {late.size} requests"]

    def counters(self) -> dict:
        return {"kind": "stream", "max_batch": self.cfg["service"]["max_batch"],
                "batch_sizes": [j["res"]["batch_size"] for j in self.jobs
                                if j["res"] is not None]}

    def close(self) -> None:
        if self.svc is not None:
            self.svc.close()
            self.svc = None

    def sample(self):
        """Jobs drawn from the seed, both mechanisms of each: ``(reference
        rows, traces, reported ED^2P ratios)``."""
        svc = self.cfg["service"]
        done = [j for j in self.jobs if j["res"] is not None]
        rng = np.random.default_rng((self.seed, 2))
        pick = sorted(rng.choice(len(done), replace=False,
                                 size=min(self.mix["check_jobs"], len(done))))
        rows, traces, reports = [], [], []
        for i in pick:
            j = done[i]
            for m in (svc["baseline"], svc["mechanism"]):
                rows.append((j["w"], m, j["axes"]["epoch_us"],
                             j["axes"]["objective"]))
                traces.append(j["res"]["traces"][m])
            reports.append(j["res"]["report"]["ed2p_norm"])
        return rows, traces, reports

    def check(self) -> List[tuple]:
        if not any(j["res"] is not None for j in self.jobs):
            return [("jobs_answered", 0, 1)]
        rows, traces, reports = self.sample()
        return CHK.compare(self.arrays, rows, self.cfg, self.seed, traces,
                           reports, self.mix["limits"])
