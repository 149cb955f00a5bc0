"""Traffic kind ``sweep``: a user re-running a figure grid. Back-to-back
``run_suite`` calls (the sweep layer's entry, one 1-point ``run_grid``) over
the deployment's workloads x mechanisms, each call ending with every trace
on the host.

End-to-end: ``cu_epochs_per_s``, the simulated CU-epochs (rows x CUs x
epochs) of every call of the window over the window's seconds. The window
starts at the first call and ends with the last call that began before
``--seconds`` ran out.

Correctness: every call answers every row with finite traces; every call
answers the same bits (the inputs and the noise seed are the same); and
one row per mechanism, its workload drawn from the seed, is replayed by
the reference (``check.py``).
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from bench import check as CHK
from bench import program as PRG

END_TO_END = ("cu_epochs_per_s", "setup_s")
CHANNELS = ("work", "energy", "fidx")


def program_names(cfg: dict, mix: dict) -> List[str]:
    """The programs a cell of this kind draws from its library."""
    return list(cfg["workloads"])


class Runner:
    def __init__(self, cfg: dict, mix: dict, library, n_dev: int):
        self.cfg, self.mix, self.library, self.n_dev = cfg, mix, library, n_dev
        self.attempted = self.failed = 0
        self.calls: List[tuple] = []
        self.results: List[dict] = []

    def setup(self, seed: int) -> None:
        from repro.core import sweep as SW
        self.seed = seed
        self.arrays = PRG.program_arrays(self.library, self.cfg,
                                         program_names(self.cfg, self.mix))
        self.progs = PRG.programs(self.arrays)
        self.sim = PRG.sim_config(self.cfg, seed)
        self.mechs = tuple(self.cfg["mechanisms"])
        self.rows = len(self.progs) * len(self.mechs)
        # one warm call at the window's shapes: it compiles (or loads) every
        # executable family the window runs
        SW.run_suite(self.progs, self.sim, self.mechs)

    def reseed(self, seed: int) -> None:
        """A new noise seed, with no new warm call: the seed is a traced
        value of the same executables."""
        self.seed, self.sim = seed, PRG.sim_config(self.cfg, seed)
        self.calls, self.results = [], []

    def trace_seconds(self, seconds: float) -> float:
        return seconds

    def window(self, seconds: float, span) -> dict:
        from repro.core import sweep as SW
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ts = time.perf_counter()
            with span("call"):
                res = SW.run_suite(self.progs, self.sim, self.mechs)
            self.calls.append((ts, time.perf_counter()))
            self.results.append(res)
        elapsed = self.calls[-1][1] - t0
        self.attempted = len(self.calls) * self.rows
        work = self.attempted * self.cfg["n_cu"] * self.cfg["n_epochs"]
        return {"cu_epochs_per_s": (work / elapsed, "CU-epochs/s")}

    def notes(self) -> List[str]:
        secs = [round(b - a, 4) for a, b in self.calls]
        return [f"{len(self.calls)} calls of {self.rows} rows, seconds: {secs}"]

    def counters(self) -> dict:
        return {"kind": "sweep", "n_epochs": self.cfg["n_epochs"],
                "n_calls": len(self.calls)}

    def close(self) -> None:
        self.progs = None

    def _row(self, res, w, m):
        tr = res.get(w, {}).get(m)
        if tr is None or any(k not in tr for k in CHANNELS):
            return None
        shape = (self.cfg["n_epochs"], self.cfg["n_cu"])
        if any(np.shape(tr[k]) != shape or not np.all(np.isfinite(tr[k]))
               for k in CHANNELS):
            return None
        return tr

    def sample(self):
        """One row per mechanism of the last call, each with a workload
        drawn from the seed: ``(reference rows, traces, None)``."""
        rng = np.random.default_rng((self.seed, 1))
        names = list(self.cfg["workloads"])
        rows = [(names[int(rng.integers(len(names)))], m) for m in self.mechs]
        traces = [self._row(self.results[-1], w, m) for w, m in rows]
        return ([(w, m, self.cfg["epoch_us"], self.cfg["objective"])
                 for w, m in rows], traces, None)

    def check(self) -> List[tuple]:
        first, differing = self.results[0], 0
        for res in self.results:
            for w in self.cfg["workloads"]:
                for m in self.mechs:
                    tr, tr0 = self._row(res, w, m), self._row(first, w, m)
                    if tr is None:
                        self.failed += 1
                    elif tr0 is not None and not all(
                            np.array_equal(tr[k], tr0[k]) for k in CHANNELS):
                        differing += 1
        rows, traces, _ = self.sample()
        return [("rows_differing_across_calls", differing, 0)] + \
            CHK.compare(self.arrays, rows, self.cfg, self.seed, traces,
                        None, self.mix["limits"])
