"""Traffic kind ``model_stream``: a fleet DVFS controller queried per GPU
step by a model's serving deployment. The stream kind's open loop
(``stream.py``, loaded as it is), with each request a step of the model
(``DVFSService.submit_step``) in place of a library program: the service
is built by ``DVFSService.for_model`` from the deployment file's model
(``"model"``, a name of the program's config registry) and its units, and
derives each step's program itself.

Request ``i`` is drawn from ``(seed, i)`` alone: its step (decode with the
mix's ``decode.share``, at a context drawn by ``decode.weights``, else a
prefill chunk on a prefix drawn uniformly), its routed load (uniform over
``loads``), its axes and telemetry as the stream kind draws them. A step's
name in the library is its program's name.

End-to-end metrics are the stream kind's. Correctness adds, ahead of the
stream kind's replay of the sampled jobs against the library's arrays:

* ``program_dev``: the largest relative gap, over every step the cell
  draws (``program_names``) and its op classes, between the FLOPs, HBM
  bytes and all-to-all bytes the service's program counted
  (``telemetry.step_ops``) and the library's copy (an op class one side
  lacks reads 1);
* ``program_blocks_differing``: blocks of those steps' programs whose
  rates or memory share differ from the library's arrays in any bit.
"""
from __future__ import annotations

from pathlib import Path
from typing import List

import numpy as np

from bench import harness

stream = harness.load_module(Path(__file__).with_name("stream.py"))
END_TO_END = stream.END_TO_END


def _decode_names(library, mix):
    d = mix["decode"]
    return [library.name_of("decode", d["batch"], c, 1, x)
            for c in d["contexts"] for x in mix["loads"]]


def _prefill_names(library, mix):
    p = mix["prefill"]
    return [library.name_of("prefill", p["batch"], pre + p["chunk"],
                            p["chunk"], x)
            for pre in p["prefixes"] for x in mix["loads"]]


def program_names(cfg: dict, mix: dict) -> List[str]:
    """The steps a cell of this kind draws from its library."""
    library = harness.load_module(Path(__file__).parents[1] / "programs"
                                  / f"{cfg['programs']}.py")
    return _decode_names(library, mix) + _prefill_names(library, mix)


def deployment(cfg: dict) -> dict:
    """``{step kind: telemetry.Deployment}`` of the deployment file."""
    from repro.dvfs_runtime.telemetry import Deployment
    g, by = cfg["gpu"], cfg["bytes"]
    return {kind: Deployment(
        peak_flops=g["bf16_flops"], fp8_flops=g["fp8_flops"],
        hbm_bw=g["hbm_bw"], coll_bw=g["all_to_all_bw"], chips=1,
        ep=u["ep"], dp=u["dp"], redundant=cfg["redundant_experts"],
        weight_bytes=by["weight"], dispatch_bytes=by["dispatch"],
        combine_bytes=by["combine"], cache_bytes=by["cache"])
        for kind, u in cfg["units"].items()}


class _Steps:
    """The service seen through its step entry, as the stream kind's loop
    submits to it."""

    def __init__(self, service):
        self.service = service

    def submit(self, step, axes, telemetry):
        return self.service.submit_step(step, axes, telemetry)

    def close(self):
        self.service.close()


def program_deviations(ops_got, ops_want):
    """Largest relative gap between two op lists' counts, by op class."""
    got = {o[0]: o[1:4] for o in ops_got}
    want = {o[0]: o[1:4] for o in ops_want}
    worst = 0.0
    for name in set(got) | set(want):
        a, b = got.get(name, (0, 0, 0)), want.get(name, (0, 0, 0))
        for x, y in zip(a, b):
            x, y = float(x), float(y)
            if x != y:
                worst = max(worst, abs(x - y) / max(abs(x), abs(y)))
    return worst


class Runner(stream.Runner):
    service = None
    derived_before = derived_in_window = 0

    def _requests(self, n: int, seed: int) -> list:
        return list(self._draw(n, seed))

    def _draw(self, n: int, seed: int):
        m = self.mix
        dec, pre = _decode_names(self.library, m), \
            _prefill_names(self.library, m)
        n_loads = len(m["loads"])
        w = np.asarray(m["decode"]["weights"], np.float64)
        for i in range(n):
            rng = np.random.default_rng((seed, i))
            if rng.random() < m["decode"]["share"]:
                pick = int(rng.choice(len(w), p=w / w.sum()))
                names = dec
            else:
                pick = int(rng.integers(len(m["prefill"]["prefixes"])))
                names = pre
            name = names[pick * n_loads + int(rng.integers(n_loads))]
            axes = {"epoch_us": float(m["epoch_us"][int(rng.integers(
                        len(m["epoch_us"])))]),
                    "objective": m["objectives"][int(rng.integers(
                        len(m["objectives"])))]}
            telemetry = tuple(
                (i * m["steps_per_request"] + s, float(rng.gamma(2.0, 0.005)))
                for s in range(m["steps_per_request"]))
            yield name, axes, telemetry

    def setup(self, seed: int) -> None:
        from repro.configs import get_config
        from repro.dvfs_runtime.service import DVFSService
        from repro.dvfs_runtime.telemetry import Step
        from bench import program as PRG
        svc = self.cfg["service"]
        self.seed = seed
        names = program_names(self.cfg, self.mix)
        self.arrays = PRG.program_arrays(self.library, self.cfg, names)
        self.sim = PRG.sim_config(self.cfg, seed)
        self.progs = {n: Step(*self.library.step(n)) for n in names}
        self.service = DVFSService.for_model(
            get_config(self.cfg["model"]), deployment(self.cfg),
            sim=self.sim, mechanism=svc["mechanism"],
            baseline=svc["baseline"], max_batch=svc["max_batch"],
            depth=svc["depth"], coalesce_s=svc["coalesce_s"],
            with_reports=svc["with_reports"], n_dev=self.n_dev)
        self.svc = _Steps(self.service)
        # warm-up: every step once (each program derived), then two full
        # batches, so both in-flight slots and the report path have run
        warm = [(n, {"epoch_us": self.mix["epoch_us"][0],
                     "objective": self.mix["objectives"][0]}, ())
                for n in names]
        warm += self._requests(2 * svc["max_batch"], seed ^ 0x3A3A)
        futs = [self.svc.submit(self.progs[w], ax, tel) for w, ax, tel in warm]
        for f in futs:
            f.result(timeout=600)
        self.derived_before = self.service.stats()["programs_derived"]

    def window(self, seconds: float, span) -> dict:
        out = super().window(seconds, span)
        self.derived_in_window = self.service.stats()["programs_derived"] \
            - self.derived_before
        return out

    def counters(self) -> dict:
        return dict(super().counters(), kind="model_stream",
                    derived_in_window=self.derived_in_window)

    def check(self) -> List[tuple]:
        if not any(j["res"] is not None for j in self.jobs):
            return [("jobs_answered", 0, 1)]
        lim = self.mix["limits"]
        dev, differing = 0.0, 0
        for name in sorted(self.progs):
            ops, prog = self.service.step_program(self.progs[name])
            dev = max(dev, program_deviations(
                ops, self.library.ops(name, self.cfg)))
            want = [self.arrays[name][k] for k in ("i0", "sens", "mem")]
            got = [np.asarray(a, np.float32) for a in
                   (prog.i0_rate, prog.sens_rate, prog.mem_frac)]
            if any(g.shape != w.shape for g, w in zip(got, want)):
                differing += want[0].size
                continue
            same = np.logical_and.reduce(
                [g.view(np.uint32) == w.view(np.uint32)
                 for g, w in zip(got, want)])
            differing += int((~same).sum())
        return [("program_dev", dev, lim["program_dev"]),
                ("program_blocks_differing", differing,
                 lim["program_blocks_differing"])] + super().check()

