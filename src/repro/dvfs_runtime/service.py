"""Streaming DVFS service: async micro-batched grid dispatch with
double-buffered donated carries.

The paper's premise makes fine-grain DVFS a *continuous* control problem;
at fleet scale the controller is a long-lived process absorbing a stream
of (job, telemetry) requests — the deadline-aware datacenter setting of
Ilager et al. (arXiv:2004.08177) is the canonical consumer. This module
turns the sweep substrate into that service:

* ``submit`` never blocks on the device: a request enqueues and resolves
  through a ``concurrent.futures.Future``;
* a dispatcher thread coalesces queued requests into micro-batches
  (up to ``max_batch`` jobs within a ``coalesce_s`` window), pads each
  batch to one of the executor's static shape ``buckets`` and dispatches
  it through the SAME shard_map'd grid executables ``run_grid`` compiles
  — so the whole stream is served by at most one compile per family
  (<= 2 fork-family compiles with the default single bucket; the
  ``run_grid`` no-retrace contract carried over to streaming) and every
  streamed row is bitwise-equal to the one-shot grid answer — at every
  batch size, including singletons (the executor floors dispatches at 2
  rows), and under either engine (a ``use_pallas="v2"`` config streams
  the fused-kernel grid engine and stays bitwise vs the one-shot v2
  grid);
* double buffering: a depth-``depth`` semaphore bounds in-flight batches,
  so batch N+1's operand staging, host->device ``jax.device_put`` and
  donated-carry build overlap batch N's compute — dispatch itself never
  calls ``block_until_ready``;
* a collector thread alone synchronizes: it harvests finished batches in
  dispatch order, cuts them into per-job traces, attaches manager-schema
  reports (``repro.dvfs_runtime.manager.point_report``) and resolves the
  futures.

* a service built by ``for_model`` also takes model steps
  (``submit_step``): the dispatcher derives each step's program from the
  model and the deployment (``telemetry.step_program``), once per
  distinct step, and stages it as it stages a submitted program.

``stats()`` reports sustained jobs/sec and job-latency percentiles, each
job timed from ``submit`` until its report is built and its future
resolved — the ``serve_stream`` benchmark record is built from exactly
these counters — and the programs derived so far.

Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock, a
few microseconds each with no profiler running), each tagged with the
batch number ``batch`` and its real job count ``jobs``: the dispatcher
writes ``repro.service.coalesce`` (first request taken until the batch
closes; ``batch`` only), ``repro.service.derive`` (a batch's steps turned
into programs; also ``misses``, the programs not found in the cache and
derived), ``repro.service.admit`` (waiting for an
in-flight slot) and ``repro.service.dispatch`` (staging and enqueue,
holding the sweep layer's ``repro.sweep.dispatch``); the collector writes
``repro.service.wait`` (device results ready), ``repro.service.unpack``
(the batch's output arrays fetched to the host, one ``repro.sweep.copy``
each, and cut there into per-job traces) and ``repro.service.report``
(reports built, futures resolved).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, Iterable, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import mechanisms as MECH
from repro.core.mechanisms import MechanismSpec
from repro.core.simulate import SimConfig
from repro.core.sweep import GridExecutor, PendingGrid
from repro.core.workloads import Program
from repro.dvfs_runtime import telemetry as TEL
from repro.dvfs_runtime.manager import StepLog, point_report
from repro.dvfs_runtime.telemetry import DEFAULT_DEPLOYMENT, Deployments, Step

Mechanism = Union[str, MechanismSpec]

_SHUTDOWN = object()


@dataclasses.dataclass
class _Request:
    program: Union[Program, Step]  # a Step until the dispatcher derives it
    axes: dict
    telemetry: Tuple[Tuple[int, float], ...]
    future: Future
    t_submit: float


class DVFSService:
    """A long-lived streaming front-end over one :class:`GridExecutor`.

    ``submit(program, axes, telemetry)`` returns a Future immediately; its
    result is ``{"traces", "report", "batch_size"}`` where
    ``traces`` is the job's ``{mechanism: trace}`` dict (bitwise-equal to
    a one-shot ``run_grid`` over the same job) and ``report`` is the
    manager-schema point report against the service baseline, including
    the request's own step-time telemetry stats.

    Shape-bucketing knobs: ``buckets`` is the set of static micro-batch
    shapes (default a single bucket of ``max_batch`` — one compile per
    family for the life of the process); ``coalesce_s`` is how long the
    dispatcher waits to fill a batch before dispatching short; ``depth``
    is the number of in-flight batches (2 = double buffering).
    """

    def __init__(self, static_cfg: SimConfig,
                 mechanism: Mechanism = "pcstall",
                 baseline: Mechanism = "static17", *,
                 max_batch: int = 8,
                 buckets: Optional[Sequence[int]] = None,
                 coalesce_s: float = 0.002,
                 depth: int = 2,
                 p_max: int = 1024,
                 n_dev: Optional[int] = None,
                 with_reports: bool = True):
        assert depth >= 1
        self.static_cfg = static_cfg
        self.baseline = MECH.resolve(baseline)
        self.mechanism = MECH.resolve(mechanism)
        specs = [self.baseline]
        if self.mechanism.name != self.baseline.name:
            specs.append(self.mechanism)
        if buckets is None:
            buckets = (max_batch,)
        self.executor = GridExecutor(static_cfg, specs, p_max=p_max,
                                     buckets=buckets, n_dev=n_dev)
        self.coalesce_s = coalesce_s
        self.depth = depth
        self.with_reports = with_reports
        # the model and deployment whose steps ``submit_step`` takes
        # (``for_model``), and the programs derived from them, by step
        self.model: Optional[ModelConfig] = None
        self.deployment: Deployments = DEFAULT_DEPLOYMENT
        self._programs: Dict[Step, tuple] = {}

        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._done: "queue.SimpleQueue" = queue.SimpleQueue()
        self._inflight = threading.BoundedSemaphore(depth)
        # reentrant: futures resolve under it (see _collect_loop), and a
        # done-callback may submit or read stats() from the collector
        self._lock = threading.RLock()
        self._lat: list = []
        self._batch_sizes: list = []
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._closed = False
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="dvfs-dispatch", daemon=True)
        self._collector = threading.Thread(
            target=self._collect_loop, name="dvfs-collect", daemon=True)
        self._dispatcher.start()
        self._collector.start()

    @classmethod
    def for_model(cls, cfg: ModelConfig,
                  dep: Deployments = DEFAULT_DEPLOYMENT,
                  objective: str = "ed2p", n_cu: int = 16,
                  sim: Optional[SimConfig] = None,
                  **kw) -> "DVFSService":
        """A service for the steps of model ``cfg`` on deployment ``dep``
        (one :class:`~repro.dvfs_runtime.telemetry.Deployment`, or one per
        step kind), sized like ``DVFSManager.for_model`` — same SimConfig,
        so a decode loop's requests ride the manager's numerics — unless
        ``sim`` is given."""
        if sim is None:
            sim = SimConfig(n_cu=n_cu, n_epochs=400, objective=objective)
        svc = cls(sim, **kw)
        svc.model, svc.deployment = cfg, dep
        return svc

    # ------------------------------------------------------------------
    # accept loop
    # ------------------------------------------------------------------

    def submit(self, program: Program, axes: Optional[dict] = None,
               telemetry: StepLog = ()) -> Future:
        """Enqueue one (job, telemetry) request. Never blocks on the
        device — returns a Future resolved by the collector thread."""
        fut: Future = Future()
        now = time.perf_counter()
        # the closed check and the enqueue share the lock with close() so
        # no request can slip in behind the shutdown token unresolved
        with self._lock:
            if self._closed:
                raise RuntimeError("DVFSService is closed")
            if self._t_first is None:
                self._t_first = now
            self._q.put(_Request(
                program, dict(axes or {}),
                tuple((int(s), float(t)) for s, t in telemetry), fut, now))
        return fut

    def submit_step(self, step: Step, axes: Optional[dict] = None,
                    telemetry: StepLog = ()) -> Future:
        """Enqueue one request for a step of the service's model: the
        dispatcher derives the step's program (once per distinct step)
        and stages it as ``submit`` stages a program."""
        if self.model is None:
            raise RuntimeError("submit_step needs a service built by "
                               "for_model")
        if not isinstance(step, Step):
            raise TypeError(f"not a Step: {step!r}")
        return self.submit(step, axes, telemetry)

    def step_program(self, step: Step) -> tuple:
        """``(ops, Program)`` of ``step``, derived once and cached."""
        with self._lock:
            hit = self._programs.get(step)
        if hit is None:
            hit = TEL.step_program(self.model, step, self.deployment)
            with self._lock:
                hit = self._programs.setdefault(step, hit)
        return hit

    def _derive(self, batch: list, tags: dict) -> None:
        """Replace each step of ``batch`` by its program."""
        steps = {r.program for r in batch if isinstance(r.program, Step)}
        if not steps:
            return
        with self._lock:
            misses = sum(s not in self._programs for s in steps)
        with jax.profiler.TraceAnnotation("repro.service.derive", **tags,
                                          misses=misses):
            for r in batch:
                if isinstance(r.program, Step):
                    r.program = self.step_program(r.program)[1]

    def map(self, requests: Iterable[tuple]) -> list:
        """Submit a whole request iterable, then gather results in order.
        Each request is ``(program, axes)`` or ``(program, axes,
        telemetry)``. Blocks only on the gather."""
        futs = [self.submit(*r) for r in requests]
        return [f.result() for f in futs]

    # ------------------------------------------------------------------
    # worker threads
    # ------------------------------------------------------------------

    def _dispatch_loop(self) -> None:
        max_batch = self.executor.max_batch
        n_batch = 0
        while True:
            req = self._q.get()
            if req is _SHUTDOWN:
                self._done.put(_SHUTDOWN)
                return
            n_batch += 1
            batch = [req]
            stop = False
            with jax.profiler.TraceAnnotation("repro.service.coalesce",
                                              batch=n_batch):
                deadline = time.perf_counter() + self.coalesce_s
                while max_batch is None or len(batch) < max_batch:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        nxt = self._q.get(timeout=remaining)
                    except queue.Empty:
                        break
                    if nxt is _SHUTDOWN:
                        stop = True
                        break
                    batch.append(nxt)
            tags = {"batch": n_batch, "jobs": len(batch)}
            try:
                self._derive(batch, tags)
            except Exception as e:  # a step with no program: fail the batch
                for r in batch:
                    r.future.set_exception(e)
                if stop:
                    self._done.put(_SHUTDOWN)
                    return
                continue
            # double buffering: at most `depth` dispatched batches alive —
            # this acquire is the ONLY backpressure, and it waits on the
            # collector (host-side), never on the device directly
            with jax.profiler.TraceAnnotation("repro.service.admit", **tags):
                self._inflight.acquire()
            try:
                with jax.profiler.TraceAnnotation("repro.service.dispatch",
                                                  **tags):
                    pending = self.executor.dispatch(
                        [(r.program, r.axes) for r in batch])
            except Exception as e:  # bad request: fail the batch, move on
                self._inflight.release()
                for r in batch:
                    r.future.set_exception(e)
            else:
                self._done.put((pending, batch, tags))
            if stop:
                self._done.put(_SHUTDOWN)
                return

    def _collect_loop(self) -> None:
        while True:
            item = self._done.get()
            if item is _SHUTDOWN:
                return
            pending, batch, tags = item
            pending: PendingGrid
            try:
                with jax.profiler.TraceAnnotation("repro.service.wait",
                                                  **tags):
                    pending.block_until_ready()
                with jax.profiler.TraceAnnotation("repro.service.unpack",
                                                  **tags):
                    traces = pending.traces()
            except Exception as e:
                for r in batch:
                    r.future.set_exception(e)
                self._inflight.release()
                continue
            self._inflight.release()
            with self._lock:
                self._batch_sizes.append(len(batch))
            with jax.profiler.TraceAnnotation("repro.service.report", **tags):
                for r, trs in zip(batch, traces):
                    res = {"traces": trs, "batch_size": len(batch)}
                    if self.with_reports:
                        epoch_us = float(r.axes.get(
                            "epoch_us", self.static_cfg.epoch_us))
                        res["report"] = point_report(
                            trs, epoch_us, self.baseline, self.mechanism,
                            self.static_cfg.power.n_freqs, r.telemetry)
                    # a job's latency ends once its caller can read the
                    # result; holding the lock across resolve and stamp
                    # keeps a caller woken by the future from reading
                    # stats() without its own job
                    with self._lock:
                        r.future.set_result(res)
                        t_done = time.perf_counter()
                        self._lat.append(t_done - r.t_submit)
                        self._t_last = t_done

    # ------------------------------------------------------------------
    # stats / lifecycle
    # ------------------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        """Sustained throughput + job-latency percentiles over every job
        resolved so far (latency = ``submit`` -> report built and future
        resolved), and the programs derived from model steps so far."""
        with self._lock:
            derived = len(self._programs)
            lat = np.asarray(self._lat, np.float64)
            sizes = list(self._batch_sizes)
            wall = (self._t_last - self._t_first) \
                if (self._t_first is not None and self._t_last is not None) \
                else 0.0
        n = int(lat.size)
        return {
            "jobs": n,
            "batches": len(sizes),
            "mean_batch": float(np.mean(sizes)) if sizes else 0.0,
            "wall_s": wall,
            "jobs_per_sec": n / wall if wall > 0 else 0.0,
            "p50_latency_s": float(np.percentile(lat, 50)) if n else 0.0,
            "p99_latency_s": float(np.percentile(lat, 99)) if n else 0.0,
            "max_latency_s": float(lat.max()) if n else 0.0,
            "programs_derived": derived,
        }

    def reset_stats(self) -> None:
        """Zero the throughput/latency counters (keep the compiled
        executables): benchmarks warm the service, reset, then measure
        steady-state only."""
        with self._lock:
            self._lat.clear()
            self._batch_sizes.clear()
            self._t_first = self._t_last = None

    def close(self) -> None:
        """Drain: everything submitted before ``close`` still resolves
        (FIFO ahead of the shutdown token), then both threads exit."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._q.put(_SHUTDOWN)
        self._dispatcher.join()
        self._collector.join()

    def __enter__(self) -> "DVFSService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
