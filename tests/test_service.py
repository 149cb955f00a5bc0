"""Streaming-service tests: micro-batched streamed dispatch must be a
bitwise re-dispatch of the existing grid graph (``grid_reference.npz`` is
the frozen contract — NO re-capture), padding to a static bucket must be
invisible in results, oob requests must fail fast, and the async service
must serve a stream with <= 2 fork-family compiles end to end."""
import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import sweep as SW
from repro.core.simulate import SimConfig
from repro.core.sweep import GridExecutor, run_grid
from repro.core.workloads import get_workload, make_program
from repro.data.pipeline import dvfs_request_stream
from repro.dvfs_runtime.service import DVFSService

SIM = SimConfig(n_cu=16, n_wf=12, n_epochs=48)
WORKLOADS = ("comd", "xsbench")
MECHS = ("static17", "crisp", "pcstall", "oracle")
# the reference's grid2x2 case re-expressed as a request stream: one job
# per (workload, epoch_us, objective), in capture order
GRID2X2_JOBS = [(wl, {"epoch_us": e, "objective": o})
                for e in (1.0, 10.0) for o in ("ed2p", "edp")
                for wl in WORKLOADS]


@pytest.fixture(scope="module")
def progs():
    return {w: get_workload(w) for w in WORKLOADS}


def _reference():
    path = Path(__file__).parent / "data" / "grid_reference.npz"
    ref = np.load(path)
    meta = json.loads(bytes(ref["__meta__"]))
    exact = (meta["jax"] == jax.__version__
             and meta["backend"] == jax.default_backend()
             and meta["n_dev"] == jax.local_device_count())
    return ref, exact


def _assert_vs_ref(got, ref, exact, key):
    if exact:
        np.testing.assert_array_equal(np.asarray(got), ref[key], err_msg=key)
    else:
        np.testing.assert_allclose(np.asarray(got), ref[key],
                                   rtol=1e-5, atol=1e-5, err_msg=key)


def test_streamed_micro_batches_bitwise_vs_captured_reference(progs):
    """Acceptance: the grid2x2 reference case, re-expressed as a stream of
    single-job requests and dispatched in micro-batches of 3 padded to a
    static bucket of 4, reproduces the captured one-shot ``run_grid``
    traces bitwise (on the capturing platform; 1e-5 otherwise). The
    stream must ride the existing dispatch graph — the reference file is
    NOT re-captured."""
    ref, exact = _reference()
    ex = GridExecutor(SIM, MECHS, buckets=(4,))
    jobs = [(progs[wl], ov) for wl, ov in GRID2X2_JOBS]
    results = []
    for i in range(0, len(jobs), 3):  # 8 jobs -> batches of 3, 3, 2
        results.extend(ex.run(jobs[i:i + 3]))
    n = 0
    for (wl, ov), trs in zip(GRID2X2_JOBS, results):
        key = (ov["epoch_us"], ov["objective"])
        for m in MECHS:
            for ch, v in trs[m].items():
                _assert_vs_ref(v, ref, exact,
                               f"grid2x2|{key!r}|{wl}|{m}|{ch}")
                n += 1
    # full coverage: every captured grid2x2 array for these mechanisms
    # was compared against a streamed row
    want = sum(1 for k in ref.files
               if k.startswith("grid2x2|") and k.split("|")[3] in MECHS)
    assert n == want > 0


def test_executor_padding_smaller_than_bucket(progs):
    """A micro-batch smaller than its static shape: pad rows (cycled jobs)
    are dropped on unpack — same per-job rows as an exact-size dispatch,
    and the batch shape (not the job count) keys the jit cache."""
    ex = GridExecutor(SIM, ("pcstall",), buckets=(8,))
    jobs = [(progs["comd"], {"epoch_us": 1.0}),
            (progs["xsbench"], {"epoch_us": 10.0}),
            (progs["comd"], {"epoch_us": 50.0})]
    pending = ex.dispatch(jobs)
    assert pending.n_jobs == 3
    padded = pending.traces()
    assert len(padded) == 3
    exact = GridExecutor(SIM, ("pcstall",)).run(jobs)  # buckets=None
    for a, b, (_, ov) in zip(padded, exact, jobs):
        for ch in a["pcstall"]:
            np.testing.assert_allclose(
                a["pcstall"][ch], b["pcstall"][ch], rtol=1e-5, atol=1e-5,
                err_msg=f"{ov}/{ch}")


def test_executor_oob_requests(progs):
    """Requests the static shapes cannot admit fail fast at dispatch."""
    ex = GridExecutor(SIM, ("pcstall",), p_max=1024, buckets=(2,))
    job = (progs["comd"], {})
    with pytest.raises(AssertionError, match="exceeds the largest"):
        ex.dispatch([job, job, job])  # batch > largest bucket
    with pytest.raises(AssertionError, match="not a traced grid axis"):
        ex.dispatch([(progs["comd"], {"n_cu": 8})])
    with pytest.raises(AssertionError, match="exceeds the executor"):
        ex.dispatch([(progs["comd"], {"n_epochs": SIM.n_epochs + 1})])
    small = GridExecutor(SIM, ("pcstall",), p_max=256, buckets=(2,))
    with pytest.raises(AssertionError, match="blocks"):
        small.dispatch([(progs["comd"], {})])  # 1024-block program
    big = make_program("small_svc", "phased", 5, P=256)
    small.run([(big, {})])  # within p_max: fine


def test_service_stream_two_fork_family_compiles_and_bitwise(progs):
    """Acceptance: a whole async request stream (trickled submits, forced
    coalescing into short micro-batches) is served by <= 2 fork-family
    compiles (TRACE_COUNTS) and every streamed row equals the one-shot
    ``run_grid`` answer for the same jobs. Uses a SimStatic no other test
    shares (n_wf=10) so the compile count is established in-test."""
    sim = dataclasses.replace(SIM, n_wf=10)
    before = dict(SW.TRACE_COUNTS)
    with DVFSService(sim, mechanism="oracle", baseline="pcstall",
                     max_batch=3, coalesce_s=0.005) as svc:
        futs = [svc.submit(progs[wl], ov) for wl, ov in GRID2X2_JOBS]
        results = [f.result(timeout=600) for f in futs]
        stats = svc.stats()
    fork = {k: SW.TRACE_COUNTS[k] - before.get(k, 0)
            for k in ("grid_forks", "grid_oracle")}
    assert 1 <= sum(fork.values()) <= 2, fork
    assert stats["jobs"] == len(GRID2X2_JOBS)
    assert stats["batches"] >= 3  # max_batch bounds coalescing
    ref = run_grid(progs, sim, {"epoch_us": [1.0, 10.0],
                                "objective": ["ed2p", "edp"]},
                   ("pcstall", "oracle"))
    for (wl, ov), res in zip(GRID2X2_JOBS, results):
        want = ref[(ov["epoch_us"], ov["objective"])][wl]
        for m in ("pcstall", "oracle"):
            for ch, v in want[m].items():
                np.testing.assert_array_equal(
                    np.asarray(res["traces"][m][ch]), np.asarray(v),
                    err_msg=f"{wl}/{ov}/{m}/{ch}")
        rep = res["report"]
        assert rep["step_time"]["n_steps"] == 0
        assert abs(sum(rep["freq_timeshare"]) - 1.0) < 1e-2


def test_service_async_api_and_lifecycle(progs):
    """submit never blocks on the device, futures carry the report with
    the request's own telemetry stats, stats() latency percentiles are
    ordered and inside the time the caller waited, close() drains FIFO,
    and a closed service rejects submits."""
    t0 = time.perf_counter()
    svc = DVFSService(SIM, max_batch=4, coalesce_s=0.001)
    futs = [svc.submit(progs["comd"], {"epoch_us": float(e)},
                       telemetry=[(i, 0.01 * (i + 1)) for i in range(3)])
            for e in (1.0, 2.0, 5.0)]
    assert not all(f.done() for f in futs)  # async: accept loop returned
    svc.close()  # drains: everything submitted above still resolves
    waited = time.perf_counter() - t0
    for f in futs:
        res = f.result(timeout=60)
        assert 1 <= res["batch_size"] <= 4
        st = res["report"]["step_time"]
        assert st["n_steps"] == 3
        assert (st["first_step"], st["last_step"]) == (0, 2)
        np.testing.assert_allclose(st["mean_step_s"], 0.02)
        np.testing.assert_allclose(res["report"]["mean_step_s"], 0.02)
    stats = svc.stats()
    assert stats["jobs"] == 3 and stats["jobs_per_sec"] > 0
    assert 0 < stats["p50_latency_s"] <= stats["p99_latency_s"] \
        <= stats["max_latency_s"] < waited
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(progs["comd"])
    svc.close()  # idempotent


def test_service_propagates_bad_request_errors(progs):
    """A bad request fails its own future (the whole batch it coalesced
    into), and the service keeps serving afterwards."""
    with DVFSService(SIM, max_batch=1, coalesce_s=0.0) as svc:
        bad = svc.submit(progs["comd"], {"n_cu": 4})  # static, not an axis
        with pytest.raises(AssertionError, match="not a traced grid axis"):
            bad.result(timeout=60)
        good = svc.submit(progs["comd"], {"epoch_us": 1.0})
        assert "traces" in good.result(timeout=600)


def test_dvfs_request_stream_deterministic():
    """The pipeline's request stream is counter-based: same seed replays
    bit-identically (programs, axes, telemetry), different seeds differ."""
    a = list(dvfs_request_stream(6, seed=3))
    b = list(dvfs_request_stream(6, seed=3))
    c = list(dvfs_request_stream(6, seed=4))
    for (pa, xa, ta), (pb, xb, tb) in zip(a, b):
        assert pa.name == pb.name and xa == xb and ta == tb
    assert any(xa != xc or ta != tc or pa.name != pc.name
               for (pa, xa, ta), (pc, xc, tc) in zip(a, c))
    for prog, axes, tel in a:
        assert set(axes) <= {"epoch_us", "objective"}
        assert len(tel) == 4 and all(t > 0 for _, t in tel)


def test_executor_batch1_bitwise_vs_oneshot(progs):
    """Satellite acceptance: a batch-1 flat dispatch (buckets=None, one
    job) is padded to the executor's 2-row bucket floor — a 1-row leading
    axis lets XLA fuse it away and codegen f32 chains at a shifted last
    ulp, which silently broke the bitwise streamed-vs-one-shot contract
    for singleton requests. Per-job executor rows must now equal the
    multi-row ``run_grid`` answer EXACTLY."""
    mechs = ("pcstall", "crisp")
    ex = GridExecutor(SIM, mechs)          # buckets=None: flat dispatch
    ref = run_grid(progs, SIM, {"epoch_us": [1.0, 10.0],
                                "objective": ["ed2p", "edp"]}, mechs)
    for wl, ov in GRID2X2_JOBS:
        res = ex.run([(progs[wl], ov)])[0]     # batch of ONE
        want = ref[(ov["epoch_us"], ov["objective"])][wl]
        for m in mechs:
            for ch, v in want[m].items():
                np.testing.assert_array_equal(
                    np.asarray(res[m][ch]), np.asarray(v),
                    err_msg=f"{wl}/{ov}/{m}/{ch}")


def test_executor_streams_v2_engine_bitwise_vs_oneshot_v2(progs):
    """Tentpole thread-through: a GridExecutor built on a ``use_pallas='v2'``
    SimConfig inherits the fused-kernel engine — streamed micro-batches
    equal the one-shot v2 ``run_grid`` bitwise, served by <= 2 new
    fork-family compiles. Uses a SimStatic no other test shares
    (n_wf=14 + v2) so the compile delta is established in-test."""
    sim = dataclasses.replace(SIM, n_wf=14, use_pallas="v2")
    mechs = ("pcstall", "crisp")
    SW.reset_counters()
    ex = GridExecutor(sim, mechs, buckets=(4,))
    jobs = [(progs[wl], ov) for wl, ov in GRID2X2_JOBS]
    results = []
    for i in range(0, len(jobs), 3):
        results.extend(ex.run(jobs[i:i + 3]))
    ref = run_grid(progs, sim, {"epoch_us": [1.0, 10.0],
                                "objective": ["ed2p", "edp"]}, mechs)
    fork = {k: v for k, v in SW.TRACE_COUNTS.items()
            if k in ("grid_forks", "grid_oracle")}
    assert 1 <= sum(fork.values()) <= 2, fork
    for (wl, ov), res in zip(GRID2X2_JOBS, results):
        want = ref[(ov["epoch_us"], ov["objective"])][wl]
        for m in mechs:
            for ch, v in want[m].items():
                np.testing.assert_array_equal(
                    np.asarray(res[m][ch]), np.asarray(v),
                    err_msg=f"{wl}/{ov}/{m}/{ch}")
