"""Tests for the beyond-paper TPU DVFS integration (arch-derived traces)."""
import numpy as np
import pytest

from repro.configs import DECODE_32K, PREFILL_32K, TRAIN_4K, get_config
from repro.core.simulate import SimConfig
from repro.dvfs_runtime.manager import DVFSManager
from repro.dvfs_runtime.service import DVFSService
from repro.dvfs_runtime.telemetry import (Deployment, Step, arch_program,
                                          step_ops)


@pytest.mark.parametrize("arch", ["llama3-405b", "rwkv6-3b", "qwen2-moe-a2.7b"])
def test_arch_program_wellformed(arch):
    cfg = get_config(arch)
    prog = arch_program(cfg, TRAIN_4K)
    i0 = np.asarray(prog.i0_rate)
    s = np.asarray(prog.sens_rate)
    m = np.asarray(prog.mem_frac)
    assert i0.shape == s.shape == m.shape
    assert np.all(i0 >= 0) and np.all(s >= 0)
    assert np.all((m >= 0) & (m <= 1))
    assert s.max() > 0  # at least one compute-sensitive phase


def test_moe_has_async_collective_phase():
    cfg = get_config("qwen2-moe-a2.7b")
    names = [o[0] for o in step_ops(cfg, TRAIN_4K)]
    assert "moe_a2a" in names and "grad_reduce" in names


def test_decode_trace_differs_from_train():
    cfg = get_config("glm4-9b")
    pt = arch_program(cfg, TRAIN_4K)
    pd = arch_program(cfg, DECODE_32K)
    # decode is far more memory-bound than train
    assert float(np.mean(np.asarray(pd.mem_frac))) > \
        float(np.mean(np.asarray(pt.mem_frac)))


def test_manager_reports_energy_savings():
    cfg = get_config("glm4-9b")
    mgr = DVFSManager.for_model(cfg, TRAIN_4K, n_cu=8)
    rep = mgr.report()
    assert rep["ed2p_norm"] < 1.0  # objective improves vs static 1.7
    assert 0.5 < rep["energy_norm"] < 1.3
    assert rep["accuracy"] > 0.9  # step programs are highly repetitive
    assert abs(sum(rep["freq_timeshare"]) - 1.0) < 1e-2


def test_manager_report_well_formed_and_jit_cached():
    """report(): freq_timeshare is a distribution, metrics are finite, and
    repeated calls dispatch cached executables (no re-trace)."""
    from repro.core import power as PWR
    from repro.core import sweep as SW
    cfg = get_config("glm4-9b")
    mgr = DVFSManager.for_model(cfg, TRAIN_4K, n_cu=8)
    rep = mgr.report()
    # one histogram bin per V/f state of the simulator's ladder
    assert len(rep["freq_timeshare"]) == len(PWR.FREQS_GHZ)
    assert abs(sum(rep["freq_timeshare"]) - 1.0) < 1e-2
    assert all(x >= 0.0 for x in rep["freq_timeshare"])
    assert np.isfinite(rep["ed2p_norm"]) and np.isfinite(rep["accuracy"])
    before = dict(SW.TRACE_COUNTS)
    rep2 = mgr.report()
    assert dict(SW.TRACE_COUNTS) == before  # jit cache hit: no new compile
    assert rep2["ed2p_norm"] == pytest.approx(rep["ed2p_norm"])
    assert rep2["accuracy"] == pytest.approx(rep["accuracy"])


def test_manager_step_time_stats_in_reports():
    """observe_step records (step, seconds) PAIRS — the step index is not
    dropped — and step-time stats reach both report() and every
    grid_report() row (with mean_step_s kept as a back-compat alias)."""
    cfg = get_config("glm4-9b")
    mgr = DVFSManager.for_model(cfg, TRAIN_4K, n_cu=8)
    for step, dt in ((10, 0.02), (20, 0.04), (40, 0.06)):
        mgr.observe_step(step, dt)
    assert mgr.step_log == [(10, 0.02), (20, 0.04), (40, 0.06)]
    rep = mgr.report()
    st = rep["step_time"]
    assert st["n_steps"] == 3
    assert (st["first_step"], st["last_step"]) == (10, 40)
    assert st["mean_step_s"] == pytest.approx(0.04)
    assert st["p50_step_s"] == pytest.approx(0.04)
    assert st["p50_step_s"] <= st["p99_step_s"] <= 0.06 + 1e-12
    assert rep["mean_step_s"] == pytest.approx(0.04)  # back-compat alias
    for row in mgr.grid_report(epoch_us=(1.0, 10.0)).values():
        assert row["step_time"]["n_steps"] == 3
        assert row["mean_step_s"] == pytest.approx(0.04)


def test_manager_empty_step_log():
    """No telemetry observed: stats are well-formed zeros, not NaN."""
    cfg = get_config("glm4-9b")
    mgr = DVFSManager.for_model(cfg, TRAIN_4K, n_cu=8)
    rep = mgr.report()
    assert rep["mean_step_s"] == 0.0
    assert rep["step_time"]["n_steps"] == 0
    assert rep["step_time"]["first_step"] == -1


def test_manager_grid_report():
    """grid_report sweeps (epoch_us x objective) in one executable family
    and returns a well-formed report per grid point."""
    cfg = get_config("glm4-9b")
    mgr = DVFSManager.for_model(cfg, TRAIN_4K, n_cu=8)
    reps = mgr.grid_report(epoch_us=(1.0, 10.0),
                           objectives=("ed2p", "perfcap05"))
    assert set(reps) == {(1.0, "ed2p"), (1.0, "perfcap05"),
                         (10.0, "ed2p"), (10.0, "perfcap05")}
    for rep in reps.values():
        assert np.isfinite(rep["ed2p_norm"])
        assert abs(sum(rep["freq_timeshare"]) - 1.0) < 1e-2
    # the 1-point report matches the matching grid point
    one = mgr.report()
    assert one["ed2p_norm"] == pytest.approx(
        reps[(1.0, "ed2p")]["ed2p_norm"])


def _dsv3_units():
    gpu = dict(chips=1, redundant=32, weight_bytes=1, dispatch_bytes=1,
               combine_bytes=2, cache_bytes=2, peak_flops=989.5e12,
               fp8_flops=1979e12, hbm_bw=3.35e12, coll_bw=50e9)
    return {"prefill": Deployment(ep=32, dp=32, **gpu),
            "decode": Deployment(ep=144, dp=144, **gpu)}


def test_manager_for_model_takes_the_unit_of_its_step():
    """A deployment per step kind: the manager's program is its shape's
    unit's, and the default deployment keeps today's program."""
    cfg = get_config("deepseek-v3")
    units = _dsv3_units()
    for shape in (PREFILL_32K, DECODE_32K):
        mgr = DVFSManager.for_model(cfg, shape, units, n_cu=8)
        want = arch_program(cfg, shape, units[shape.kind])
        np.testing.assert_array_equal(np.asarray(mgr.program.sens_rate),
                                      np.asarray(want.sens_rate))
    glm = get_config("glm4-9b")
    np.testing.assert_array_equal(
        np.asarray(DVFSManager.for_model(glm, TRAIN_4K).program.i0_rate),
        np.asarray(arch_program(glm, TRAIN_4K).i0_rate))
    # an EP144 decode step spends most of its time in the all-to-all and
    # the latent cache: most of its blocks are bound by them
    ep = np.asarray(arch_program(cfg, DECODE_32K, units["decode"]).mem_frac)
    assert np.mean(ep == 1.0) > 0.9


def test_expert_parallel_deployment_checks_its_split():
    with pytest.raises(ValueError, match="chips must be 1"):
        Deployment(ep=32)
    with pytest.raises(ValueError, match="do not split"):
        Deployment(chips=1, ep=30).experts_held(256)
    with pytest.raises(ValueError, match="per-device 'train'"):
        step_ops(get_config("deepseek-v3"), TRAIN_4K, _dsv3_units()["decode"])
    with pytest.raises(ValueError, match="MLA attention only"):
        step_ops(get_config("glm4-9b"), DECODE_32K, _dsv3_units()["decode"])


def test_service_step_entry_derives_each_step_once():
    """``submit_step``: the dispatcher derives a step's program once, and
    the job's traces equal a ``submit`` of that program."""
    cfg = get_config("glm4-9b")
    sim = SimConfig(n_cu=4, n_wf=8, n_epochs=16)
    s1, s2 = Step("decode", 4, 1024), Step("decode", 4, 2048, load=2.0)
    with DVFSService.for_model(cfg, sim=sim, max_batch=2,
                               coalesce_s=0.001) as svc:
        futs = [svc.submit_step(s, {"epoch_us": 1.0}) for s in (s1, s2, s1)]
        res = [f.result(timeout=600) for f in futs]
        assert svc.stats()["programs_derived"] == 2
        ops, prog = svc.step_program(s1)
        direct = svc.submit(prog, {"epoch_us": 1.0}).result(timeout=600)
        assert svc.stats()["programs_derived"] == 2
    for m, tr in direct["traces"].items():
        for r in (res[0], res[2]):
            np.testing.assert_array_equal(r["traces"][m]["work"], tr["work"])
    np.testing.assert_array_equal(np.asarray(prog.i0_rate),
                                  np.asarray(arch_program(cfg, s1.shape).i0_rate))
    assert [o.name for o in ops] == [o.name for o in step_ops(cfg, s1.shape)]


def test_service_step_entry_refusals():
    sim = SimConfig(n_cu=4, n_wf=8, n_epochs=16)
    with DVFSService(sim, max_batch=2) as svc:
        with pytest.raises(RuntimeError, match="for_model"):
            svc.submit_step(Step("decode", 4, 1024))
    with DVFSService.for_model(get_config("deepseek-v3"), _dsv3_units(),
                               sim=sim, max_batch=2) as svc:
        with pytest.raises(TypeError, match="not a Step"):
            svc.submit_step(("decode", 4, 1024))
        # a step kind the deployment has no unit for fails its own future
        bad = svc.submit_step(Step("train", 4, 1024))
        with pytest.raises(KeyError):
            bad.result(timeout=600)
        ok = svc.submit_step(Step("decode", 128, 4096))
        assert ok.result(timeout=600)["batch_size"] == 1


def test_serve_loop_reports_its_own_decode_steps():
    from repro.configs import get_smoke_config
    from repro.launch.serve import serve
    rep = serve(get_smoke_config("glm4-9b"), batch=2, prompt_len=8, gen=4,
                dvfs=True, dvfs_stride=2)
    assert rep["dvfs_requests"] == 2
    # contexts 10 and 12: two distinct steps, each derived once
    assert rep["dvfs_stream"]["programs_derived"] == 2
    assert rep["dvfs"]["step_time"]["n_steps"] == 2
