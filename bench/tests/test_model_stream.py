"""The ``model_stream`` kind on the CPU at a tiny simulator size: a sound
run of DeepSeek-V3's step programs through ``DVFSService.for_model`` is
correct, its traced run reads the derive metrics, and ``correct`` comes
out false for each fault the stream kind's controls plant and for each
way the program could count the model wrongly."""
import dataclasses
import json

import numpy as np
import pytest

from bench.tests import test_control as CTL
from bench.tests import tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    bench = tiny.make_tree(tmp_path_factory.mktemp("tree"))
    cfg = json.loads((bench / "configs/dsv3_ep_service.json").read_text())
    cfg.update(tiny.TINY)
    # a coalescing window wide enough that the burst's batches hold
    # several jobs
    cfg["service"] = dict(cfg["service"], max_batch=4, coalesce_s=0.05)
    mix = json.loads((bench / "traffic/dsv3_pd.json").read_text())
    mix.update(rate_per_s=400.0, epoch_us=[1.0], trace_seconds=0.1,
               check_jobs=64, loads=[0.5, 2.0],
               limits=dict(mix["limits"], work_dev=1e-4, choice_gap=1e-4,
                           report_dev=1e-4))
    mix["decode"] = dict(mix["decode"], contexts=[1024, 32768],
                         weights=[0.5, 0.5])
    mix["prefill"] = dict(mix["prefill"], prefixes=[8192])
    for rel, obj in {
            "configs/tiny_dsv3.json": cfg,
            "traffic/tiny_pd.json": mix,
            "workloads/tiny_pd.json": {"config": "tiny_dsv3",
                                       "traffic": "tiny_pd", "chips": 1}
    }.items():
        (bench / rel).write_text(json.dumps(obj))
    return bench


def _run(tree, monkeypatch, trace="0", seed="2147483659"):
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload", "tiny_pd",
                                 "--seed", seed, "--seconds", "0.1",
                                 "--trace", trace)
    assert rc == 0 and res is not None, err
    return res, err


def test_sound_run_is_correct(tree, monkeypatch):
    res, err = _run(tree, monkeypatch)
    assert res["correct"], err
    assert set(res["metrics"]) == {"jobs_per_s", "job_p95_ms", "setup_s"}
    assert res["attempted"] == 40 and res["failed"] == 0
    checks = res["checks"]
    assert checks["program_dev"]["value"] == 0.0
    assert checks["program_blocks_differing"]["value"] == 0
    assert "programs built inside the window: 0" in err


def test_traced_run_reads_the_derive_span(tree, monkeypatch):
    res, err = _run(tree, monkeypatch, trace="1", seed="5")
    assert res["correct"], err
    m = res["metrics"]
    assert m["svc_derive_ms.model_stream"]["value"] > 0
    # every step was derived in the warm-up: the window derives none
    assert m["derive_misses.model_stream"]["value"] == 0
    # the stream kind's service readers, read under this kind's name
    for name in ("batch_fill", "svc_admit_ms", "svc_dispatch_ms",
                 "svc_collect_ms"):
        assert m[f"{name}.model_stream"]["value"] > 0, name
        assert f"{name}.stream" not in m


def _gqa(real):
    """Attention counted as GQA in place of MLA: each head's query, key
    and value projected from the hidden state, keys and values read from
    the cache at full head width."""
    from repro.dvfs_runtime import telemetry as TEL

    def mla_ops(cfg, B, C, S, decode, dep):
        d, T, H, Hkv = cfg.d_model, B * C, cfg.n_heads, cfg.n_kv_heads
        hd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        a, w, o, c = dep.dispatch_bytes, dep.weight_bytes, \
            dep.combine_bytes, dep.cache_bytes
        pairs = TEL._causal_pairs(B, C, S)
        return [TEL._mm("qkv_proj", T, d, (H + 2 * Hkv) * hd, a, w, o),
                TEL._rope(T, H, hd, dep),
                TEL.Op("attention", 4 * pairs * H * hd,
                       2 * T * H * hd * o + 2 * B * S * Hkv * hd * c),
                TEL._mm("o_proj", T, H * hd, d, a, w, o)]
    return mla_ops


def _all_experts(real):
    """Every routed expert's weights read on every step."""
    def step_ops(cfg, shape, dep):
        e, ops = cfg.moe, real(cfg, shape, dep)
        unread = e.num_experts + dep.redundant - dep.experts_held(
            e.num_experts)
        extra = unread * 3 * cfg.d_model * e.expert_d_ff * dep.weight_bytes
        return [o._replace(bytes=o.bytes + extra * cfg.n_moe_layers)
                if o.name == "moe.experts" else o for o in ops]
    return step_ops


def _v5e_peaks(real):
    """The TPU v5e's peaks in place of the deployment's GPU."""
    from repro.roofline.hlo_analysis import HBM_BW, ICI_BW, PEAK_FLOPS

    def ops_program(name, ops, dep, n_blocks=1024):
        return real(name, ops, dataclasses.replace(
            dep, peak_flops=PEAK_FLOPS, fp8_flops=PEAK_FLOPS, hbm_bw=HBM_BW,
            coll_bw=ICI_BW), n_blocks)
    return ops_program


COUNTING_FAULTS = {
    "gqa_in_place_of_mla": ("_mla_ops", _gqa, "program_dev"),
    "every_expert_read": ("step_ops", _all_experts, "program_dev"),
    "tpu_v5e_peaks": ("ops_program", _v5e_peaks, "program_blocks_differing"),
}


@pytest.mark.parametrize("fault", sorted(COUNTING_FAULTS))
def test_counting_fault_is_not_correct(tree, monkeypatch, fault):
    from repro.dvfs_runtime import telemetry as TEL
    attr, wrap, number = COUNTING_FAULTS[fault]
    monkeypatch.setattr(TEL, attr, wrap(getattr(TEL, attr)))
    res, _ = _run(tree, monkeypatch)
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", sorted(CTL.STREAM_FAULTS))
def test_stream_fault_is_not_correct(tree, monkeypatch, fault):
    from repro.core.sweep import PendingGrid
    real = PendingGrid.traces
    monkeypatch.setattr(PendingGrid, "traces",
                        lambda self: CTL.STREAM_FAULTS[fault](real(self)))
    res, _ = _run(tree, monkeypatch)
    assert not res["correct"]
    assert res["checks"]["program_blocks_differing"]["value"] == 0


def test_library_names_parse_to_their_steps():
    from bench import harness
    lib = harness.load_module(tiny.BENCH / "programs/deepseek_v3.py")
    assert len(lib.NAMES) == len(set(lib.NAMES)) == 27
    for name in lib.NAMES:
        assert lib.name_of(*lib.step(name)) == name
    kinds = [lib.step(n)[0] for n in lib.NAMES]
    assert kinds.count("decode") == 18 and kinds.count("prefill") == 9
    assert np.all([lib.step(n)[3] == 4096 for n in lib.NAMES
                   if n.startswith("prefill")])
