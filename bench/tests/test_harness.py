"""The harness is driven by files: a cell, a configuration, a traffic mix
and a metric reader added as files run with no edit to any existing file;
without an accelerator the entry point refuses to run."""
import os
import subprocess
import sys

import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("tree"))


def test_added_sweep_cell_runs(tree, monkeypatch):
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload", "tiny_suite",
                                 "--seed", str(2**31 + 7), "--seconds", "0.2",
                                 "--trace", "0")
    assert rc == 0 and res["correct"], err
    assert set(res["metrics"]) == {"cu_epochs_per_s", "setup_s"}
    assert res["attempted"] >= 22 and res["failed"] == 0
    assert res["checks"]["rows_differing_across_calls"]["value"] == 0
    assert "programs built inside the window: 0" in err


def test_added_metric_reads_traced_run(tree, monkeypatch):
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload", "tiny_suite",
                                 "--seed", "3", "--seconds", "0.2",
                                 "--trace", "1")
    assert rc == 0 and res["correct"], err
    assert res["metrics"]["calls_seen.tiny"]["value"] >= 1
    assert "cu_epochs_per_s" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0


def test_added_stream_cell_runs(tree, monkeypatch):
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload",
                                 "tiny_stream", "--seed", "11",
                                 "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"], err
    assert set(res["metrics"]) == {"jobs_per_s", "job_p95_ms", "setup_s"}
    assert res["attempted"] == 40 and res["failed"] == 0
    assert "generator lateness" in err
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload",
                                 "tiny_stream", "--seed", "12",
                                 "--seconds", "1", "--trace", "1")
    assert rc == 0 and res["correct"], err
    assert 0 < res["metrics"]["batch_fill.stream"]["value"] <= 100


def test_run_refuses_without_accelerator():
    root = tiny.BENCH.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "suite_1us", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
