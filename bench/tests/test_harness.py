"""The harness is driven by files: a cell, a configuration, a traffic mix,
a traffic kind, a program library and a metric reader added as files run
with no edit to any existing file; a kind whose window reports other
metrics than it declares fails the run; without an accelerator the entry
point refuses to run."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny

# a program library of two constant-rate programs
FLAT_LIBRARY = '''"""Two programs at one rate each, every block alike."""
import numpy as np

NAMES = ("flat", "flat_mem")


def arrays(name, cfg):
    n = cfg["program_blocks"]
    mem = {"flat": 0.1, "flat_mem": 0.8}[name]
    return {"i0": np.full(n, 40.0, np.float32),
            "sens": np.full(n, 30.0, np.float32),
            "mem": np.full(n, mem, np.float32)}
'''

# a traffic kind that reports rows a second: the sweep kind's calls,
# found beside it by name
ROWS_KIND = '''"""Back-to-back sweep calls, reported as rows a second."""
from pathlib import Path

from bench import harness

sweep = harness.load_module(Path(__file__).with_name("sweep.py"))
END_TO_END = {end_to_end!r}
program_names = sweep.program_names


class Runner(sweep.Runner):
    def window(self, seconds, span):
        super().window(seconds, span)
        took = self.calls[-1][1] - self.calls[0][0]
        return {{"rows_per_s": (self.attempted / took, "rows/s")}}
'''


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


def _add_files(bench, files):
    for rel, text in files.items():
        (bench / rel).write_text(text if isinstance(text, str)
                                 else json.dumps(text))


def test_added_library_and_kind_run(tmp_path, monkeypatch):
    bench = tiny.make_tree(tmp_path)
    cfg = json.loads((bench / "configs/tiny_sweep.json").read_text())
    cfg.update(programs="tiny_flat", workloads=["flat", "flat_mem"])
    limits = json.loads((bench / "traffic/tiny_suite.json")
                        .read_text())["limits"]
    _add_files(bench, {
        "programs/tiny_flat.py": FLAT_LIBRARY,
        "traffic/rows.py": ROWS_KIND.format(end_to_end=("rows_per_s",
                                                        "setup_s")),
        "configs/tiny_flat.json": cfg,
        "traffic/tiny_rows.json": {"kind": "rows", "limits": limits},
        "workloads/tiny_rows.json": {"config": "tiny_flat",
                                     "traffic": "tiny_rows", "chips": 1}})
    rc, res, err = tiny.run_cell(bench, monkeypatch, "--workload",
                                 "tiny_rows", "--seed", str(2**32 + 5),
                                 "--seconds", "0.2", "--trace", "0")
    assert rc == 0 and res["correct"], err
    assert set(res["metrics"]) == {"rows_per_s", "setup_s"}
    assert res["metrics"]["rows_per_s"]["unit"] == "rows/s"
    assert res["attempted"] >= 22 and res["failed"] == 0
    # every file of the benchmark ran as it is
    for f in tiny.BENCH.rglob("*"):
        rel = f.relative_to(tiny.BENCH)
        if f.is_file() and rel.parts[0] != "tests" \
                and "__pycache__" not in rel.parts:
            assert (bench / rel).read_bytes() == f.read_bytes(), rel


def test_kind_reporting_undeclared_metrics_fails(tmp_path, monkeypatch):
    bench = tiny.make_tree(tmp_path)
    _add_files(bench, {
        "traffic/rows.py": ROWS_KIND.format(end_to_end=("cu_epochs_per_s",
                                                        "setup_s")),
        "traffic/tiny_rows.json": {"kind": "rows", "limits": {}},
        "workloads/tiny_rows.json": {"config": "tiny_sweep",
                                     "traffic": "tiny_rows", "chips": 1}})
    with pytest.raises(RuntimeError, match="declares END_TO_END"):
        tiny.run_cell(bench, monkeypatch, "--workload", "tiny_rows",
                      "--seed", "5", "--seconds", "0.05", "--trace", "0")


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
