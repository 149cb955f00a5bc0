"""A throwaway benchmark tree for the CPU tests: a copy of ``bench/`` with
a tiny sweep cell, a tiny stream cell and one more metric reader added as
files, and a way to run a cell of it in this process on the CPU."""
from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

# Few enough epochs that no wavefront wraps its program loop: past a wrap
# the noise hash's argument takes terms whose rounding the CPU compiler
# fuses differently from one program shape to another, and the hash
# amplifies one ulp into a different noise value.
TINY = {"n_cu": 4, "n_wf": 8, "n_epochs": 16}


def make_tree(tmp: Path) -> Path:
    """``tmp/bench``: the benchmark plus the tiny cells, added as files."""
    dst = tmp / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    sweep = json.loads((BENCH / "configs/gcn64_1us_sweep.json").read_text())
    sweep.update(TINY, workloads=["comd", "FwdSoft"])
    svc = json.loads((BENCH / "configs/gcn16_service.json").read_text())
    svc.update(TINY)
    svc["service"] = dict(svc["service"], max_batch=4)
    limits = {"work_dev": 1e-4, "energy_dev": 1e-4, "choice_gap": 1e-4,
              "report_dev": 1e-4}
    files = {
        "configs/tiny_sweep.json": sweep,
        "configs/tiny_service.json": svc,
        "traffic/tiny_suite.json": {"kind": "sweep", "limits": limits},
        "traffic/tiny_stream.json": {
            "kind": "stream", "rate_per_s": 40.0, "workloads": ["comd", "lulesh"],
            "epoch_us": [1.0], "objectives": ["ed2p"],
            "steps_per_request": 4, "trace_seconds": 0.5, "check_jobs": 3,
            "limits": limits},
        "traffic/tiny_burst.json": {
            "kind": "stream", "rate_per_s": 400.0, "workloads": ["comd", "lulesh"],
            "epoch_us": [1.0], "objectives": ["ed2p"],
            "steps_per_request": 4, "trace_seconds": 0.1, "check_jobs": 6,
            "limits": limits},
        "workloads/tiny_burst.json": {"config": "tiny_service",
                                      "traffic": "tiny_burst", "chips": 1},
        "workloads/tiny_suite.json": {"config": "tiny_sweep",
                                      "traffic": "tiny_suite", "chips": 1},
        "workloads/tiny_stream.json": {"config": "tiny_service",
                                       "traffic": "tiny_stream", "chips": 1},
    }
    for rel, obj in files.items():
        (dst / rel).write_text(json.dumps(obj))
    (dst / "metrics/calls_seen.tiny.py").write_text(
        'UNIT = "calls"\n\n\ndef read(ctx):\n'
        '    return len(ctx.trace.span("call")) or None\n')
    return dst


def run_cell(bench: Path, monkeypatch, *args):
    """Run a cell of ``bench`` on the CPU in this process: returns (exit
    code, last stdout line as JSON or None, stderr). JAX's in-memory caches
    are emptied first, as a run's own process starts with them empty:
    after other tests have filled them, the window's calls were seen to
    trace their programs again."""
    import jax
    from bench import harness
    jax.clear_caches()
    monkeypatch.setattr(harness, "use_cache", lambda root: None)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = harness.run(list(args), bench=bench,
                         device_check=lambda chips: jax.devices()[:chips])
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
