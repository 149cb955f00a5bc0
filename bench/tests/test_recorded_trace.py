"""The trace reduction and the per-layer readers on small traces recorded
on a TPU v5e (``record_trace.py``: the tiny sweep and stream cells of
``tiny.py``, traced): every reader and the ``device`` and ``breakdown``
entries read back what the recording run printed, and the numbers hold
together."""
import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import harness

DATA = Path(__file__).resolve().parent / "data"
CELLS = {"tiny_suite": {"calls_seen.tiny", "device_idle_share.suite",
                        "grid_prep_ms.suite", "grid_unpack_ms.suite",
                        "scan_step_ms.suite"},
         "tiny_stream": {"batch_fill.stream", "device_idle_share.stream",
                         "job_device_ms.stream"}}


def _read(cell, tmp_path):
    pb = tmp_path / f"{cell}.xplane.pb"
    with gzip.open(DATA / f"{cell}.xplane.pb.gz", "rb") as f, \
            open(pb, "wb") as g:
        shutil.copyfileobj(f, g)
    counters = json.loads((DATA / f"{cell}.counters.json").read_text())
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH / "metrics", bench / "metrics")
    # the recording tree's one added reader
    (bench / "metrics/calls_seen.tiny.py").write_text(
        'UNIT = "calls"\n\n\ndef read(ctx):\n'
        '    return len(ctx.trace.span("call")) or None\n')
    got = harness.read_trace(str(pb), counters, bench)
    return got, json.loads((DATA / f"{cell}.result.json").read_text())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_recorded_trace_reads_back(cell, tmp_path):
    (metrics, device, breakdown), rec = _read(cell, tmp_path)
    assert rec["device"]["platform"] == "tpu"
    assert set(metrics) == CELLS[cell] == set(rec["metrics"])
    assert metrics == rec["metrics"]
    assert device == {k: rec["device"][k] for k in ("busy_s", "window_s")}
    assert json.loads(json.dumps(breakdown)) == rec["breakdown"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_recorded_trace_holds_together(cell, tmp_path):
    (metrics, device, breakdown), _ = _read(cell, tmp_path)
    assert 0 < device["busy_s"] < device["window_s"]
    kind = cell.split("_")[1]
    idle = metrics[f"device_idle_share.{kind}"]["value"]
    assert idle == pytest.approx(
        100 * (1 - device["busy_s"] / device["window_s"]))
    ops = breakdown["device_ops"]
    assert 0 < len(ops) <= 10 and all(op.startswith("jit_") for op, _ in ops)
    assert sum(t for _, t in ops) <= device["busy_s"] * (1 + 1e-9)
    gaps = [t for _, t in breakdown["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= device["window_s"] - device["busy_s"] + 1e-9
