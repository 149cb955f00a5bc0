"""The per-layer metrics that read the program's own host spans
(``bench/spans.py`` and its readers in ``bench/metrics/``): on the tiny
cells traced on the CPU each gives a value, but for the device idle share
the service's host path accounts for, which needs device operations; the
sweep layer's spans of a call lie inside the benchmark's ``bench.call``;
on traces of a program that writes no such spans (the recordings
``test_recorded_trace.py`` reads) every one of them returns None; and on
the tiny cells recorded traced on a TPU v5e with the spans in place
(``data/spans/``, written by ``record_trace.py``) every reader reads back
what the recording run printed."""
import gzip
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness
from bench import trace as T
from bench.tests import tiny

DATA = Path(__file__).resolve().parent / "data"
SPANS = DATA / "spans"
SUITE = ("sweep_prep_ms.suite", "sweep_wait_ms.suite",
         "sweep_unpack_ms.suite", "unpack_copies.suite")
STREAM = ("svc_admit_ms.stream", "svc_dispatch_ms.stream",
          "svc_collect_ms.stream")
DEVICE = ("idle_host_share.stream",)
# channel arrays copied per row in the recording (a program that cut each
# row on the device): the fork family's six for its 7 traced mechanisms,
# five for each of the 3 statics and oracle
COPIES_PER_WORKLOAD = 7 * 6 + 4 * 5


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("tree"))


def _traced(tree, monkeypatch, cell, seed, seconds):
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload", cell,
                                 "--seed", seed, "--seconds", seconds,
                                 "--trace", "1")
    assert rc == 0 and res["correct"], err
    pb = sorted((tree.parent / ".bench_trace" / cell)
                .glob("**/*.xplane.pb"))[-1]
    return res, T.load(pb)


def test_sweep_span_metrics_read_on_cpu(tree, monkeypatch):
    from repro.core import sweep as SW
    fetch, fetched = SW._fetch, []

    def counting_fetch(families):
        fetched.append(sum(len(arrs) for arrs in families))
        return fetch(families)
    monkeypatch.setattr(SW, "_fetch", counting_fetch)
    res, tr = _traced(tree, monkeypatch, "tiny_suite", str(2**31 + 3), "0.2")
    m = res["metrics"]
    for name in SUITE:
        assert m[name]["value"] > 0, name
    # one copy span per executable-family output array of a call: the fork
    # family's six for its 7 traced mechanisms, five for each of the 3
    # statics and oracle, whatever the workloads
    assert len(set(fetched)) == 1 and fetched[0] == 6 + 4 * 5
    assert m["unpack_copies.suite"] == {"value": fetched[0],
                                        "unit": "copies"}
    calls = tr.span("call")
    assert calls
    for lo, hi in calls:
        mine = [(s, e) for s, e, n in tr.host if lo <= s <= hi
                and n in ("repro.sweep.prep", "repro.sweep.dispatch",
                          "repro.sweep.wait", "repro.sweep.unpack")]
        assert len(mine) >= 4 and all(e <= hi for _, e in mine)
        assert sum(e - s for s, e in mine) <= hi - lo
    mean_call_ms = sum(hi - lo for lo, hi in calls) / len(calls) * 1e-6
    assert sum(m[n]["value"] for n in SUITE[:3]) <= mean_call_ms


def test_service_span_metrics_read_on_cpu(tree, monkeypatch):
    res, _ = _traced(tree, monkeypatch, "tiny_stream", "13", "1")
    m = res["metrics"]
    for name in STREAM:
        assert m[name]["value"] > 0, name
    assert m["svc_admit_ms.stream"]["unit"] == "ms"
    # a CPU trace holds no device operations
    assert not any(n in m for n in DEVICE)


@pytest.mark.parametrize("cell", ["tiny_suite", "tiny_stream"])
def test_span_readers_silent_without_program_spans(cell, tmp_path):
    pb = tmp_path / f"{cell}.xplane.pb"
    with gzip.open(DATA / f"{cell}.xplane.pb.gz", "rb") as f, \
            open(pb, "wb") as g:
        shutil.copyfileobj(f, g)
    counters = json.loads((DATA / f"{cell}.counters.json").read_text())
    tr = T.load(pb)
    assert tr.devices and not any(n.startswith("repro.")
                                  for _, _, n in tr.host)
    lo, hi = tr.window()
    ctx = SimpleNamespace(trace=tr, T=T, lo=lo, hi=hi, **counters)
    found = harness.readers()
    for name in SUITE + STREAM + DEVICE:
        assert found[name].read(ctx) is None, name


def test_idle_host_share_counts_idle_under_service_work():
    tr = T.Trace(ops={"/device:TPU:0": [T.Op(0, 10, "a", "jit_dispatch"),
                                         T.Op(20, 30, "b", "jit_dispatch")]},
                 host=[(5, 25, "repro.service.dispatch"),
                       (22, 28, "repro.service.report"),
                       (32, 36, "repro.service.coalesce")])
    ctx = SimpleNamespace(trace=tr, T=T, lo=0, hi=40, kind="stream")
    # idle in [10, 20] under the dispatch; the coalesce is not host work
    # that holds the device back
    assert harness.readers()["idle_host_share.stream"].read(ctx) == 25.0


def _read_recording(cell, tmp_path):
    pb = tmp_path / f"{cell}.xplane.pb"
    with gzip.open(SPANS / f"{cell}.xplane.pb.gz", "rb") as f, \
            open(pb, "wb") as g:
        shutil.copyfileobj(f, g)
    counters = json.loads((SPANS / f"{cell}.counters.json").read_text())
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH / "metrics", bench / "metrics")
    # the recording tree's one added reader (``tiny.make_tree``)
    (bench / "metrics/calls_seen.tiny.py").write_text(
        'UNIT = "calls"\n\n\ndef read(ctx):\n'
        '    return len(ctx.trace.span("call")) or None\n')
    got = harness.read_trace(str(pb), counters, bench)
    rec = json.loads((SPANS / f"{cell}.result.json").read_text())
    return got, rec, T.load(pb)


@pytest.mark.parametrize("cell", ["tiny_suite", "tiny_stream"])
def test_recorded_spans_read_back(cell, tmp_path):
    (metrics, device, breakdown), rec, tr = _read_recording(cell, tmp_path)
    assert rec["device"]["platform"] == "tpu"
    assert metrics == rec["metrics"]
    assert device == {k: rec["device"][k] for k in ("busy_s", "window_s")}
    assert json.loads(json.dumps(breakdown)) == rec["breakdown"]
    new = SUITE if cell == "tiny_suite" else STREAM + DEVICE
    assert all(n in metrics for n in new)
    if cell == "tiny_suite":
        assert metrics["unpack_copies.suite"]["value"] == \
            2 * COPIES_PER_WORKLOAD
        for lo, hi in tr.span("call"):
            mine = sum(e - s for s, e, n in tr.host if lo <= s <= hi and n in
                       ("repro.sweep.prep", "repro.sweep.dispatch",
                        "repro.sweep.wait", "repro.sweep.unpack"))
            assert mine <= hi - lo
    else:
        assert 0 <= metrics["idle_host_share.stream"]["value"] \
            <= metrics["device_idle_share.stream"]["value"]
