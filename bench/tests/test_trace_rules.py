"""The trace reduction and the per-layer readers on a small hand-built
trace whose every answer is known: two calls on one device, an enclosing
``while`` op, an op outside the grid executables, idle gaps, and host
events to label them. Times are in microseconds of the trace's clock."""
from types import SimpleNamespace

import pytest

from bench import harness
from bench import trace as T

US = 1_000_000  # picoseconds per microsecond


def _xspace():
    from jax.profiler import ProfileData
    # (module, op, start us, end us) on /device:TPU:0
    ops = [("jit_dispatch(7)", "%while.5 = (f32[8]) while(a)", 20, 60),
           ("jit_dispatch(7)", "%fusion.1 = f32[8] fusion(b)", 20, 40),
           ("jit_dispatch(7)", "%fusion.2 = f32[8] fusion(c)", 40, 60),
           ("jit_squeeze(3)", "%copy.1 = f32[8] copy(d)", 70, 75),
           ("jit_dispatch(7)", "%fusion.1 = f32[8] fusion(b)", 120, 150)]
    mods = {("jit_dispatch(7)", 20, 60), ("jit_squeeze(3)", 70, 75),
            ("jit_dispatch(7)", 120, 150)}
    # (name, start us, end us) on the host
    host = [("bench.window", 0, 200), ("bench.call", 10, 90),
            ("bench.call", 100, 180), ("PjitFunction(dynamic_slice)", 75, 90),
            ("np.asarray(jax.Array)", 150, 180)]
    meta, names = {}, []

    def mid(name):
        if name not in meta:
            meta[name] = len(meta) + 1
            names.append(name)
        return meta[name]

    def events(items):
        return "".join(f"events {{ metadata_id: {mid(n)} offset_ps: {s * US} "
                       f"duration_ps: {(e - s) * US} }}\n" for n, s, e in items)
    dev_ops = events([(op, s, e) for _, op, s, e in ops])
    dev_mods = events(sorted(((m, s, e) for m, s, e in mods),
                             key=lambda x: x[1]))
    host_ev = events(host)
    md = "".join(f'event_metadata {{ key: {meta[n]} value {{ id: {meta[n]} '
                 f'name: "{n}" }} }}\n' for n in names)
    txt = (f'planes {{ id: 1 name: "/device:TPU:0"\n'
           f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n{dev_mods}}}\n'
           f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n{dev_ops}}}\n'
           f'{md}}}\n'
           f'planes {{ id: 2 name: "/host:CPU"\n'
           f'lines {{ id: 1 name: "python3" timestamp_ns: 0\n{host_ev}}}\n'
           f'{md}}}\n')
    return ProfileData.text_proto_to_serialized_xspace(txt)


@pytest.fixture(scope="module")
def tr(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(_xspace())
    return T.load(path)


def test_reduction(tr):
    lo, hi = tr.window()
    assert (lo, hi) == (0, 200_000)
    dev = "/device:TPU:0"
    assert T.busy(tr, dev, lo, hi) == [(20_000, 60_000), (70_000, 75_000),
                                       (120_000, 150_000)]
    assert T.length(T.busy(tr, dev, lo, hi, T.GRID_MODULE)) == 70_000
    assert T.gaps(T.busy(tr, dev, lo, hi), lo, hi) == [
        (0, 20_000), (60_000, 70_000), (75_000, 120_000), (150_000, 200_000)]
    assert T.busy_s(tr, lo, hi) == pytest.approx(75e-6)
    # the enclosing while op is not counted beside the ops inside it
    assert T.op_totals(tr, lo, hi) == pytest.approx(
        {"jit_dispatch:fusion.1": 50e-6, "jit_dispatch:fusion.2": 20e-6,
         "jit_squeeze:copy.1": 5e-6})
    bd = T.breakdown(tr, lo, hi)
    assert bd["device_ops"][0] == ["jit_dispatch:fusion.1", pytest.approx(50e-6)]
    # labelled by the innermost span open at the gap's middle and the host
    # event overlapping the gap most
    assert bd["idle_gaps"] == [
        ["call|np.asarray(jax.Array)", pytest.approx(50e-6)],
        ["window|PjitFunction(dynamic_slice)", pytest.approx(45e-6)],
        ["call", pytest.approx(20e-6)],
        ["call", pytest.approx(10e-6)]]


def _read(tr, **counters):
    lo, hi = tr.window()
    ctx = SimpleNamespace(trace=tr, T=T, lo=lo, hi=hi, **counters)
    return {n: m.read(ctx) for n, m in harness.readers().items()}


def test_sweep_readers(tr):
    got = _read(tr, kind="sweep", n_epochs=10, n_calls=2)
    assert got["scan_step_ms.suite"] == pytest.approx(70e-3 / 20)
    assert got["grid_prep_ms.suite"] == pytest.approx((10 + 20) / 2 * 1e-3)
    assert got["grid_unpack_ms.suite"] == pytest.approx((30 + 30) / 2 * 1e-3)
    assert got["device_idle_share.suite"] == pytest.approx(62.5)
    assert {n for n, v in got.items() if v is not None} == {
        "scan_step_ms.suite", "grid_prep_ms.suite", "grid_unpack_ms.suite",
        "device_idle_share.suite"}


def test_stream_readers(tr):
    got = _read(tr, kind="stream", max_batch=8, batch_sizes=[2, 2, 4, 4, 4, 4])
    # 6 jobs in one batch of 2 and one of 4: 3 jobs a batch of 8
    assert got["batch_fill.stream"] == pytest.approx(37.5)
    assert got["job_device_ms.stream"] == pytest.approx(75e-3 / 6)
    assert got["device_idle_share.stream"] == pytest.approx(62.5)
    assert {n for n, v in got.items() if v is not None} == {
        "batch_fill.stream", "job_device_ms.stream",
        "device_idle_share.stream"}
