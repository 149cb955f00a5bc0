"""``correct`` comes out false for the control (the reference in bfloat16
in the program's place) and for each fault a cell can have, planted under
the timed path of a whole run at a tiny size on the CPU."""
import numpy as np
import pytest

from bench import check as CHK
from bench import gen
from bench.tests import tiny

NF = 10


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("tree"))


def _each(res, fn):
    return {w: {m: fn(tr) for m, tr in trs.items()} for w, trs in res.items()}


def _frozen(tr):
    """The scan's step returns its state unchanged: every epoch repeats
    the first."""
    return {k: np.repeat(np.asarray(v)[:1], len(v), 0) for k, v in tr.items()}


def _altered(tr):
    """A frequency choice altered where it is produced, every 4th epoch."""
    f = np.asarray(tr["fidx"]).copy()
    f[::4] = (f[::4] + 1) % NF
    return dict(tr, fidx=f.astype(np.asarray(tr["fidx"]).dtype))


def _half_batch(res):
    """Half the rows left out, the rest repeated in their place."""
    names = list(res)
    return {w: res[names[i % max(1, len(names) // 2)]]
            for i, w in enumerate(names)}


def _no_exchange(res):
    """The rows of every chip but the first never come back: zeros."""
    names = list(res)
    keep = names[:max(1, len(names) // 2)]
    return {w: res[w] if w in keep else
            _each({w: res[w]}, lambda tr: {k: np.zeros_like(v)
                                           for k, v in tr.items()})[w]
            for w in names}


SWEEP_FAULTS = {
    "state_unchanged": lambda res: _each(res, _frozen),
    "half_batch_left_out": _half_batch,
    "exchange_left_out": _no_exchange,
    "answer_altered": lambda res: _each(res, _altered),
}


def _sweep_control(progs, sim, mechs, *a, **k):
    arrays = {w: {"i0": np.asarray(p.i0_rate), "sens": np.asarray(p.sens_rate),
                  "mem": np.asarray(p.mem_frac)} for w, p in progs.items()}
    cfg = dict(vars(sim), power=vars(sim.power))
    rows = [(w, m, sim.epoch_us, sim.objective) for w in progs for m in mechs]
    ctl = CHK.control_traces(arrays, rows, cfg, sim.seed)
    return {w: {m: ctl[i * len(mechs) + j] for j, m in enumerate(mechs)}
            for i, w in enumerate(progs)}


def _suite(tree, monkeypatch):
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload", "tiny_suite",
                                 "--seed", "2024", "--seconds", "0.05",
                                 "--trace", "0")
    assert rc == 0 and res is not None, err
    return res


def test_sound_suite_run_is_correct(tree, monkeypatch):
    assert _suite(tree, monkeypatch)["correct"]


def test_suite_control_is_not_correct(tree, monkeypatch):
    from repro.core import sweep as SW
    monkeypatch.setattr(SW, "run_suite", _sweep_control)
    res = _suite(tree, monkeypatch)
    assert not res["correct"]
    assert res["checks"]["work_dev"]["value"] > 1e-2


@pytest.mark.parametrize("fault", sorted(SWEEP_FAULTS))
def test_suite_fault_is_not_correct(tree, monkeypatch, fault):
    from repro.core import sweep as SW
    real = SW.run_suite
    monkeypatch.setattr(SW, "run_suite",
                        lambda *a, **k: SWEEP_FAULTS[fault](real(*a, **k)))
    assert not _suite(tree, monkeypatch)["correct"]


def _jobs_frozen(jobs):
    return [{m: _frozen(tr) for m, tr in job.items()} for job in jobs]


def _jobs_altered(jobs):
    return [{m: _altered(tr) for m, tr in job.items()} for job in jobs]


def _jobs_half(jobs):
    half = max(1, len(jobs) // 2)
    return [jobs[i % half] for i in range(len(jobs))]


STREAM_FAULTS = {
    "state_unchanged": _jobs_frozen,
    "half_batch_left_out": _jobs_half,
    "answer_altered": _jobs_altered,
}


def _burst(tree, monkeypatch):
    rc, res, err = tiny.run_cell(tree, monkeypatch, "--workload", "tiny_burst",
                                 "--seed", "99", "--seconds", "0.1",
                                 "--trace", "0")
    assert rc == 0 and res is not None, err
    return res


def test_sound_stream_run_is_correct(tree, monkeypatch):
    assert _burst(tree, monkeypatch)["correct"]


@pytest.mark.parametrize("fault", sorted(STREAM_FAULTS))
def test_stream_fault_is_not_correct(tree, monkeypatch, fault):
    from repro.core.sweep import PendingGrid
    real = PendingGrid.traces
    monkeypatch.setattr(PendingGrid, "traces",
                        lambda self: STREAM_FAULTS[fault](real(self)))
    assert not _burst(tree, monkeypatch)["correct"]


def test_stream_control_is_not_correct(tree, monkeypatch):
    from repro.core import sweep as SW

    def dispatch(self, jobs):
        arrays = {p.name: {"i0": np.asarray(p.i0_rate),
                           "sens": np.asarray(p.sens_rate),
                           "mem": np.asarray(p.mem_frac)} for p, _ in jobs}
        st = self.static_cfg
        cfg = dict(vars(st), power=vars(st.power))
        mechs = [s.name for s in self.specs]
        rows = [(p.name, m, ax.get("epoch_us", st.epoch_us),
                 ax.get("objective", st.objective))
                for p, ax in jobs for m in mechs]
        ctl = CHK.control_traces(arrays, rows, cfg, st.seed)
        # the service's reports read the prediction-error channel too
        out = [{m: dict(ctl[i * len(mechs) + j],
                        err=np.zeros_like(ctl[i * len(mechs) + j]["work"]))
                for j, m in enumerate(mechs)} for i in range(len(jobs))]

        class Done:
            def block_until_ready(self):
                return self

            def traces(self):
                return out
        return Done()
    monkeypatch.setattr(SW.GridExecutor, "dispatch", dispatch)
    res = _burst(tree, monkeypatch)
    assert not res["correct"]
    assert res["checks"]["work_dev"]["value"] > 1e-2


def test_program_arrays_match_the_tiny_programs():
    a = gen.program_arrays("comd")
    assert a["i0"].dtype == np.float32 and a["i0"].shape == (1024,)
