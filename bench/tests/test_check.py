"""The compared numbers on a tiny sweep on the CPU: a replay that departs
from the program's run in one CU is marked departed from that epoch and
leaves ``choice_gap`` to the epochs before it; the bfloat16 reference's
choices at the program's states read a choice gap above the cells' limit."""
import contextlib
import json

import numpy as np
import pytest

from bench import check as CHK
from bench import harness
from bench.tests import tiny

SUITE_LIMITS = json.loads((harness.BENCH / "traffic/suite.json")
                          .read_text())["limits"]


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    tree = tiny.make_tree(tmp_path_factory.mktemp("tree"))
    cell = harness.load_cell("tiny_suite", tree)
    r = cell.kind.Runner(cell.cfg, cell.mix, cell.library, n_dev=1)
    r.setup(2**31 + 11)
    r.window(0.01, lambda name: contextlib.nullcontext())
    rows, traces, _ = r.sample()
    return r, rows, traces


def _numbers(r, rows, traces, judged=None):
    return {n: v for n, v, _ in CHK.compare(r.arrays, rows, r.cfg, r.seed,
                                            traces, None, SUITE_LIMITS,
                                            judged)}


def _departed(r, rows, traces):
    """(rows, CU): the pairs whose replay departed."""
    return CHK.deviations(r.arrays, rows, r.cfg, r.seed, traces)[2].any(1)


def test_sound_sample_reads_no_departure(sample):
    got = _numbers(*sample)
    assert not _departed(*sample).any()
    assert got["choice_gap"] < 1e-4 and got["work_dev"] < 1e-5


def test_departure_is_counted_and_its_later_choices_are_not_judged(sample):
    r, rows, traces = sample
    row = next(i for i, (_, m, *_) in enumerate(rows) if m == "pcstall")
    t0, cu = 6, 1
    tr = {k: np.array(v) for k, v in traces[row].items()}
    # the CU runs its own course from epoch t0: other work, other choices
    tr["work"][t0:, cu] *= 1.01
    tr["fidx"][t0:, cu] = (tr["fidx"][t0:, cu] + 5) % 10
    bent = list(traces)
    bent[row] = tr
    got = _numbers(r, rows, bent)
    gone = _departed(r, rows, bent)
    assert gone[row, cu] and gone.sum() == 1
    gone_at = CHK.deviations(r.arrays, rows, r.cfg, r.seed, bent)[2]
    assert gone_at[row, t0, cu] and not gone_at[row, t0 - 1, cu]
    assert got["choice_gap"] < 1e-4
    assert got["work_dev"] > 1e-3


def test_altered_choice_before_any_departure_is_judged(sample):
    r, rows, traces = sample
    row = next(i for i, (_, m, *_) in enumerate(rows) if m == "oracle")
    judged = np.stack([np.asarray(t["fidx"]) for t in traces])
    judged[row, 3, 0] = (judged[row, 3, 0] + 5) % 10
    assert _numbers(r, rows, traces, judged)["choice_gap"] > 1e-2


def test_bfloat16_choices_at_the_programs_states_fail_choice_gap(sample):
    r, rows, traces = sample
    judged = CHK.control_choices(r.arrays, rows, r.cfg, r.seed, traces)
    assert _numbers(r, rows, traces, judged)["choice_gap"] > \
        SUITE_LIMITS["choice_gap"]
