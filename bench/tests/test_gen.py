"""The benchmark's copies of the program's generators give what the
program's own give at this commit, so a change to the program cannot move
the benchmark's inputs unseen."""
import numpy as np
import pytest

from bench import gen
from bench import program as PRG


@pytest.mark.parametrize("name", sorted(gen.TABLE_II))
def test_table_ii_programs_equal_the_programs_own(name):
    from repro.core.workloads import get_workload
    want = get_workload(name)
    got = PRG.programs({name: gen.program_arrays(name)})[name]
    for field in ("i0_rate", "sens_rate", "mem_frac", "cum3"):
        assert np.array_equal(np.asarray(getattr(got, field)),
                              np.asarray(getattr(want, field))), field


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_request_stream_equals_the_programs_own(seed):
    from repro.data.pipeline import dvfs_request_stream
    mix = dict(workloads=("comd", "xsbench", "lulesh", "minife"),
               epoch_us=(1.0, 10.0), objectives=("ed2p",),
               steps_per_request=4)
    want = list(dvfs_request_stream(40, seed=seed, **mix))
    got = list(gen.request_stream(40, seed, *mix.values()))
    assert [(p.name, ax, tel) for p, ax, tel in want] == got


def test_arrival_schedule_same_gaps_for_every_seed():
    a = gen.arrival_offsets(300, 30.0, 1)
    b = gen.arrival_offsets(300, 30.0, 2**31 + 9)
    assert a[0] == b[0] == 0.0 and np.all(np.diff(a) > 0)
    ga, gb = np.diff(np.append(a, 30.0)), np.diff(np.append(b, 30.0))
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert np.isclose(ga.sum(), 30.0)
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    assert np.isclose(ga.mean(), 0.1) and 0.9 < ga.std() / ga.mean() < 1.05
    assert np.array_equal(a, gen.arrival_offsets(300, 30.0, 1))
