"""Equivalence and caching tests for the device-sharded grid sweep
(``repro.core.sweep.run_grid`` — the ONE dispatch path every sweep uses):
a whole (epoch_us x objective) figure grid must (a) reproduce per-point
``run_suite`` results (bitwise — run_suite is itself a 1-point run_grid) —
including masked logical-epoch tails and padded mixed-size workloads —
(b) compile at most two fork-family executables regardless of grid size,
and (c) execute each static-frequency mechanism once per
``STATIC_EXEC_AXES`` equivalence class, not once per grid point."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import sweep as SW
from repro.core.simulate import SimConfig, objective_weights, run_sim
from repro.core.sweep import GridExecutor, run_grid, run_suite
from repro.core.workloads import get_workload, make_program

SIM = SimConfig(n_cu=16, n_wf=12, n_epochs=48)
WORKLOADS = ("comd", "xsbench")
MECHS = ("static17", "crisp", "pcstall", "oracle")
GRID_2X2 = {"epoch_us": [1.0, 10.0], "objective": ["ed2p", "edp"]}


@pytest.fixture(scope="module")
def progs():
    return {w: get_workload(w) for w in WORKLOADS}


@pytest.fixture(scope="module")
def grid_2x2(progs):
    return run_grid(progs, SIM, GRID_2X2, MECHS)


def _assert_traces_match(got, want, ctx):
    assert set(got) == set(want), ctx
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5,
                                   err_msg=f"{ctx}/{k}")


@pytest.mark.parametrize("key", [(1.0, "ed2p"), (1.0, "edp"),
                                 (10.0, "ed2p"), (10.0, "edp")])
def test_grid_matches_per_point_suite(progs, grid_2x2, key):
    """2x2 (epoch_us x objective) grid == per-point run_suite, <= 1e-5
    (empirically bitwise: same traced-id executable family)."""
    sim_pt = dataclasses.replace(SIM, epoch_us=key[0], objective=key[1])
    suite = run_suite(progs, sim_pt, MECHS)
    for wl in WORKLOADS:
        for m in MECHS:
            _assert_traces_match(grid_2x2[key][wl][m], suite[wl][m],
                                 f"{key}/{wl}/{m}")


def test_grid_fork_family_executable_count(progs):
    """Acceptance: a >= 2x2 grid compiles <= 2 fork-family executables
    (the traced-id family + oracle's specialized one) and at least one,
    and repeated calls hit the jit cache (no new traces).

    Uses a SimStatic no other test shares (n_cu=8) so the executables are
    compiled *inside this test* — a cached fixture grid would make the
    count vacuous."""
    sim = dataclasses.replace(SIM, n_cu=8)
    SW.reset_counters()
    run_grid(progs, sim, GRID_2X2, MECHS)
    fork_traces = {k: v for k, v in SW.TRACE_COUNTS.items()
                   if k in ("grid_forks", "grid_oracle")}
    assert 1 <= sum(fork_traces.values()) <= 2, fork_traces
    before = dict(SW.TRACE_COUNTS)
    run_grid(progs, sim, GRID_2X2, MECHS)
    assert dict(SW.TRACE_COUNTS) == before  # cache hit: zero new compiles


def test_static_mech_dedup_rows_and_broadcast(progs):
    """Acceptance: on a multi-objective grid each static mechanism compiles
    AND executes once per (epoch_us, sigma, cap_per_ghz, membw) equivalence
    class — a 3-objective grid must not triple static-mech compute — and
    the class trace is broadcast bitwise to every member grid key."""
    sim = dataclasses.replace(SIM, n_cu=4)  # SimStatic unique to this test
    grid = {"epoch_us": [1.0, 10.0],
            "objective": ["ed2p", "edp", "perfcap05"]}
    W, G, C = len(WORKLOADS), 6, 2
    SW.reset_counters()
    res = run_grid(progs, sim, grid, ("static17", "pcstall"))
    assert SW.DISPATCH_ROWS["grid_forks"] == W * G
    assert SW.DISPATCH_ROWS["grid_static17"] == W * C   # deduped rows
    assert SW.TRACE_COUNTS["grid_static17"] == 1        # one compile
    run_grid(progs, sim, grid, ("static17", "pcstall"))
    assert SW.TRACE_COUNTS["grid_static17"] == 1        # jit cache hit
    assert SW.DISPATCH_ROWS["grid_static17"] == 2 * W * C
    for T in (1.0, 10.0):
        for wl in WORKLOADS:
            a = res[(T, "ed2p")][wl]["static17"]
            for obj in ("edp", "perfcap05"):
                b = res[(T, obj)][wl]["static17"]
                for k in a:
                    np.testing.assert_array_equal(
                        a[k], b[k], err_msg=f"{T}/{obj}/{wl}/{k}")
        # the deduped trace still equals a per-point run_suite
        suite = run_suite(progs, dataclasses.replace(sim, epoch_us=T),
                          ("static17",))
        for wl in WORKLOADS:
            _assert_traces_match(res[(T, "ed2p")][wl]["static17"],
                                 suite[wl]["static17"], f"dedup/{T}/{wl}")


def test_static_dedup_coupled_epoch_counts(progs):
    """Points sharing execution axes but differing in logical n_epochs form
    ONE class: the representative scans to the class max and each member
    slices its logical prefix."""
    points = [{"epoch_us": 1.0, "n_epochs": 24, "objective": "ed2p"},
              {"epoch_us": 1.0, "n_epochs": 48, "objective": "edp"}]
    SW.reset_counters()
    res = run_grid(progs, SIM, points, ("static17",))
    assert SW.DISPATCH_ROWS["grid_static17"] == len(WORKLOADS)  # one class
    for pt in points:
        key = (1.0, pt["n_epochs"], pt["objective"])
        suite = run_suite(progs,
                          dataclasses.replace(SIM, n_epochs=pt["n_epochs"]),
                          ("static17",))
        for wl in WORKLOADS:
            got = res[key][wl]["static17"]
            assert got["work"].shape[0] == pt["n_epochs"]
            _assert_traces_match(got, suite[wl]["static17"], f"{key}/{wl}")


def test_grid_point_key_order_normalized(progs):
    """List-of-dicts points delivering the same axes in different key
    insertion order describe the same grid (keys follow the first point's
    axis order); genuinely different axis *sets* still assert."""
    a = run_grid(progs, SIM, [{"epoch_us": 1.0, "n_epochs": 32},
                              {"n_epochs": 48, "epoch_us": 10.0}],
                 ("pcstall",))
    assert list(a) == [(1.0, 32), (10.0, 48)]
    b = run_grid(progs, SIM, [{"epoch_us": 1.0, "n_epochs": 32},
                              {"epoch_us": 10.0, "n_epochs": 48}],
                 ("pcstall",))
    for key in a:
        for wl in WORKLOADS:
            for k, v in a[key][wl]["pcstall"].items():
                np.testing.assert_array_equal(v, b[key][wl]["pcstall"][k])
    with pytest.raises(AssertionError, match="share axes"):
        run_grid(progs, SIM, [{"epoch_us": 1.0}, {"sigma": 0.1}],
                 ("pcstall",))


def test_grid_masked_epoch_tail(progs):
    """Coupled (epoch_us, n_epochs) points: the shorter point scans to the
    grid max with its tail masked, and still matches a run_suite sized
    exactly to its logical epoch count."""
    points = [{"epoch_us": 1.0, "n_epochs": 32},
              {"epoch_us": 10.0, "n_epochs": 48}]
    grid = run_grid(progs, SIM, points, ("static17", "pcstall"))
    for pt in points:
        key = (pt["epoch_us"], pt["n_epochs"])
        sim_pt = dataclasses.replace(SIM, **pt)
        suite = run_suite(progs, sim_pt, ("static17", "pcstall"))
        for wl in WORKLOADS:
            for m in ("static17", "pcstall"):
                got = grid[key][wl][m]
                assert got["work"].shape[0] == pt["n_epochs"]
                _assert_traces_match(got, suite[wl][m], f"{key}/{wl}/{m}")


def test_grid_mask_ratio_bucketing(progs):
    """max_mask_ratio splits strongly-coupled n_epochs points into
    bounded-waste buckets without changing results or key order."""
    points = [{"epoch_us": 1.0, "n_epochs": 48},
              {"epoch_us": 10.0, "n_epochs": 12},
              {"epoch_us": 50.0, "n_epochs": 12}]
    whole = run_grid(progs, SIM, points, ("pcstall",))
    bucketed = run_grid(progs, SIM, points, ("pcstall",), max_mask_ratio=2.0)
    assert list(bucketed) == list(whole)  # caller's point order preserved
    for key in whole:
        for wl in WORKLOADS:
            _assert_traces_match(bucketed[key][wl]["pcstall"],
                                 whole[key][wl]["pcstall"], f"bucket/{key}")


def test_grid_padded_workload_mix():
    """Mixed block counts ride the grid unchanged: padding must not change
    the wrapped window semantics of the shorter program."""
    small = make_program("small", "phased", 5, P=256)
    big = get_workload("comd")  # P=1024
    grid = run_grid([small, big], SIM, {"epoch_us": [1.0, 10.0]},
                    ("pcstall",))
    for T in (1.0, 10.0):
        suite = run_suite([small, big],
                          dataclasses.replace(SIM, epoch_us=T), ("pcstall",))
        for prog in (small, big):
            _assert_traces_match(grid[(T,)][prog.name]["pcstall"],
                                 suite[prog.name]["pcstall"],
                                 f"{T}/{prog.name}")


def test_grid_odd_flat_axis(progs):
    """A flat (workload x grid-point) axis that is not a device multiple
    exercises the _pad_flat cycling path on multi-device hosts (and is the
    identity on one device, where the mesh is capped at the flat length);
    either way every row matches the serial engine."""
    three = {**progs, "small": make_program("small", "phased", 5, P=256)}
    res = run_grid(three, SIM, {"epoch_us": [1.0]}, ("pcstall",))[(1.0,)]
    for name, prog in three.items():
        ser = run_sim(prog, SIM, "pcstall")
        for k in ser:
            np.testing.assert_allclose(res[name]["pcstall"][k], ser[k],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"{name}/{k}")


def test_grid_seed_axis(progs):
    out = run_grid(progs, SIM, {"objective": ["ed2p"]}, ("pcstall",),
                   seeds=[0, 3])
    tr = out[("ed2p",)]["comd"]["pcstall"]
    assert tr["work"].shape[:2] == (2, SIM.n_epochs)
    want = run_suite(progs, SIM, ("pcstall",), seeds=[0, 3])
    np.testing.assert_allclose(tr["work"], want["comd"]["pcstall"]["work"],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.skipif(jax.local_device_count() != 1,
                    reason="identity-mesh check is 1-device-specific "
                           "(multi-device equivalence holds too — run this "
                           "file under a forced multi-device config)")
def test_grid_runs_under_one_device_shard_map(progs):
    """The flattened (workload x grid-point) axis is sharded via shard_map;
    on this host that is a 1-device mesh, which must be the identity
    layout — results already checked against run_suite above."""
    res = run_grid(progs, SIM, {"epoch_us": [1.0]}, ("pcstall",))
    ser = run_suite(progs, SIM, ("pcstall",))
    for wl in WORKLOADS:
        _assert_traces_match(res[(1.0,)][wl]["pcstall"],
                             ser[wl]["pcstall"], wl)


def test_grid_rejects_static_axis(progs):
    with pytest.raises(AssertionError, match="not a traced grid axis"):
        run_grid(progs, SIM, {"n_cu": [8, 16]}, ("pcstall",))


def test_objective_weights_lowering():
    np.testing.assert_allclose(objective_weights("edp"), [1.0, 1.0, 0.0])
    np.testing.assert_allclose(objective_weights("ed2p"), [2.0, 1.0, 0.0])
    np.testing.assert_allclose(objective_weights("perfcap05"),
                               [0.0, 0.0, 0.95])
    np.testing.assert_allclose(objective_weights("perfcap10"),
                               [0.0, 0.0, 0.90])
    with pytest.raises(ValueError):
        objective_weights("nope")


def test_axis_change_does_not_retrace(progs):
    """The SimConfig split: sweeping any traced axis through run_suite
    reuses the same executable (no new compile)."""
    run_suite(progs, SIM, ("pcstall",))
    before = dict(SW.TRACE_COUNTS)
    for repl in ({"epoch_us": 3.0}, {"objective": "perfcap10"},
                 {"sigma": 0.01}, {"membw": 2e5}, {"table_ema": 0.3},
                 {"cap_per_ghz": 4000.0}):
        run_suite(progs, dataclasses.replace(SIM, **repl), ("pcstall",))
    assert dict(SW.TRACE_COUNTS) == before


# A SimStatic no other test of this file compiles, so the compile-count
# tests above still see their own first traces.
CUT_SIM = SimConfig(n_cu=4, n_wf=6, n_epochs=16)


def _padded_flat_operands(real):
    """``_flat_operands`` padding the flat axis with at least one cycled
    row, as a host with more devices pads it, on any device count."""
    def flat_operands(stacked, p_logical, sims, n_dev):
        progs, p_log, axes, n_flat = real(stacked, p_logical, sims, n_dev)
        n_pad = -(-(n_flat + 1) // n_dev) * n_dev
        return (SW._pad_flat(progs, n_pad), SW._pad_flat(p_log, n_pad),
                SW._pad_flat(axes, n_pad), n_flat)
    return flat_operands


def _run_cut_case(case, progs):
    """Run one case's grid or executor batch; returns how many traces it
    must hand back (one per real row and mechanism)."""
    three = {**progs, "small": make_program("small", "phased", 5, P=256)}
    if case == "dedup_broadcast":  # static17: one class for two objectives
        run_grid(progs, CUT_SIM, {"objective": ["ed2p", "edp"]},
                 ("static17", "pcstall"))
        return len(progs) * 2 * 2
    if case == "masked_tail":
        run_grid(progs, CUT_SIM, [{"epoch_us": 1.0, "n_epochs": 8},
                                  {"epoch_us": 10.0, "n_epochs": 16}],
                 ("static17", "pcstall", "crisp"))
        return len(progs) * 2 * 3
    if case == "seed_axis":
        run_grid(progs, CUT_SIM, {"objective": ["ed2p"]},
                 ("crisp", "pcstall", "oracle"), seeds=[0, 3])
        return len(progs) * 3
    if case == "odd_flat_pad":
        run_grid(three, CUT_SIM, {"epoch_us": [1.0]}, ("pcstall", "crisp"))
        return len(three) * 2
    assert case == "executor_small_batch"
    jobs = [(three["comd"], {"epoch_us": 1.0}),
            (three["small"], {"epoch_us": 10.0, "n_epochs": 8}),
            (three["xsbench"], {"objective": "edp"})]
    traces = GridExecutor(CUT_SIM, ("pcstall", "static17"),
                          buckets=(8,)).run(jobs)
    assert len(traces) == len(jobs)
    return len(jobs) * 2


@pytest.mark.parametrize("case", ["dedup_broadcast", "masked_tail",
                                  "seed_axis", "odd_flat_pad",
                                  "executor_small_batch"])
def test_host_cut_matches_device_cut(progs, monkeypatch, case):
    """Every trace the sweep layer returns, cut on the host from its
    family's fetched output array, equals bit for bit the device slice
    ``v[i, :, :n_ep]`` (the fork family's ``v[:, :, j]`` first, the seed
    axis squeezed where it was implicit) of the same executable outputs
    copied to the host: same keys, dtype, shape and bytes, read-only and
    C-contiguous. Pad rows and cycled bucket rows are never cut."""
    real_rows, dev_of, cuts = {}, [], []
    real_run, real_fetch, real_cut = \
        SW._run_family, SW._fetch, SW._unpack_trace

    def run_family(st, n_dev, mechanism, operands, seed_arr, ids):
        ys = real_run(st, n_dev, mechanism, operands, seed_arr, ids)
        real_rows[id(ys)] = (ys, operands[3])
        return ys

    def fetch(families):
        host = real_fetch(families)
        dev_of.extend(zip(host, families))
        return host

    def cut(arrs, i, spec, squeeze_seed, n_ep=None, mech=None):
        tr = real_cut(arrs, i, spec, squeeze_seed, n_ep, mech)
        dev = next(d for h, d in dev_of if h is arrs)
        cuts.append((dev, i, spec, squeeze_seed, n_ep, mech, tr))
        return tr

    monkeypatch.setattr(SW, "_run_family", run_family)
    monkeypatch.setattr(SW, "_fetch", fetch)
    monkeypatch.setattr(SW, "_unpack_trace", cut)
    if case == "odd_flat_pad":
        monkeypatch.setattr(SW, "_flat_operands",
                            _padded_flat_operands(SW._flat_operands))
    n_traces = _run_cut_case(case, progs)
    assert len(cuts) == n_traces
    assert any(mech is not None for *_, mech, _ in cuts)
    if case == "odd_flat_pad":
        assert all(next(iter(d.values())).shape[0] > real_rows[id(d)][1]
                   for _, d in dev_of)
    for dev, i, spec, squeeze, n_ep, mech, tr in cuts:
        assert i < real_rows[id(dev)][1], "a pad row was cut"
        assert list(tr) == [k for k in dev
                            if spec.hit_telemetry or k != "hit_rate"]
        for k, got in tr.items():
            v = dev[k] if mech is None else dev[k][:, :, mech]
            want = np.asarray(v[i, 0, :n_ep] if squeeze else v[i, :, :n_ep])
            ctx = f"{case}/{spec.name}/row {i}/{k}"
            assert (got.dtype, got.shape) == (want.dtype, want.shape), ctx
            assert got.tobytes() == want.tobytes(), ctx
            assert not got.flags.writeable, ctx
            assert got.flags.c_contiguous, ctx
