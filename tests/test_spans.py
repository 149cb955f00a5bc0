"""The program's host spans and the engine's phase scopes: a CPU profiler
trace around a tiny ``run_grid`` and a tiny ``DVFSService`` stream holds
each ``repro.*`` span on the thread that does the work, nested as the
per-layer metrics read them (``bench/metrics/``), with one copy span per
family output array fetched to the host; and the compiled grid executable names each epoch phase
in its ops' ``op_name`` metadata."""
import collections
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import simulate as SIM
from repro.core import sweep as SW
from repro.core.simulate import SimConfig
from repro.core.workloads import get_workload
from repro.dvfs_runtime.service import DVFSService

SIM_CFG = SimConfig(n_cu=4, n_wf=8, n_epochs=16)
WORKLOADS = ("comd", "FwdSoft")
# one traced fork-family mechanism without hit telemetry, one with it, and
# one static: the two traced ones share the fork family's executable, which
# emits six channel arrays (hit_rate included, dropped on unpack where the
# spec declines it), and the static its own five. Each array is fetched to
# the host once per call, whatever the rows in it.
MECHS = ("crisp", "pcstall", "static17")
FAMILY_ARRAYS = 6 + 5


@pytest.fixture(scope="module")
def progs():
    return {w: get_workload(w) for w in WORKLOADS}


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns ``{thread line: [(name,
    start, end, stats)]}`` of the ``repro.*`` and ``test.*`` host spans."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True))[-1]
    lines = collections.defaultdict(list)
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("repro.", "test.")):
                    lines[k].append((e.name, e.start_ns, e.end_ns,
                                     dict(e.stats)))
    return lines


def _thread_of(lines, name):
    found = {k for k, evs in lines.items() for n, *_ in evs if n == name}
    assert len(found) == 1, (name, found)
    return found.pop()


def _named(evs, name):
    return [(s, e, st) for n, s, e, st in evs if n == name]


def _inside(inner, outer):
    return all(any(os_ <= s and e <= oe for os_, oe, _ in outer)
               for s, e, _ in inner)


def test_sweep_spans_on_the_callers_thread(progs, tmp_path):
    SW.run_suite(progs, SIM_CFG, MECHS)  # compile outside the trace

    def call():
        with jax.profiler.TraceAnnotation("test.call"):
            SW.run_suite(progs, SIM_CFG, MECHS)

    lines = _traced(tmp_path, call)
    main = _thread_of(lines, "test.call")
    for name in ("prep", "dispatch", "wait", "unpack", "copy"):
        assert _thread_of(lines, f"repro.sweep.{name}") == main
    evs = lines[main]
    (call_s, call_e, _), = _named(evs, "test.call")
    prep, disp = _named(evs, "repro.sweep.prep"), \
        _named(evs, "repro.sweep.dispatch")
    (wait,), (unpack,) = _named(evs, "repro.sweep.wait"), \
        _named(evs, "repro.sweep.unpack")
    # the fork family and the static's own executable
    assert len(prep) == 1 and len(disp) == 2
    order = [prep[0], *disp, wait, unpack]
    assert all(a[1] <= b[0] for a, b in zip(order, order[1:]))
    assert call_s <= prep[0][0] and unpack[1] <= call_e
    copies = _named(evs, "repro.sweep.copy")
    assert _inside(copies, [unpack])
    assert len(copies) == FAMILY_ARRAYS


def test_service_spans_on_dispatcher_and_collector(progs, tmp_path):
    jobs = [(progs[w], {"epoch_us": e}) for w in WORKLOADS
            for e in (1.0, 2.0)] + [(progs["comd"], {"epoch_us": 5.0})]
    svc = DVFSService(SIM_CFG, "pcstall", "static17", max_batch=2,
                      coalesce_s=0.001)
    # warm: both batch shapes compile outside the trace
    for f in [svc.submit(p, ax) for p, ax in jobs[:2]]:
        f.result(timeout=600)

    def stream():
        with jax.profiler.TraceAnnotation("test.submit"):
            futs = [svc.submit(p, ax) for p, ax in jobs]
        svc.close()
        for f in futs:
            f.result(timeout=60)

    lines = _traced(tmp_path, stream)
    main = _thread_of(lines, "test.submit")
    disp = _thread_of(lines, "repro.service.dispatch")
    coll = _thread_of(lines, "repro.service.unpack")
    assert len({main, disp, coll}) == 3
    for name in ("coalesce", "admit", "dispatch"):
        assert _thread_of(lines, f"repro.service.{name}") == disp
    for name in ("wait", "unpack", "report"):
        assert _thread_of(lines, f"repro.service.{name}") == coll
    assert _thread_of(lines, "repro.sweep.dispatch") == disp
    assert _thread_of(lines, "repro.sweep.copy") == coll

    d, c = lines[disp], lines[coll]
    admit = _named(d, "repro.service.admit")
    batches = [st["batch"] for _, _, st in admit]
    assert batches == sorted(batches) and len(batches) == len(set(batches))
    assert sum(st["jobs"] for _, _, st in admit) == len(jobs)
    for name in ("dispatch", "wait", "unpack", "report"):
        evs = _named(d if name == "dispatch" else c,
                     f"repro.service.{name}")
        assert [(st["batch"], st["jobs"]) for _, _, st in evs] == \
            [(st["batch"], st["jobs"]) for _, _, st in admit], name
    assert [st["batch"] for _, _, st in _named(d, "repro.service.coalesce")
            ] == batches
    assert _inside(_named(d, "repro.sweep.dispatch"),
                   _named(d, "repro.service.dispatch"))
    unpack = _named(c, "repro.service.unpack")
    copies = _named(c, "repro.sweep.copy")
    assert _inside(copies, unpack)
    # each batch fetches its families' arrays once, whatever its job
    # count: pcstall's fork family has six, static17's own family five
    for s, e, st in unpack:
        assert sum(s <= cs and ce <= e for cs, ce, _ in copies) \
            == FAMILY_ARRAYS, st
    assert len(copies) == len(unpack) * FAMILY_ARRAYS


@pytest.mark.parametrize("use_pallas,scopes", [
    (False, ("engine.context", "engine.predict", "engine.execute",
             "engine.update")),
    ("v2", ("engine.context", "engine.kernel")),
])
def test_grid_executable_names_engine_phases(use_pallas, scopes):
    sim = SimConfig(n_cu=4, n_wf=8, n_epochs=4, use_pallas=use_pallas)
    st = sim.static_part()
    stacked, p_log = SW._stack_programs([get_workload("comd")])
    ops = SW._flat_operands(stacked, p_log, [sim], 1)
    carry0 = SW._carry_builder(st)(ops[1])
    ids = jnp.asarray([SIM.FORK_MECH_IDS["pcstall"]], jnp.int32)
    seed = jnp.asarray(SIM.seed_i32([0]))
    text = SW._grid_exec(st, 1, None).lower(
        carry0, *ops[:3], seed, ids).compile().as_text()
    found = set(re.findall(r'op_name="[^"]*?(engine\.\w+)', text))
    assert found == set(scopes)


def test_service_derive_span_counts_misses(tmp_path):
    """``submit_step``: the dispatcher writes ``repro.service.derive``
    ahead of the batch's admit, tagged with the batch, its jobs and the
    programs it derived; a step seen before derives nothing."""
    from repro.configs import get_config
    from repro.dvfs_runtime.telemetry import Step
    steps = [Step("decode", 4, 1024), Step("decode", 4, 2048)]
    svc = DVFSService.for_model(get_config("glm4-9b"), sim=SIM_CFG,
                                max_batch=2, coalesce_s=0.05)
    svc.submit(get_workload("comd"), {}).result(timeout=600)  # compile

    def stream():
        futs = [svc.submit_step(s, {}) for s in steps]
        for f in futs:
            f.result(timeout=60)
        svc.submit_step(steps[0], {}).result(timeout=60)
        svc.close()

    lines = _traced(tmp_path, stream)
    disp = _thread_of(lines, "repro.service.derive")
    assert disp == _thread_of(lines, "repro.service.admit")
    derive = _named(lines[disp], "repro.service.derive")
    admit = _named(lines[disp], "repro.service.admit")
    assert [(st["jobs"], st["misses"]) for _, _, st in derive] == \
        [(2, 2), (1, 0)]
    for (_, e, st), (s, _, st2) in zip(derive, admit):
        assert st["batch"] == st2["batch"] and e <= s
    assert svc.stats()["programs_derived"] == 2
