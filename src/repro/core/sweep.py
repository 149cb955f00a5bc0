"""Batched sweep layer: ONE dispatch path — the device-sharded grid
executable family — for every sweep, from a single ``run_suite`` call to a
whole figure grid.

The paper's headline figures (14/15/17/18) sweep ~10 mechanisms x ~10
workloads x epoch granularities x objectives through the fork--pre-execute
engine. Run serially that is hundreds of scan traces; this layer instead

  1. pads every ``Program`` to a common block count (``pad_program`` keeps
     the wrapped prefix-sum window semantics exact by rebuilding the doubled
     cumulative arrays at the *logical* length before padding, and threads
     the logical block count through the scan as a traced scalar);
  2. stacks whole ``SimAxes`` grid points — epoch_us, sigma, capacity,
     bandwidth, EMA, lowered objective, logical epoch count — along a
     leading axis, cartesian-products them with the workloads, and shards
     the flattened (workload x grid-point) axis across local devices with
     ``shard_map`` (a 1-device mesh is the identity layout). Points with
     fewer logical epochs scan to the grid max and mask the tail, the same
     pad-and-mask move applied to programs;
  3. vmaps seeds and, within the fork family, mechanisms: all traced
     fork--pre-execute mechanisms (``simulate.FORK_MECHS``, ids frozen by
     the ``repro.core.mechanisms`` registry) share a shape-identical carry
     and run as executables indexed by a traced mechanism id, while oracle
     (whose prediction needs this epoch's forks), the static frequencies
     and registered custom mechanisms (``MechanismSpec.predict`` hooks)
     compile to their own specialized executables;
  4. deduplicates every mechanism across grid points by its spec's
     declared live axes (``MechanismSpec.exec_axes``): points agreeing on
     a mechanism's live axes form one equivalence class and share one
     scan, broadcast back to every member grid key. A static frequency
     never reads the objective or the table EMA (a 3-objective grid would
     otherwise triple static-mech compute for bitwise-identical traces);
     reactive (table-free) mechanisms and oracle never read the table EMA,
     so a table_ema-only grid axis stops multiplying their rows too.
     Traced mechanisms inducing the same point partition share one
     dispatch — on a grid with no dead axes the whole fork family is ONE
     dispatch over the full operands, exactly as before the spec redesign.
     The ``power`` axis (a swept IVR/hardware regime, ``PowerConfig``
     values) is live for EVERY mechanism — the V/f ladder and the energy
     accounting read it even for a static frequency — so power classes
     never collapse; only the other dead axes around them do;
     ``DISPATCH_ROWS`` records the logical scan rows actually executed per
     family (the dedup savings show up here);
  5. builds the initial scan carry outside the executables
     (``simulate.init_carry``, jitted once per ``SimStatic``) and donates
     it (``donate_argnums``), so the runtime can release the carry buffers
     as soon as the scan consumes them instead of pinning a protected
     input copy for the whole dispatch.

``run_suite`` IS a 1-point ``run_grid``: there is no parallel suite
executable family, so every consumer — figures, benchmarks, the DVFS
runtime manager, examples — dispatches through the same executables and
cross-path comparisons are bitwise by construction. A full
Fig-15/17/18-style sweep over several epoch granularities and objectives is
at most two fork-family executables (the traced-id family plus oracle's
specialized one) plus one per static mechanism; repeated sweeps with the
same ``SimStatic`` and grid shape hit the jit cache and never re-trace
(``TRACE_COUNTS`` records compiles for tests/benchmarks).

Execution-model / caching contract: see ``repro.core.simulate``'s module
docstring. The only remaining cross-family numerics boundary is the
specialized per-mechanism ``run_sim`` string-mech trace: its math is
identical to the traced-id family at the jaxpr level, but XLA may fuse f32
chains differently, and the resulting last-ulp differences can compound
through the closed control loop over hundreds of epochs (rarely enough to
flip a frequency decision, after which traces genuinely separate).
``run_suite``/``run_grid`` results agree with ``run_sim`` to f32 exactness
(tested to 1e-5 by ``tests/test_sweep.py``); comparisons *among* sweep-layer
results need no tolerance at all (bitwise, ``tests/test_grid.py``).

Pallas kernels (``SimConfig.use_pallas``) are an *opt-in engine mode* of
this layer: under v2 the traced-mechanism-id family scans the fused epoch
kernel (``kernels.epoch_fused`` in its ``family="fork"`` mode, which
multiplexes every traced mechanism behind one traced id) inside the SAME
vmapped, shard_map'd executables — the engine switch lives in
``simulate._scan_sim`` keyed off ``SimStatic.use_pallas``, so the ≤2
fork-family-compile and ``DISPATCH_ROWS`` dedup contracts above are
unchanged. Specs the kernel cannot serve (static pins, oracle, custom
predict hooks — ``MechanismSpec.v2_capable`` is False) run the jnp body
inside their own specialized executables (``chip_smoke.py`` reports the
engine each family compiled to). The DEFAULT
(``use_pallas=False``) grid path still runs the pure-jnp scan body and
stays bitwise against ``tests/data/grid_reference.npz``; v2 results are
held to the PR-6 aggregate tolerances instead (XLA cannot be forced to
reproduce the fused kernel's op order; ``lean=False`` pins the exact
reference op order for scan-equivalence tests). ``SimConfig.pallas_block_cu``
additionally selects the blocked ``(CU,)``-grid kernel pair for large CU
counts (fork family only, lean math; ignored on the direct-eval interpret
engine).

Host spans: the layer writes ``jax.profiler.TraceAnnotation`` spans named
``repro.sweep.*`` on the profiler's clock, beside the device operations
of a trace: ``prep`` (grid points through the flattened operands),
``dispatch`` (one executable family's carry build and enqueue), ``wait``
(every family's outputs ready), ``unpack`` (the result dict) and ``copy``
(one family output array fetched to the host, whole). Results are cut on
the host: each family output array crosses to the host once per call and
every (row, mechanism, seed, epoch) trace is cut from it with numpy, so
the cut enqueues no device program. With no profiler running each span
costs a few microseconds.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import threading
import warnings
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import mechanisms as MECH
from repro.core import power as PWR
from repro.core import simulate as SIM
from repro.launch.mesh import grid_mesh
from repro.core.mechanisms import MechanismSpec
from repro.core.simulate import (MECHANISMS, SimConfig, SimStatic, ednp,
                                 prediction_accuracy)
from repro.core.workloads import Program

# Back-compat alias: the SimAxes fields a static-frequency mechanism's
# trace depends on. Since the spec redesign this is just the static
# builtin's declared ``exec_axes`` (minus the specially-handled logical
# epoch count) — the dedup below is generic over every spec's axes.
STATIC_EXEC_AXES = MECH.get("static17").dedup_axes


def _fetch(families: Sequence[Dict[str, jax.Array]]
           ) -> List[Dict[str, np.ndarray]]:
    """Every family output array on the host, fetched once each (one
    ``repro.sweep.copy`` span per array): all transfers are started before
    the first is read. A sharded array gathers its shards in its fetch."""
    for arrs in families:
        for v in arrs.values():
            v.copy_to_host_async()
    host = []
    for arrs in families:
        h = {}
        for k, v in arrs.items():
            with jax.profiler.TraceAnnotation("repro.sweep.copy"):
                h[k] = np.asarray(v)
        host.append(h)
    return host


def _unpack_trace(arrs: Dict[str, np.ndarray], i: int, spec: MechanismSpec,
                  squeeze_seed: bool, n_ep: Optional[int] = None,
                  mech: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Cut flat-row ``i`` of a family's host outputs (``_fetch``) down to
    the ``run_sim`` trace schema: pick the mechanism ``mech`` of a fork
    family's mechanism axis (``None`` for a specialised family, which has
    none), squeeze the seed axis when it was implicit, slice the epoch axis
    to the logical count (``None`` = full), and drop the ``hit_rate``
    telemetry channel for mechanisms whose spec doesn't declare it (the
    traced family computes it for all; registered PC-family mechanisms get
    the channel by setting ``hit_telemetry`` — no sweep-layer edit needed).
    Each array is a read-only view of the fetched array, C-contiguous as a
    device slice copied to the host was (a copy only where a view is not)."""
    idx = ((i, 0 if squeeze_seed else slice(None))
           + (() if mech is None else (mech,))
           + (slice(None) if n_ep is None else slice(None, n_ep),))
    tr = {}
    for k, v in arrs.items():
        if k == "hit_rate" and not spec.hit_telemetry:
            continue
        tr[k] = np.ascontiguousarray(v[idx])
        tr[k].flags.writeable = False
    return tr

# SimConfig fields that may vary across a grid without re-tracing (they map
# onto SimAxes); n_epochs is the *logical* epoch count of a point — the
# executable scans to the grid max and masks the tail. ``power`` values
# are whole ``power.PowerConfig`` regimes (traced except the ladder
# length ``n_freqs``, which sets shapes and must be grid-constant).
AXIS_FIELDS = ("epoch_us", "sigma", "cap_per_ghz", "membw", "table_ema",
               "objective", "n_epochs", "power")

# executable-compile counter, keyed by family ("grid_forks", "grid_oracle",
# "grid_static17", ...): incremented at trace time only, so tests and
# benchmarks can assert cache hits / count fork-family compiles per figure.
TRACE_COUNTS: collections.Counter = collections.Counter()

# logical (workload x grid-point x mechanism) scan rows dispatched per
# family, incremented on every dispatch (cached or not): the spec-driven
# dedup shows up here as W x n_classes rows per mechanism instead of
# W x n_points — for static mechanisms AND for any fork mechanism whose
# ``exec_axes`` make a grid axis dead (e.g. reactive mechanisms on a
# table_ema-only axis).
DISPATCH_ROWS: collections.Counter = collections.Counter()

# Counter increments are read-modify-write: the DVFSService dispatches
# grids from worker threads, so unlocked `+=` would drop updates. Every
# mutation of the two counters above takes this lock; snapshot reads
# (``dict(TRACE_COUNTS)``) are safe without it.
_COUNTER_LOCK = threading.Lock()


def reset_counters() -> None:
    """Zero ``TRACE_COUNTS`` and ``DISPATCH_ROWS`` atomically. Tests and
    benchmarks use this instead of ad-hoc ``.clear()`` calls so the reset
    cannot interleave with a concurrent dispatch's increment."""
    with _COUNTER_LOCK:
        TRACE_COUNTS.clear()
        DISPATCH_ROWS.clear()


def pad_program(prog: Program, p_max: int) -> Program:
    """Pad ``prog``'s arrays to ``p_max`` blocks without changing semantics.

    The per-block arrays are zero-padded (never gathered past the logical
    length), and the doubled cumulative arrays are rebuilt so indices up to
    ``2 * n_blocks`` — the maximum window extent the execute can request —
    still see the wrap-around copy of the *logical* program, with flat
    padding beyond."""
    P = prog.n_blocks
    if P == p_max:
        return prog
    assert P < p_max, (P, p_max)
    pad1 = jnp.zeros((p_max - P,), jnp.float32)
    pad2 = jnp.zeros((2 * (p_max - P),), jnp.float32)

    def cum(a):
        doubled = jnp.concatenate([jnp.tile(a, 2), pad2])
        return jnp.concatenate([jnp.zeros(1), jnp.cumsum(doubled)])

    arr = lambda a: jnp.concatenate([a, pad1])
    return Program(prog.name, arr(prog.i0_rate), arr(prog.sens_rate),
                   arr(prog.mem_frac),
                   jnp.stack([cum(prog.i0_rate), cum(prog.sens_rate),
                              cum(prog.mem_frac)], axis=-1))


def _stack_programs(progs: Sequence[Program]) -> Tuple[Program, jnp.ndarray]:
    """Pad to a common block count and stack into one batched Program
    (leading workload axis); returns it plus the logical block counts."""
    p_max = max(p.n_blocks for p in progs)
    p_logical = jnp.asarray([p.n_blocks for p in progs], jnp.int32)
    padded = [pad_program(p, p_max) for p in progs]
    stacked = Program(
        "suite",
        *(jnp.stack([getattr(p, f) for p in padded])
          for f in ("i0_rate", "sens_rate", "mem_frac", "cum3")))
    return stacked, p_logical


# ---------------------------------------------------------------------------
# The grid executable family — the only dispatch path
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _grid_exec(st: SimStatic, n_dev: int,
               mechanism: Optional[MechanismSpec]):
    """Build (once per (SimStatic, device count, family)) the sharded grid
    executable: the flattened (workload x grid-point) axis is split across
    an ``n_dev``-device mesh with ``shard_map`` (identity on one device),
    and each local entry vmaps seeds (x traced fork-mechanism ids when
    ``mechanism`` is None). ``mechanism`` is a spec for the specialized
    families — static frequencies, oracle, and registered custom
    mechanisms (whose predict/update hooks trace in here without any
    sweep-layer change). The initial scan carry arrives pre-built and
    donated (see ``simulate.init_carry``)."""
    mesh = grid_mesh(n_dev)   # built once per process (launch.mesh)
    family = "grid_forks" if mechanism is None else f"grid_{mechanism.name}"

    @functools.partial(jax.jit, donate_argnums=(0,))
    def dispatch(carry0, progs, p_log, axes, seeds, mech_ids):
        with _COUNTER_LOCK:  # trace-time side effect; threads dispatch
            TRACE_COUNTS[family] += 1

        def shard_fn(carry0_s, progs_s, p_log_s, axes_s, seeds_s,
                     mech_ids_s):
            def per_entry(c0, prog, p_blocks, ax):
                def per_seed(seed):
                    if mechanism is None:
                        return jax.vmap(
                            lambda m: SIM._scan_sim(prog, p_blocks, seed, st,
                                                    ax, m, carry0=c0)
                        )(mech_ids_s)
                    return SIM._scan_sim(prog, p_blocks, seed, st, ax,
                                         mechanism, carry0=c0)
                return jax.vmap(per_seed)(seeds_s)
            return jax.vmap(per_entry)(carry0_s, progs_s, p_log_s, axes_s)

        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("i"), P("i"), P("i"), P("i"), P(), P()),
            out_specs=P("i"), check_vma=False,
        )(carry0, progs, p_log, axes, seeds, mech_ids)

    return dispatch


def _grid_points(axes_grid) -> Tuple[Tuple[str, ...], List[dict]]:
    """Normalize ``axes_grid`` into (axis names, list of override dicts).

    Dict-of-lists => cartesian product of the values; list-of-dicts =>
    explicit points (for coupled axes like the paper's epoch_us/n_epochs
    granularity sweep). Points must share the same axis *set*; their key
    insertion order is normalized to the first point's (callers building
    points from heterogeneous sources are describing the same grid).
    Output keys are the point's values in axis order.
    """
    if isinstance(axes_grid, dict):
        names = tuple(axes_grid)
        for n, vals in axes_grid.items():
            # catch {"objective": "edp"} (product would iterate the chars)
            # and bare scalars with a clean assert instead of a late error
            assert isinstance(vals, (list, tuple)), \
                f"axis {n!r} needs a list of values, got {vals!r}"
        points = [dict(zip(names, combo))
                  for combo in itertools.product(*axes_grid.values())]
        assert points, "axes_grid needs at least one point"  # empty values
    else:
        points = [dict(p) for p in axes_grid]
        assert points, "axes_grid needs at least one point"
        names = tuple(points[0])
        for p in points:
            assert set(p) == set(names), \
                f"grid points must share axes: {sorted(p)} vs {sorted(names)}"
        points = [{n: p[n] for n in names} for p in points]
    for p in points:
        for k in p:
            assert k in AXIS_FIELDS, \
                f"{k!r} is not a traced grid axis (one of {AXIS_FIELDS})"
            if k == "power":
                assert isinstance(p[k], PWR.PowerConfig), \
                    f"power axis values must be PowerConfig, got {p[k]!r}"
    return names, points


def _pad_flat(tree, n: int):
    """Pad a pytree's leading (flattened grid) axis to length ``n`` by
    cycling its entries (the pad rows are dropped on unpack)."""
    def pad(a):
        if a.shape[0] >= n:
            return a
        reps = -(-n // a.shape[0])
        return jnp.concatenate([a] * reps, axis=0)[:n]
    return jax.tree.map(pad, tree)


def _flat_operands(stacked: Program, p_logical: jnp.ndarray,
                   sims: Sequence[SimConfig], n_dev: int):
    """Flatten workload-major (flat index i = w * G + g for G grid points)
    and pad the flat axis to a device multiple for ``shard_map``."""
    W, G = int(p_logical.shape[0]), len(sims)
    axes_g = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[s.axes() for s in sims])
    progs_flat = jax.tree.map(lambda a: jnp.repeat(a, G, axis=0), stacked)
    p_log_flat = jnp.repeat(p_logical, G, axis=0)
    axes_flat = jax.tree.map(
        lambda a: jnp.tile(a, (W,) + (1,) * (a.ndim - 1)), axes_g)
    n_flat = W * G
    n_pad = -(-n_flat // n_dev) * n_dev
    if n_pad != n_flat:
        progs_flat = _pad_flat(progs_flat, n_pad)
        p_log_flat = _pad_flat(p_log_flat, n_pad)
        axes_flat = _pad_flat(axes_flat, n_pad)
    return progs_flat, p_log_flat, axes_flat, n_flat


@functools.lru_cache(maxsize=None)
def _carry_builder(st: SimStatic):
    """Jitted batched ``init_carry`` (compiled once per SimStatic + flat
    shape): the carry is rebuilt on every dispatch because it is donated,
    so the build itself must not re-trace on the warm path."""
    return jax.jit(jax.vmap(lambda pb: SIM.init_carry(pb, st)))


def _run_family(st: SimStatic, n_dev: int,
                mechanism: Optional[MechanismSpec],
                operands, seed_arr: jnp.ndarray, mech_ids: jnp.ndarray
                ) -> Dict[str, jnp.ndarray]:
    """Dispatch one executable family over pre-flattened grid operands."""
    progs_flat, p_log_flat, axes_flat, n_flat = operands
    family = "grid_forks" if mechanism is None else f"grid_{mechanism.name}"
    with _COUNTER_LOCK:
        DISPATCH_ROWS[family] += n_flat * max(int(mech_ids.shape[0]), 1)
    with jax.profiler.TraceAnnotation("repro.sweep.dispatch"), \
            warnings.catch_warnings():
        # the initial scan carry is rebuilt per dispatch: it is donated to
        # the executable, which invalidates its buffers
        carry0 = _carry_builder(st)(p_log_flat)
        # The donated carry can never alias into the executable's outputs
        # (the traces carry epoch/seed/mech axes the carry lacks), so XLA's
        # "not usable" warning is expected by construction on every
        # backend; donation still releases the init buffers to the runtime
        # as soon as the scan consumes them instead of pinning them for
        # the whole dispatch.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _grid_exec(st, n_dev, mechanism)(
            carry0, progs_flat, p_log_flat, axes_flat, seed_arr, mech_ids)


def _exec_classes(sims: Sequence[SimConfig], dedup_axes: Tuple[str, ...]
                  ) -> Tuple[List[int], List[SimConfig]]:
    """Partition grid points into equivalence classes of a mechanism's
    live axes (``MechanismSpec.dedup_axes`` — its declared ``exec_axes``
    mapped to SimConfig fields, minus the logical epoch count): points
    agreeing on every live axis produce bitwise-identical traces, so the
    mechanism scans once per class.

    Returns ``(class_of, class_sims)``: ``class_of[g]`` is the class index
    of point ``g``, and ``class_sims[c]`` the class representative — the
    point's execution-relevant axes with the class-max logical epoch count
    (the mask only zeroes outputs past ``n_ep``; the scan state is causal,
    so every member's trace is a prefix slice of the representative's)."""
    class_of: List[int] = []
    class_sims: List[SimConfig] = []
    index: Dict[tuple, int] = {}
    for s in sims:
        ck = tuple(getattr(s, a) for a in dedup_axes)
        c = index.setdefault(ck, len(class_sims))
        if c == len(class_sims):
            class_sims.append(s)
        elif s.n_epochs > class_sims[c].n_epochs:
            class_sims[c] = s
        class_of.append(c)
    return class_of, class_sims


def run_suite(programs: Union[Dict[str, Program], Sequence[Program]],
              sim: SimConfig,
              mechanisms: Sequence[Union[str, MechanismSpec]] = MECHANISMS,
              seeds: Optional[Sequence[int]] = None
              ) -> Dict[str, Dict[str, Dict[str, np.ndarray]]]:
    """Batched-sweep counterpart of calling ``run_sim`` in nested loops.

    This IS a 1-point ``run_grid`` — same executables, same numerics, no
    parallel dispatch family. Returns ``{workload_name: {mechanism: trace}}``
    with the same per-trace arrays ``run_sim`` produces. If ``seeds`` is
    given, every trace array gains a leading seed axis; otherwise
    ``sim.seed`` is used and the axis is squeezed away.
    """
    return run_grid(programs, sim, [{}], mechanisms, seeds)[()]


def run_grid(programs: Union[Dict[str, Program], Sequence[Program]],
             static_cfg: SimConfig, axes_grid,
             mechanisms: Sequence[Union[str, MechanismSpec]] = MECHANISMS,
             seeds: Optional[Sequence[int]] = None,
             max_mask_ratio: Optional[float] = None,
             dedup: bool = True
             ) -> Dict[tuple, Dict[str, Dict[str, Dict[str, np.ndarray]]]]:
    """One executable family for the whole figure grid.

    ``axes_grid`` is either a dict ``{axis: [values...]}`` whose values are
    cartesian-producted, or an explicit list of ``{axis: value}`` points
    (coupled axes); axes are the traced ``SimConfig`` fields in
    ``AXIS_FIELDS``. ``static_cfg`` supplies the static shape/flag fields
    and the default value of every axis not named in the grid.
    ``mechanisms`` are registered names or ``MechanismSpec`` values
    (resolved uniformly through ``repro.core.mechanisms``); results are
    keyed by spec name.

    Each grid point's ``SimAxes`` (with ``n_epochs`` as its logical epoch
    count — the scan runs to the grid max and the tail is masked/sliced)
    is stacked and vmapped alongside workloads x seeds x mechanism ids;
    the flattened (workload x grid-point) axis is sharded across local
    devices with ``shard_map`` (1-device mesh = identity). Traced
    fork--pre-execute mechanisms share executables; oracle, static
    frequencies and registered custom mechanisms compile specialized ones
    — for any grid size. Every mechanism is deduplicated across grid
    points by its spec's declared live axes (``MechanismSpec.exec_axes``):
    it scans once per equivalence class of points agreeing on those axes
    and the class trace is broadcast back to every member's grid key
    (bitwise — the other axes are dead inputs to its executable). A
    static frequency collapses objective and table_ema axes; a reactive
    (table-free) mechanism and oracle collapse table_ema axes; PC
    mechanisms consume every axis; a swept ``power`` regime (the traced
    IVR hardware point, ``PowerConfig`` values sharing one ladder length)
    is live for everyone and never collapses. ``dedup=False`` forces one
    scan per (mechanism x grid point), for A/B benchmarking.

    When logical epoch counts are strongly coupled to an axis (the paper's
    granularity sweeps pair 1 us with 6x the epochs of 100 us), scanning
    every point to the grid max wastes masked-tail compute;
    ``max_mask_ratio`` bounds that waste by partitioning the points into
    buckets whose max/min ``n_epochs`` ratio stays below it (one
    executable family per bucket, same merged result dict). ``None``
    keeps the whole grid in a single executable family.

    Returns ``{grid_key: {workload: {mechanism: trace}}}`` where
    ``grid_key`` is the tuple of the point's axis values in axis order and
    each trace matches the per-point ``run_suite`` output (seed axis
    squeezed unless ``seeds`` is given, epoch axis cut to the point's
    logical ``n_epochs``).
    """
    axis_names, points = _grid_points(axes_grid)
    keys = [tuple(p[n] for n in axis_names) for p in points]
    assert len(set(keys)) == len(keys), "duplicate grid points"

    if max_mask_ratio is not None and len(points) > 1:
        assert max_mask_ratio >= 1.0, max_mask_ratio
        buckets: List[List[dict]] = []
        for p in sorted(points, reverse=True,
                        key=lambda p: p.get("n_epochs", static_cfg.n_epochs)):
            n_ep = p.get("n_epochs", static_cfg.n_epochs)
            b_max = buckets[-1][0].get("n_epochs", static_cfg.n_epochs) \
                if buckets else None
            if buckets and b_max / n_ep <= max_mask_ratio:
                buckets[-1].append(p)
            else:
                buckets.append([p])
        if len(buckets) > 1:
            out: Dict[tuple, Dict] = {}
            for bucket in buckets:
                out.update(run_grid(programs, static_cfg, bucket,
                                    mechanisms, seeds, dedup=dedup))
            # restore the caller's grid-point order
            return {k: out[k] for k in keys}

    with jax.profiler.TraceAnnotation("repro.sweep.prep"):
        if isinstance(programs, dict):
            names_w = list(programs)
            progs = [programs[n] for n in names_w]
        else:
            progs = list(programs)
            names_w = [p.name for p in progs]
        assert progs, "run_grid needs at least one program"
        specs = [MECH.resolve(m) for m in mechanisms]
        if dedup:
            # Refuse under-declared specs BEFORE any dispatch: dedup
            # broadcasts one scan across every grid point agreeing on a
            # spec's declared live axes, so a trace reading an undeclared
            # axis would get silently wrong results. The audit (a tiny
            # make_jaxpr, no compile) is cached per spec per process —
            # builtins and repeat grids pay nothing after the first call.
            from repro.analysis.deps import require_dedup_sound
            for s in specs:
                require_dedup_sound(s)
        assert static_cfg.n_cu % static_cfg.cus_per_domain == 0

        squeeze_seed = seeds is None
        seed_arr = jnp.asarray(SIM.seed_i32(
            [static_cfg.seed] if seeds is None else list(seeds)))
        stacked, p_logical = _stack_programs(progs)
        W, G = len(progs), len(points)

        sims = [dataclasses.replace(static_cfg, **p) for p in points]
        n_ep_max = max(s.n_epochs for s in sims)
        # the ladder length is the one *static* field a power regime
        # carries: it sets shapes, so a grid may sweep regimes but not
        # n_freqs
        pstats = {s.power.static_part() for s in sims}
        assert len(pstats) == 1, \
            f"power grid values must share one ladder length, got {pstats}"
        st = sims[0].static_part(n_epochs=n_ep_max)
        # never shard wider than the flat axis: a 1-point manager report
        # on an 8-device host would otherwise pad one row to 8 identical
        # scans
        n_dev = min(jax.local_device_count(), W * G)
        full_ops = _flat_operands(stacked, p_logical, sims, n_dev)

    def classes_of(spec: MechanismSpec):
        """Grid-point equivalence classes of one spec's live axes."""
        if not dedup:
            return list(range(G)), sims
        return _exec_classes(sims, spec.dedup_axes)

    ops_cache: Dict[tuple, tuple] = {}

    def class_operands(class_of, class_sims):
        """(operands, n_dev) for a partition — the shared full-grid
        operands when it is trivial (so the common no-dead-axis case
        dispatches exactly the full-grid executable), memoized per
        partition so specs sharing one (all three statics, say) build the
        flattened arrays once."""
        if len(class_sims) == G:
            return full_ops, n_dev
        key = tuple(class_of)
        if key not in ops_cache:
            dev = min(jax.local_device_count(), W * len(class_sims))
            with jax.profiler.TraceAnnotation("repro.sweep.prep"):
                ops_cache[key] = (_flat_operands(stacked, p_logical,
                                                 class_sims, dev), dev)
        return ops_cache[key]

    # every family's outputs, whole, and per mechanism where its rows lie:
    # name -> (family index, mechanism index in a fork family or None,
    # class_of, n_classes)
    families: List[Dict[str, jax.Array]] = []
    where: Dict[str, Tuple[int, Optional[int], List[int], int]] = {}
    no_ids = jnp.zeros((0,), jnp.int32)  # specialized mechs ignore mech_ids

    # Traced fork-family mechanisms share executables; group them by the
    # *partition* their live axes induce on this grid (not by the axes
    # themselves), so mechanisms that agree on which points are equivalent
    # ride one dispatch. On a grid with no dead axes every traced spec
    # induces the identity partition and the whole family is ONE dispatch
    # over the full operands — bitwise-identical to the pre-spec dispatch;
    # a table_ema-only axis collapses the reactive (table-free) group to
    # one class per point set while PC mechanisms still span every point.
    groups: Dict[tuple, List[MechanismSpec]] = {}
    group_classes: Dict[tuple, Tuple[List[int], List[SimConfig]]] = {}
    for s in specs:
        if s.is_traced:
            class_of, class_sims = classes_of(s)
            gk = tuple(class_of)
            groups.setdefault(gk, []).append(s)
            group_classes[gk] = (class_of, class_sims)
    for gk, group in groups.items():
        class_of, class_sims = group_classes[gk]
        ops, dev = class_operands(class_of, class_sims)
        ids = jnp.asarray([SIM.FORK_MECH_IDS[s.name] for s in group],
                          jnp.int32)
        for j, s in enumerate(group):
            where[s.name] = (len(families), j, class_of, len(class_sims))
        families.append(_run_family(st, dev, None, ops, seed_arr, ids))

    # Specialized families — static frequencies, oracle, and registered
    # custom mechanisms — compile their own executable and dedup the same
    # generic way (a static mech ignores objective AND table_ema; oracle
    # ignores table_ema).
    for s in specs:
        if s.is_traced:
            continue
        class_of, class_sims = classes_of(s)
        ops, dev = class_operands(class_of, class_sims)
        where[s.name] = (len(families), None, class_of, len(class_sims))
        families.append(_run_family(st, dev, s, ops, seed_arr, no_ids))

    with jax.profiler.TraceAnnotation("repro.sweep.wait"):
        jax.block_until_ready(families)
    out: Dict[tuple, Dict[str, Dict[str, Dict[str, np.ndarray]]]] = {}
    with jax.profiler.TraceAnnotation("repro.sweep.unpack"):
        host = _fetch(families)
        for g, (key, sim_pt) in enumerate(zip(keys, sims)):
            out[key] = {}
            for w, name in enumerate(names_w):
                trs = {}
                for s in specs:
                    f, j, class_of, C = where[s.name]
                    trs[s.name] = _unpack_trace(host[f], w * C + class_of[g],
                                                s, squeeze_seed,
                                                sim_pt.n_epochs, j)
                out[key][name] = trs
    return out


# ---------------------------------------------------------------------------
# GridExecutor — the long-lived compiled-family handle for request streams
# ---------------------------------------------------------------------------


class PendingGrid:
    """The in-flight result of one :class:`GridExecutor` micro-batch.

    Dispatch is asynchronous: this object holds the executables' output
    arrays, whole, plus the row bookkeeping to cut them back into per-job
    ``run_sim``-schema traces, and nothing here synchronizes with the
    device until ``block_until_ready``/``traces`` is called — the caller
    can keep preparing and dispatching later batches while this one
    computes. ``traces`` fetches each output array to the host once and
    cuts every job from it there, so collecting a batch enqueues no device
    program behind the batches still in flight."""

    def __init__(self, families, mechs, jobs):
        # families: the executables' output dicts; mechs: per mechanism,
        # (spec, family index, mechanism index in a fork family or None);
        # jobs: per real job, (flat row, logical n_epochs)
        self._families = families
        self._mechs = mechs
        self._jobs = jobs
        self.n_jobs = len(jobs)

    def block_until_ready(self) -> "PendingGrid":
        jax.block_until_ready(self._families)
        return self

    def traces(self) -> List[Dict[str, Dict[str, np.ndarray]]]:
        """Per-job ``{mechanism: trace}`` results (np arrays; blocks)."""
        host = _fetch(self._families)
        return [{s.name: _unpack_trace(host[f], i, s, True, n_ep, j)
                 for s, f, j in self._mechs}
                for i, n_ep in self._jobs]


class GridExecutor:
    """A reusable handle on the compiled grid-executable family: the
    object a long-lived DVFS service holds between requests.

    ``run_grid`` lays out its operands per call from a (workloads x
    grid-points) product; a service consuming a *stream* of (job,
    telemetry) requests instead wants one static configuration compiled
    once and then fed micro-batches forever. A GridExecutor pins the
    static half — the ``SimStatic`` (shapes, flags, ladder length), the
    padded program block count ``p_max``, the mechanism set and the seed —
    plus a small set of static micro-batch shapes (``buckets``).
    ``dispatch`` pads each job list to the smallest admitting bucket by
    cycling jobs (the same move as ``run_grid``'s device-multiple
    padding; pad rows are dropped on unpack) and rides the SAME
    ``_grid_exec`` executables every ``run_grid`` call uses, so streamed
    rows are bitwise-equal to the one-shot grid answer for the same jobs
    and a whole request stream compiles at most one executable per
    (bucket shape x family) — with a single service bucket the fork
    family compiles ONCE for the life of the process, exactly the
    ``run_grid`` no-retrace contract carried over to streaming.

    ``buckets=None`` dispatches each batch at its exact size (one shape
    per distinct batch length — the mode for fixed-shape clients like the
    DVFS manager, whose repeated reports always arrive at the same batch
    size and therefore share ``run_grid``'s own executables); a tuple of
    sizes is the streaming mode. Dispatch is async — the returned
    :class:`PendingGrid` does not synchronize — and every dispatch builds
    its families' initial carries through the jit-cached per-``SimStatic``
    ``_carry_builder`` pool and donates them, so a depth-2 service
    pipeline keeps two carry generations alive: batch N+1's carry build
    and host->device transfer overlap batch N's compute."""

    def __init__(self, static_cfg: SimConfig,
                 mechanisms: Sequence[Union[str, MechanismSpec]] = MECHANISMS,
                 *, p_max: int = 1024,
                 buckets: Optional[Sequence[int]] = None,
                 n_dev: Optional[int] = None):
        self.static_cfg = static_cfg
        self.specs = [MECH.resolve(m) for m in mechanisms]
        assert self.specs, "GridExecutor needs at least one mechanism"
        self.p_max = p_max
        self.buckets = None if buckets is None else tuple(sorted(buckets))
        assert self.buckets is None or all(b >= 1 for b in self.buckets)
        self.n_dev = jax.local_device_count() if n_dev is None else n_dev
        self._st = static_cfg.static_part()
        self._seed_arr = jnp.asarray(SIM.seed_i32([static_cfg.seed]))
        self._traced = [s for s in self.specs if s.is_traced]
        self._special = [s for s in self.specs if not s.is_traced]
        self._fork_ids = jnp.asarray(
            [SIM.FORK_MECH_IDS[s.name] for s in self._traced], jnp.int32)
        self._no_ids = jnp.zeros((0,), jnp.int32)

    @property
    def max_batch(self) -> Optional[int]:
        """Largest micro-batch one dispatch admits (None = unbounded)."""
        return None if self.buckets is None else self.buckets[-1]

    def _bucket(self, n: int) -> int:
        if self.buckets is None:
            return n
        for b in self.buckets:
            if b >= n:
                return b
        raise AssertionError(
            f"micro-batch of {n} jobs exceeds the largest static shape "
            f"bucket {self.buckets[-1]} — split the batch or widen buckets")

    def dispatch(self, jobs: Sequence[Tuple[Program, dict]]) -> PendingGrid:
        """Dispatch one micro-batch of ``(Program, axes_overrides)`` jobs.

        Each job is one flat row of the grid executable: its program
        (padded to ``p_max`` blocks — semantics preserved, see
        ``pad_program``) and its own traced ``SimAxes`` point built from
        the executor's static config plus the per-job overrides (any
        ``AXIS_FIELDS`` subset; a job's logical ``n_epochs`` may not
        exceed the executor's static scan length). Asynchronous: returns
        a :class:`PendingGrid` immediately."""
        n = len(jobs)
        assert n >= 1, "dispatch needs at least one job"
        # Floor the bucket at 2 rows: a 1-row flat dispatch lets XLA fuse
        # the degenerate leading axis and codegen f32 chains at a shifted
        # last ulp vs the >=2-row shapes run_grid dispatches, breaking the
        # bitwise streamed-vs-one-shot contract for batch-1 requests. The
        # pad row is a cycled copy dropped on unpack, and ``ops[3]`` below
        # stays the logical ``n`` so DISPATCH_ROWS accounting is unchanged.
        bucket = max(self._bucket(n), 2)
        padded = [jobs[i % n] for i in range(bucket)]
        sims = []
        for prog, ov in padded:
            for k in ov:
                assert k in AXIS_FIELDS, \
                    f"{k!r} is not a traced grid axis (one of {AXIS_FIELDS})"
            s = dataclasses.replace(self.static_cfg, **dict(ov))
            assert s.n_epochs <= self._st.n_epochs, \
                f"job n_epochs {s.n_epochs} exceeds the executor's static " \
                f"scan length {self._st.n_epochs}"
            assert s.static_part(n_epochs=self._st.n_epochs) == self._st, \
                "job overrides must not change the executor's static half " \
                f"(got {s.static_part(n_epochs=self._st.n_epochs)})"
            assert prog.n_blocks <= self.p_max, \
                f"program {prog.name!r} has {prog.n_blocks} blocks > " \
                f"executor p_max {self.p_max}"
            sims.append(s)

        axes_flat = jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *[s.axes() for s in sims])
        p_log = jnp.asarray([p.n_blocks for p, _ in padded], jnp.int32)
        pp = [pad_program(p, self.p_max) for p, _ in padded]
        stacked = Program(
            "suite",
            *(jnp.stack([getattr(p, f) for p in pp])
              for f in ("i0_rate", "sens_rate", "mem_frac", "cum3")))
        n_dev = min(self.n_dev, bucket)
        n_pad = -(-bucket // n_dev) * n_dev
        if n_pad != bucket:
            stacked = _pad_flat(stacked, n_pad)
            p_log = _pad_flat(p_log, n_pad)
            axes_flat = _pad_flat(axes_flat, n_pad)
        # stage operands on device explicitly and asynchronously: under a
        # depth-2 service pipeline this host->device transfer (and the
        # donated carry build inside _run_family) overlaps the previous
        # batch's compute instead of queueing behind it at call time
        stacked, p_log, axes_flat = jax.device_put(
            (stacked, p_log, axes_flat))
        ops = (stacked, p_log, axes_flat, n)

        families = []
        where: Dict[str, Tuple[int, Optional[int]]] = {}
        if self._traced:
            where.update({s.name: (0, j) for j, s in enumerate(self._traced)})
            families.append(_run_family(self._st, n_dev, None, ops,
                                        self._seed_arr, self._fork_ids))
        for s in self._special:
            where[s.name] = (len(families), None)
            families.append(_run_family(self._st, n_dev, s, ops,
                                        self._seed_arr, self._no_ids))
        return PendingGrid(families,
                           [(s, *where[s.name]) for s in self.specs],
                           [(j, sims[j].n_epochs) for j in range(n)])

    def run(self, jobs: Sequence[Tuple[Program, dict]]
            ) -> List[Dict[str, Dict[str, np.ndarray]]]:
        """Synchronous convenience: ``dispatch`` + unpack."""
        return self.dispatch(jobs).traces()


def suite_metrics(programs: Union[Dict[str, Program], Sequence[Program]],
                  sim: SimConfig,
                  mechanisms: Sequence[Union[str, MechanismSpec]] = MECHANISMS,
                  n: int = 2,
                  traces: Optional[Dict] = None,
                  baseline: Union[str, MechanismSpec] = "static17"
                  ) -> Dict[str, Dict[str, Dict[str, float]]]:
    """Batched counterpart of ``run_workload`` over a whole suite: ED^nP
    per workload, normalized to ``baseline`` (any registered mechanism;
    default the paper's static 1.7 GHz). Pass ``traces`` (a ``run_suite``
    result that includes the baseline) to reuse already-computed traces."""
    mech_specs = [MECH.resolve(m) for m in mechanisms]
    base_spec = MECH.resolve(baseline)
    if traces is None:
        need = tuple(mechanisms)
        if all(s.name != base_spec.name for s in mech_specs):
            need = (base_spec,) + need
        traces = run_suite(programs, sim, need)
    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    for name, trs in traces.items():
        base = trs[base_spec.name]
        budget = 0.9 * base["work"].sum()
        E0, D0, M0 = ednp(base, budget, sim.epoch_us, n)
        out[name] = {}
        for s in mech_specs:
            E, D, M = ednp(trs[s.name], budget, sim.epoch_us, n)
            out[name][s.name] = {
                "accuracy": prediction_accuracy(trs[s.name])
                if s.family != "static" else float("nan"),
                "E": E, "D": D, "ednp": M, "ednp_norm": M / M0,
                "energy_norm": E / E0, "delay_norm": D / D0,
            }
    return out
