"""Fine-grain DVFS simulation engine (paper §5 methodology, in JAX).

One ``lax.scan`` step = one fixed-time epoch (paper §3.1):

  1. *fork--pre-execute oracle* (paper Fig 13): the epoch is evaluated at all
     10 V/f states from bit-identical starting conditions — a functional
     simulator needs no process forking, and the per-epoch noise is keyed by
     (block, loop-iteration, wavefront) so forks see identical stochasticity,
     exactly like the paper's forked gem5 processes;
  2. the mechanism under test predicts next-epoch instructions I(f);
  3. the controller picks the per-domain frequency optimizing the objective;
  4. the epoch is (re-)executed with the chosen mixed per-CU frequencies;
  5. estimators digest the epoch's counters and update predictor state.

Ground-truth execution model: wavefront at PC block b commits
``(i0 + sens*f)*T`` instructions (window-averaged over the blocks traversed),
subject to (a) oldest-first issue-capacity contention within the CU
(Fig 11a) and (b) a shared L2/DRAM bandwidth cap across CUs (the FwdSoft
L2-thrash second-order effect, §6.2).

Batched execution model
-----------------------
The fork--pre-execute step and the real mixed-frequency execution share one
per-epoch *context* (``_epoch_context``): the PC-block gather, the loop
iteration index, and the deterministic noise hash are computed once per
epoch and reused by every frequency row. ``_execute_ctx`` then evaluates an
arbitrary ``(..., CU)`` batch of frequency vectors against that context, so
for every mechanism whose prediction does not depend on this epoch's forks
(everything except ``oracle``) the 10 uniform fork rows and the chosen
mixed-frequency row run as a single 11-way batched execute.

Caching contract
----------------
``SimConfig`` is split into two views for the jit boundary:

* ``SimStatic`` (``sim.static_part()``) — the shape/flag fields (CU/WF
  counts, scan length, table geometry, ``record_wf``, ``use_pallas``).
  This hashable frozen dataclass is the *only* config key of the cached
  executables.
* ``SimAxes`` (``sim.axes()``) — everything that can vary across a figure
  grid (``epoch_us``, ``sigma``, ``cap_per_ghz``, ``membw``, ``table_ema``,
  the objective lowered to a weight vector, the logical epoch count, and
  the ``power`` regime — a nested ``power.PowerAxes`` pytree carrying the
  V/f ladder endpoints, leakage/efficiency/capacitance constants and the
  IVR transition-latency model) as a traced pytree of scalars. The V/f
  ladder itself is built in-trace from the traced endpoints and the static
  ladder length (``PowerStatic.n_freqs``, nested in ``SimStatic``), so a
  whole IVR-regime sensitivity sweep rides one executable.

Mechanism dispatch contract
---------------------------
Mechanisms are *data*: every mechanism is a frozen ``MechanismSpec`` in
the ``repro.core.mechanisms`` registry, and this engine derives its whole
dispatch structure from the specs — the family branch taken by the scan
body (static / reactive / pc / oracle), the static V/f index, the traced
fork-family ids the branch selects compare against (frozen by the
registry: they are part of the bitwise contract, verified against
captured reference traces in ``tests/data``), and the predictor/estimator
hooks of user-registered mechanisms (which trace into their own
specialized executable without any engine edit — see
``mechanisms.register``).

``run_sim`` dispatches through a ``jax.jit`` entry point whose static keys
are ``SimStatic`` and the resolved ``MechanismSpec``; ``Program`` is a
registered pytree traced by shape only, and ``SimAxes`` rides along as a
traced operand. Repeated calls that differ only in axis values — a
fig-15/17/18 sweep over epoch granularities or objectives — therefore hit
the same executable and never re-trace. The scan body also accepts a
*traced* mechanism id (see ``FORK_MECHS``) so the batched sweep layer
(``repro.core.sweep``) can vmap one compiled executable across mechanisms,
workloads, seeds, *and* whole ``SimAxes`` grids (``run_grid``).

The objective is lowered from a string branch to a (3,) weight vector
``[pbar_weight, use_rate, cap_fraction]`` (see ``objective_weights``) so
EDP, ED^2P and the perf-cap objectives are a single traced code path.

``n_epochs`` couples to ``epoch_us`` in the paper's granularity sweeps, so
the scan always runs to the static ``SimStatic.n_epochs`` while
``SimAxes.n_ep`` carries the *logical* epoch count: epochs past it are
masked to zero in the outputs (the same pad-and-mask move the sweep layer
applies to programs of different block counts).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import estimators as EST
from repro.core import mechanisms as MECH
from repro.core import power as PWR
from repro.core import predictors as PRED
from repro.core.mechanisms import MechanismSpec
from repro.core.workloads import INSTR_PER_BLOCK, Program

# The mechanism family is DATA (repro.core.mechanisms): every dispatch
# structure below — name tuples, static frequency indices, traced fork ids,
# predictor-branch selection — derives from the MechanismSpec registry.
# The derived VALUES are part of the bitwise contract (captured reference
# traces in tests/data/): the registry freezes builtin traced ids, so the
# compiled graphs cannot drift.
MECHANISMS = MECH.BUILTIN_NAMES

# Mechanisms that run the fork--pre-execute step, in traced-id order: the
# batched sweep layer vmaps the scan over these integer ids (the carry is
# shape-identical across all of them). The traced path only accepts
# non-oracle ids (0..6): oracle predicts from this epoch's forks, which
# breaks the fused 11-way execute, so run_suite gives it its own
# specialized executable (user-registered mechanisms dispatch the same
# way — see mechanisms.register).
FORK_MECHS = tuple(s.name for s in MECH.fork_specs())
FORK_MECH_IDS = {m: i for i, m in enumerate(FORK_MECHS)}
# traced ids 0.._N_REACT-1 predict from CU-level reactive state (registry
# asserts contiguity: the branch select is a single `mech < n` compare)
_N_REACT = MECH.traced_reactive_count()
_REACT_SPECS = tuple(s for s in MECH.fork_specs()
                     if s.is_traced and s.family == "reactive")
_PC_IDS = tuple(s.traced_id for s in MECH.fork_specs()
                if s.is_traced and s.family == "pc")
# the one traced PC mechanism estimating from hardware counters (pcstall);
# the other (accpc) takes the exact per-WF linear model from the forks
_ID_CTR_PC = next(s.traced_id for s in MECH.fork_specs()
                  if s.is_traced and s.family == "pc"
                  and not s.fork_estimator)
# the traced scan body builds its reactive-estimator select in this order:
# counter models at ids 0..n-2, the fork-accurate reactive (accreac) last
assert all(s.cu_model for s in _REACT_SPECS[:-1]) and \
    _REACT_SPECS[-1].fork_estimator, _REACT_SPECS
# the shared traced-id executable can run the fused v2 epoch kernel only
# if EVERY mechanism it multiplexes is v2-capable (all builtin traced
# fork mechanisms are; the flag exists for the fallback contract of
# oracle/custom/static specs — see MechanismSpec.v2_capable)
_FORK_V2_CAPABLE = all(s.v2_capable for s in MECH.fork_specs()
                       if s.is_traced)


@dataclass(frozen=True)
class SimStatic:
    """Shape/flag half of ``SimConfig`` — the jit cache key. Everything
    here changes array shapes or trace structure; everything that doesn't
    lives in ``SimAxes`` and is traced. Construct via
    ``SimConfig.static_part()`` (all fields required: defaults live on
    ``SimConfig`` only, so they cannot drift)."""
    n_cu: int
    n_wf: int
    n_epochs: int                 # static scan length (max over a grid)
    entries: int
    offset_blocks: int
    cus_per_table: int
    cus_per_domain: int
    record_wf: bool
    # Pallas kernel generation: False (pure jnp), "v1" (fused PC-table
    # predict/update pair), "v2" (ONE fused fork--execute epoch kernel),
    # True = auto (v2 when the mechanism/flags permit, else v1, else jnp)
    use_pallas: Union[bool, str]
    # v2 only: tile the CU axis of the fused kernel over a
    # (n_cu // pallas_block_cu,)-grid pallas_call pair (None = monolithic)
    pallas_block_cu: Optional[int]
    power: PWR.PowerStatic        # ladder length (sets fork/predict shapes)


class SimAxes(NamedTuple):
    """Traced sweep axes: one grid point of the figure grid. All scalars
    (``obj`` is the (3,) lowered objective) so the sweep layer can stack
    grid points along a leading axis and vmap the scan over them."""
    epoch_us: jnp.ndarray     # () f32
    sigma: jnp.ndarray        # () f32
    cap_per_ghz: jnp.ndarray  # () f32
    membw: jnp.ndarray        # () f32
    table_ema: jnp.ndarray    # () f32
    obj: jnp.ndarray          # (3,) f32 [pbar_weight, use_rate, cap_frac]
    n_ep: jnp.ndarray         # () i32 logical epochs (<= SimStatic.n_epochs)
    power: PWR.PowerAxes      # nested traced IVR/hardware regime


# the registry declares the axis vocabulary MechanismSpec.exec_axes is
# validated against; it must be exactly the traced grid-point fields
assert SimAxes._fields == MECH.SIM_AXES_FIELDS, \
    (SimAxes._fields, MECH.SIM_AXES_FIELDS)


def objective_weights(objective: str) -> np.ndarray:
    """Lower an objective name to the traced weight vector
    ``[pbar_weight, use_rate, cap_frac]`` consumed by ``_select_freq``:

      cost = (P_dom + pbar_weight * Pbar) / where(use_rate, I_sum, 1)
             + BIG * (I_sum < cap_frac * I_sum[fmax])

    EDP/ED^2P set ``pbar_weight`` to the delay exponent n (the online
    Lagrangian marginal-cost weight) and divide by the rate; perf-cap
    objectives drop both and penalize infeasible frequencies instead.

    ``deadline<pct>`` is the deadline-aware energy objective in the
    Ilager et al. style (arXiv:2004.08177): minimize power — including
    the online average-power term, which keeps sustained draw low across
    phases — subject to holding at least ``1 - pct/100`` of the
    max-frequency rate (the per-epoch deadline slack). It differs from
    ``perfcap<pct>`` exactly by the ``pbar_weight`` term: perf-cap
    minimizes instantaneous power alone under the same feasibility
    penalty. New objectives lower here into the FIXED (3,) vector — the
    traced graph never changes, so they sweep through ``run_grid`` like
    any other ``objective`` axis value with zero dispatch edits."""
    if objective == "edp":
        return np.asarray([1.0, 1.0, 0.0], np.float32)
    if objective == "ed2p":
        return np.asarray([2.0, 1.0, 0.0], np.float32)
    if objective.startswith("perfcap"):
        capf = 1.0 - float(objective[-2:]) / 100.0
        return np.asarray([0.0, 0.0, capf], np.float32)
    if objective.startswith("deadline"):
        pct = objective[len("deadline"):]
        if len(pct) != 2 or not pct.isdigit():
            raise ValueError(objective)
        capf = 1.0 - float(pct) / 100.0
        return np.asarray([1.0, 0.0, capf], np.float32)
    raise ValueError(objective)


@dataclass(frozen=True)
class SimConfig:
    n_cu: int = 64
    n_wf: int = 40
    epoch_us: float = 1.0
    n_epochs: int = 1500
    entries: int = 128
    offset_blocks: int = 8        # blocks/entry: 128 entries cover a 1024-block loop
    cus_per_table: int = 1
    cus_per_domain: int = 1
    objective: str = "ed2p"       # 'edp'|'ed2p'|'perfcap<pct>'|'deadline<pct>'
    sigma: float = 0.06           # same-PC iteration noise (Fig 10 ~10%)
    cap_per_ghz: float = 5500.0   # CU issue capacity, instr/us per GHz
    membw: float = 160_000.0      # shared-path capacity, instr-traffic/us
    table_ema: float = 0.5
    record_wf: bool = False
    # False | True | "v1" | "v2" — Pallas kernel generation (see SimStatic)
    use_pallas: Union[bool, str] = False
    # v2 blocked-(CU,)-grid tile size (None = monolithic kernel)
    pallas_block_cu: Optional[int] = None
    power: PWR.PowerConfig = PWR.DEFAULT  # V/f + IVR hardware regime
    seed: int = 0

    def static_part(self, n_epochs: Optional[int] = None) -> SimStatic:
        """The hashable jit key. ``n_epochs`` overrides the scan length
        (the sweep layer passes the max over a grid)."""
        return SimStatic(
            n_cu=self.n_cu, n_wf=self.n_wf,
            n_epochs=self.n_epochs if n_epochs is None else n_epochs,
            entries=self.entries, offset_blocks=self.offset_blocks,
            cus_per_table=self.cus_per_table,
            cus_per_domain=self.cus_per_domain,
            record_wf=self.record_wf, use_pallas=self.use_pallas,
            pallas_block_cu=self.pallas_block_cu,
            power=self.power.static_part())

    def axes(self) -> SimAxes:
        """The traced grid-point operand (logical epochs = ``n_epochs``)."""
        return SimAxes(
            epoch_us=jnp.float32(self.epoch_us),
            sigma=jnp.float32(self.sigma),
            cap_per_ghz=jnp.float32(self.cap_per_ghz),
            membw=jnp.float32(self.membw),
            table_ema=jnp.float32(self.table_ema),
            obj=jnp.asarray(objective_weights(self.objective)),
            n_ep=jnp.int32(self.n_epochs),
            power=self.power.axes())


class Carry(NamedTuple):
    pos: jnp.ndarray         # (CU,WF) absolute instruction index
    react_i0: jnp.ndarray    # (CU,) reactive CU-level state
    react_sens: jnp.ndarray
    wf_i0: jnp.ndarray       # (CU,WF) per-WF fallback state
    wf_sens: jnp.ndarray
    table: PRED.PCTable
    f_prev: jnp.ndarray      # (CU,)
    e_acc: jnp.ndarray       # (CU,) accumulated energy (for online Pbar)
    t_acc: jnp.ndarray       # () accumulated time


class EpochCtx(NamedTuple):
    """Frequency-independent per-epoch state, computed once and shared by
    every frequency row of the batched execute (forks + real execution)."""
    blk: jnp.ndarray    # (CU,WF) int32 starting PC block
    i0_l: jnp.ndarray   # (CU,WF) local i0 rate at blk
    s_l: jnp.ndarray    # (CU,WF) local sens rate at blk
    eps: jnp.ndarray    # (CU,WF) deterministic (block,loop,wf,cu)-keyed noise
    cum3: jnp.ndarray   # (2P+1,3) packed (cum_i0, cum_sens, cum_mem)
    cum_lo: jnp.ndarray  # (CU,WF,3) cum3 gathered at blk (window low side)


def _epoch_context(prog: Program, pos: jnp.ndarray, p_blocks,
                   seed) -> EpochCtx:
    blk = (pos.astype(jnp.int32) // INSTR_PER_BLOCK) % p_blocks  # (CU,WF)
    i0_l = prog.i0_rate[blk]
    s_l = prog.sens_rate[blk]
    # one packed gather row per window side: 12 contiguous bytes/index
    # instead of three strided single-float gathers; the low side depends
    # only on pos, so it is shared by all frequency rows. The packed
    # (2P+1,3) stack is a scan-invariant precomputed on Program.
    cum3 = prog.cum3
    cum_lo = cum3[blk]
    # deterministic (block, loop, wf, cu)-keyed noise — identical for every
    # fork and for the real execution (the paper's fork property). The seed
    # is carried as int32 end-to-end (a float32 seed aliases integers above
    # 2^24 to the same noise stream: consecutive large seeds silently share
    # a stream) and cast only here, split into exactly-representable
    # halves folded into ONE scalar phase: the low half keeps the
    # historical ``seed * 3.7`` term (seeds < 65536 reproduce the pre-int32
    # stream bitwise — the high term is an exact +0, and the array-side
    # graph is unchanged: one scalar-broadcast add, so XLA fusion and the
    # downstream reduction orders stay put); the high half rotates by a
    # golden-ratio multiple of 3.7 so nearby (lo, hi) pairs stay ulp-
    # separated. f32 cannot hold 2^32 distinct phases, so pathological
    # distant pairs can still collide — but no *consecutive* seeds do,
    # at any magnitude.
    loop = (pos // (INSTR_PER_BLOCK * p_blocks)).astype(jnp.float32)
    wf_id = jnp.arange(pos.shape[1], dtype=jnp.float32)[None, :]
    cu_id = jnp.arange(pos.shape[0], dtype=jnp.float32)[:, None]
    seed = jnp.asarray(seed, jnp.int32)
    s_lo = (seed % 65536).astype(jnp.float32)
    s_hi = (seed // 65536).astype(jnp.float32)
    seed_phase = s_lo * 3.7 + s_hi * 2.2867257  # 3.7 * golden ratio
    h = jnp.sin(blk * 12.9898 + loop * 78.233 + wf_id * 37.719
                + cu_id * 9.131 + seed_phase) * 43758.5453
    eps = (h - jnp.floor(h)) * 2.0 - 1.0
    return EpochCtx(blk=blk, i0_l=i0_l, s_l=s_l, eps=eps,
                    cum3=cum3, cum_lo=cum_lo)


class _SteadyParts(NamedTuple):
    """Intermediates of the steady-state execute for a ``(..., CU)`` batch of
    frequency rows. Fork rows consume only ``steady``; the selected row is
    completed into full hardware counters by ``_row_counters`` (so XLA DCEs
    the barrier/contention math for the 10 fork rows)."""
    steady: jnp.ndarray
    alloc: jnp.ndarray
    demand: jnp.ndarray
    i0w: jnp.ndarray
    sw: jnp.ndarray
    mfw: jnp.ndarray


def _steady_parts(ctx: EpochCtx, pos: jnp.ndarray,
                  f_cu: jnp.ndarray, p_blocks, ax: SimAxes) -> _SteadyParts:
    """Steady-state committed instructions at frequency rows ``f_cu`` of
    shape ``(..., CU)`` against a shared epoch context; all outputs carry
    the batch shape."""
    T = ax.epoch_us
    f_b = f_cu[..., :, None]                                  # (...,CU,1)
    est_instr = (ctx.i0_l + ctx.s_l * f_b) * T
    nblk = jnp.clip((est_instr / INSTR_PER_BLOCK).astype(jnp.int32) + 1,
                    1, p_blocks)
    wavg = (ctx.cum3[ctx.blk + nblk] - ctx.cum_lo) / nblk[..., None]
    i0w, sw, mfw = wavg[..., 0], wavg[..., 1], wavg[..., 2]
    demand = (i0w + sw * f_b) * T
    demand = demand * (1.0 + ax.sigma * ctx.eps)
    # oldest-first issue allocation (slot index = age priority)
    C = ax.cap_per_ghz * f_cu * T
    before = jnp.cumsum(demand, axis=-1) - demand
    alloc = jnp.clip(C[..., :, None] - before, 0.0, demand)
    # shared L2/DRAM bandwidth coupling across all CUs
    traffic = (alloc * mfw).sum(axis=(-2, -1))
    scale = jnp.minimum(1.0, ax.membw * T / jnp.maximum(traffic, 1e-6))
    steady = alloc * (1.0 - mfw * (1.0 - scale[..., None, None]))
    return _SteadyParts(steady, alloc, demand, i0w, sw, mfw)


def _row_counters(parts: _SteadyParts, pos: jnp.ndarray, f_cu: jnp.ndarray,
                  p_blocks
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Complete one frequency row into the full hardware-counter view.

    Workgroup barrier at each kernel-loop boundary: wavefronts wait for the
    slowest wave in their CU before starting the next iteration. This keeps
    a CU's waves phase-aligned (GPU kernels barrier/relaunch per loop) and
    is what gives CUs their strong fine-grain phase behavior (Figs 6-8).
    Barrier-idle time truncates *work* but controllers/estimators reason on
    steady-state throughput ("committed" counter continues to tick in HW).
    """
    f_b = f_cu[..., :, None]
    q = parts.alloc / jnp.maximum(parts.demand, 1e-6)
    plen = jnp.asarray(p_blocks * INSTR_PER_BLOCK, jnp.float32)
    tentative = pos + parts.steady
    group_min = tentative.min(axis=-1)                        # slowest wave
    boundary = (jnp.floor(group_min / plen) + 1.0) * plen     # (...,CU)
    committed = jnp.minimum(parts.steady,
                            jnp.maximum(boundary[..., :, None] - pos, 0.0))
    core_frac = parts.sw * f_b / jnp.maximum(parts.i0w + parts.sw * f_b, 1e-6)
    counters = {"committed": committed, "steady": parts.steady,
                "core_frac": core_frac, "issue_q": q, "mem_frac": parts.mfw}
    return committed, counters


def _execute_ctx(ctx: EpochCtx, pos: jnp.ndarray,
                 f_cu: jnp.ndarray, p_blocks, ax: SimAxes
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full execute (steady + barrier/contention counters) of ``f_cu``
    frequency rows of shape ``(..., CU)`` against a shared epoch context."""
    parts = _steady_parts(ctx, pos, f_cu, p_blocks, ax)
    return _row_counters(parts, pos, f_cu, p_blocks)


def epoch_execute(prog: Program, pos: jnp.ndarray, f_cu: jnp.ndarray,
                  sim: SimConfig) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Ground-truth execution of one epoch at per-CU frequencies ``f_cu``.
    Deterministic in (pos, f) — this *is* the fork property."""
    ax = sim.axes()
    ctx = _epoch_context(prog, pos, prog.n_blocks, sim.seed)
    committed, counters = _execute_ctx(ctx, pos, f_cu, prog.n_blocks, ax)
    counters = dict(counters, start_block=ctx.blk)
    return committed, counters


def _predict_instr(i0_cu, sens_cu, st: SimStatic, ax: SimAxes):
    """(CU,) linear state -> predicted I at every ladder frequency,
    capacity-clipped. The ladder derives from the traced power regime."""
    F = PWR.freqs_ghz(ax.power, st.power.n_freqs)
    I = (i0_cu[:, None] + sens_cu[:, None] * F[None, :]) * ax.epoch_us
    cap = ax.cap_per_ghz * F[None, :] * ax.epoch_us * st.n_wf
    return jnp.clip(I, 0.0, cap)


# public alias for MechanismSpec.predict hooks: lower a per-CU linear model
# (rates in instr/us and instr/us/GHz) to the capacity-clipped (CU, 10)
# prediction the frequency controller consumes
predict_instr = _predict_instr


def _select_freq(I_pred_f: jnp.ndarray, st: SimStatic, ax: SimAxes,
                 pbar_dom: jnp.ndarray) -> jnp.ndarray:
    """Choose per-domain frequency minimizing the objective.

    For ED^nP the globally-optimal allocation equalizes the marginal
    energy-per-delay de/dd = -n*(E/D) across phases, so the correct greedy
    per-epoch cost is (P(f) + n*Pbar) / rate(f) where Pbar = E/D is the
    domain's accumulated average power (online Lagrangian; a naive P/I^(n+1)
    greedy systematically over/under-clocks heterogeneous phase mixes).

    The objective arrives lowered as ``ax.obj = [w_pbar, use_rate, capf]``
    (see ``objective_weights``) so all objectives share one traced path:
    EDP/ED^2P divide the Lagrangian power by the rate (``use_rate=1``,
    ``capf=0`` never penalizes), perf-cap objectives keep raw power and add
    a big penalty on frequencies below ``capf`` of the max-frequency rate.

    I_pred_f: (CU, n_freqs); pbar_dom: (n_dom,). Returns selected index (CU,).
    """
    F = PWR.freqs_ghz(ax.power, st.power.n_freqs)
    n_dom = st.n_cu // st.cus_per_domain
    I_dom = I_pred_f.reshape(n_dom, st.cus_per_domain, -1)
    act = I_pred_f / (ax.cap_per_ghz * F[None, :] * ax.epoch_us * st.n_wf)
    p_cu = PWR.power(F[None, :], act, ax.power)             # (CU,NF)
    P_dom = p_cu.reshape(n_dom, st.cus_per_domain, -1).sum(1)  # (dom,10)
    I_sum = jnp.maximum(I_dom.sum(1), 1e-3)                 # (dom,10)
    w_pbar, use_rate, capf = ax.obj[0], ax.obj[1], ax.obj[2]
    denom = jnp.where(use_rate > 0.0, I_sum, 1.0)
    infeasible = I_sum < capf * I_sum[:, -1:]
    cost = (P_dom + w_pbar * pbar_dom[:, None]) / denom + 1e9 * infeasible
    idx_dom = jnp.argmin(cost, axis=-1)                     # (dom,)
    return jnp.repeat(idx_dom, st.cus_per_domain)


def _true_wf_linear(c_f: jnp.ndarray, F: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """c_f: (NF, CU, WF) fork-committed at ladder ``F`` -> exact per-WF
    (i0_rate, sens)."""
    sens = (c_f[-1] - c_f[0]) / (F[-1] - F[0])
    i0 = c_f[0] - sens * F[0]
    return i0, sens


def init_carry(p_blocks, st: SimStatic) -> Carry:
    """The scan-initial state for a ``p_blocks``-block program.

    Exposed so the sweep layer can build the (batched) initial carry
    *outside* the grid executables and donate its buffers to the dispatch
    (``jax.jit(..., donate_argnums)``): the runtime may then release the
    carry allocation as soon as the scan consumes it instead of pinning a
    protected input copy for the whole dispatch (it cannot alias into the
    trace outputs, whose shapes differ). Values are bitwise-identical to
    the in-trace construction (same ops, same dtypes)."""
    n_tables = max(st.n_cu // st.cus_per_table, 1)
    plen = jnp.asarray(p_blocks * INSTR_PER_BLOCK, jnp.float32)
    cu_off = (jnp.arange(st.n_cu, dtype=jnp.float32)[:, None] * 97.0) % plen
    wf_off = jnp.arange(st.n_wf, dtype=jnp.float32)[None, :] * 1.0
    pos0 = (cu_off + wf_off) % plen
    return Carry(
        pos=pos0,
        react_i0=jnp.full((st.n_cu,), 50.0),
        react_sens=jnp.full((st.n_cu,), 30.0),
        wf_i0=jnp.full((st.n_cu, st.n_wf), 1.2),
        wf_sens=jnp.full((st.n_cu, st.n_wf), 0.8),
        table=PRED.table_init(n_tables, st.entries),
        # F_STATIC of the DEFAULT ladder on purpose: the carry must not
        # depend on the traced power axes (it is built once per SimStatic
        # and donated); off-default regimes just see one initial
        # transition per CU, like real hardware coming out of a fixed
        # boot frequency
        f_prev=jnp.full((st.n_cu,), 1.7),
        # warm-start Pbar near the static-1.7 operating point
        e_acc=jnp.full((st.n_cu,), 0.42 * 20.0),
        t_acc=jnp.asarray(20.0),
    )


def _scan_sim(prog: Program, p_blocks, seed, st: SimStatic, ax: SimAxes,
              mech: Union[str, MechanismSpec, jnp.ndarray],
              carry0: Optional[Carry] = None) -> Dict[str, jnp.ndarray]:
    """The simulation scan. ``mech`` is either a concrete mechanism — a
    name or :class:`MechanismSpec`, resolved through the registry to a
    maximally specialized trace (fused 11-way execute for non-oracle fork
    mechanisms; ``predict``/``update`` hooks traced in for registered
    custom mechanisms) — or a traced int32 id into ``FORK_MECHS`` (one
    executable shared by all builtin fork mechanisms — the batched-sweep
    hot path; branch selection compares against the registry's frozen
    traced ids).

    ``p_blocks`` (logical block count; array may be padded beyond it),
    ``seed`` (int32 noise key) and the ``SimAxes`` grid point are all traced
    so the sweep layer can vmap over them. The scan runs to the static
    ``st.n_epochs``; epochs at index >= ``ax.n_ep`` are masked to zero in
    every output channel (the logical-epoch tail of a shorter grid point).
    ``carry0`` overrides the initial state (the sweep layer passes a
    donated ``init_carry``); ``None`` builds it in-trace.
    """
    static_mech = isinstance(mech, (str, MechanismSpec))
    NF = st.power.n_freqs
    F = PWR.freqs_ghz(ax.power, NF)   # traced ladder (endpoints are axes)
    T = ax.epoch_us
    n_dom = st.n_cu // st.cus_per_domain
    n_tables = max(st.n_cu // st.cus_per_table, 1)
    lat_us = PWR.transition_latency_us(ax.epoch_us, ax.power)
    # hoisted scan-body constants
    tid = jnp.arange(st.n_cu) // st.cus_per_table
    F_rows = jnp.broadcast_to(F[:, None], (NF, st.n_cu))  # (NF,CU)

    if static_mech:
        spec = MECH.resolve(mech)
        is_static_f = spec.family == "static"
        assert spec.static_fidx is None or spec.static_fidx < NF, \
            f"{spec.name}: static_fidx {spec.static_fidx} is off the " \
            f"{NF}-state ladder of this power regime"
        is_custom = spec.predict is not None
        is_pc = spec.family == "pc" and not is_custom
        is_react = spec.family == "reactive" and not is_custom
        is_oracle = spec.family == "oracle"
    else:
        spec = None
        is_static_f = is_custom = False
        is_pc = is_react = is_oracle = None  # resolved per-trace via mech id
    # Pallas generation select: "v2" is the fused fork--execute epoch
    # kernel (kernels.epoch_fused) and covers the builtin traced fork
    # family — every mechanism whose epoch is the standard predict ->
    # select -> 11-way execute -> estimate shape — both as a specialized
    # trace AND as the traced-mechanism-id executable the sweep layer
    # vmaps (family='fork' kernel mode), so one compiled fused kernel
    # serves every grid point. Non-capable specs (oracle/custom/static —
    # see MechanismSpec.v2_capable) and record_wf (per-WF fork channels
    # the fused kernel does not materialize) fall back to the unfused
    # body. "v1" (and v2-ineligible fallback) is the PC-table
    # predict/update kernel pair; True auto-selects v2 -> v1 -> jnp.
    mode = st.use_pallas
    assert mode in (False, True, "v1", "v2"), \
        f"use_pallas must be False|True|'v1'|'v2', got {mode!r}"
    use_pallas_v2 = (mode in (True, "v2") and not st.record_wf
                     and ((static_mech and spec.is_traced
                           and spec.v2_capable)
                          or (not static_mech and _FORK_V2_CAPABLE)))
    use_pallas = (not use_pallas_v2 and mode in (True, "v1", "v2")
                  and static_mech and not is_static_f
                  and not is_custom and st.n_cu % st.cus_per_table == 0)
    if use_pallas:
        from repro.kernels import pc_table as KPT
    if use_pallas_v2:
        from repro.kernels import epoch_fused as KEF

    def _pc_lookup(carry, idx_lu):
        """Table lookup + CU reduce + I(f) + capacity clip; jnp or Pallas."""
        if use_pallas:
            I_pc = KPT.pc_table_predict(
                carry.table.i0, carry.table.sens, carry.table.count,
                tid, idx_lu, carry.wf_i0, carry.wf_sens, F,
                epoch_us=T, cap_per_ghz=ax.cap_per_ghz)
            hit = (carry.table.count[tid[:, None], idx_lu] > 0) \
                .astype(jnp.float32)
        else:
            i0t, s_t, hit = PRED.table_lookup(carry.table, tid, idx_lu,
                                              carry.wf_i0, carry.wf_sens)
            I_pc = _predict_instr(i0t.sum(-1), s_t.sum(-1), st, ax)
        return I_pc, hit

    def _table_update(carry, idx_lu, i0_wf, s_wf):
        if use_pallas:
            G = st.cus_per_table
            shp = (n_tables, G * st.n_wf)
            i0n, sn, cn = KPT.pc_table_update(
                carry.table.i0, carry.table.sens, carry.table.count,
                idx_lu.reshape(shp), i0_wf.reshape(shp), s_wf.reshape(shp),
                ema=ax.table_ema)
            return PRED.PCTable(i0n, sn, cn)
        return PRED.table_update(carry.table, tid, idx_lu, i0_wf, s_wf,
                                 ax.table_ema)

    def body(carry: Carry, ep_i):
        # named scopes mark the epoch's phases in the executable's op_name
        # metadata (a profiler's op view groups device time by them)
        pos = carry.pos
        with jax.named_scope("engine.context"):
            ctx = _epoch_context(prog, pos, p_blocks, seed)

        hit_rate = None
        c_f = I_f = I_pred_f = idx_lu = None
        oracle_now = static_mech and is_oracle
        with jax.named_scope("engine.predict"):
            if is_static_f:
                fidx = jnp.full((st.n_cu,), spec.static_fidx, jnp.int32)
            else:
                # --- predict I(f) from carry state (no forks needed) ------
                idx_lu = PRED.table_index(ctx.blk, st.entries,
                                          st.offset_blocks)
                # custom pc-family specs keep the standard table machinery
                # (lookup telemetry here, counter-driven update below):
                # their predict hook reads carry.table and customizes only
                # prediction
                if (not static_mech) or is_pc or (is_custom
                                                  and spec.family == "pc"):
                    I_pc, hit = _pc_lookup(carry, idx_lu)
                    hit_rate = hit.mean()
                if (not static_mech) or is_react:
                    I_react = _predict_instr(carry.react_i0,
                                             carry.react_sens, st, ax)
                if static_mech and is_custom:
                    # registered mechanism: the spec's predictor hook
                    # supplies I(f) from the same carry/context view the
                    # builtins see
                    I_hook = spec.predict(carry, ctx, st, ax)
                pbar = (carry.e_acc / jnp.maximum(carry.t_acc, 1e-3)) \
                    .reshape(n_dom, st.cus_per_domain).sum(1)

                if oracle_now:
                    # oracle's prediction IS this epoch's forks -> forks
                    # first, then the mixed-frequency row (still sharing
                    # the context).
                    c_f = _steady_parts(ctx, pos, F_rows, p_blocks,
                                        ax).steady
                    I_f = c_f.sum(-1).T
                    I_pred_f = I_f
                elif static_mech:
                    I_pred_f = I_hook if is_custom else \
                        (I_pc if is_pc else I_react)
                else:
                    I_pred_f = jnp.where(mech < _N_REACT, I_react, I_pc)
                fidx = _select_freq(I_pred_f, st, ax, pbar)

        with jax.named_scope("engine.execute"):
            if is_static_f or oracle_now:
                f_sel = F[fidx]
                committed, ctr = _execute_ctx(ctx, pos, f_sel, p_blocks, ax)
            else:
                # fused fork--pre-execute: for every non-oracle mechanism
                # the selection depends only on carry, so the 10 uniform
                # fork rows and the chosen mixed row run as one 11-way
                # batched execute over the shared context;
                # barrier/contention counters materialize only for row 10.
                # (The traced family therefore excludes oracle — run_suite
                # routes it to its own specialized executable.)
                f_all = jnp.concatenate([F_rows, F[fidx][None]], axis=0)
                parts = _steady_parts(ctx, pos, f_all, p_blocks, ax)
                c_f = parts.steady[:NF]                     # (NF,CU,WF)
                sel_parts = _SteadyParts(*(x[NF] for x in parts))
                committed, ctr = _row_counters(sel_parts, pos, f_all[NF],
                                               p_blocks)
                f_sel = f_all[NF]
                I_f = c_f.sum(-1).T                         # (CU,NF)

            # --- transition overhead + counter views ----------------------
            trans = (f_sel != carry.f_prev)
            committed = committed * (1.0 - lat_us / T * trans[:, None])
            I_actual = ctr["steady"].sum(-1)             # (CU,) counter view
            work_actual = committed.sum(-1)              # (CU,) real progress
            # --- accuracy of the prediction for THIS epoch ----------------
            if I_pred_f is not None:
                I_at_sel = jnp.take_along_axis(I_pred_f, fidx[:, None],
                                               1)[:, 0]
                err = jnp.abs(I_at_sel - I_actual) \
                    / jnp.maximum(I_actual, 1e-3)
            else:
                err = jnp.zeros((st.n_cu,))
            # --- energy ----------------------------------------------------
            act = work_actual / (ax.cap_per_ghz * f_sel * T * st.n_wf)
            energy = PWR.power(f_sel, act, ax.power) * T \
                + PWR.transition_energy(carry.f_prev, f_sel, ax.power) \
                * trans

        with jax.named_scope("engine.update"):
            # --- estimation + state update ---------------------------------
            new = carry._replace(pos=pos + committed, f_prev=f_sel,
                                 e_acc=carry.e_acc + energy,
                                 t_acc=carry.t_acc + T)
            est_ctrs = dict(ctr, committed=ctr["steady"])
            if static_mech:
                if is_custom:
                    if spec.family == "pc":
                        # standard counter-driven table maintenance
                        # (pcstall's estimator path) so a registered
                        # pc-family predictor sees a live table without
                        # reimplementing it
                        i0_wf, s_wf = EST.wf_stall_estimate(est_ctrs, f_sel)
                        i0_wf, s_wf = i0_wf / T, s_wf / T
                        tbl = _table_update(carry, idx_lu, i0_wf, s_wf)
                        new = new._replace(table=tbl, wf_i0=i0_wf,
                                           wf_sens=s_wf)
                    if spec.update is not None:
                        upd = spec.update(est_ctrs, f_sel, I_f, carry, ctx,
                                          st, ax)
                        if upd is not None:
                            new = new._replace(react_i0=upd[0],
                                               react_sens=upd[1])
                elif is_react and not spec.fork_estimator:
                    i0_cu, s_cu = EST.cu_estimate(est_ctrs, f_sel,
                                                  spec.cu_model)
                    new = new._replace(react_i0=i0_cu / T,
                                       react_sens=s_cu / T)
                elif is_react:  # fork-accurate reactive: exact from forks
                    sens_cu = (I_f[:, -1] - I_f[:, 0]) \
                        / ((F[-1] - F[0]) * T)
                    i0_cu = I_f[:, 0] / T - sens_cu * F[0]
                    new = new._replace(react_i0=i0_cu, react_sens=sens_cu)
                elif is_pc:
                    if not spec.fork_estimator:  # counter-driven (pcstall)
                        i0_wf, s_wf = EST.wf_stall_estimate(est_ctrs, f_sel)
                    else:  # exact per-WF linear model from the forks (accpc)
                        i0_wf, s_wf = _true_wf_linear(c_f, F)
                    i0_wf, s_wf = i0_wf / T, s_wf / T
                    tbl = _table_update(carry, idx_lu, i0_wf, s_wf)
                    new = new._replace(table=tbl, wf_i0=i0_wf, wf_sens=s_wf)
            else:
                # traced mechanism id: evaluate every estimator (cheap next
                # to the batched executes) and select, so one executable
                # serves the whole fork-mechanism family under vmap. Case
                # order follows the registry's traced ids (asserted at
                # import: counter models 0..n-2, the fork-accurate reactive
                # last).
                cu_ests = [EST.cu_estimate(est_ctrs, f_sel, s.cu_model)
                           for s in _REACT_SPECS if not s.fork_estimator]
                sens_ar = (I_f[:, -1] - I_f[:, 0]) / ((F[-1] - F[0]) * T)
                i0_ar = I_f[:, 0] / T - sens_ar * F[0]
                sel = [mech == k for k in range(_N_REACT)]
                r_i0 = jnp.select(sel, [e[0] / T for e in cu_ests] + [i0_ar],
                                  carry.react_i0)
                r_se = jnp.select(sel,
                                  [e[1] / T for e in cu_ests] + [sens_ar],
                                  carry.react_sens)
                new = new._replace(react_i0=r_i0, react_sens=r_se)
                i0_est, s_est = EST.wf_stall_estimate(est_ctrs, f_sel)
                i0_tr, s_tr = _true_wf_linear(c_f, F)
                i0_wf = jnp.where(mech == _ID_CTR_PC, i0_est, i0_tr) / T
                s_wf = jnp.where(mech == _ID_CTR_PC, s_est, s_tr) / T
                tbl_u = _table_update(carry, idx_lu, i0_wf, s_wf)
                pc_now = functools.reduce(
                    lambda a, b: a | b, [mech == i for i in _PC_IDS])
                tbl = jax.tree.map(lambda a, b: jnp.where(pc_now, a, b),
                                   tbl_u, carry.table)
                new = new._replace(
                    table=tbl,
                    wf_i0=jnp.where(pc_now, i0_wf, carry.wf_i0),
                    wf_sens=jnp.where(pc_now, s_wf, carry.wf_sens))
            # true CU sensitivity for phase-variability analyses
            if is_static_f:
                true_sens_cu = jnp.zeros((st.n_cu,))
            else:
                true_sens_cu = (I_f[:, -1] - I_f[:, 0]) \
                    / ((F[-1] - F[0]) * T)
            ys = {"work": work_actual, "energy": energy, "err": err,
                  "fidx": fidx.astype(jnp.int8), "true_sens": true_sens_cu}
            # emit the channel only when the spec declares it (custom pc
            # specs may decline), so run_sim and the sweep layer agree on
            # the trace schema; the traced family (spec is None) emits for
            # all and the sweep layer filters per spec on unpack
            if hit_rate is not None and (spec is None or spec.hit_telemetry):
                ys["hit_rate"] = hit_rate
            if st.record_wf and not is_static_f:
                ys["wf_sens"] = ((c_f[-1] - c_f[0]) / (F[-1] - F[0])) \
                    .astype(jnp.float32)
                ys["wf_blk"] = ctx.blk.astype(jnp.int32)
            # logical-epoch mask: grid points shorter than the static scan
            # length report zeros past their tail (state keeps advancing,
            # but the scan is causal so live epochs are unaffected)
            live = ep_i < ax.n_ep
            ys = jax.tree.map(
                lambda v: jnp.where(live, v, jnp.zeros_like(v)), ys)
        return new, ys

    def body_v2(carry: Carry, ep_i):
        # the epoch's math — predict, select, execute, counters,
        # estimate — is ONE fused kernel, with its gathers and the table
        # update in XLA around it (see kernels.epoch_fused). The sin-hash
        # noise is computed here: the kernel's module docstring explains
        # why eps must not be recomputed in a different fusion context
        # (the unused context gathers are DCE'd).
        with jax.named_scope("engine.context"):
            eps = _epoch_context(prog, carry.pos, p_blocks, seed).eps
        with jax.named_scope("engine.kernel"):
            out = KEF.epoch_fused(
                prog.i0_rate, prog.sens_rate, cum_t, carry.pos, F, eps,
                carry.f_prev, carry.e_acc, carry.t_acc,
                p_blocks=p_blocks, epoch_us=T, sigma=ax.sigma,
                cap_per_ghz=ax.cap_per_ghz, membw=ax.membw, obj=ax.obj,
                lat_us=lat_us, power=ax.power,
                cus_per_domain=st.cus_per_domain,
                table=carry.table, tid=tid, wf_i0=carry.wf_i0,
                wf_sens=carry.wf_sens, table_ema=ax.table_ema,
                offset_blocks=st.offset_blocks,
                react_i0=carry.react_i0, react_sens=carry.react_sens,
                **v2_kw)
        new = carry._replace(pos=out.pos, f_prev=out.f_sel,
                             e_acc=out.e_acc, t_acc=out.t_acc[0])
        if spec is None:
            # traced-id mode advances every state group; the kernel's
            # id-gated selects already kept the dead ones at carry values
            new = new._replace(table=out.table, wf_i0=out.wf_i0,
                               wf_sens=out.wf_sens, react_i0=out.react_i0,
                               react_sens=out.react_sens)
        elif spec.family == "pc":
            new = new._replace(table=out.table, wf_i0=out.wf_i0,
                               wf_sens=out.wf_sens)
        else:
            new = new._replace(react_i0=out.react_i0,
                               react_sens=out.react_sens)
        ys = {"work": out.work, "energy": out.energy, "err": out.err,
              "fidx": out.fidx.astype(jnp.int8),
              "true_sens": out.true_sens}
        # traced-id mode emits for all (like the jnp traced family; the
        # sweep layer filters per spec on unpack)
        if spec is None or (spec.family == "pc" and spec.hit_telemetry):
            ys["hit_rate"] = out.hit_rate[0]
        live = ep_i < ax.n_ep
        return new, jax.tree.map(
            lambda v: jnp.where(live, v, jnp.zeros_like(v)), ys)

    if use_pallas_v2:
        # three contiguous gather rows per window side (see epoch_fused);
        # scan-invariant, hoisted out of the body
        cum_t = jnp.transpose(prog.cum3)
        if static_mech:
            v2_kw = dict(family=spec.family,
                         fork_estimator=spec.fork_estimator,
                         cu_model=spec.cu_model)
        else:
            # the traced-mechanism-id kernel mode: mech rides in as a
            # traced operand, and the registry-derived id layout becomes
            # kernel statics (counter estimators in id order, table ids)
            v2_kw = dict(family="fork", mech=mech,
                         react_models=tuple(
                             s.cu_model for s in _REACT_SPECS
                             if not s.fork_estimator),
                         pc_ids=_PC_IDS, id_ctr_pc=_ID_CTR_PC,
                         block_cu=st.pallas_block_cu)
    if carry0 is None:
        carry0 = init_carry(p_blocks, st)
    _, ys = lax.scan(body_v2 if use_pallas_v2 else body, carry0,
                     jnp.arange(st.n_epochs, dtype=jnp.int32))
    return ys


@functools.partial(jax.jit, static_argnames=("st", "mechanism"))
def _run_sim_jit(prog: Program, p_blocks, seed, ax: SimAxes, st: SimStatic,
                 mechanism: MechanismSpec) -> Dict[str, jnp.ndarray]:
    return _scan_sim(prog, p_blocks, seed, st, ax, mechanism)


def seed_i32(seeds) -> np.ndarray:
    """Fold integer seeds of any width into int32 by keeping the low 32
    bits (two's complement; masked in Python so arbitrary-width ints never
    overflow). The noise hash keys on int32; a deterministic wrap for
    hash/time-derived 64-bit seeds beats both an OverflowError and the old
    silent float32 aliasing."""
    scalar = np.ndim(seeds) == 0
    vals = [seeds] if scalar else list(seeds)
    folded = np.asarray([int(s) & 0xFFFFFFFF for s in vals],
                        np.uint32).astype(np.int32)
    return folded[0] if scalar else folded


def run_sim(prog: Program, sim: SimConfig,
            mechanism: Union[str, MechanismSpec]) -> Dict[str, np.ndarray]:
    """Simulate ``mechanism`` (a registered name or a ``MechanismSpec``)
    on ``prog``. Returns per-epoch traces.

    Compile-once: the scan is traced at most once per (SimStatic, mechanism
    spec, program shape) — subsequent calls, *including ones that change
    only traced axes like epoch_us/sigma/objective*, dispatch a cached XLA
    executable.
    """
    spec = MECH.resolve(mechanism)
    assert sim.n_cu % sim.cus_per_domain == 0
    ys = _run_sim_jit(prog, jnp.int32(prog.n_blocks),
                      jnp.asarray(seed_i32(sim.seed)), sim.axes(),
                      sim.static_part(), spec)
    return {k: np.asarray(v) for k, v in ys.items()}


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def prediction_accuracy(trace: Dict[str, np.ndarray], warmup: int = 50) -> float:
    err = trace["err"][warmup:]
    return float(np.clip(1.0 - np.mean(np.clip(err, 0, 1)), 0.0, 1.0))


def ednp(trace: Dict[str, np.ndarray], work_budget: float, epoch_us: float,
         n: int = 2) -> Tuple[float, float, float]:
    """(E, D, E*D^n) to complete ``work_budget`` total instructions."""
    cum_work = np.cumsum(trace["work"].sum(-1))
    cum_energy = np.cumsum(trace["energy"].sum(-1))
    if cum_work[-1] < work_budget:  # extrapolate at terminal rate
        rate = trace["work"].sum(-1)[-200:].mean() / epoch_us
        p_rate = trace["energy"].sum(-1)[-200:].mean() / epoch_us
        extra_t = (work_budget - cum_work[-1]) / rate
        D = len(cum_work) * epoch_us + extra_t
        E = cum_energy[-1] + p_rate * extra_t
    else:
        i = int(np.searchsorted(cum_work, work_budget))
        frac = ((work_budget - (cum_work[i - 1] if i else 0.0))
                / max(cum_work[i] - (cum_work[i - 1] if i else 0.0), 1e-9))
        D = (i + frac) * epoch_us
        E = (cum_energy[i - 1] if i else 0.0) + frac * (
            cum_energy[i] - (cum_energy[i - 1] if i else 0.0))
    return E, D, E * D ** n


def run_workload(prog: Program, sim: SimConfig, mechanisms=MECHANISMS,
                 n: int = 2, baseline: Union[str, MechanismSpec] = "static17"
                 ) -> Dict[str, Dict[str, float]]:
    """Run a mechanism suite; ED^nP normalized to ``baseline`` (any
    registered mechanism; default the paper's static 1.7 GHz)."""
    base_spec = MECH.resolve(baseline)
    base = run_sim(prog, sim, base_spec)
    budget = 0.9 * base["work"].sum()
    out: Dict[str, Dict[str, float]] = {}
    E0, D0, M0 = ednp(base, budget, sim.epoch_us, n)
    for mech in mechanisms:
        spec = MECH.resolve(mech)
        tr = base if spec.name == base_spec.name else run_sim(prog, sim, spec)
        E, D, M = ednp(tr, budget, sim.epoch_us, n)
        out[spec.name] = {
            "accuracy": prediction_accuracy(tr)
            if spec.family != "static" else float("nan"),
            "E": E, "D": D, "ednp": M, "ednp_norm": M / M0,
            "energy_norm": E / E0, "delay_norm": D / D0,
        }
    return out
