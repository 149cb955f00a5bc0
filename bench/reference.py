"""Plain reference of the simulated GPU: one (program, mechanism) run at a
time, written from the paper's model (arXiv:2205.00121, §5) and imported by
nothing of the program under test.

Each epoch of a run: the epoch is executed at every V/f state from the same
start (the forks), the mechanism predicts instructions per state from its
own state, the controller picks the state of least cost per V/f domain
(``(P + n * Pbar) / I`` for ED^nP), the epoch runs at the picked states,
and the mechanism's estimator updates its state from the counters.

``simulate`` runs many rows side by side (``vmap``), each with its own
program, mechanism and operating point. Given ``forced`` choices it replays
a run of the program under test: the states it executes are the forced
ones, and each epoch's ``cost`` of every state under the reference's own
prediction says what each forced choice costs against the best one
(``check.gaps``); ``own`` is the reference's own choice at that epoch.
Without ``forced`` it runs on its own choices. ``dtype=jnp.bfloat16``
gives the lower-precision control that the correctness limits are set
against.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

INSTR_PER_BLOCK = 4

# The reference's reading of each builtin mechanism (paper Table III):
# (family, variant, static V/f index). Families: 0 static, 1 reactive
# (variant: CU-level counter model 0 stall, 1 lead, 2 crit, 3 crisp, or 4
# the exact linear model from the forks), 2 PC table (variant 0 per-WF
# stall estimate from counters, 1 exact per-WF linear model from the
# forks), 3 oracle (predicts from this epoch's forks).
MECHANISMS: Dict[str, Tuple[int, int, int]] = {
    "static13": (0, 0, 0), "static17": (0, 0, 4), "static22": (0, 0, 9),
    "stall": (1, 0, 0), "lead": (1, 1, 0), "crit": (1, 2, 0),
    "crisp": (1, 3, 0), "accreac": (1, 4, 0),
    "pcstall": (2, 0, 0), "accpc": (2, 1, 0), "oracle": (3, 0, 0),
}

OBJECTIVES = {"edp": (1.0, 1.0, 0.0), "ed2p": (2.0, 1.0, 0.0)}


def _ladder(pw: dict, nf: int, dt) -> jnp.ndarray:
    lo, hi = jnp.asarray(pw["f_min"], dt), jnp.asarray(pw["f_max"], dt)
    t = jnp.arange(nf - 1, dtype=dt) / jnp.asarray(nf - 1, dt)
    return jnp.concatenate([lo * (1.0 - t) + hi * t, hi[None]])


def _volt(f, pw):
    return pw["v_min"] + (f - pw["f_min"]) / (pw["f_max"] - pw["f_min"]) \
        * (pw["v_max"] - pw["v_min"])


def _power(f, act, pw):
    """Domain power: (C V^2 f A + k_leak V) / IVR efficiency(V)."""
    v = _volt(f, pw)
    p_dyn = pw["c_eff"] * v * v * f * jnp.clip(act, 0.05, 1.0)
    eta = pw["eta0"] + pw["eta_slope"] * (v - pw["v_min"]) \
        / (pw["v_max"] - pw["v_min"])
    return (p_dyn + pw["k_leak"] * v) / eta


def _run(arrs, seed, mech, axes, forced, *, cfg, n_epochs, dt):
    """One row: program arrays ``arrs`` (i0, sens, mem), int32 ``seed``,
    ``mech`` = (family, variant, static index), ``axes`` = (epoch_us,
    pbar weight), ``forced`` (n_epochs, CU) int32 or None."""
    n_cu, n_wf = cfg["n_cu"], cfg["n_wf"]
    cpd, cpt = cfg["cus_per_domain"], cfg["cus_per_table"]
    entries, offb = cfg["entries"], cfg["offset_blocks"]
    pw = {k: jnp.asarray(v, dt) for k, v in cfg["power"].items()
          if k != "n_freqs"}
    nf = cfg["power"]["n_freqs"]
    sigma = jnp.asarray(cfg["sigma"], dt)
    cap = jnp.asarray(cfg["cap_per_ghz"], dt)
    membw = jnp.asarray(cfg["membw"], dt)
    ema = jnp.asarray(cfg["table_ema"], dt)
    fam, var, sfidx = mech
    T, w_pbar = axes[0].astype(dt), axes[1].astype(dt)
    i0r, sr, mr = (arrs[k].astype(dt) for k in ("i0", "sens", "mem"))
    P = i0r.shape[0]
    cum = jnp.stack([jnp.concatenate([jnp.zeros(1, dt),
                                      jnp.cumsum(jnp.tile(a, 2))])
                     for a in (i0r, sr, mr)], -1)            # (2P+1, 3)
    F = _ladder(pw, nf, dt)
    n_dom = n_cu // cpd
    tid = jnp.arange(n_cu) // cpt
    lat = jnp.minimum(pw["lat_per_us"] * T, pw["lat_cap_us"])
    plen = jnp.asarray(P * INSTR_PER_BLOCK, dt)
    s = seed.astype(jnp.int32)
    seed_phase = (s % 65536).astype(dt) * 3.7 \
        + (s // 65536).astype(dt) * 2.2867257
    wf_id = jnp.arange(n_wf, dtype=dt)[None, :]
    cu_id = jnp.arange(n_cu, dtype=dt)[:, None]

    def execute(pos, blk, eps, f):
        """Instructions each WF commits this epoch with CU frequencies
        ``f`` (..., CU): its rate averaged over the blocks it crosses,
        the epoch's noise, oldest-first issue up to the CU's capacity and
        a memory bandwidth shared by all CUs."""
        fb = f[..., :, None]
        est = (i0r[blk] + sr[blk] * fb) * T
        nblk = jnp.clip((est / INSTR_PER_BLOCK).astype(jnp.int32) + 1, 1, P)
        avg = (cum[blk + nblk] - cum[blk]) / nblk[..., None].astype(dt)
        i0w, sw, mfw = avg[..., 0], avg[..., 1], avg[..., 2]
        demand = (i0w + sw * fb) * T * (1.0 + sigma * eps)
        before = jnp.cumsum(demand, axis=-1) - demand
        alloc = jnp.clip((cap * f * T)[..., :, None] - before, 0.0, demand)
        traffic = (alloc * mfw).sum(axis=(-2, -1))
        scale = jnp.minimum(1.0, membw * T / jnp.maximum(traffic, 1e-6))
        steady = alloc * (1.0 - mfw * (1.0 - scale[..., None, None]))
        return steady, alloc, demand, i0w, sw

    def predict(i0_cu, s_cu):
        I = (i0_cu[:, None] + s_cu[:, None] * F[None, :]) * T
        return jnp.clip(I, 0.0, cap * F[None, :] * T * n_wf)

    def cost_of(I, pbar):
        act = I / (cap * F[None, :] * T * n_wf)
        P_dom = _power(F[None, :], act, pw).reshape(n_dom, cpd, nf).sum(1)
        I_sum = jnp.maximum(I.reshape(n_dom, cpd, nf).sum(1), 1e-3)
        return (P_dom + w_pbar * pbar[:, None]) / I_sum

    def step(state, forced_t):
        pos, r_i0, r_s, w_i0, w_s, t_i0, t_s, t_n, f_prev, e_acc, t_acc = state
        blk = (pos.astype(jnp.int32) // INSTR_PER_BLOCK) % P
        loop = (pos // (INSTR_PER_BLOCK * P)).astype(dt)
        h = jnp.sin(blk * 12.9898 + loop * 78.233 + wf_id * 37.719
                    + cu_id * 9.131 + seed_phase) * 43758.5453
        eps = (h - jnp.floor(h)) * 2.0 - 1.0
        forks, *_ = execute(pos, blk, eps,
                            jnp.broadcast_to(F[:, None], (nf, n_cu)))
        I_f = forks.sum(-1).T                                # (CU, NF)
        # predictions of each family from the mechanism's own state
        idx = (blk // offb) % entries
        hit = t_n[tid[:, None], idx] > 0
        I_pc = predict(jnp.where(hit, t_i0[tid[:, None], idx], w_i0).sum(-1),
                       jnp.where(hit, t_s[tid[:, None], idx], w_s).sum(-1))
        I_pred = jnp.select([fam == 1, fam == 2], [predict(r_i0, r_s), I_pc],
                            I_f)
        pbar = (e_acc / jnp.maximum(t_acc, 1e-3)).reshape(n_dom, cpd).sum(1)
        cost = cost_of(I_pred, pbar)                         # (dom, NF)
        best = jnp.where(fam == 0, sfidx, jnp.argmin(cost, -1))
        own = jnp.repeat(best, cpd)
        fidx = own if forced_t is None else forced_t
        # the epoch at the chosen states
        f = F[fidx]
        steady, alloc, demand, i0w, sw = execute(pos, blk, eps, f)
        gmin = (pos + steady).min(-1)
        boundary = (jnp.floor(gmin / plen) + 1.0) * plen
        committed = jnp.minimum(steady, jnp.maximum(boundary[:, None] - pos,
                                                    0.0))
        trans = f != f_prev
        committed = committed * (1.0 - lat / T * trans[:, None])
        work = committed.sum(-1)
        act = work / (cap * f * T * n_wf)
        dv = _volt(f, pw) - _volt(f_prev, pw)
        energy = _power(f, act, pw) * T + pw["c_trans"] * dv * dv * trans
        # estimators read the counters of the steady execution
        fb = f[:, None]
        q = alloc / jnp.maximum(demand, 1e-6)
        core = sw * fb / jnp.maximum(i0w + sw * fb, 1e-6)
        I_cu = steady.sum(-1)
        qc = jnp.maximum(q, 0.05)
        wsum = (steady * core).sum(-1) / jnp.maximum(steady.sum(-1), 1e-6)
        s_models = jnp.stack([
            I_cu * core.mean(-1) / f,
            I_cu * wsum / f,
            I_cu * wsum / (f * jnp.maximum(qc.mean(-1), 0.05)),
            ((steady / qc) * core).sum(-1) / f])             # (4, CU)
        i0_models = jnp.maximum(I_cu[None] - s_models * f[None], 0.0)
        s_fork = (I_f[:, -1] - I_f[:, 0]) / ((F[-1] - F[0]) * T)
        i0_fork = I_f[:, 0] / T - s_fork * F[0]
        v = jnp.minimum(var, 3)
        r_new_s = jnp.where(var == 4, s_fork, s_models[v] / T)
        r_new_i0 = jnp.where(var == 4, i0_fork, i0_models[v] / T)
        react = fam == 1
        r_i0 = jnp.where(react, r_new_i0, r_i0)
        r_s = jnp.where(react, r_new_s, r_s)
        # PC table: per-WF estimates scattered by starting PC
        q_cu = jnp.maximum(q.mean(-1, keepdims=True), 0.05)
        cfq = jnp.round(core * 16.0) / 16.0
        dem = steady / q_cu
        s_ctr = dem * cfq / fb
        i0_ctr = jnp.maximum(dem - s_ctr * fb, 0.0)
        s_wf_f = (forks[-1] - forks[0]) / (F[-1] - F[0])
        i0_wf_f = forks[0] - s_wf_f * F[0]
        n_i0 = jnp.where(var == 1, i0_wf_f, i0_ctr) / T
        n_s = jnp.where(var == 1, s_wf_f, s_ctr) / T
        n_tab = t_i0.shape[0]
        flat = (tid[:, None] * entries + idx).reshape(-1)
        acc = jnp.zeros((n_tab * entries, 3), dt).at[flat].add(
            jnp.stack([n_i0.reshape(-1), n_s.reshape(-1),
                       jnp.ones(flat.shape, dt)], -1)).reshape(n_tab, entries, 3)
        cnt = acc[..., 2]
        mean_i0 = jnp.where(cnt > 0, acc[..., 0] / jnp.maximum(cnt, 1), 0.0)
        mean_s = jnp.where(cnt > 0, acc[..., 1] / jnp.maximum(cnt, 1), 0.0)
        blend = jnp.where((t_n == 0) & (cnt > 0), 1.0,
                          jnp.where(cnt > 0, ema, 0.0))
        pc = fam == 2
        t_i0 = jnp.where(pc, t_i0 * (1 - blend) + mean_i0 * blend, t_i0)
        t_s = jnp.where(pc, t_s * (1 - blend) + mean_s * blend, t_s)
        t_n = jnp.where(pc, t_n + cnt, t_n)
        w_i0 = jnp.where(pc, n_i0, w_i0)
        w_s = jnp.where(pc, n_s, w_s)
        state = (pos + committed, r_i0, r_s, w_i0, w_s, t_i0, t_s, t_n, f,
                 e_acc + energy, t_acc + T)
        return state, {"work": work, "energy": energy, "fidx": fidx,
                       "cost": cost, "own": best}

    n_tab = max(n_cu // cpt, 1)
    pos0 = ((jnp.arange(n_cu, dtype=dt)[:, None] * 97.0) % plen
            + jnp.arange(n_wf, dtype=dt)[None, :]) % plen
    tab = jnp.zeros((n_tab, entries), dt)
    state0 = (pos0, jnp.full((n_cu,), 50.0, dt), jnp.full((n_cu,), 30.0, dt),
              jnp.full((n_cu, n_wf), 1.2, dt), jnp.full((n_cu, n_wf), 0.8, dt),
              tab, tab, tab, jnp.full((n_cu,), 1.7, dt),
              jnp.full((n_cu,), 0.42 * 20.0, dt), jnp.asarray(20.0, dt))
    if forced is None:
        _, ys = lax.scan(lambda c, _: step(c, None), state0, None,
                         length=n_epochs)
    else:
        _, ys = lax.scan(step, state0, forced)
    return ys


def simulate(programs: Dict[str, Dict[str, np.ndarray]],
             rows: Sequence[Tuple[str, str, float, str]], cfg: dict,
             seed: int, forced: Optional[np.ndarray] = None,
             dtype=jnp.float32) -> Dict[str, np.ndarray]:
    """Run ``rows`` = [(workload, mechanism, epoch_us, objective)] side by
    side for ``cfg["n_epochs"]`` epochs with noise seed ``seed`` (any
    integer; its low 32 bits key the noise). ``forced`` (rows, epochs, CU)
    replays given choices. Returns per-row arrays ``work``, ``energy``,
    ``fidx`` (rows, epochs, CU), ``own`` (rows, epochs, domains) and
    ``cost`` (rows, epochs, domains, V/f states) on the host."""
    arrs = {k: jnp.asarray(np.stack([programs[w][k] for w, *_ in rows]))
            for k in ("i0", "sens", "mem")}
    mech = tuple(jnp.asarray([MECHANISMS[m][j] for _, m, *_ in rows],
                             jnp.int32) for j in range(3))
    axes = jnp.asarray([[e, OBJECTIVES[o][0]] for _, _, e, o in rows],
                       jnp.float32)
    s32 = (int(seed) & 0xFFFFFFFF) - ((int(seed) & 0x80000000) << 1)
    seeds = jnp.full((len(rows),), s32, jnp.int32)
    static = {k: v for k, v in cfg.items()
              if k in ("n_cu", "n_wf", "cus_per_domain", "cus_per_table",
                       "entries", "offset_blocks", "sigma", "cap_per_ghz",
                       "membw", "table_ema", "power")}
    fn = _compiled(_freeze(static), int(cfg["n_epochs"]),
                   jnp.dtype(dtype).name, forced is not None)
    f_in = None if forced is None else jnp.asarray(forced, jnp.int32)
    ys = fn(arrs, seeds, mech, axes, f_in)
    return {k: np.asarray(v) for k, v in ys.items()}


def _freeze(d):
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def _thaw(t):
    return {k: _thaw(v) if isinstance(v, tuple) and v
            and isinstance(v[0], tuple) else v for k, v in t}


@functools.lru_cache(maxsize=None)
def _compiled(static, n_epochs, dtype_name, has_forced):
    """The jitted, row-vmapped run for one frozen configuration."""
    cfg, dt = _thaw(static), jnp.dtype(dtype_name)

    def one(arrs, seed, mech, axes, forced):
        return _run(arrs, seed, mech, axes, forced, cfg=cfg,
                    n_epochs=n_epochs, dt=dt)
    in_f = 0 if has_forced else None
    return jax.jit(jax.vmap(one, in_axes=(0, 0, 0, 0, in_f)))


def ednp(work: np.ndarray, energy: np.ndarray, budget: float,
         epoch_us: float, n: int = 2) -> Tuple[float, float, float]:
    """(E, D, E * D^n) to complete ``budget`` instructions, from per-epoch
    (epochs, CU) work and energy; past the run's end at its closing rate."""
    w = work.sum(-1).astype(np.float64)
    e = energy.sum(-1).astype(np.float64)
    cw, ce = np.cumsum(w), np.cumsum(e)
    if cw[-1] < budget:
        rate, p = w[-200:].mean() / epoch_us, e[-200:].mean() / epoch_us
        extra = (budget - cw[-1]) / rate
        D = len(cw) * epoch_us + extra
        E = ce[-1] + p * extra
    else:
        i = int(np.searchsorted(cw, budget))
        prev_w = cw[i - 1] if i else 0.0
        prev_e = ce[i - 1] if i else 0.0
        frac = (budget - prev_w) / max(cw[i] - prev_w, 1e-9)
        D = (i + frac) * epoch_us
        E = prev_e + frac * (ce[i] - prev_e)
    return E, D, E * D ** n
