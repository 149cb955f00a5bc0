"""The numbers that decide ``correct``: what the program produced for a
sample of rows, replayed by the plain reference (``reference.py``) on the
program's own frequency choices.

The replay follows the program's run as long as its state is the
program's. A wavefront whose position or noise lies within an ulp of an
edge (a block boundary, the hash's wrap) can take the other side in one of
two sound float32 evaluations and then run its own course: from that epoch
the replay's work on that CU departs from the program's by 1e-3 or more in
an epoch, where rounding alone stays near 1e-7. Where the row is bound by
the memory bandwidth that all its CUs share, the others follow within tens
of epochs. A CU has *departed* from the first epoch whose work differs by
more than ``DEPART_TOL`` of its mean epoch.

* ``work_dev``, ``energy_dev``: the largest relative gap, over the sampled
  rows and their CUs, between the program's total committed instructions
  (energy) over the run and the replay's. A departed CU reads ~6e-3;
* ``choice_gap``: the largest relative excess cost, under the replay's
  prediction at that epoch, of a frequency the program chose over the
  best one, over the epochs before its domain departed (a static mechanism
  reads 1 where it left its state). After a departure the replay judges a
  state the program was never in;
* ``report_dev`` (served jobs): the largest relative gap between a job's
  reported ED^2P ratio and the one the replay gives.

How many (row, CU) pairs departed is printed, not compared: whole rows
bound by the memory bandwidth depart on many sound seeds (up to 4 of 11
sampled suite rows), within 3x of the control's reading, where every pair
departs.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference as REF

DEPART_TOL = 1e-4


def gaps(ref: dict, rows, choices: np.ndarray) -> np.ndarray:
    """(rows, epochs, domains): the relative excess cost of ``choices``
    (rows, epochs, CU) under the replay's ``cost``; for a static mechanism
    1 where the choice is not its state."""
    cost = ref["cost"].astype(np.float64)                # rows, ep, dom, NF
    n_dom = cost.shape[2]
    f_dom = np.asarray(choices).reshape(*cost.shape[:2], n_dom, -1)[..., 0]
    c_sel = np.take_along_axis(cost, f_dom[..., None].astype(np.int64),
                               -1)[..., 0]
    c_min = cost.min(-1)
    fam = np.array([REF.MECHANISMS[m][0] for _, m, *_ in rows])
    sfidx = np.array([REF.MECHANISMS[m][2] for _, m, *_ in rows])
    static = (f_dom != sfidx[:, None, None]).astype(np.float64)
    return np.where(fam[:, None, None] == 0, static,
                    (c_sel - c_min) / np.abs(c_min))


def departed(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(rows, epochs, CU) bool: the CU's per-epoch work ``got`` has
    differed from ``want`` by more than ``DEPART_TOL`` of the CU's mean
    epoch (an epoch that meets the program's loop end commits little) at
    this epoch or an earlier one."""
    scale = np.maximum(np.abs(want).mean(1, keepdims=True), 1e-30)
    return np.maximum.accumulate(np.abs(got - want) / scale > DEPART_TOL,
                                 axis=1)


def deviations(arrays, rows, cfg, seed, traces: Sequence[dict],
               judged: Optional[np.ndarray] = None
               ) -> Tuple[Dict[str, float], dict, np.ndarray]:
    """Replay ``traces`` (per row: ``work``, ``energy``, ``fidx``, each
    (epochs, CU)) of ``rows`` = [(workload, mechanism, epoch_us,
    objective)]. Returns the compared numbers, the replay and the departed
    mask. ``judged`` (rows, epochs, CU) are the choices ``choice_gap``
    judges at the program's states, the program's own by default."""
    forced = np.stack([np.asarray(t["fidx"], np.int32) for t in traces])
    ref = REF.simulate(arrays, rows, cfg, seed, forced=forced)
    out = {}
    for k in ("work", "energy"):
        got = np.stack([np.asarray(t[k], np.float64).sum(0) for t in traces])
        want = ref[k].astype(np.float64).sum(1)
        out[f"{k}_dev"] = float(np.max(np.abs(got - want)
                                       / np.maximum(np.abs(want), 1e-30)))
    gone = departed(np.stack([np.asarray(t["work"], np.float64)
                              for t in traces]),
                    ref["work"].astype(np.float64))
    pairs = gone.any(1)
    print(f"bench: the replay departed in {int(pairs.sum())} of {pairs.size}"
          " sampled (row, CU) pairs", file=sys.stderr, flush=True)
    gap = gaps(ref, rows, forced if judged is None else judged)
    gone_dom = gone.reshape(*gap.shape, -1).any(-1)
    out["choice_gap"] = float(np.where(gone_dom, 0.0, gap).max())
    return out, ref, gone


def control_traces(arrays, rows, cfg, seed) -> List[dict]:
    """The control: the reference itself in bfloat16 in the program's
    place, running on its own choices."""
    import jax.numpy as jnp
    ys = REF.simulate(arrays, rows, cfg, seed, dtype=jnp.bfloat16)
    return [{k: ys[k][i].astype(np.float32) if k != "fidx" else ys[k][i]
             for k in ("work", "energy", "fidx")} for i in range(len(rows))]


def control_choices(arrays, rows, cfg, seed, traces) -> np.ndarray:
    """The control of the choices alone: at each epoch of the program's
    run, the state that the reference in bfloat16, replaying the same
    choices, puts first; (rows, epochs, CU)."""
    import jax.numpy as jnp
    forced = np.stack([np.asarray(t["fidx"], np.int32) for t in traces])
    own = REF.simulate(arrays, rows, cfg, seed, forced=forced,
                       dtype=jnp.bfloat16)["own"]
    return np.repeat(own, forced.shape[-1] // own.shape[-1], axis=-1)


def ed2p_ratio(base: dict, mech: dict, epoch_us: float) -> float:
    """ED^2P of ``mech`` over ``base`` to complete 90% of the base's work."""
    budget = 0.9 * float(np.asarray(base["work"], np.float64).sum())
    *_, m0 = REF.ednp(np.asarray(base["work"]), np.asarray(base["energy"]),
                      budget, epoch_us)
    *_, m = REF.ednp(np.asarray(mech["work"]), np.asarray(mech["energy"]),
                     budget, epoch_us)
    return m / m0


def compare(arrays, rows, cfg, seed, traces, reports, limits,
            judged: Optional[np.ndarray] = None) -> List[tuple]:
    """``[(number, value, limit)]`` for ``traces`` of ``rows``; with
    ``reports`` (one reported ED^2P ratio per consecutive baseline,
    mechanism pair of rows) also ``report_dev``."""
    if any(t is None for t in traces):
        return [("sampled_rows_missing", 1, 0)]
    dev, ref, _ = deviations(arrays, rows, cfg, seed, traces, judged)
    names = ["work_dev", "energy_dev", "choice_gap"]
    if reports is not None:
        worst = 0.0
        for k, got in enumerate(reports):
            base, mech = ({c: ref[c][2 * k + r] for c in ("work", "energy")}
                          for r in (0, 1))
            want = ed2p_ratio(base, mech, rows[2 * k][2])
            worst = max(worst, abs(got - want) / abs(want))
        dev["report_dev"] = worst
        names.append("report_dev")
    return [(k, dev[k], limits[k]) for k in names]
