"""The one place where the benchmark builds the program's own objects: the
simulator's configuration and its ``Program`` inputs, from a deployment
file and the per-block arrays of its program library."""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

# the deployment-file keys that are the simulator's configuration fields
SIM_FIELDS = ("n_cu", "n_wf", "epoch_us", "n_epochs", "entries",
              "offset_blocks", "cus_per_table", "cus_per_domain",
              "objective", "sigma", "cap_per_ghz", "membw", "table_ema",
              "use_pallas")


def sim_config(cfg: dict, seed: int):
    """``SimConfig`` of deployment ``cfg`` with noise seed ``seed``."""
    from repro.core.power import PowerConfig
    from repro.core.simulate import SimConfig
    return SimConfig(**{k: cfg[k] for k in SIM_FIELDS},
                     power=PowerConfig(**cfg["power"]), seed=int(seed))


def program_arrays(library, cfg: dict, names: Iterable[str]
                   ) -> Dict[str, Dict[str, np.ndarray]]:
    """``{name: per-block arrays}`` of programs ``names`` from the
    deployment's program library (``programs/<cfg["programs"]>.py``, a
    module with ``NAMES`` and ``arrays(name, cfg)``): float32 ``i0``,
    ``sens`` and ``mem`` of one length each."""
    out = {}
    for name in names:
        if name not in library.NAMES:
            raise KeyError(f"program {name!r} is not in library "
                           f"{cfg['programs']!r}: {library.NAMES}")
        a = library.arrays(name, cfg)
        cols = [np.asarray(a[k]) for k in ("i0", "sens", "mem")]
        if any(c.dtype != np.float32 or c.ndim != 1
               or c.shape != cols[0].shape for c in cols):
            raise ValueError(f"library {cfg['programs']!r} gave {name!r} "
                             "arrays that are not float32 of one length")
        out[name] = a
    return out


def programs(arrays: Dict[str, Dict[str, np.ndarray]]):
    """``{name: Program}`` from the benchmark's per-block arrays. The
    prefix sums over the doubled program are built on the device, in
    float32, as the program's own generator builds them."""
    import jax.numpy as jnp
    from repro.core.workloads import Program

    def cum(a):
        return jnp.concatenate([jnp.zeros(1), jnp.cumsum(jnp.tile(a, 2))])
    out = {}
    for name, a in arrays.items():
        i0, s, m = (jnp.asarray(a[k], jnp.float32)
                    for k in ("i0", "sens", "mem"))
        out[name] = Program(name, i0, s, m,
                            jnp.stack([cum(i0), cum(s), cum(m)], axis=-1))
    return out
