"""The benchmark's own input generators: the paper's Table II programs, the
DVFS request stream and the open-loop arrival schedule.

The program generator and the request stream are copies of
``repro.core.workloads.make_program`` and
``repro.data.pipeline.dvfs_request_stream`` (a test holds them equal), kept
here so that no change to the program can move the benchmark's inputs.
Everything is numpy on the host; the harness turns the arrays into the
program's ``Program`` objects itself.
"""
from __future__ import annotations

from typing import Dict, Iterator, Sequence, Tuple

import numpy as np

INSTR_PER_BLOCK = 4

# base per-WF rate scale: a wavefront at 1.7 GHz commits ~100 instr/us
_RATE = 100.0

# phase palettes: (core_share, rate_mult, mem_frac)
_COMPUTE = [(0.9, 1.4, 0.05), (0.8, 0.7, 0.1), (0.95, 1.1, 0.02),
            (0.85, 1.8, 0.08), (0.45, 0.9, 0.45)]
_MEMORY = [(0.15, 0.7, 0.8), (0.25, 0.8, 0.7), (0.1, 0.6, 0.9)]
_BALANCED = [(0.55, 1.0, 0.35), (0.45, 0.9, 0.45)]
_ALL = _COMPUTE + _MEMORY + _BALANCED

# (palettes, mean segment length, heterogeneity), accepted mem_frac band
_KIND_SPECS = {
    "compute": (([_COMPUTE, _COMPUTE, _BALANCED], 32, 0.7), (0.0, 0.3)),
    "memory": (([_MEMORY, _MEMORY, _MEMORY, _BALANCED], 32, 0.4), (0.5, 1.0)),
    "phased": (([_COMPUTE, _MEMORY], 36, 0.5), (0.25, 0.55)),
    "irregular": (([_ALL], 12, 0.8), (0.15, 0.6)),
    "constant": (([(0.5, 1.0, 0.3)], 100_000, 0.0), (0.0, 1.0)),
    "thrash": (([(0.7, 1.2, 0.75), (0.6, 1.1, 0.8)], 40, 0.3), (0.5, 1.0)),
    "mixed": (([_BALANCED, _COMPUTE, _MEMORY], 24, 0.5), (0.15, 0.45)),
}

# the paper's workload suite (Table II): name -> (generator kind, seed)
TABLE_II: Dict[str, Tuple[str, int]] = {
    "comd": ("phased", 11), "hpgmg": ("memory", 12),
    "lulesh": ("irregular", 13), "minife": ("mixed", 14),
    "xsbench": ("memory", 15), "hacc": ("phased", 16),
    "quickS": ("irregular", 17), "pennant": ("mixed", 18),
    "snapc": ("memory", 19), "dgemm": ("compute", 21),
    "BwdBN": ("mixed", 22), "BwdPool": ("constant", 23),
    "BwdSoft": ("memory", 24), "FwdBN": ("mixed", 25),
    "FwdPool": ("constant", 26), "FwdSoft": ("thrash", 27),
}


def _segments(rng, P, palettes, seg_len_mean, hetero):
    if palettes and isinstance(palettes[0], tuple) \
            and isinstance(palettes[0][0], float):
        palettes = [palettes]
    i0, sens, mem = np.zeros(P), np.zeros(P), np.zeros(P)
    pos, pi = 0, 0
    while pos < P:
        ln = max(2, int(rng.exponential(seg_len_mean)))
        kinds = palettes[pi % len(palettes)]
        pi += 1
        core_share, rate_mult, mfrac = kinds[rng.integers(len(kinds))]
        jitter = 1.0 + hetero * rng.standard_normal()
        rate = _RATE * rate_mult * max(jitter, 0.3)
        i0[pos:pos + ln] = (1 - core_share) * rate
        sens[pos:pos + ln] = core_share * rate / 1.7
        mem[pos:pos + ln] = mfrac
        pos += ln
    return i0, sens, mem


def program_arrays(name: str, P: int = 1024) -> Dict[str, np.ndarray]:
    """One Table II program as float32 per-block arrays ``i0``, ``sens``
    and ``mem``."""
    kind, seed = TABLE_II[name]
    (palettes, seg_len, hetero), (lo, hi) = _KIND_SPECS[kind]
    for trial in range(50):
        rng = np.random.default_rng(seed + 1000 * trial)
        i0, s, m = _segments(rng, P, palettes, min(seg_len, P), hetero)
        if lo <= float(np.mean(m)) <= hi:
            break
    return {"i0": i0.astype(np.float32), "sens": s.astype(np.float32),
            "mem": m.astype(np.float32)}


def request_stream(n_requests: int, seed: int,
                   workloads: Sequence[str], epoch_us: Sequence[float],
                   objectives: Sequence[str], steps_per_request: int
                   ) -> Iterator[Tuple[str, dict, tuple]]:
    """Request ``i`` drawn from ``(seed, i)`` alone: ``(workload name,
    axes overrides, (step, seconds) telemetry)``."""
    names = tuple(workloads)
    for i in range(n_requests):
        rng = np.random.default_rng((seed, i))
        name = names[int(rng.integers(len(names)))]
        axes = {"epoch_us": float(epoch_us[int(rng.integers(len(epoch_us)))]),
                "objective": objectives[int(rng.integers(len(objectives)))]}
        telemetry = tuple(
            (i * steps_per_request + s, float(rng.gamma(2.0, 0.005)))
            for s in range(steps_per_request))
        yield name, axes, telemetry


def arrival_offsets(n: int, seconds: float, seed: int) -> np.ndarray:
    """Due times of ``n`` open-loop arrivals over ``seconds``: exponential
    inter-arrival gaps (the quantiles of Exp at the mid-points of ``n``
    equal strata, so every seed offers the same set of gaps), scaled to
    span ``seconds`` exactly and put in an order drawn from ``seed``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = gaps / gaps.sum() * seconds
    rng = np.random.default_rng((seed, 0x5EED))
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
