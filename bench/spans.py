"""The program's own host spans in a trace: the ``repro.*``
``jax.profiler.TraceAnnotation`` names that ``core/sweep.py`` and
``dvfs_runtime/service.py`` write, which ``trace.load`` keeps in
``Trace.host``. The readers in ``metrics/`` that use them return None where
a trace has none (a program that writes no such spans)."""
from __future__ import annotations

from typing import List, Optional

from bench import trace as T


def named(ctx, *names: str) -> List[T.Interval]:
    """Intervals of the host events called ``names``, clipped to the
    window, in start order."""
    want = set(names)
    return sorted((max(s, ctx.lo), min(e, ctx.hi))
                  for s, e, n in ctx.trace.host
                  if n in want and e > ctx.lo and s < ctx.hi)


def per_call(ctx, *names: str, count: bool = False) -> Optional[float]:
    """Mean over the benchmark's ``bench.call`` spans of the milliseconds
    (or, with ``count``, the number) of the spans called ``names`` that
    start inside each call."""
    spans = named(ctx, *names)
    calls = ctx.trace.span("call")
    if not spans or not calls:
        return None
    out = []
    for lo, hi in calls:
        inside = [(s, e) for s, e in spans if lo <= s <= hi]
        out.append(len(inside) if count
                   else sum(e - s for s, e in inside) * 1e-6)
    return sum(out) / len(out)


def per_batch_ms(ctx, first: str, *more: str) -> Optional[float]:
    """Milliseconds of the spans called ``first`` and ``more``, summed over
    the window, over the number of ``first`` spans (the service writes one
    of each per batch)."""
    n = len(named(ctx, first))
    if not n:
        return None
    return sum(e - s for s, e in named(ctx, first, *more)) * 1e-6 / n

