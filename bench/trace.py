"""Reduction of a ``jax.profiler`` trace (an ``.xplane.pb`` file) to what the
per-layer metrics read: the device operations of each chip, the
executables (modules) they belong to, the benchmark's own host spans
(``bench.*`` ``TraceAnnotation`` names) and the host runtime's events, all
on the profiler's one clock, in nanoseconds.

Device busy time is the union of the intervals in which an operation runs
on a device; an idle gap is a stretch of the window in which none runs.
"""
from __future__ import annotations

import gzip
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "bench."
GRID_MODULE = "jit_dispatch"   # the sweep layer's grid executables


@dataclass
class Op:
    start: float
    end: float
    name: str
    module: str


@dataclass
class Trace:
    """One trace: ``ops[device]`` sorted by start, ``spans`` (the
    benchmark's host spans) and ``host`` (every other host event)."""
    ops: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Tuple[float, float, str]] = field(default_factory=list)
    host: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def span(self, name: str) -> List[Interval]:
        """Intervals of the host span ``bench.<name>``, in start order."""
        return sorted((s, e) for s, e, n in self.spans
                      if n == SPAN_PREFIX + name)

    def window(self) -> Optional[Interval]:
        w = self.span("window")
        return w[0] if w else None


def _short(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or name


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (optionally gzipped) file."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                or plane.name.startswith("/device:GPU"):
            mods: List[Interval] = []
            mod_names: List[str] = []
            ops: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        mods.append((e.start_ns, e.end_ns))
                        mod_names.append(e.name)
                elif line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.end_ns, e.name)
                               for e in line.events)
            if not ops:
                continue
            order = sorted(range(len(mods)), key=lambda i: mods[i][0])
            mods = [mods[i] for i in order]
            mod_names = [mod_names[i] for i in order]
            ops.sort()
            out, j = [], 0
            for s, e, n in ops:
                while j + 1 < len(mods) and mods[j + 1][0] <= s:
                    j += 1
                inside = j < len(mods) and mods[j][0] <= s <= mods[j][1]
                out.append(Op(s, e, _short(n),
                              mod_names[j].split("(")[0] if inside else ""))
            tr.ops[plane.name] = out
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    item = (e.start_ns, e.end_ns, e.name)
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append(item)
                    else:
                        tr.host.append(item)
    return tr


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def length(merged: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in merged))


def busy(tr: Trace, device: str, lo: float, hi: float,
         module: Optional[str] = None) -> List[Interval]:
    """Disjoint intervals of ``[lo, hi]`` in which an operation (of
    executables whose name starts with ``module``, if given) runs."""
    ops = tr.ops.get(device, [])
    return clip(union([(o.start, o.end) for o in ops
                       if module is None or o.module.startswith(module)]),
                lo, hi)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of ``merged`` within ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The operations that contain no other (a ``while`` op spans its
    body's operations, which the trace lists as well)."""
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or not (nxt.start < o.end and nxt.end <= o.end):
            out.append(o)
    return out


def op_totals(tr: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Seconds per ``module:op`` of the leaf operations, summed over the
    window and averaged over the devices that ran operations."""
    tot: Dict[str, float] = {}
    for dev in tr.devices:
        for o in leaves(tr.ops[dev]):
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                key = f"{o.module}:{o.name}" if o.module else o.name
                tot[key] = tot.get(key, 0.0) + d * 1e-9
    n = max(len(tr.devices), 1)
    return {k: v / n for k, v in tot.items()}


def label(tr: Trace, lo: float, hi: float) -> str:
    """What the host was doing in ``[lo, hi]``: the innermost benchmark
    span open at its middle and the host runtime event overlapping it
    most, as ``span|event``."""
    mid = 0.5 * (lo + hi)
    open_spans = [(e - s, n) for s, e, n in tr.spans if s <= mid <= e]
    span = min(open_spans)[1][len(SPAN_PREFIX):] if open_spans else "none"
    best, name = 0.0, ""
    for s, e, n in tr.host:
        ov = min(e, hi) - max(s, lo)
        if ov > best:
            best, name = ov, n
    return f"{span}|{name}" if name else span


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps of the first device, each labelled by :func:`label`."""
    ops = sorted(op_totals(tr, lo, hi).items(), key=lambda kv: -kv[1])
    dev = tr.devices[0] if tr.devices else None
    idle = gaps(busy(tr, dev, lo, hi), lo, hi) if dev else []
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[label(tr, s, e), (e - s) * 1e-9]
                          for s, e in idle]}


def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = tr.devices
    if not devs:
        return 0.0
    return sum(length(busy(tr, d, lo, hi)) for d in devs) * 1e-9 / len(devs)
