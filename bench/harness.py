"""The benchmark harness. Everything it knows about a cell comes from files
found by name under the benchmark directory:

* ``workloads/<cell>.json``  the cell: its configuration, traffic mix, chips;
* ``configs/<config>.json``  the deployment, as it is run;
* ``programs/<library>.py``  the program library the deployment names
                             under ``"programs"``: ``NAMES`` and
                             ``arrays(name, cfg)``;
* ``traffic/<mix>.json``     the traffic mix: its kind and parameters;
* ``traffic/<kind>.py``      the generator and runner of that kind:
                             ``END_TO_END``, the metrics it reports,
                             ``program_names(cfg, mix)``, the programs it
                             draws, and ``Runner(cfg, mix, library,
                             n_dev)``;
* ``metrics/<metric>.py``    one per-layer metric's reader.

A run loads the cell, sets the deployment up (inputs, compilation, one warm
call), measures one window of ``--seconds`` and then checks what the window
produced against the plain reference (``reference.py``). The last line of
standard output is one JSON object; the numbers the check compared are
printed, each beside its limit, as the last lines of standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional, Tuple

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# trace and compilation events that mean a program was built (counted
# inside the window, where there should be none)
_BUILD_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench: Path = BENCH) -> SimpleNamespace:
    """The cell ``name`` with its configuration and traffic mix."""
    cell_file = bench / "workloads" / f"{name}.json"
    if not cell_file.is_file():
        raise FileNotFoundError(f"no cell {name!r}: {cell_file} is missing")
    cell = json.loads(cell_file.read_text())
    cfg = json.loads((bench / "configs" / f"{cell['config']}.json")
                     .read_text())
    mix = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    kind = load_module(bench / "traffic" / f"{mix['kind']}.py")
    if "programs" not in cfg:
        raise KeyError(f"configuration {cell['config']!r} names no program "
                       "library under \"programs\"")
    library = load_module(bench / "programs" / f"{cfg['programs']}.py")
    return SimpleNamespace(name=name, chips=int(cell["chips"]), cfg=cfg,
                           mix=mix, kind=kind, library=library, bench=bench)


def readers(bench: Path = BENCH) -> Dict[str, object]:
    """Every per-layer metric reader: ``{metric name: module}``; each
    module has ``UNIT`` and ``read(ctx)``, which returns None where the
    run has nothing for it to read."""
    return {p.name[:-3]: load_module(p)
            for p in sorted((bench / "metrics").glob("*.py"))}


def read_trace(path: str, counters: dict, bench: Path = BENCH
               ) -> Tuple[dict, dict, dict]:
    """Per-layer metrics, the ``device`` entries and the ``breakdown`` of
    one traced window."""
    from bench import trace as T
    tr = T.load(path)
    lo, hi = tr.window()
    ctx = SimpleNamespace(trace=tr, T=T, lo=lo, hi=hi, **counters)
    metrics = {}
    for name, mod in readers(bench).items():
        value = mod.read(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": mod.UNIT}
    device = {"busy_s": T.busy_s(tr, lo, hi), "window_s": (hi - lo) * 1e-9}
    return metrics, device, T.breakdown(tr, lo, hi)


def check_devices(chips: int):
    """The accelerator devices the cell runs on; refuses the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"no accelerator found (devices: {devs})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def use_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class BuildCounter:
    """Counts programs traced or compiled while ``on``."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0

        def listen(event, duration, **_):
            if self.on and event in _BUILD_EVENTS:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


@contextlib.contextmanager
def span(name: str):
    """A host span ``bench.<name>`` on the profiler's clock (free when no
    profiler runs)."""
    import jax
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


def memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(argv: Optional[List[str]] = None, bench: Path = BENCH,
        device_check: Callable = check_devices) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, bench)
    sys.path.insert(0, str(bench.parent / "src"))
    import jax
    try:
        devs = device_check(cell.chips)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    use_cache(bench.parent)
    builds = BuildCounter()

    runner = cell.kind.Runner(cell.cfg, cell.mix, cell.library,
                              n_dev=cell.chips)
    runner.setup(args.seed)
    setup_s = time.perf_counter() - T_START
    log(f"bench: {cell.name} on {len(devs)} x {devs[0].device_kind}, "
        f"set-up {setup_s:.3f} s")

    trace_dir = None
    if args.trace:
        trace_dir = bench.parent / ".bench_trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    builds.on = True
    seconds = runner.trace_seconds(args.seconds) if args.trace \
        else args.seconds
    with span("window"):
        e2e = runner.window(seconds, span)
    builds.on = False
    if args.trace:
        jax.profiler.stop_trace()
    elif set(e2e) | {"setup_s"} != set(cell.kind.END_TO_END):
        raise RuntimeError(
            f"kind {cell.mix['kind']!r} declares END_TO_END "
            f"{sorted(cell.kind.END_TO_END)}, its window reported "
            f"{sorted(e2e)} and setup_s")
    log(f"bench: programs built inside the window: {builds.n}")
    for line in runner.notes():
        log(f"bench: {line}")
    peak = memory_peak(devs)
    runner.close()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"device": device}
    if args.trace:
        tr_file = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                                   recursive=True))[-1]
        counters = runner.counters()
        (trace_dir / "counters.json").write_text(json.dumps(counters))
        out["metrics"], dev_time, out["breakdown"] = read_trace(
            tr_file, counters, bench)
        device.update(dev_time)
    else:
        out["metrics"] = {k: {"value": v, "unit": u}
                          for k, (v, u) in e2e.items()}
        out["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}

    checks = [(n, float(v), lim) for n, v, lim in runner.check()]
    correct = bool(checks) and runner.failed == 0 and runner.attempted > 0 \
        and all(v <= lim for _, v, lim in checks)
    for n, v, lim in checks:
        log(f"check {n}: {v!r} limit {lim!r} {'ok' if v <= lim else 'FAIL'}")
    out = {"correct": correct, "attempted": runner.attempted,
           "failed": runner.failed, **out,
           "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks}}
    print(json.dumps(out), flush=True)
    return 0
