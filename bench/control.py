"""Readings that the correctness limits are set from, on the chip at the
cell's own size: the program's compared numbers over many seeds, and the
control's over a few, on the same sampled rows: the reference in bfloat16
in the program's place (``control_traces``), and the bfloat16 reference's
choices at the program's states (``control_choices``). The benchmark's own
runs never run this.

    python3 bench/control.py --workload suite_1us --seconds 0.1 \
        --seeds 1 2 3 --control-seeds 1 2

One process; one short window per seed (a sweep: one call). Prints one
JSON line per reading."""
import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import check as CHK  # noqa: E402
from bench import harness  # noqa: E402


def readings(runner, control):
    """The compared numbers of the runner's sample with ``control``
    ``"traces"`` (the bfloat16 reference's run in the program's place) or
    ``"choices"`` (the bfloat16 reference's choices at the program's
    states, judged by ``choice_gap``)."""
    rows, traces, reports = runner.sample()
    judged = None
    if control == "traces":
        traces = CHK.control_traces(runner.arrays, rows, runner.cfg,
                                    runner.seed)
        if reports is not None:
            reports = [CHK.ed2p_ratio(traces[2 * k], traces[2 * k + 1],
                                      rows[2 * k][2])
                       for k in range(len(reports))]
    else:
        judged = CHK.control_choices(runner.arrays, rows, runner.cfg,
                                     runner.seed, traces)
    return {n: v for n, v, _ in CHK.compare(
        runner.arrays, rows, runner.cfg, runner.seed, traces, reports,
        runner.mix["limits"], judged)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.check_devices(cell.chips)
    harness.use_cache(harness.ROOT)
    quiet = lambda name: contextlib.nullcontext()  # noqa: E731
    d = None
    for seed in args.seeds:
        if d is not None and hasattr(d, "reseed"):
            d.reseed(seed)
        else:
            d = cell.kind.Runner(cell.cfg, cell.mix, cell.library,
                                 n_dev=cell.chips)
            d.setup(seed)
        d.window(args.seconds, quiet)
        if not hasattr(d, "reseed"):
            d.close()
        checks = d.check()
        print(json.dumps({"seed": seed, "who": "program",
                          "failed": d.failed,
                          **{n: v for n, v, _ in checks}}), flush=True)
        if seed in args.control_seeds:
            for control in ("traces", "choices"):
                print(json.dumps({"seed": seed, "who": f"control_{control}",
                                  **readings(d, control)}), flush=True)
    d.close()


if __name__ == "__main__":
    main()
