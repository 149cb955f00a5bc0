"""Find the stream cell's knee once, on the chip: the highest offered rate
at which the service completes every job with no growing backlog.

    python3 bench/knee.py --workload stream_steady --seed 1 --seconds 15 \
        --rates 8 10 12 14

One process; the service is set up anew at each rate (the executables stay
compiled). Prints one JSON line per rate: offered and completed jobs/s,
latency percentiles, and the backlog trend (mean latency of the last
quarter of arrivals minus that of the first quarter)."""
import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import numpy as np
    harness.check_devices(cell.chips)
    harness.use_cache(harness.ROOT)
    for rate in args.rates:
        mix = dict(cell.mix, rate_per_s=rate)
        d = cell.kind.Runner(cell.cfg, mix, cell.library, n_dev=cell.chips)
        d.setup(args.seed)
        e2e = d.window(args.seconds, lambda name: contextlib.nullcontext())
        d.close()
        lat = np.asarray([j["lat"] for j in d.jobs]) * 1e3
        q = max(1, lat.size // 4)
        print(json.dumps({
            "rate_per_s": rate, "jobs_per_s": e2e["jobs_per_s"][0],
            "failed": d.failed, "p50_ms": float(np.median(lat)),
            "p95_ms": e2e["job_p95_ms"][0], "max_ms": float(lat.max()),
            "backlog_trend_ms": float(lat[-q:].mean() - lat[:q].mean()),
            "batch_fill": float(np.mean([j["res"]["batch_size"]
                                         for j in d.jobs if j["res"]]))}),
            flush=True)


if __name__ == "__main__":
    main()
