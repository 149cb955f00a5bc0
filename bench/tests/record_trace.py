"""Record the small chip traces the trace-reduction test reads: the tiny
sweep and stream cells of ``tiny.py`` run traced on the accelerator, each
trace gzipped with the run's counters and its result line beside it.

    python3 bench/tests/record_trace.py <out dir>
"""
import contextlib
import gzip
import io
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])
sys.path.insert(1, str(Path(__file__).resolve().parents[2] / "src"))

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402


def main(out: Path) -> None:
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        tree = tiny.make_tree(Path(tmp))
        for cell, seconds in (("tiny_suite", "0.01"), ("tiny_stream", "0.25")):
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                harness.run(["--workload", cell, "--seed", "5", "--seconds",
                             seconds, "--trace", "1"], bench=tree)
            result = buf.getvalue().strip().splitlines()[-1]
            print(result, flush=True)
            (out / f"{cell}.result.json").write_text(result + "\n")
            tdir = tree.parent / ".bench_trace" / cell
            pb = sorted(tdir.glob("**/*.xplane.pb"))[-1]
            with open(pb, "rb") as f, \
                    gzip.open(out / f"{cell}.xplane.pb.gz", "wb") as g:
                shutil.copyfileobj(f, g)
            counters = json.loads((tdir / "counters.json").read_text())
            (out / f"{cell}.counters.json").write_text(json.dumps(counters))


if __name__ == "__main__":
    main(Path(sys.argv[1]))
