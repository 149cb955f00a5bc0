"""Run one benchmark cell:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs an accelerator; see ``harness.py``."""
import sys
from pathlib import Path

# import the benchmark as the package ``bench`` from the checkout's root,
# and not its files as top-level modules (``trace`` would shadow the
# standard library's)
sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.run())
