"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the TPU only: any other first device, or fewer chips than the cell
asks for, exits 3 with no result.  See ``bench/harness.py``.
"""
import time

STARTED = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], started=STARTED))
