"""Readings for a cell's correctness limit, several seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10
        [--trace 1] [--keep-trace FILE]

Each seed is a whole run of the cell (set-up, window, check).  Beside the
program's reading of each compared number it prints the control's: the
plain reference of the configuration's architecture module
(``bench/archs``) computed in float8 on the same prompts and weights.  ``--keep-trace`` writes the first seconds of a traced
run's trace, reduced to the events ``bench/trace.py`` reads.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace")
    args = ap.parse_args(argv)
    bench = harness.Bench()
    harness.configure_compile_cache(bench.root)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(bench, args.workload, seed, args.seconds,
                               bool(args.trace), time.monotonic(),
                               control=True, keep_trace=args.keep_trace)
        print(json.dumps({"seed": seed, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
