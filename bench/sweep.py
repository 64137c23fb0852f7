"""One sweep of an open-loop cell's arrival rate, to find the knee: the
highest rate the system sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 8
        --rates 100,150,200

Each rate is a whole run of the cell with its mix's rates scaled to it.
A rate is sustained when the requests finished inside the window keep up
with those due in it (backlog under 2 %) and the median latency stays
within twice the lowest rate's.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = harness.Bench()
    harness.configure_compile_cache(bench.root)
    mix = bench.mix(bench.workload(args.workload)["traffic"])
    base_p50, knee = None, None
    for rate in (float(r) for r in args.rates.split(",")):
        res = harness.run_cell(bench, args.workload, args.seed,
                               args.seconds, False, time.monotonic(),
                               rate_scale=rate / mix["rate_per_s"])
        p50 = res["metrics"]["latency_p50_ms"]["value"]
        base_p50 = p50 if base_p50 is None else base_p50
        backlog = res["finished_late"] / max(1, res["attempted"])
        held = backlog < 0.02 and p50 <= 2 * base_p50
        knee = rate if held else knee
        print(json.dumps({"rate_per_s": rate, "attempted": res["attempted"],
                          "finished_late_share": backlog, "held": held,
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()},
                          "correct": res["correct"]}), flush=True)
        if not held:
            break
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
