"""Benchmark harness: one module per paper table/figure + the roofline
aggregation.  Prints ``name,us_per_call,derived`` CSV (and a summary).
Exits non-zero when any module raised: its ERROR row is a failure, not a
result."""
import sys
import time


def main() -> int:
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import (backend_cold_start, chain_e2e, cluster_scale,
                            elastic_shards, fig4_fetch, fig5_warming,
                            hot_path, pool_load, prediction_quality,
                            roofline, router_overhead, table1_triggers,
                            trace_replay, warmth_levels)
    mods = [("table1_triggers", table1_triggers),
            ("fig4_fetch", fig4_fetch),
            ("fig5_warming", fig5_warming),
            ("chain_e2e", chain_e2e),
            ("prediction_quality", prediction_quality),
            ("pool_load", pool_load),
            ("backend_cold_start", backend_cold_start),
            ("trace_replay", trace_replay),
            ("cluster_scale", cluster_scale),
            ("elastic_shards", elastic_shards),
            ("warmth_levels", warmth_levels),
            ("router_overhead", router_overhead),
            ("hot_path", hot_path),
            ("roofline", roofline)]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failed = []
    for name, mod in mods:
        if only and only != name:
            continue
        t0 = time.monotonic()
        try:
            rows = mod.run()
        except Exception as e:
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            failed.append(name)
            continue
        for r in rows:
            print(",".join(str(x) for x in r))
        print(f"# {name} finished in {time.monotonic()-t0:.1f}s",
              file=sys.stderr)
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
