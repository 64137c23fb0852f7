"""Single-submission hot path: sustained RPS per shard and client p50
of the scheduler's admission, alone and behind the endpoint batcher.

Two arms, same closed-loop workload (each client thread fires its next
request the moment the previous one resolves — the steady-state regime
where admission overhead, not burst queueing, bounds throughput):

* **fast** — a two-shard cluster: the caller thread ``try_acquire``s
  inline and dispatches a run-only tail; prediction freshening runs on
  a dedicated low-priority executor.  A warm hit pays no admission hop
  for the acquire and no predictor work on the critical path.
* **batched** — the same admission behind a pool-aware
  ``EndpointBatcher``: single requests coalesce into adaptively-sized
  batches, each batch one pooled invocation — per-request platform
  overhead divides by the fill.

Reported per arm: client-observed p50/p95, completed requests, wall
time, RPS per shard.  The cluster arm also runs under a fabric
``Tracer`` for the phase breakdown, and its ``invoke.fast_path`` /
``invoke.slow_path`` counters are read back from the metrics registry.

The verdict row carries the CI gate (grep-able key=value):
``fast_path_gt0=1`` (the fast-path counter actually moved — the inline
admission is exercised, not silently bypassed), plus ``batched_ratio``,
the batched arm's RPS per shard over the fast arm's.

``HOT_PATH_SMOKE=1`` shrinks the run for CI (same arms and gate, fewer
requests).

CSV rows (stdout; schema in docs/benchmarks.md): ``name`` is
``hot_path/<fast|batched|phase/fast/<phase>|counters|batch_fill|verdict>``.

Run on CPU:  PYTHONPATH=src python benchmarks/hot_path.py
(or: PYTHONPATH=src:. python benchmarks/run.py hot_path)
"""
import os
import sys
import threading
import time

from repro.cluster.router import ClusterRouter
from repro.core import FunctionSpec, PoolConfig, ServiceClass
from repro.core.accounting import percentile
from repro.core.scheduler import FreshenScheduler
from repro.serving.batching import EndpointBatcher
from repro.telemetry import Tracer

SMOKE = bool(os.environ.get("HOT_PATH_SMOKE"))

SHARDS = 2
CLIENTS = 8 if SMOKE else 16
REQS_PER_CLIENT = 30 if SMOKE else 150
WARMUP = 4
COMPUTE = 0.0002          # seconds: near-zero body so admission cost shows
BATCH_SIZE = 8
POOL = dict(max_instances=8, keep_alive=30.0, cold_start_cost=0.002,
            scale_up_queue_depth=1)


def _spec(batched: bool = False) -> FunctionSpec:
    if batched:
        def code(ctx, args):
            time.sleep(COMPUTE)          # one body serves the whole batch
            return [p * 2 for p in args]
    else:
        def code(ctx, args):
            time.sleep(COMPUTE)
            return args
    return FunctionSpec("hot", code, app="bench")


def _closed_loop(submit, n_clients: int, per_client: int):
    """Closed-loop drive: returns (client latencies, wall seconds)."""
    lats = [[] for _ in range(n_clients)]
    errors = []

    def client(k: int):
        try:
            for i in range(per_client):
                t0 = time.monotonic()
                submit(k * per_client + i).result(timeout=60)
                lats[k].append(time.monotonic() - t0)
        except BaseException as e:       # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(n_clients)]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.monotonic() - t0
    if errors:
        raise errors[0]
    return [x for per in lats for x in per], wall


def _drive_cluster():
    """The cluster arm; returns (lats, wall, phase_totals, counters)."""
    tracer = Tracer(capacity=16384)
    cluster = ClusterRouter.build(
        SHARDS, policy="least-loaded", pool_config=PoolConfig(**POOL),
        max_router_threads=16, tracer=tracer)
    cluster.register(_spec())
    for w in cluster.workers:
        w.scheduler.accountant.service_class["bench"] = \
            ServiceClass.LATENCY_SENSITIVE
    for _ in range(WARMUP * SHARDS):     # populate warm instances
        cluster.submit("hot", 0).result(timeout=30)
    lats, wall = _closed_loop(lambda i: cluster.submit("hot", i),
                              CLIENTS, REQS_PER_CLIENT)
    snap = tracer.snapshot()
    counters = {"fast": 0, "slow": 0}
    for key, val in cluster.metrics_snapshot().items():
        if key.endswith("invoke.fast_path"):
            counters["fast"] += val
        elif key.endswith("invoke.slow_path"):
            counters["slow"] += val
    cluster.shutdown()
    return lats, wall, snap["phase_totals"], counters


def _drive_batched():
    """Admission + EndpointBatcher on one scheduler (one shard)."""
    sched = FreshenScheduler(pool_config=PoolConfig(**POOL),
                             max_router_threads=16)
    sched.register(_spec(batched=True))
    pool = sched.pools["hot"]

    def run_batch(payloads):
        return sched.submit("hot", list(payloads))

    batcher = EndpointBatcher("hot", run_batch, batch_size=BATCH_SIZE,
                              max_wait=0.002,
                              capacity=pool.idle_capacity)
    for _ in range(WARMUP):
        sched.submit("hot", [0]).result(timeout=30)
    lats, wall = _closed_loop(batcher.submit, CLIENTS, REQS_PER_CLIENT)
    stats = batcher.stats()
    batcher.close()
    sched.shutdown()
    return lats, wall, stats


def _row(arm: str, lats, wall, shards: int):
    n = len(lats)
    p50, p95 = percentile(lats, 50), percentile(lats, 95)
    rps_shard = (n / wall / shards) if wall else 0.0
    return (p50, p95, rps_shard,
            (f"hot_path/{arm}", f"{p50*1e6:.0f}",
             f"p95us={p95*1e6:.0f};n={n};wall_s={wall:.2f};"
             f"rps_per_shard={rps_shard:.0f}"))


def run():
    """Harness entry (benchmarks/run.py): CSV rows name,us_per_call,derived."""
    err = sys.stderr
    n = CLIENTS * REQS_PER_CLIENT
    fast_lats, fast_wall, fast_phases, counters = _drive_cluster()
    bat_lats, bat_wall, bat_stats = _drive_batched()

    fast_p50, _, fast_rps, fast_row = _row("fast", fast_lats, fast_wall,
                                           SHARDS)
    bat_p50, _, bat_rps, bat_row = _row("batched", bat_lats, bat_wall, 1)
    rows = [fast_row, bat_row]

    print(f"\n=== hot_path ({n} requests, {CLIENTS} clients, {SHARDS} "
          f"shards{', SMOKE' if SMOKE else ''}) ===", file=err)
    for arm, p50, rps in (("fast", fast_p50, fast_rps),
                          ("batched", bat_p50, bat_rps)):
        print(f"{arm:>8s}: p50 {p50*1e6:7.0f}us  {rps:7.0f} rps/shard",
              file=err)

    # phase shares: the warm-hit admission cost is route+queue
    total = sum(t["seconds"] for t in fast_phases.values()) or 1.0
    for name, t in sorted(fast_phases.items()):
        share = t["seconds"] / total
        rows.append((f"hot_path/phase/fast/{name}",
                     f"{t['mean']*1e6:.0f}",
                     f"count={t['count']};share_pct={share*100:.1f}"))
    adm = sum(fast_phases.get(p, {"seconds": 0.0})["seconds"]
              for p in ("route", "queue")) / total
    print(f"    fast: route+queue share {adm:.1%}", file=err)

    rows.append(("hot_path/counters", "0",
                 f"fast_path={counters['fast']};"
                 f"slow_path={counters['slow']}"))
    rows.append(("hot_path/batch_fill", "0",
                 f"mean_fill={bat_stats['mean_fill']:.2f};"
                 f"batches={bat_stats['batches']};"
                 f"backpressure={bat_stats['backpressure']}"))

    bat_ratio = bat_rps / fast_rps if fast_rps else 0.0
    fp_ok = int(counters["fast"] > 0)
    print(f"verdict: fast_path_gt0={fp_ok} batched_ratio={bat_ratio:.2f}",
          file=err)
    rows.append(("hot_path/verdict", "0",
                 f"fast_path_gt0={fp_ok};batched_ratio={bat_ratio:.2f}"))
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for r in run():
        print(",".join(str(x) for x in r))
