"""End-to-end serving driver: a three-model inference pipeline behind the
freshen platform, with batched requests — the paper's serving scenario on
the JAX substrate.

Stage chain:  embed-small -> rank-medium -> generate-small
The platform knows the chain (orchestration DAG), so invoking stage k
freshens stage k+1 (weights, XLA executable, warmup) inside the trigger
window.  Single requests are admitted with ``submit_request`` and batched
by each endpoint's ``EndpointBatcher`` (``deploy(..., batch_size=)``).

Platform architecture (see repro.core.pool / repro.core.scheduler): each
deployed endpoint is backed by an InstancePool of warm containers — idle
instances expire after a keep-alive (scale-to-zero), bursts scale the pool
up to a cap (cold starts are charged to latency), and predicted-successor
freshen is dispatched to *idle pooled instances*, so prewarming is a pool
policy, not a per-runtime call.  ``ServingEngine.submit`` admits requests
concurrently through the scheduler's thread-pool router; queueing delay,
cold starts, and p50/p95/p99 latency land in the Accountant
(``accountant.latency_summary(app)``).

For the open-loop Poisson/burst tail-latency study of the pool itself
(freshen on vs off, single function and chains), run:

    PYTHONPATH=src python benchmarks/pool_load.py

Run this example:  PYTHONPATH=src python examples/serve_chain.py [--requests 12]
"""
import argparse
import dataclasses
import tempfile
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import ServiceClass
from repro.launch.compile_cache import configure_compile_cache
from repro.models import make_model
from repro.serving import Executor, ModelEndpoint, ServingEngine, WeightStore

BATCH, SEQ = 4, 32


def build(freshen_on: bool):
    root = tempfile.mkdtemp(prefix="serve-chain-")
    store = WeightStore(root)
    eng = ServingEngine()
    stages = ["embed-small", "rank-medium", "generate-small"]
    dims = {"embed-small": 128, "rank-medium": 256, "generate-small": 128}
    for i, name in enumerate(stages):
        cfg = get_config("qwen2-0.5b").reduced(d_model=dims[name])
        cfg = dataclasses.replace(cfg, vocab_size=512)
        store.publish(name, make_model(cfg).init(jax.random.PRNGKey(i)))
        eng.deploy(ModelEndpoint(name, cfg, store, Executor(),
                                 batch_size=BATCH, seq_len=SEQ),
                   batch_size=BATCH, batch_max_wait=0.02)
    if freshen_on:
        eng.chain(stages)
    else:
        # the BATCH service class never freshens: predictions still run,
        # the accountant gates every dispatch
        eng.scheduler.accountant.service_class["serving"] = \
            ServiceClass.BATCH
    return eng, stages


def run_pipeline(eng, stages, requests):
    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    for i in range(requests):
        x = rng.integers(0, 512, size=(SEQ,), dtype=np.int32)
        for s in stages:
            logits = eng.submit_request(s, x).result(timeout=300)
            x = np.argsort(logits[-1])[-SEQ:].astype(np.int32)  # feed fwd
    wall = time.monotonic() - t0
    # per-batch run-hook time, as each endpoint measured it
    lat = {s: [t["total"] for t in eng.endpoints[s].timings] for s in stages}
    return lat, wall


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()
    configure_compile_cache()

    for mode in (False, True):
        eng, stages = build(freshen_on=mode)
        try:
            lat, wall = run_pipeline(eng, stages, args.requests)
            label = "freshen ON " if mode else "freshen OFF"
            print(f"=== {label}: {args.requests} requests, "
                  f"wall {wall:.2f}s ===")
            for s in stages:
                arr = np.array(lat[s]) * 1e3
                print(f"  {s:16s} first={arr[0]:8.1f}ms  "
                      f"p50={np.percentile(arr,50):7.1f}ms  "
                      f"max={arr.max():8.1f}ms  ({len(arr)} batches)")
            st = eng.scheduler.accountant.bill("serving")
            print(f"  bill: fn={st.function_seconds:.2f}s "
                  f"freshen={st.freshen_seconds:.2f}s "
                  f"useful={st.useful_freshens} "
                  f"mispred={st.mispredicted_freshens} "
                  f"cold_starts={st.cold_starts}")
            lat = eng.scheduler.accountant.latency_summary("serving")
            print(f"  latency: p50={lat['p50']*1e3:.1f}ms "
                  f"p95={lat['p95']*1e3:.1f}ms p99={lat['p99']*1e3:.1f}ms "
                  f"queue={lat['mean_queue_delay']*1e3:.2f}ms")
            for name, ps in eng.platform_stats().items():
                print(f"  pool[{name}]: instances={ps['instances']} "
                      f"cold={ps['cold_starts']} hits={ps['hits']} "
                      f"inline={ps['inline']}")
        finally:
            # batcher/router threads must die even when the demo fails
            eng.close()
