"""Serving launcher: deploy model endpoints of the named config, at its
published widths, behind the freshen platform and drive synthetic load
through a chain.  Everything runs in this one process (the process that
holds the accelerator); weights are random, made from ``--seed``.

  PYTHONPATH=src python -m repro.launch.serve --stages 3 --requests 8
  PYTHONPATH=src python -m repro.launch.serve --no-freshen ...   # baseline
"""
from __future__ import annotations
# fabriclint: allow-file[clock] -- launch-time measurement harness:
# wall-clock stamps feed the printed timings only.

import argparse
import tempfile
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-freshen", action="store_true")
    ap.add_argument("--service-class", default="latency_sensitive",
                    choices=["latency_sensitive", "standard", "batch"])
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.core.accounting import ServiceClass
    from repro.launch.compile_cache import configure_compile_cache
    from repro.models import make_model
    from repro.serving import (Executor, ModelEndpoint, ServingEngine,
                               WeightStore)

    configure_compile_cache()
    freshen_on = not args.no_freshen
    cfg = get_config(args.arch)
    store = WeightStore(tempfile.mkdtemp(prefix="serve-"))
    eng = ServingEngine()
    eng.scheduler.accountant.service_class["serving"] = \
        ServiceClass(args.service_class)
    names = [f"stage{i}" for i in range(args.stages)]
    for i, name in enumerate(names):
        store.publish(name, make_model(cfg).init(
            jax.random.PRNGKey(args.seed + i)))
        eng.deploy(ModelEndpoint(name, cfg, store, Executor(),
                                 batch_size=args.batch, seq_len=args.seq))
    if freshen_on:
        eng.chain(names)

    rng = np.random.default_rng(args.seed)
    lat = {n: [] for n in names}
    t0 = time.monotonic()
    try:
        for r in range(args.requests):
            toks = rng.integers(0, cfg.vocab_size,
                                size=(args.batch, args.seq), dtype=np.int32)
            for n in names:
                if freshen_on and n != names[0]:
                    eng.scheduler.runtimes[n].join_freshen(timeout=120)
                out = eng.invoke(n, toks, freshen_successors=freshen_on)
                lat[n].append(out["timing"]["total"])
        wall = time.monotonic() - t0
    finally:
        eng.close()
    print(f"mode={'freshen' if freshen_on else 'baseline'} "
          f"requests={args.requests} wall={wall:.2f}s")
    for n in names:
        arr = np.array(lat[n]) * 1e3
        print(f"  {n}: first={arr[0]:.1f}ms p50={np.percentile(arr,50):.1f}ms")
        st = eng.scheduler.runtimes[n].fr_state.stats()
        print(f"     freshen stats: {st}")
    bill = eng.scheduler.accountant.bill("serving")
    print(f"bill: fn={bill.function_seconds:.2f}s "
          f"freshen={bill.freshen_seconds:.2f}s "
          f"overhead={bill.freshen_overhead_ratio*100:.1f}% "
          f"useful={bill.useful_freshens} mispred={bill.mispredicted_freshens}")


if __name__ == "__main__":
    main()
