"""ClusterWorker — one shard of the serving fabric.

A worker wraps one ``FreshenScheduler`` (and therefore one set of
``InstancePool``s) and gives it a shard identity: pools raise
shard-tagged ``PoolSaturated`` errors, load/warmth signals are exposed
in the shape the routing policies consume, and the worker can be pinned
to a slice of the host's jax devices so each shard's function bodies run
on distinct hardware (``repro.sharding.partitioning`` can then build
per-shard parameter shardings over ``ClusterWorker.mesh()``).

Workers never talk to each other: all cross-shard behavior (routing,
freshen propagation, queue rebalancing) lives in
``repro.cluster.router.ClusterRouter``.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import Future
from typing import Any, List, Optional, Sequence

from repro.core.accounting import Accountant
from repro.core.pool import InstancePool, PoolConfig
from repro.core.prediction import HybridPredictor
from repro.core.runtime import FunctionSpec, Runtime
from repro.core.scheduler import FreshenScheduler
from repro.telemetry import Tracer


class ClusterWorker:
    """One shard: a FreshenScheduler plus shard identity and device slice.

    ``predictor`` is usually the cluster-shared ``HybridPredictor`` —
    prediction is global knowledge (chains and periodicity do not care
    which shard an invocation landed on), so every shard's observations
    feed one model while accounting stays per-shard (each worker gets its
    own ``Accountant``, so the cluster can tell *where* latency and cold
    starts happen).
    """

    def __init__(self, shard_id: int,
                 predictor: Optional[HybridPredictor] = None,
                 accountant: Optional[Accountant] = None,
                 pool_config: Optional[PoolConfig] = None,
                 devices: Optional[Sequence] = None,
                 max_router_threads: int = 16,
                 tracer: Optional[Tracer] = None):
        self.shard_id = shard_id
        self.devices = list(devices) if devices else None
        # set by ClusterRouter.remove_worker: a draining shard finishes
        # its in-flight work but admits nothing new
        self.draining = False
        # like the predictor, the tracer is cluster-shared: a freshen
        # dispatched on this shard and the arrival it anchored (possibly
        # routed elsewhere) must meet in one pending table.  A routed
        # warm hit try_acquires inline on the router's calling thread and
        # pays no admission hop.
        self.scheduler = FreshenScheduler(
            predictor=predictor, accountant=accountant,
            pool_config=pool_config, max_router_threads=max_router_threads,
            tracer=tracer)

    # -- registration ---------------------------------------------------
    def _placement(self):
        """Context pinning this shard's first device as the jax default:
        every hook of the shard's instances (init, freshen, run) runs
        inside it, so weights, executables and warm-ups land on the
        shard's own hardware, not on whatever device is the default."""
        import jax
        return jax.default_device(self.devices[0])

    def register(self, spec: FunctionSpec,
                 config: Optional[PoolConfig] = None,
                 backend: Optional[str] = None) -> Runtime:
        """Register a function on this shard; its pool is shard-tagged so
        saturation errors name the shard.  ``backend`` selects the
        instance backend (repro.core.backend: thread, subprocess, or
        snapshot — a snapshot pool's fork template lives and dies with
        this shard's pools); device pinning is a per-thread jax context
        and therefore requires the in-process thread backend."""
        if self.devices:
            chosen = backend or (config.backend if config
                                 else self.scheduler.pool_config.backend)
            if chosen != "thread":
                raise ValueError(
                    f"shard {self.shard_id} pins jax devices, which "
                    f"requires the thread backend (got {chosen!r})")
            spec = dataclasses.replace(spec, placement=self._placement)
        rt = self.scheduler.register(spec, config=config, backend=backend)
        self.scheduler.pools[spec.name].shard = self.shard_id
        return rt

    def mesh(self, axis_name: str = "model"):
        """A 1-axis jax Mesh over this worker's device slice, for use with
        ``repro.sharding.partitioning.shard_params`` when an endpoint's
        weights should be tensor-parallel *within* the shard."""
        if not self.devices:
            raise ValueError(f"shard {self.shard_id} has no pinned devices")
        import numpy as np
        from jax.sharding import Mesh
        return Mesh(np.asarray(self.devices), (axis_name,))

    # -- invocation (delegated) -----------------------------------------
    def has_function(self, fn: str) -> bool:
        return fn in self.scheduler.pools

    def begin_drain(self):
        """Stop admitting new invocations; in-flight work completes.
        Called by ``ClusterRouter.remove_worker`` after the shard left
        the routing set — a direct ``submit`` afterwards is a caller
        holding a stale shard reference, and must fail loudly rather
        than queue work on a shard about to shut down."""
        self.draining = True

    def _check_admitting(self):
        if self.draining:
            raise RuntimeError(
                f"shard {self.shard_id} is draining (removed from its "
                f"cluster): it accepts no new invocations")

    def submit(self, fn: str, args: Any = None,
               freshen_successors: bool = True,
               acquire_timeout: Optional[float] = None,
               _span=None) -> Future:
        self._check_admitting()
        return self.scheduler.submit(fn, args, freshen_successors,
                                     acquire_timeout, _span=_span)

    def submit_chain(self, fns: List[str], args: Any = None,
                     freshen: bool = True) -> Future:
        self._check_admitting()
        return self.scheduler.submit_chain(fns, args, freshen)

    def invoke(self, fn: str, args: Any = None,
               freshen_successors: bool = True):
        self._check_admitting()
        return self.scheduler.invoke(fn, args,
                                     freshen_successors=freshen_successors)

    def prewarm(self, fn: str, provision: bool = True, level=None):
        return self.scheduler.prewarm(fn, provision=provision, level=level)

    def try_acquire(self, fn: str):
        """Non-blocking fast-path probe on this shard's pool: returns
        ``(instance, cold)`` or None.  ``submit`` already runs this
        inline via the shard scheduler's fast path; the explicit
        delegate exists for callers (batchers, probes) that need the
        grab without the dispatch."""
        self._check_admitting()
        pool = self.scheduler.pools.get(fn)
        return pool.try_acquire() if pool is not None else None

    # -- routing signals ------------------------------------------------
    def pool(self, fn: str) -> Optional[InstancePool]:
        return self.scheduler.pools.get(fn)

    def warm_idle(self, fn: str) -> int:
        """Idle initialized instances of ``fn`` on this shard — the
        warmth-aware policy's primary signal."""
        pool = self.scheduler.pools.get(fn)
        return pool.warm_idle_count() if pool is not None else 0

    def warm_total(self, fn: str) -> int:
        """Initialized instances of ``fn``, idle or busy — the drain
        handoff's signal (warmth an in-flight invocation is borrowing
        still needs a new home)."""
        pool = self.scheduler.pools.get(fn)
        return pool.warm_total_count() if pool is not None else 0

    def warmth_weight(self, fn: str) -> float:
        """Level-weighted idle warmth of ``fn`` here (HOT instance = 1.0,
        PROCESS standby = 1/3): the graded routing signal — a shard
        holding a HOT instance outranks one holding only a standby, which
        still outranks a cold shard."""
        pool = self.scheduler.pools.get(fn)
        return pool.warmth_score() if pool is not None else 0.0

    def queue_depth(self, fn: Optional[str] = None) -> int:
        """Blocked acquires, for one function or the whole shard."""
        pools = self.scheduler.pools
        if fn is not None:
            pool = pools.get(fn)
            return pool.waiting_count() if pool is not None else 0
        return sum(p.waiting_count() for p in pools.values())

    def load(self, fn: Optional[str] = None) -> int:
        """Busy instances + blocked acquires — the least-loaded policy's
        signal.  Whole-shard by default: one worker's instances share the
        shard's hardware, so load on any pool slows every pool.  Each
        pool's contribution is read under one lock (``InstancePool.load``)
        — summing busy and waiting from separate lock acquisitions tears
        across a concurrent release and double-counts."""
        pools = self.scheduler.pools
        if fn is not None:
            pool = pools.get(fn)
            return pool.load() if pool is not None else 0
        return sum(p.load() for p in pools.values())

    def idle_capacity(self, fn: str) -> int:
        """Instances ``fn`` could run on here without queueing: idle ones
        plus the headroom below the pool cap.  Rebalancing drains a hot
        shard's queue toward the neighbor maximizing this.  One lock
        acquisition (``InstancePool.idle_capacity``): the former
        stats()-then-config read could tear across a reconfigure."""
        pool = self.scheduler.pools.get(fn)
        return pool.idle_capacity() if pool is not None else 0

    # -- lifecycle ------------------------------------------------------
    def stats(self) -> dict:
        out = {"shard": self.shard_id, "load": self.load(),
               "queue_depth": self.queue_depth()}
        out["pools"] = self.scheduler.platform_stats()
        return out

    def shutdown(self, wait: bool = True):
        self.scheduler.shutdown(wait=wait)
