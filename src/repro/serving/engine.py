"""The serving engine: model endpoints as serverless functions with
first-class freshen integration.

A ``ModelEndpoint`` is the JAX analogue of the paper's λ (Algorithm 1):

    procedure λ(tokens):
        params   := FrFetch(0, WeightStore.load(NAME))        # DataGet
        compiled := FrFetch(1, Executor.compile(score_fn))    # connection est.
        FrWarm(2, compiled.warmup())                          # CWND warming
        [data   := FrFetch(3, Datastore.get(CONST_KEY))]      # prefetch
        return compiled(params, tokens)

The freshen plan for the endpoint is exactly these entries in access order;
``build_endpoint_plan`` can also be produced by §3.3 inference from traces
(see tests).  The warm-budget controller implements the provider-policy half
of ``warm_cwnd``: warming is only permitted when observed repetition
justifies it.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.freshen import Action, FreshenPlan, PlanEntry
from repro.core.runtime import FunctionSpec, RunContext, Runtime
from repro.models import make_model
from repro.serving.batching import EndpointBatcher, pad_batch
from repro.serving.executor import Executor
from repro.serving.weights import WeightStore
from repro.telemetry import current_span


@dataclass
class WarmBudget:
    """Provider-side policy half of warm_cwnd: allow warming only after
    ``min_repetitions`` observed invocations of the same shape (repetitive
    invocations anticipate workload characteristics, §3.2)."""
    min_repetitions: int = 2
    observed: Dict[Any, int] = field(default_factory=dict)

    def observe(self, key):
        self.observed[key] = self.observed.get(key, 0) + 1

    def allows(self, key) -> bool:
        return self.observed.get(key, 0) >= self.min_repetitions


class ModelEndpoint:
    """One servable model = one serverless function."""

    def __init__(self, name: str, cfg: ModelConfig, store: WeightStore,
                 executor: Optional[Executor] = None, *,
                 batch_size: int = 4, seq_len: int = 64, app: str = "serving",
                 datastore=None, prefetch_key: Optional[str] = None,
                 prefetch_ttl: Optional[float] = None,
                 warm_budget: Optional[WarmBudget] = None):
        self.name = name
        self.cfg = cfg
        self.model = make_model(cfg)
        self.store = store
        self.executor = executor or Executor()
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.app = app
        self.datastore = datastore
        self.prefetch_key = prefetch_key
        self.prefetch_ttl = prefetch_ttl
        self.warm_budget = warm_budget or WarmBudget(min_repetitions=0)
        self.timings: List[dict] = []
        # an endpoint with expert layers also returns, from its score
        # step, the rows each held expert computed, per expert layer
        self.counts_expert_rows = any(
            k in ("attn_moe", "mla_moe") for k in cfg.layer_kinds)

    # ------------------------------------------------------------------
    def _score_fn(self):
        model = self.model

        if self.counts_expert_rows:
            def score(params, tokens):
                x, _, rows = model.forward(params, tokens, expert_rows=True)
                return model._logits(params, x[:, -1:]), rows
            return score

        def score(params, tokens):
            x, _ = model.forward(params, tokens)
            return model._logits(params, x[:, -1:])
        return score

    def _specs(self):
        sds = jax.ShapeDtypeStruct
        params_spec = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0)))
        return (params_spec, sds((self.batch_size, self.seq_len), jnp.int32))

    # ------------------------------------------------------------------
    # The freshen plan: ordered resources, §3.3 fr_state indices 0..3
    def build_plan(self, runtime: Runtime) -> FreshenPlan:
        entries = [
            PlanEntry("weights", Action.FETCH, self._load_weights,
                      version_fn=lambda: self.store.version(self.name)),
            PlanEntry("compiled", Action.FETCH, self._compile),
            PlanEntry("warmup", Action.WARM, self._warmup),
        ]
        if self.datastore is not None and self.prefetch_key is not None:
            entries.append(PlanEntry(
                "prefetch", Action.FETCH,
                lambda: self.datastore.get(self.prefetch_key)[0],
                ttl=self.prefetch_ttl,
                version_fn=lambda: self.datastore.version(self.prefetch_key)))
        return FreshenPlan(entries)

    def _load_weights(self):
        return self.store.load(self.name)

    def _compile(self):
        compiled, dt = self.executor.compile(
            f"{self.name}/score", self._score_fn(), self._specs())
        return compiled

    def _warmup(self):
        key = (self.name, self.batch_size, self.seq_len)
        if not self.warm_budget.allows(key):
            return 0.0
        compiled = self.executor.get(f"{self.name}/score", self._specs())
        if compiled is None:
            compiled = self._compile()
        return self.executor.warmup(compiled, self._specs())

    # ------------------------------------------------------------------
    # Decode sessions: the KV cache is a freshen-preallocatable resource
    # (the paper's buffer/CWND-warming analogue for serving state).
    def _decode_fns(self, max_len: int):
        model = self.model

        def prefill(params, tokens):
            return model.prefill(params, tokens, max_len=max_len)

        def decode(params, cache, token, pos):
            return model.decode_step(params, cache, token, pos)
        return prefill, decode

    def _compile_decode(self, max_len: int):
        sds = jax.ShapeDtypeStruct
        params_spec = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0)))
        prefill, decode = self._decode_fns(max_len)
        c_pre, _ = self.executor.compile(
            f"{self.name}/prefill{max_len}", prefill,
            (params_spec, sds((self.batch_size, self.seq_len), jnp.int32)))
        cache_spec = jax.eval_shape(
            lambda: self.model.init_cache(self.batch_size, max_len))
        c_dec, _ = self.executor.compile(
            f"{self.name}/decode{max_len}", decode,
            (params_spec, cache_spec,
             sds((self.batch_size, 1), jnp.int32),
             sds((self.batch_size,), jnp.int32)))
        return c_pre, c_dec

    def _prealloc_session(self, max_len: int):
        """Allocate (for real) the decode cache buffers ahead of time."""
        cache = self.model.init_cache(self.batch_size, max_len)
        return jax.block_until_ready(cache)

    def session_plan_entries(self, max_len: int):
        """Extra freshen resources for generation endpoints."""
        from repro.core.freshen import Action, PlanEntry
        return [
            PlanEntry("decode_executables", Action.FETCH,
                      lambda: self._compile_decode(max_len)),
            PlanEntry("session_cache", Action.FETCH,
                      lambda: self._prealloc_session(max_len)),
        ]

    def generate(self, ctx: RunContext, tokens, n_steps: int, max_len: int,
                 plan_offset: int):
        """Autoregressive generation using freshened executables + cache.
        ``plan_offset`` = fr_state index of 'decode_executables'."""
        params = ctx.fr_fetch(0)
        c_pre, c_dec = ctx.fr_fetch(plan_offset)
        cache0 = ctx.fr_fetch(plan_offset + 1)      # preallocated buffers
        logits, cache = c_pre(params, jnp.asarray(tokens, jnp.int32))
        del cache0                                   # donated lineage
        B, S = tokens.shape
        out = [int(jnp.argmax(logits[0, -1]))]
        pos = jnp.full((B,), S, jnp.int32)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for _ in range(n_steps - 1):
            logits, cache = c_dec(params, cache, tok, pos)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            pos = pos + 1
            out.append(int(tok[0, 0]))
        return out

    def code(self, ctx: RunContext, args):
        """The run-hook body (Algorithm 3: annotated λ)."""
        # fabriclint: allow[clock] -- measured run-phase timing is a wall-clock contract
        t0 = time.monotonic()
        tokens = jnp.asarray(args["tokens"], jnp.int32)
        assert tokens.shape == (self.batch_size, self.seq_len), tokens.shape
        params = ctx.fr_fetch(0)                  # FrFetch(0, DataGet(...))
        # fabriclint: allow[clock] -- measured run-phase timing is a wall-clock contract
        t_w = time.monotonic()
        compiled = ctx.fr_fetch(1)                # FrFetch(1, compile)
        # fabriclint: allow[clock] -- measured run-phase timing is a wall-clock contract
        t_c = time.monotonic()
        ctx.fr_warm(2)                            # FrWarm(2, warmup)
        # fabriclint: allow[clock] -- measured run-phase timing is a wall-clock contract
        t_u = time.monotonic()
        extra = ctx.fr_fetch(3) if len(ctx.runtime.fr_state.plan) > 3 else None
        logits = compiled(params, tokens)
        if self.counts_expert_rows:
            logits, rows = logits
        logits = jax.block_until_ready(logits)
        # fabriclint: allow[clock] -- measured run-phase timing is a wall-clock contract
        t1 = time.monotonic()
        if self.counts_expert_rows and (span := current_span()):
            span.annotate(expert_rows=np.asarray(rows).tolist())
        self.warm_budget.observe((self.name, self.batch_size, self.seq_len))
        timing = {"total": t1 - t0, "weights": t_w - t0,
                  "compile": t_c - t_w, "warmup": t_u - t_c,
                  "execute": t1 - t_u}
        self.timings.append(timing)
        return {"logits": np.asarray(logits), "timing": timing,
                "extra": extra}

    def spec(self) -> FunctionSpec:
        return FunctionSpec(self.name, self.code,
                            plan_factory=self.build_plan, app=self.app)


def batch_span(tracer, fn: str, app: str, fill: int,
               submitted: Optional[List[float]] = None):
    """The invocation span of one batch.  Given the rows' submit times it
    opens at the oldest, and its ``batch_wait`` phase runs from there to
    now, the dispatch, with each row's own wait in ``waits``."""
    if not submitted:
        return tracer.invocation(fn, app=app, batch=True, fill=fill)
    oldest = min(submitted)
    span = tracer.invocation(fn, app=app, start=oldest, batch=True,
                             fill=fill)
    wait = span.phase_from("batch_wait", oldest)
    if wait is not None:
        wait.annotate(waits=[wait.end - t for t in submitted])
    return span


class ServingEngine:
    """Model endpoints behind a FreshenScheduler — the 'serverless
    platform' of the evaluation.

    Each deployed endpoint is backed by an ``InstancePool``
    (repro.core.pool): concurrent requests admitted via ``submit`` fan out
    across warm instances, scale the pool up under queue pressure, and are
    prewarmed by predicted-successor freshen dispatch.  ``deploy`` eagerly
    initializes the primary instance (the seed-era warm container);
    additional instances cold-start on demand.

    The sharded fabric pins each shard to a device of its own, taken from
    ``devices`` (default: ``jax.local_devices()``): a shard's weights,
    executables and warm-ups land on that device.  A fabric wider than
    the device list is refused; a caller that means several shards to
    share hardware says so by listing a device more than once."""

    def __init__(self, scheduler=None, router_policy: str = "warmth-aware",
                 spill_timeout: Optional[float] = None,
                 tracer=None, devices: Optional[List[Any]] = None):
        from repro.core.scheduler import FreshenScheduler
        # one tracer for the whole engine: the base scheduler and (if a
        # fabric is built) every shard share it, so exported traces show
        # the full request path regardless of placement
        self.scheduler = scheduler or FreshenScheduler(tracer=tracer)
        if tracer is not None and not self.scheduler.tracer.enabled:
            self.scheduler.tracer = tracer
        self.tracer = self.scheduler.tracer
        self.endpoints: Dict[str, ModelEndpoint] = {}
        # pool-aware request batchers, one per endpoint deployed with
        # ``batch_size=`` — single requests admitted via submit_request
        # are formed into fabric-sized batches and run as ONE pooled
        # invocation each
        self.batchers: Dict[str, EndpointBatcher] = {}
        # the sharded fabric (repro.cluster), created lazily by the first
        # deploy(..., shards=N>1); single-scheduler deploys are untouched
        self.cluster = None
        self.router_policy = router_policy
        self.spill_timeout = spill_timeout
        self._clustered: set = set()          # endpoint names on the fabric
        # device slots the fabric's shards are pinned to (resolved at the
        # first sharded deploy); ``_slot_of`` maps shard id -> slot
        self._devices = None if devices is None else list(devices)
        self._slot_of: Dict[int, int] = {}

    def _default_pool_config(self):
        # model endpoints hold multi-second XLA compiles and weight
        # loads: a generic 30s keep-alive would reap them between
        # pipeline stages, so serving defaults to a long retention —
        # on top of the scheduler-wide pool policy, not replacing it
        import dataclasses
        return dataclasses.replace(self.scheduler.pool_config,
                                   keep_alive=600.0)

    def _device_slots(self) -> List[Any]:
        if self._devices is None:
            self._devices = list(jax.local_devices())
        return self._devices

    def _ensure_cluster(self, shards: int, elastic: bool = False):
        if self.cluster is None:
            from repro.cluster import ClusterRouter
            devices = self._device_slots()
            if shards > len(devices):
                raise ValueError(
                    f"{shards} shards need {shards} devices to pin, and "
                    f"this engine has {len(devices)}; pass devices= listing "
                    f"a device more than once to share it between shards")
            # the fabric shares the engine scheduler's predictor:
            # prediction (chains, periodicity) is global knowledge, so
            # chain() and trace priming keep working unchanged
            self.cluster = ClusterRouter.build(
                shards, policy=self.router_policy,
                pool_config=self.scheduler.pool_config,
                predictor=self.scheduler.predictor,
                devices=devices[:shards],
                spill_timeout=self.spill_timeout,
                tracer=self.tracer if self.tracer.enabled else None)
            self._slot_of = {k: k for k in range(shards)}
        elif shards > self.cluster.num_shards:
            if not elastic:
                raise ValueError(
                    f"cluster already built with {self.cluster.num_shards} "
                    f"shards; deploy the widest endpoint first (asked for "
                    f"{shards}) or pass elastic=True to grow the fleet")
            while self.cluster.num_shards < shards:
                self._add_shard()
        return self.cluster

    def _add_shard(self):
        """Grow the fabric by one shard, pinned to a device slot no live
        shard holds (a drained shard's slot is free again)."""
        live = {w.shard_id for w in self.cluster.workers}
        held = {slot for sid, slot in self._slot_of.items() if sid in live}
        free = [i for i in range(len(self._devices)) if i not in held]
        if not free:
            raise ValueError(f"no free device for another shard: all "
                             f"{len(self._devices)} devices hold one")
        worker = self.cluster.add_worker(devices=[self._devices[free[0]]])
        self._slot_of[worker.shard_id] = free[0]

    def scale_shards(self, n: int, drain: bool = True) -> int:
        """Resize the sharded fabric to ``n`` shards at runtime.

        Growing replays every *elastic* endpoint's registration onto the
        new shards (``ClusterRouter.add_worker``); fixed-width deploys
        (``elastic=False``) keep their width.  Shrinking drains the
        newest shards first — warm endpoints are prewarm-provisioned onto
        survivors and in-flight requests complete before each shard shuts
        down.  Builds the fabric on first use so ``scale_shards`` can
        precede the first sharded ``deploy``.  Returns the live shard
        count."""
        if n < 1:
            raise ValueError(f"a fabric needs at least one shard (got {n})")
        if self.cluster is None:
            if n == 1:
                return 1              # the base scheduler is the one shard
            self._ensure_cluster(n)
            return self.cluster.num_shards
        while self.cluster.num_shards < n:
            self._add_shard()
        while self.cluster.num_shards > n:
            victim = max(w.shard_id for w in self.cluster.workers)
            self.cluster.remove_worker(victim, drain=drain)
        return self.cluster.num_shards

    def deploy(self, ep: ModelEndpoint, pool_config=None,
               shards: Optional[int] = None,
               elastic: bool = False,
               graded_warmth: Optional[bool] = None,
               batch_size: Optional[int] = None,
               batch_max_wait: float = 0.01) -> Runtime:
        """Register an endpoint; with ``shards=N`` (N>1) it joins the
        sharded fabric: one ``InstancePool`` per shard behind the
        ``ClusterRouter`` (lazily built at the first sharded deploy),
        warmth-aware routing and cross-shard freshen included.  Only the
        shard-0 primary is eagerly initialized — the other shards warm up
        on demand or by prewarm, which is the point of the fabric.

        Endpoints run on the in-process thread backend: their instances
        share the process that holds the accelerator, which a child
        process could not reach.

        ``elastic=True`` makes the deploy fleet-elastic: asking for more
        shards than the fabric currently has grows it (instead of
        raising), and the endpoint registers cluster-wide — every shard
        the fleet ever grows to (``add_worker`` / ``scale_shards``)
        serves it too.  With ``shards`` omitted an elastic deploy joins
        the fabric at its current size (building a 1-shard fabric when
        none exists yet) rather than silently staying on the base
        scheduler.

        ``graded_warmth=True`` turns on the SPES-style warmth ladder for
        the endpoint's pools: keep-alive expiry demotes instances one
        warmth rung at a time (HOT -> INITIALIZED -> PROCESS) instead of
        reaping outright, and prewarm depth follows prediction
        confidence.  ``None`` (default) keeps the pool config's own
        setting.

        ``batch_size=N`` installs a pool-aware ``EndpointBatcher`` in
        front of the endpoint: single token rows admitted through
        ``submit_request`` leave at once when the endpoint has an idle
        instance (every queued row, up to N, spread over the free
        slots) or when N rows are queued; while the endpoint's instance
        runs one of its batches, rows gather behind it and leave
        together when it is released.  ``batch_max_wait`` bounds only a
        wait nothing else ends (the instance held by a prewarm or a
        direct ``submit``).  Each batch runs as ONE pooled invocation —
        one acquire/release, one span annotated with the fill count.
        Saturation backpressures the batcher instead of failing
        requests.  N is clamped to the endpoint's compiled batch shape
        (padding covers partial fills; slicing beyond it cannot)."""
        self.endpoints[ep.name] = ep
        if pool_config is None:
            pool_config = self._default_pool_config()
        if pool_config.backend != "thread":
            raise ValueError(
                f"endpoint {ep.name!r}: serving runs on the thread backend "
                f"(the process holding the accelerator), not "
                f"{pool_config.backend!r}")
        if graded_warmth is not None:
            import dataclasses
            pool_config = dataclasses.replace(pool_config,
                                              graded_warmth=graded_warmth)
        if elastic or (shards is not None and shards > 1):
            cluster = self._ensure_cluster(max(shards or 1, 1),
                                           elastic=elastic)
            # elastic churn leaves live shard ids non-contiguous (ids are
            # never reused), so a fixed-width deploy takes the N lowest
            # live ids, not range(N)
            runtimes = cluster.register(
                ep.spec(), config=pool_config,
                # None = cluster-wide: elastic endpoints follow the fleet
                shards=None if elastic else sorted(
                    w.shard_id for w in cluster.workers)[:shards])
            self._clustered.add(ep.name)
            rt = min(runtimes.items())[1]
        else:
            rt = self.scheduler.register(ep.spec(), config=pool_config)
        rt.init()
        if batch_size is not None:
            self._install_batcher(ep, batch_size, batch_max_wait)
        return rt

    # -- pool-aware batching --------------------------------------------
    def _idle_capacity(self, name: str) -> int:
        """The fabric signal the endpoint batcher dispatches on: free
        slots (idle instances plus cap headroom), summed across shards
        when the endpoint lives on the cluster."""
        if self.cluster is not None and name in self._clustered:
            return sum(w.idle_capacity(name) for w in self.cluster.workers)
        pool = self.scheduler.pools.get(name)
        return pool.idle_capacity() if pool is not None else 0

    def _install_batcher(self, ep: ModelEndpoint, batch_size: int,
                         max_wait: float):
        fill_cap = max(1, min(batch_size, ep.batch_size))

        def run_batch(payloads: List[Any],
                      submitted: Optional[List[float]] = None) -> Future:
            # one pooled invocation for the whole batch: pad the rows to
            # the endpoint's compiled shape, slice per-request logits
            # rows back out when it resolves
            fill = len(payloads)
            tokens = pad_batch([np.asarray(p, np.int32) for p in payloads],
                               ep.batch_size)
            target = self._target(ep.name)
            if target is self.scheduler:
                span = batch_span(self.tracer, ep.name, ep.app, fill,
                                  submitted)
                inner = self.scheduler.submit(ep.name, {"tokens": tokens},
                                              _span=span)
            else:                        # cluster routing opens its own span
                inner = target.submit(ep.name, {"tokens": tokens})
            out: Future = Future()

            def _done(f: Future):
                try:
                    res = f.result()
                    logits = res["logits"]
                    out.set_result([logits[i] for i in range(fill)])
                except BaseException as e:           # noqa: BLE001
                    out.set_exception(e)

            inner.add_done_callback(_done)
            return out

        self.batchers[ep.name] = EndpointBatcher(
            ep.name, run_batch, batch_size=fill_cap, max_wait=max_wait,
            capacity=lambda: self._idle_capacity(ep.name),
            submit_times=self.tracer.enabled)

    def submit_request(self, name: str, tokens_row) -> "Future":
        """Admit ONE request (a single token row of the endpoint's
        ``seq_len``) through the endpoint's pool-aware batcher; resolves
        to that request's logits row.  Requires the endpoint to have been
        deployed with ``batch_size=``."""
        batcher = self.batchers.get(name)
        if batcher is None:
            raise KeyError(
                f"endpoint {name!r} has no batcher: deploy it with "
                f"batch_size= to enable single-request admission")
        return batcher.submit(tokens_row)

    def _target(self, name: str):
        if self.cluster is not None and name in self._clustered:
            return self.cluster
        return self.scheduler

    def invoke(self, name: str, tokens, freshen_successors: bool = True):
        return self._target(name).invoke(
            name, {"tokens": tokens}, freshen_successors=freshen_successors)

    def submit(self, name: str, tokens, freshen_successors: bool = True):
        """Concurrent admission through the scheduler's router (or the
        cluster router for sharded endpoints); returns a Future for the
        endpoint result."""
        return self._target(name).submit(
            name, {"tokens": tokens}, freshen_successors=freshen_successors)

    def chain(self, names: List[str], delay: float = 0.06):
        self.scheduler.predictor.graph.add_chain(names, delay=delay)

    def adopt_trace_policy(self, policy, time_scale: float = 1.0
                           ) -> Dict[str, object]:
        """Apply a trace-learned ``repro.workloads.HistoryPolicy`` to the
        deployed endpoints: each pool whose endpoint name appears in the
        policy's history is live-reconfigured (keep-alive from the observed
        idle-time distribution, max_instances from Little's law), and the
        policy's inter-arrival histograms seed recurrence prediction so
        periodic endpoints self-prewarm.  Each pool's *measured* cold
        start is passed through as the keep-alive floor, so a pool on a
        measured backend (subprocess spawn, snapshot restore) is never
        retuned to reap faster than it can boot.  Returns
        ``{name: PoolConfig}`` for the pools that were retuned."""
        applied = {}
        schedulers = [self.scheduler]
        if self.cluster is not None:
            schedulers += [w.scheduler for w in self.cluster.workers]
        for name in policy.functions:
            for sched in schedulers:
                pool = sched.pools.get(name)
                if pool is None:
                    continue
                cfg = policy.pool_config(
                    name, base=pool.config, time_scale=time_scale,
                    measured_cold_start=pool.measured_cold_start())
                sched.apply_pool_config(name, cfg)
                applied[name] = cfg
        # one prime covers everything: cluster workers share this predictor
        policy.prime(self.scheduler.predictor, time_scale=time_scale)
        return applied

    def latency_summary(self, app: str) -> dict:
        """Merged latency view across the base scheduler and every cluster
        shard (raw-sample merge — percentiles do not compose).  Shards
        drained by an elastic shrink keep counting: their retained
        ledgers are merged in, so the view never loses history."""
        from repro.cluster import ClusterAccountant
        accts = [self.scheduler.accountant]
        if self.cluster is not None:
            accts += [w.scheduler.accountant for w in self.cluster.workers]
            accts += list(self.cluster.accountant.retired)
        return ClusterAccountant(accts).latency_summary(app)

    def close(self, wait: bool = True):
        """Shut the scheduler's router down (idempotent); demos and tests
        should call this in a finally block so worker threads never leak.
        Batchers close first: their drains dispatch through the
        scheduler, which must still be alive to run them."""
        for batcher in self.batchers.values():
            batcher.close()
        self.scheduler.shutdown(wait=wait)
        if self.cluster is not None:
            self.cluster.shutdown(wait=wait)

    def platform_stats(self) -> Dict[str, dict]:
        stats = dict(self.scheduler.platform_stats())
        if self.cluster is not None:
            stats.update(self.cluster.platform_stats())
        return stats

    def metrics_snapshot(self) -> Dict[str, object]:
        """Unified typed-metrics dump: the base scheduler's registry plus
        (when a fabric exists) every shard's, under ``cluster.``."""
        out = dict(self.scheduler.metrics_snapshot())
        if self.cluster is not None:
            for key, val in self.cluster.metrics_snapshot().items():
                out[f"cluster.{key}"] = val
        for batcher in self.batchers.values():
            out.update(batcher.metrics_snapshot())
        return out
