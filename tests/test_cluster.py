"""repro.cluster: routing policies, cross-shard freshen placement, queue
rebalancing, cluster-wide accounting, the adaptation daemon, and the
ServingEngine/TraceReplayer wiring.  Timing constants are chosen so every
test settles in well under a second of wall time."""
import threading
import time

import pytest

from repro.cluster import (ClusterAccountant, ClusterRouter, ClusterWorker,
                           StickyPolicy, make_policy, partition_devices)
from repro.core import (Accountant, FunctionSpec, PoolConfig, PoolSaturated,
                        Prediction, ServiceClass)
from repro.core.freshen import Action, FreshenPlan, PlanEntry
from repro.core.pool import InstancePool
from repro.workloads import AdaptDaemon, HistoryPolicy, Trace, TraceReplayer

APP = "clustertest"


def make_spec(name, fetch_cost=0.0, compute=0.0, app=APP):
    def make_plan(rt):
        def fetch():
            if fetch_cost:
                time.sleep(fetch_cost)
            return {"resource": name}
        return FreshenPlan([PlanEntry("data", Action.FETCH, fetch)])

    def code(ctx, args):
        data = ctx.fr_fetch(0)
        if compute:
            time.sleep(compute)
        return data["resource"]

    return FunctionSpec(name, code, plan_factory=make_plan, app=app)


def build_cluster(shards, policy, *, cross_freshen=True, spill_timeout=None,
                  **pool_kw):
    cfg = PoolConfig(**pool_kw)
    cluster = ClusterRouter.build(shards, policy=policy, pool_config=cfg,
                                  spill_timeout=spill_timeout,
                                  cross_freshen=cross_freshen)
    for w in cluster.workers:
        w.scheduler.accountant.service_class[APP] = \
            ServiceClass.LATENCY_SENSITIVE
        w.scheduler.accountant.disable_after = 10 ** 9
    return cluster


# ---------------------------------------------------------------------------
# routing policies
def test_warmth_aware_beats_least_loaded_on_periodic_trace():
    """The acceptance dynamic at test scale: keep-alive between one and
    two periods, so same-shard reuse is warm and any routing bounce is
    cold.  Warmth-aware + cross-shard freshen concentrates arrivals on
    the warmth the router itself placed; least-loaded + shard-local
    freshen scatters them cold."""
    # three functions: an odd count, so least-loaded's round-robin tie
    # spreading cannot phase-lock into accidental per-function affinity
    trace = Trace.merge([Trace.periodic(f"f{i}", period=1.0, invocations=8,
                                        phase=i * 0.3) for i in range(3)])
    scale = 0.1                    # 100 ms wall period

    def drive(policy, cross):
        cluster = build_cluster(2, policy, cross_freshen=cross,
                                max_instances=4, keep_alive=0.125,
                                cold_start_cost=0.005,
                                prewarm_provision=True)
        for fn in trace.functions:
            cluster.register(make_spec(fn, fetch_cost=0.008, compute=0.001))
        HistoryPolicy().fit(trace).prime(cluster.predictor, time_scale=scale)
        report = TraceReplayer(cluster, trace, time_scale=scale).run(
            freshen=True)
        summary = cluster.accountant.latency_summary(APP)
        cluster.shutdown()
        assert report.errors == 0
        return summary, report

    # wall-clock dependent: the warm/cold contrast assumes arrivals fire
    # near their scheduled times.  On a loaded machine the open-loop
    # replay lags and arrivals bunch inside one keep-alive window, which
    # voids the premise — retry on measured lag, not on the outcome.
    for attempt in range(3):
        warm, warm_rep = drive("warmth-aware", cross=True)
        cold, cold_rep = drive("least-loaded", cross=False)
        if max(warm_rep.lag_p95, cold_rep.lag_p95) < 0.3 * scale:
            break
    assert warm["count"] == cold["count"] == 24
    # least-loaded spreads ties round-robin: most returns outlive the
    # keep-alive; warmth-aware should cold-start little beyond warmup
    assert warm["cold_starts"] < cold["cold_starts"]
    assert warm["cold_start_rate"] <= 0.5 < cold["cold_start_rate"]


def test_sticky_routing_is_deterministic():
    cluster = build_cluster(4, "sticky")
    fns = [f"fn-{i}" for i in range(40)]
    for fn in fns:
        cluster.register(make_spec(fn))
    first = {fn: cluster.route(fn) for fn in fns}
    # stable across repeated calls and across a fresh policy instance
    assert first == {fn: cluster.route(fn) for fn in fns}
    cluster.policy = StickyPolicy()
    assert first == {fn: cluster.route(fn) for fn in fns}
    # and actually spreads: a 40-function population hits several shards
    assert len(set(first.values())) >= 3
    cluster.shutdown()


def test_sticky_remaps_only_a_fraction_under_shard_count_change():
    """Consistent hashing's point: growing N -> N+1 shards moves only the
    functions whose ring segment the new shard captures, not everything
    (modulo hashing would remap ~N/(N+1) of them)."""

    class _W:  # the policy only reads .shard_id
        def __init__(self, shard_id):
            self.shard_id = shard_id

    policy = StickyPolicy()
    fns = [f"endpoint-{i}" for i in range(300)]
    four = {fn: policy.select(fn, [_W(k) for k in range(4)]) for fn in fns}
    five = {fn: policy.select(fn, [_W(k) for k in range(5)]) for fn in fns}
    moved = sum(four[fn] != five[fn] for fn in fns)
    assert 0 < moved < len(fns) * 0.45        # ~1/5 expected, bound loosely
    # keys that moved all moved TO the new shard
    assert all(five[fn] == 4 for fn in fns if four[fn] != five[fn])


def test_make_policy_rejects_unknown():
    with pytest.raises(ValueError, match="unknown routing policy"):
        make_policy("random")


# ---------------------------------------------------------------------------
# cross-shard freshen placement
def test_cross_shard_freshen_lands_on_routed_shard():
    cluster = build_cluster(2, "warmth-aware", max_instances=2,
                            keep_alive=60.0, prewarm_provision=True)
    cluster.register(make_spec("fn"))
    # warm shard 1 only: the router must now route fn's arrivals there
    w1 = cluster.worker(1)
    for th in w1.prewarm("fn", provision=True):
        th.join()
    assert w1.warm_idle("fn") == 1
    assert cluster.route("fn") == 1
    before = w1.pool("fn").stats()["prewarm_dispatches"]
    # a prediction fires on shard 0; the router must place it on shard 1
    sched0 = cluster.worker(0).scheduler
    sched0._dispatch_freshen(Prediction("fn", probability=1.0,
                                        expected_delay=0.05))
    assert cluster.stats()["cross_freshens"] == 1
    assert w1.pool("fn").stats()["prewarm_dispatches"] == before + 1
    assert cluster.worker(0).pool("fn").stats()["prewarm_dispatches"] == 0
    assert sched0.events[-1].reason == "routed-cross-shard"
    # and the shard the freshen landed on is the shard an arrival routes to
    assert cluster.route("fn") == 1
    cluster.shutdown()


def test_local_freshen_when_target_is_origin():
    cluster = build_cluster(2, "warmth-aware", max_instances=2,
                            keep_alive=60.0, prewarm_provision=True)
    cluster.register(make_spec("fn"))
    w0 = cluster.worker(0)
    for th in w0.prewarm("fn", provision=True):
        th.join()
    dispatched_before = w0.pool("fn").stats()["prewarm_dispatches"]
    w0.scheduler._dispatch_freshen(Prediction("fn", 1.0, 0.05))
    stats = cluster.stats()
    assert stats["cross_freshens"] == 0 and stats["local_freshens"] == 1
    assert w0.pool("fn").stats()["prewarm_dispatches"] == \
        dispatched_before + 1
    cluster.shutdown()


def test_gated_cross_freshen_not_counted_as_dispatched():
    """The target shard's accounting gate can still drop a routed
    prewarm; that must not count as a cross-shard freshen or log a
    dispatched event on the origin."""
    cluster = build_cluster(2, "warmth-aware", max_instances=2,
                            keep_alive=60.0, prewarm_provision=True)
    cluster.register(make_spec("fn"))
    w1 = cluster.worker(1)
    for th in w1.prewarm("fn", provision=True):
        th.join()
    # BATCH service class on the target: should_freshen always False
    w1.scheduler.accountant.service_class[APP] = ServiceClass.BATCH
    before = w1.pool("fn").stats()["prewarm_dispatches"]
    sched0 = cluster.worker(0).scheduler
    sched0._dispatch_freshen(Prediction("fn", 1.0, 0.05))
    assert cluster.stats()["cross_freshens"] == 0
    assert w1.pool("fn").stats()["prewarm_dispatches"] == before
    event = sched0.events[-1]
    assert event.reason == "routed-cross-shard-gated" and not event.dispatched
    cluster.shutdown()


def test_cross_freshen_disabled_stays_local():
    cluster = build_cluster(2, "warmth-aware", cross_freshen=False,
                            max_instances=2, keep_alive=60.0,
                            prewarm_provision=True)
    cluster.register(make_spec("fn"))
    w1 = cluster.worker(1)
    for th in w1.prewarm("fn", provision=True):
        th.join()
    w0 = cluster.worker(0)
    w0.scheduler._dispatch_freshen(Prediction("fn", 1.0, 0.05))
    assert cluster.stats()["cross_freshens"] == 0
    # dispatched locally (provisioned an instance on shard 0) instead
    assert w0.pool("fn").stats()["prewarm_dispatches"] == 1
    cluster.shutdown()


# ---------------------------------------------------------------------------
# saturation + rebalancing
def test_pool_saturated_carries_context():
    pool = InstancePool(make_spec("busy"), PoolConfig(max_instances=1))
    pool.shard = 3
    inst, _, _ = pool.acquire()
    with pytest.raises(PoolSaturated) as exc_info:
        pool.acquire(timeout=0.01)
    err = exc_info.value
    assert err.fn == "busy" and err.shard == 3
    assert err.pool_size == 1 and err.max_instances == 1
    assert err.queue_depth >= 1
    assert "busy" in str(err) and "shard 3" in str(err)
    pool.release(inst)


def test_scheduler_submit_surfaces_saturation_context():
    cluster = build_cluster(2, "sticky", max_instances=1, keep_alive=60.0)
    cluster.register(make_spec("slow", compute=0.2))
    shard = cluster.route("slow")
    worker = cluster.worker(shard)
    blocker = worker.submit("slow")
    time.sleep(0.03)                       # let the blocker claim the pool
    fut = worker.scheduler.submit("slow", acquire_timeout=0.02)
    err = fut.exception(timeout=5.0)
    assert isinstance(err, PoolSaturated)
    assert err.fn == "slow" and err.shard == shard
    blocker.result(timeout=5.0)
    cluster.shutdown()


def test_spill_drains_saturated_shard_to_neighbor():
    """Sticky pins every arrival of one function to a single shard; with
    max_instances=1 and a slow body, queued work must spill to the
    neighbor instead of timing out — the queue-draining half of
    rebalancing."""
    cluster = build_cluster(2, "sticky", spill_timeout=0.03,
                            max_instances=1, keep_alive=60.0)
    cluster.register(make_spec("slow", compute=0.08))
    hot = cluster.route("slow")
    cold = 1 - hot
    futures = [cluster.submit("slow") for _ in range(4)]
    assert [f.result(timeout=10.0) for f in futures] == ["slow"] * 4
    stats = cluster.stats()
    assert stats["spills"] >= 1
    assert stats["saturations"][hot] >= 1
    # spilled work really ran on the neighbor
    neighbor = cluster.worker(cold).pool("slow").stats()
    assert neighbor["cold_starts"] + neighbor["warm_acquires"] >= 1
    cluster.shutdown()


def test_rebalance_pushes_warmth_to_idle_neighbor():
    cluster = build_cluster(2, "sticky", max_instances=1, keep_alive=60.0,
                            prewarm_provision=True)
    cluster.register(make_spec("slow", compute=0.15))
    hot = cluster.route("slow")
    cold = 1 - hot
    blocker = cluster.submit("slow")
    waiter = threading.Thread(
        target=lambda: cluster.worker(hot).invoke("slow"), daemon=True)
    waiter.start()
    deadline = time.monotonic() + 2.0
    while (cluster.worker(hot).queue_depth("slow") == 0
           and time.monotonic() < deadline):
        time.sleep(0.005)                  # wait for the queued acquire
    actions = cluster.rebalance()
    assert ("slow", hot, cold) in actions
    # the neighbor's (registration-eager, still-cold) instance received
    # the prewarm and becomes a warm target for future arrivals
    assert cluster.worker(cold).pool("slow").stats()[
        "prewarm_dispatches"] >= 1
    deadline = time.monotonic() + 2.0
    while (cluster.worker(cold).warm_idle("slow") == 0
           and time.monotonic() < deadline):
        time.sleep(0.005)
    assert cluster.worker(cold).warm_idle("slow") == 1
    blocker.result(timeout=5.0)
    waiter.join(timeout=5.0)
    cluster.shutdown()


# ---------------------------------------------------------------------------
# cluster-wide accounting
def test_cluster_accountant_merges_raw_samples():
    a, b = Accountant(), Accountant()
    for ms in (1, 2, 3, 4):
        a.record_invocation(APP, "f", ms / 1000.0, queue_delay=0.001)
    for ms in (100, 200):
        b.record_invocation(APP, "f", ms / 1000.0, cold_start=True)
    merged = ClusterAccountant([a, b]).latency_summary(APP)
    assert merged["count"] == 6
    assert merged["cold_starts"] == 2
    assert merged["cold_start_rate"] == pytest.approx(2 / 6)
    # the cluster p95 reflects shard b's tail, which a's summary never saw
    assert merged["p95"] > a.latency_summary(APP)["p95"]
    assert merged["max"] == pytest.approx(0.2, abs=1e-3)
    per_shard = ClusterAccountant([a, b]).per_shard(APP)
    assert [s["count"] for s in per_shard] == [4, 2]
    bill = ClusterAccountant([a, b]).bill(APP)
    assert bill.function_invocations == 6 and bill.cold_starts == 2


# ---------------------------------------------------------------------------
# online adaptation daemon
def test_adapt_daemon_widens_cold_pools_per_shard():
    cluster = build_cluster(2, "sticky", max_instances=1, keep_alive=0.05,
                            cold_start_cost=0.0)
    cluster.register(make_spec("fn"))
    hot = cluster.route("fn")
    acct = cluster.worker(hot).scheduler.accountant
    for _ in range(30):                    # cold-heavy ledger on one shard
        acct.record_invocation(APP, "fn", 0.01, cold_start=True)
    policy = HistoryPolicy(min_adapt_samples=10, target_cold_start_rate=0.05)
    daemon = AdaptDaemon([w.scheduler for w in cluster.workers], policy,
                         interval=30.0)
    applied = daemon.step()
    # only the shard whose ledger shows cold starts is widened
    assert (hot, "fn") in applied
    assert (1 - hot, "fn") not in applied
    pool = cluster.worker(hot).pool("fn")
    assert pool.config.keep_alive == pytest.approx(0.1)
    assert pool.config.max_instances == 2
    assert cluster.worker(1 - hot).pool("fn").config.keep_alive == \
        pytest.approx(0.05)
    assert daemon.passes == 1 and daemon.adaptations == 1
    cluster.shutdown()


def test_adapt_daemon_thread_lifecycle():
    sched_cluster = build_cluster(1, "least-loaded")
    sched = sched_cluster.workers[0].scheduler
    with AdaptDaemon(sched, HistoryPolicy(), interval=0.01) as daemon:
        assert daemon.running
        deadline = time.monotonic() + 2.0
        while daemon.passes == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert daemon.passes >= 1
    assert not daemon.running
    sched_cluster.shutdown()


# ---------------------------------------------------------------------------
# trace replay + worker plumbing
def test_trace_replay_into_cluster_with_oracle():
    trace = Trace.periodic("tick", period=0.5, invocations=6)
    cluster = build_cluster(2, "warmth-aware", max_instances=2,
                            keep_alive=60.0, prewarm_provision=True)
    cluster.register(make_spec("tick"))
    replayer = TraceReplayer(cluster, trace, time_scale=0.05,
                             oracle_lead=0.2)
    report = replayer.run(freshen=False)
    assert report.errors == 0 and report.skipped == 0
    assert report.requests == 6 and report.prewarms == 6
    summary = cluster.accountant.latency_summary(APP)
    assert summary["count"] == 6
    cluster.shutdown()


def test_register_on_shard_subset():
    cluster = build_cluster(3, "least-loaded")
    runtimes = cluster.register(make_spec("edge"), shards=[1, 2])
    assert sorted(runtimes) == [1, 2]
    assert not cluster.worker(0).has_function("edge")
    assert cluster.route("edge") in (1, 2)
    with pytest.raises(KeyError):
        cluster.route("nowhere")
    cluster.shutdown()


def test_explicit_register_config_not_shared_across_shards():
    """Pools own their config object (reconfigure mutates in place), so
    registering one explicit PoolConfig on N shards must hand each pool
    its own copy — retuning shard 0 cannot leak into shard 1."""
    cluster = build_cluster(2, "least-loaded")
    shared = PoolConfig(max_instances=2, keep_alive=1.0)
    cluster.register(make_spec("fn"), config=shared)
    p0, p1 = (cluster.worker(k).pool("fn") for k in (0, 1))
    assert p0.config is not p1.config and p0.config is not shared
    cluster.worker(0).scheduler.apply_pool_config(
        "fn", PoolConfig(max_instances=8, keep_alive=9.0))
    assert p1.config.keep_alive == 1.0 and p1.config.max_instances == 2
    assert shared.keep_alive == 1.0
    cluster.shutdown()


def test_partition_devices_round_robin():
    assert partition_devices(None, 3) == [None, None, None]
    assert partition_devices(list("abcde"), 2) == [["a", "c", "e"],
                                                   ["b", "d"]]
    with pytest.raises(ValueError, match="4 shards need at least 4"):
        partition_devices(list("ab"), 4)


def test_worker_shard_tags_and_signals():
    worker = ClusterWorker(7, pool_config=PoolConfig(max_instances=2))
    worker.register(make_spec("fn"))
    assert worker.pool("fn").shard == 7
    assert worker.load() == 0 and worker.queue_depth() == 0
    assert worker.warm_idle("fn") == 0      # adopted instance is cold
    worker.invoke("fn")
    assert worker.warm_idle("fn") == 1      # warmed by the invocation
    assert worker.idle_capacity("fn") == 2  # 1 idle + 1 headroom
    worker.shutdown()


# ---------------------------------------------------------------------------
# ServingEngine wiring
class _StubEndpoint:
    """Duck-typed endpoint: ServingEngine only needs .name and .spec()."""

    def __init__(self, name):
        self.name = name

    def spec(self):
        return make_spec(self.name, app="serving-cluster")


def test_engine_deploy_shards_routes_through_cluster():
    import jax
    from repro.serving.engine import ServingEngine
    # two shards sharing the one CPU device, listed twice on purpose
    eng = ServingEngine(devices=jax.local_devices()[:1] * 2)
    try:
        eng.deploy(_StubEndpoint("sharded"), pool_config=PoolConfig(
            max_instances=2, keep_alive=60.0), shards=2)
        assert eng.cluster is not None and eng.cluster.num_shards == 2
        # cluster workers share the engine predictor: chain() keeps working
        assert eng.cluster.predictor is eng.scheduler.predictor
        out = eng.submit("sharded", tokens=None).result(timeout=5.0)
        assert out == "sharded"
        summary = eng.latency_summary("serving-cluster")
        assert summary["count"] == 1
        stats = eng.platform_stats()
        assert "shard0/sharded" in stats and "shard1/sharded" in stats
        with pytest.raises(ValueError, match="widest endpoint first"):
            eng.deploy(_StubEndpoint("wider"), shards=4)
    finally:
        eng.close()


def test_engine_refuses_fabric_wider_than_its_devices():
    import jax
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(devices=jax.local_devices()[:1])
    try:
        with pytest.raises(ValueError, match="2 shards need 2 devices"):
            eng.deploy(_StubEndpoint("too-wide"), shards=2)
        assert eng.cluster is None
    finally:
        eng.close()


def test_pinned_shard_runs_every_hook_on_its_device():
    """A pinned shard's init, freshen and run hooks all see the shard's
    device as the jax default — not only the function body."""
    import jax
    dev = jax.local_devices()[0]
    seen = {}

    def init_fn(rt):
        seen["init"] = jax.config.jax_default_device

    def plan(rt):
        return FreshenPlan([PlanEntry(
            "probe", Action.FETCH,
            lambda: seen.setdefault("freshen", jax.config.jax_default_device))])

    def code(ctx, args):
        seen["run"] = jax.config.jax_default_device
        return ctx.fr_fetch(0)

    w = ClusterWorker(0, devices=[dev])
    try:
        rt = w.register(FunctionSpec("pinned", code, plan_factory=plan,
                                     init_fn=init_fn))
        rt.freshen(blocking=True)
        assert rt.run({}) == dev
        assert seen == {"init": dev, "freshen": dev, "run": dev}
    finally:
        w.shutdown()
