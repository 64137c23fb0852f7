"""Elastic cluster fabric: runtime add/remove-worker with warm-state
draining, sticky reshard invariants, bounded ring caching, fleet-level
AdaptDaemon scaling, retained accounting, and the ServingEngine /
TraceReplayer elastic wiring.  Timing constants keep every test well
under a second of wall time."""
import threading
import time

import pytest

from repro.cluster import ClusterRouter, ClusterWorker, StickyPolicy
from repro.core import FunctionSpec, PoolConfig, ServiceClass
from repro.core.freshen import Action, FreshenPlan, PlanEntry
from repro.workloads import (AdaptDaemon, FleetPolicy, HistoryPolicy, Trace,
                             TraceReplayer)

APP = "elastictest"


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


def build_cluster(shards, policy="least-loaded", *, cross_freshen=True,
                  spill_timeout=None, **pool_kw):
    cfg = PoolConfig(**pool_kw)
    cluster = ClusterRouter.build(shards, policy=policy, pool_config=cfg,
                                  spill_timeout=spill_timeout,
                                  cross_freshen=cross_freshen)

    def make_accountant():
        from repro.core import Accountant
        acct = Accountant()
        acct.service_class[APP] = ServiceClass.LATENCY_SENSITIVE
        acct.disable_after = 10 ** 9
        return acct

    cluster.accountant_factory = make_accountant
    for w in cluster.workers:
        w.scheduler.accountant.service_class[APP] = \
            ServiceClass.LATENCY_SENSITIVE
        w.scheduler.accountant.disable_after = 10 ** 9
    return cluster


# ---------------------------------------------------------------------------
# add_worker
def test_add_worker_replays_registrations_and_routes():
    cluster = build_cluster(1, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    added = cluster.add_worker()
    assert cluster.num_shards == 2
    assert added.shard_id == 1                 # fresh id, monotone
    assert added.has_function("fn")            # registration replayed
    # the new shard shares the cluster predictor and is routable
    assert added.scheduler.predictor is cluster.predictor
    assert cluster.route("fn") in (0, 1)
    # and actually serves traffic
    futures = [cluster.submit("fn") for _ in range(4)]
    assert [f.result(timeout=5.0) for f in futures] == ["fn"] * 4
    stats = cluster.stats()
    assert stats["num_shards"] == 2 and stats["added"] == 1
    cluster.shutdown()


def test_add_worker_skips_shard_subset_registrations():
    cluster = build_cluster(2)
    cluster.register(make_spec("everywhere"))
    cluster.register(make_spec("edge"), shards=[1])
    added = cluster.add_worker()
    assert added.has_function("everywhere")
    assert not added.has_function("edge")      # subset stays on its subset
    cluster.shutdown()


def test_add_worker_never_reuses_departed_ids():
    cluster = build_cluster(2, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    cluster.remove_worker(1, drain=True)
    added = cluster.add_worker()
    assert added.shard_id == 2                 # not 1: ids never recycle
    assert sorted(w.shard_id for w in cluster.workers) == [0, 2]
    with pytest.raises(ValueError, match="never reused"):
        cluster.add_worker(ClusterWorker(1))
    cluster.shutdown()


def test_add_worker_accountant_joins_cluster_summary():
    cluster = build_cluster(1, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    added = cluster.add_worker()
    added.invoke("fn")
    summary = cluster.accountant.latency_summary(APP)
    assert summary["count"] == 1
    assert len(cluster.accountant.per_shard(APP)) == 2
    cluster.shutdown()


# ---------------------------------------------------------------------------
# remove_worker + drain
def test_remove_worker_drain_loses_no_inflight_and_hands_off_warmth():
    """The acceptance-criterion drain: sticky pins every arrival of one
    function to a single shard; queue several slow invocations there,
    then remove that shard with drain — every future must complete and
    the survivor must hold warmth for the function afterwards."""
    cluster = build_cluster(2, "sticky", max_instances=1, keep_alive=60.0,
                            prewarm_provision=True)
    cluster.register(make_spec("slow", compute=0.05))
    hot = cluster.route("slow")
    survivor = 1 - hot
    futures = [cluster.submit("slow") for _ in range(4)]
    deadline = time.monotonic() + 2.0
    while (cluster.worker(hot).load() < 2 and time.monotonic() < deadline):
        time.sleep(0.002)                      # let work queue on the shard
    report = cluster.remove_worker(hot, drain=True)
    # zero dropped invocations: every admitted future resolves
    assert [f.result(timeout=5.0) for f in futures] == ["slow"] * 4
    assert report.shard == hot and report.drained
    assert report.inflight_at_removal >= 1
    # warmth reappeared on the survivor via prewarm-provision handoff
    assert ("slow", survivor) in report.handoffs
    w = cluster.worker(survivor)
    deadline = time.monotonic() + 2.0
    while w.warm_idle("slow") == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert w.warm_idle("slow") >= 1
    # the departed shard is gone from routing; the survivor serves
    assert cluster.route("slow") == survivor
    assert cluster.invoke("slow") == "slow"
    with pytest.raises(KeyError):
        cluster.worker(hot)
    cluster.shutdown()


def test_removed_worker_rejects_direct_submits():
    cluster = build_cluster(2, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    worker = cluster.worker(1)
    cluster.remove_worker(1, drain=True)
    with pytest.raises(RuntimeError, match="draining"):
        worker.submit("fn")
    with pytest.raises(RuntimeError, match="draining"):
        worker.invoke("fn")
    cluster.shutdown()


def test_remove_last_worker_raises():
    cluster = build_cluster(1)
    with pytest.raises(ValueError, match="last shard"):
        cluster.remove_worker(0)
    with pytest.raises(KeyError):
        cluster.remove_worker(99)
    cluster.shutdown()


def test_departed_shard_history_retained_in_summaries():
    cluster = build_cluster(2, "sticky", max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    hot = cluster.route("fn")
    for _ in range(3):
        cluster.invoke("fn")
    before = cluster.accountant.latency_summary(APP)
    assert before["count"] == 3
    bill_before = cluster.accountant.bill(APP)
    cluster.remove_worker(hot, drain=True)
    # merged views keep the departed shard's samples and bill
    after = cluster.accountant.latency_summary(APP)
    assert after["count"] == 3
    assert after["p95"] == pytest.approx(before["p95"])
    bill_after = cluster.accountant.bill(APP)
    assert bill_after.function_invocations == bill_before.function_invocations
    assert bill_after.function_seconds == \
        pytest.approx(bill_before.function_seconds)
    # live-only decomposition no longer shows it
    assert len(cluster.accountant.per_shard(APP)) == 1
    cluster.shutdown()


def test_remove_worker_undrained_still_closes_idle_instances():
    """drain=False cuts the shard loose without waiting, but idle
    instances must still be closed — an undrained removal on the
    subprocess backend must not leak worker processes."""
    cluster = build_cluster(2, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    worker = cluster.worker(1)
    worker.invoke("fn")                        # a live, warm, idle instance
    assert sum(p.size() for p in worker.scheduler.pools.values()) >= 1
    cluster.remove_worker(1, drain=False)
    assert sum(p.size() for p in worker.scheduler.pools.values()) == 0
    cluster.shutdown()


def test_remove_worker_undrained_closes_busy_instance_on_release():
    """An instance busy at undrained removal must close when its
    invocation finishes — not park in an idle list nobody will reap."""
    cluster = build_cluster(2, max_instances=1, keep_alive=60.0)
    cluster.register(make_spec("slow", compute=0.08))
    worker = cluster.worker(1)
    fut = worker.submit("slow")
    deadline = time.monotonic() + 2.0
    while worker.load() == 0 and time.monotonic() < deadline:
        time.sleep(0.002)                      # wait for the body to start
    cluster.remove_worker(1, drain=False)
    assert fut.result(timeout=5.0) == "slow"   # in-flight work completes
    pool = worker.pool("slow")
    deadline = time.monotonic() + 2.0
    while pool.size() > 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pool.size() == 0 and pool.idle_count() == 0
    cluster.shutdown()


def test_submit_after_shutdown_raises():
    cluster = build_cluster(2)
    cluster.register(make_spec("fn"))
    cluster.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        cluster.submit("fn")
    with pytest.raises(RuntimeError, match="shut down"):
        cluster.route("fn")
    with pytest.raises(RuntimeError, match="shut down"):
        cluster.add_worker()
    cluster.shutdown()                         # idempotent


# ---------------------------------------------------------------------------
# sticky reshard invariants + ring cache bound
class _W:  # the policy only reads .shard_id
    def __init__(self, shard_id):
        self.shard_id = shard_id


def test_sticky_add_shard_remaps_bounded_fraction():
    policy = StickyPolicy()
    fns = [f"endpoint-{i}" for i in range(300)]
    four = {fn: policy.select(fn, [_W(k) for k in range(4)]) for fn in fns}
    five = {fn: policy.select(fn, [_W(k) for k in range(5)]) for fn in fns}
    moved = sum(four[fn] != five[fn] for fn in fns)
    assert 0 < moved < len(fns) * 0.45         # ~1/5 expected, bound loosely
    assert all(five[fn] == 4 for fn in fns if four[fn] != five[fn])


def test_sticky_remove_shard_remaps_only_departed_keys():
    policy = StickyPolicy()
    fns = [f"endpoint-{i}" for i in range(300)]
    ids = [0, 1, 2, 3]
    before = {fn: policy.select(fn, [_W(k) for k in ids]) for fn in fns}
    after = {fn: policy.select(fn, [_W(k) for k in (0, 1, 3)]) for fn in fns}
    for fn in fns:
        if before[fn] != 2:
            # survivors' keys never move
            assert after[fn] == before[fn]
        else:
            assert after[fn] in (0, 1, 3)
    assert any(before[fn] == 2 for fn in fns)  # the test saw real remaps


def test_sticky_ring_cache_bounded_under_elastic_churn():
    policy = StickyPolicy(max_rings=4)
    for i in range(32):                        # 32 distinct memberships
        policy.select("fn", [_W(k) for k in range(i + 1)])
    assert len(policy._rings) <= 4
    # and through a real router's add/remove cycles
    cluster = build_cluster(2, "sticky", max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    for _ in range(12):
        added = cluster.add_worker()
        cluster.route("fn")
        cluster.remove_worker(added.shard_id, drain=False)
        cluster.route("fn")
    assert len(cluster.policy._rings) <= cluster.policy.max_rings
    cluster.shutdown()


# ---------------------------------------------------------------------------
# AdaptDaemon: fleet scaling rules
def test_daemon_scales_out_on_aggregate_queue_depth():
    cluster = build_cluster(1, max_instances=1, keep_alive=60.0)
    cluster.register(make_spec("slow", compute=0.2))
    daemon = AdaptDaemon(cluster=cluster, interval=30.0,
                         fleet=FleetPolicy(scale_out_queue_depth=2,
                                           max_shards=2),
                         adapt_pools=False)
    futures = [cluster.submit("slow") for _ in range(3)]
    deadline = time.monotonic() + 2.0
    while (cluster.worker(0).queue_depth() < 2
           and time.monotonic() < deadline):
        time.sleep(0.002)
    daemon.step()
    assert cluster.num_shards == 2 and daemon.scale_outs == 1
    assert daemon.fleet_actions[-1][1] == "add"
    daemon.step()                              # capped at max_shards
    assert cluster.num_shards == 2
    assert [f.result(timeout=10.0) for f in futures] == ["slow"] * 3
    cluster.shutdown()


def test_daemon_scales_out_on_windowed_cold_rate():
    cluster = build_cluster(1, max_instances=4, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    daemon = AdaptDaemon(cluster=cluster, interval=30.0,
                         fleet=FleetPolicy(scale_out_queue_depth=10 ** 6,
                                           scale_out_cold_rate=0.5,
                                           min_window_invocations=4,
                                           max_shards=2),
                         adapt_pools=False)
    acct = cluster.worker(0).scheduler.accountant
    for _ in range(6):                         # a fully cold window
        acct.record_invocation(APP, "fn", 0.01, cold_start=True)
    daemon.step()
    assert cluster.num_shards == 2 and daemon.scale_outs == 1
    # window consumed: a pass with no new invocations sees rate 0
    daemon.step()
    assert cluster.num_shards == 2
    cluster.shutdown()


def test_daemon_cold_rate_window_ignores_predaemon_history():
    """A cluster with a cold-heavy lifetime bill must not trigger a
    spurious scale-out on the daemon's first pass: the window baseline
    is seeded from the bills at daemon construction."""
    cluster = build_cluster(1, max_instances=4, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    acct = cluster.worker(0).scheduler.accountant
    for _ in range(20):                        # history before the daemon
        acct.record_invocation(APP, "fn", 0.01, cold_start=True)
    daemon = AdaptDaemon(cluster=cluster, interval=30.0,
                         fleet=FleetPolicy(scale_out_queue_depth=10 ** 6,
                                           scale_out_cold_rate=0.5,
                                           min_window_invocations=4),
                         adapt_pools=False)
    daemon.step()
    assert cluster.num_shards == 1 and daemon.scale_outs == 0
    # but cold starts arriving after construction still trip the rule
    for _ in range(6):
        acct.record_invocation(APP, "fn", 0.01, cold_start=True)
    daemon.step()
    assert cluster.num_shards == 2 and daemon.scale_outs == 1
    cluster.shutdown()


def test_daemon_drains_idle_shards_down_to_min():
    cluster = build_cluster(3, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    daemon = AdaptDaemon(cluster=cluster, interval=30.0,
                         fleet=FleetPolicy(min_shards=1,
                                           scale_in_idle_passes=2),
                         adapt_pools=False)
    daemon.step()                              # idle pass 1: no action yet
    assert cluster.num_shards == 3
    daemon.step()                              # idle pass 2: drain newest
    assert cluster.num_shards == 2 and daemon.scale_ins == 1
    assert daemon.fleet_actions[-1] == (1, "remove", 2)
    daemon.step()
    daemon.step()
    assert cluster.num_shards == 1
    for _ in range(4):                         # never below min_shards
        daemon.step()
    assert cluster.num_shards == 1
    cluster.shutdown()


def test_daemon_cold_rate_window_accumulates_below_threshold():
    """Cold starts arriving slower than the pass rate must accumulate
    across passes until the window is large enough — not be discarded
    by advancing the baseline on every sub-threshold pass."""
    cluster = build_cluster(1, max_instances=4, keep_alive=60.0)
    cluster.register(make_spec("fn"))
    daemon = AdaptDaemon(cluster=cluster, interval=30.0,
                         fleet=FleetPolicy(scale_out_queue_depth=10 ** 6,
                                           scale_out_cold_rate=0.5,
                                           min_window_invocations=8,
                                           max_shards=2),
                         adapt_pools=False)
    acct = cluster.worker(0).scheduler.accountant
    for _ in range(5):                         # below the window threshold
        acct.record_invocation(APP, "fn", 0.01, cold_start=True)
    daemon.step()
    assert cluster.num_shards == 1             # window still accumulating
    for _ in range(5):                         # now 10 >= 8, all cold
        acct.record_invocation(APP, "fn", 0.01, cold_start=True)
    daemon.step()
    assert cluster.num_shards == 2 and daemon.scale_outs == 1
    cluster.shutdown()


def test_daemon_never_drains_sole_host_of_subset_function():
    """Automated scale-in must not take a function out of service: a
    shard that is the only host of an explicit shard-subset registration
    (which add_worker never replays) is skipped, and the next removable
    shard is drained instead."""
    cluster = build_cluster(2, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("everywhere"))
    cluster.register(make_spec("edge"), shards=[1])   # newest = sole host
    daemon = AdaptDaemon(cluster=cluster, interval=30.0,
                         fleet=FleetPolicy(min_shards=1,
                                           scale_in_idle_passes=1),
                         adapt_pools=False)
    daemon.step()
    # shard 1 (newest, but sole host of "edge") survives; shard 0 drains
    assert sorted(w.shard_id for w in cluster.workers) == [1]
    assert cluster.invoke("edge") == "edge"
    assert cluster.invoke("everywhere") == "everywhere"
    for _ in range(3):                         # sole survivor: no more drains
        daemon.step()
    assert cluster.num_shards == 1
    cluster.shutdown()


def test_daemon_adapts_pools_on_elastic_shards():
    """A shard added after the daemon was built still gets pool-level
    adaptation: the scheduler set is re-read from the cluster each pass."""
    cluster = build_cluster(1, max_instances=1, keep_alive=0.05)
    cluster.register(make_spec("fn"))
    daemon = AdaptDaemon(cluster=cluster, interval=30.0,
                         policy=HistoryPolicy(min_adapt_samples=10,
                                              target_cold_start_rate=0.05),
                         fleet=FleetPolicy(scale_out_queue_depth=10 ** 6))
    added = cluster.add_worker()
    acct = added.scheduler.accountant
    for _ in range(30):
        acct.record_invocation(APP, "fn", 0.01, cold_start=True)
    applied = daemon.step()
    assert any(fn == "fn" for _, fn in applied)
    assert added.pool("fn").config.max_instances == 2
    cluster.shutdown()


# ---------------------------------------------------------------------------
# AdaptDaemon: lifecycle bugfixes
def test_daemon_stop_before_start_is_noop():
    sched_cluster = build_cluster(1)
    daemon = AdaptDaemon(sched_cluster.workers[0].scheduler)
    daemon.stop()                              # must not raise
    daemon.stop(wait=False)
    assert not daemon.running
    sched_cluster.shutdown()


def test_daemon_double_start_runs_one_thread():
    sched_cluster = build_cluster(1)
    daemon = AdaptDaemon(sched_cluster.workers[0].scheduler, interval=0.01)
    try:
        assert daemon.start() is daemon.start()
        threads = [t for t in threading.enumerate()
                   if t.name == "adapt-daemon"]
        assert len(threads) == 1
        assert threads[0].daemon              # interpreter-exit safe
    finally:
        daemon.stop()
    assert not daemon.running
    sched_cluster.shutdown()


def test_daemon_restart_after_nonblocking_stop_does_not_leak():
    """stop(wait=False) then start() must join the old loop before
    clearing the stop event — otherwise the old thread can miss the set
    and keep running alongside the new one."""
    sched_cluster = build_cluster(1)
    daemon = AdaptDaemon(sched_cluster.workers[0].scheduler, interval=0.005)
    try:
        for _ in range(3):
            daemon.start()
            daemon.stop(wait=False)
        daemon.start()
        time.sleep(0.03)
        threads = [t for t in threading.enumerate()
                   if t.name == "adapt-daemon"]
        assert len(threads) == 1
    finally:
        daemon.stop()
    assert not daemon.running
    sched_cluster.shutdown()


def test_daemon_requires_a_target():
    with pytest.raises(ValueError, match="needs schedulers"):
        AdaptDaemon()


# ---------------------------------------------------------------------------
# replay across a resizing fleet
def test_trace_replay_with_fleet_resize_controls():
    trace = Trace.periodic("tick", period=0.05, invocations=8)
    cluster = build_cluster(1, max_instances=2, keep_alive=60.0,
                            prewarm_provision=True)
    cluster.register(make_spec("tick"))
    shrunk = []
    controls = [
        (0.12, lambda: cluster.add_worker()),
        (0.27, lambda: shrunk.append(
            cluster.remove_worker(
                max(w.shard_id for w in cluster.workers), drain=True))),
    ]
    report = TraceReplayer(cluster, trace, time_scale=1.0,
                           controls=controls).run(freshen=False)
    assert report.requests == 8 and report.errors == 0
    assert report.controls == 2 and report.control_errors == 0
    assert cluster.num_shards == 1
    assert shrunk and shrunk[0].drained
    # every arrival accounted for across the membership change
    assert cluster.accountant.latency_summary(APP)["count"] == 8
    cluster.shutdown()


def test_trace_replay_control_errors_do_not_kill_replay():
    trace = Trace.periodic("tick", period=0.02, invocations=3)
    cluster = build_cluster(1, max_instances=2, keep_alive=60.0)
    cluster.register(make_spec("tick"))

    def boom():
        raise RuntimeError("resize failed")

    report = TraceReplayer(cluster, trace, time_scale=1.0,
                           controls=[(0.03, boom)]).run(freshen=False)
    assert report.requests == 3 and report.errors == 0
    assert report.controls == 1 and report.control_errors == 1
    cluster.shutdown()


# ---------------------------------------------------------------------------
# ServingEngine elastic wiring
class _StubEndpoint:
    def __init__(self, name):
        self.name = name

    def spec(self):
        return make_spec(self.name, app="serving-elastic")


def _shared_cpu(n):
    """``n`` device slots on the one CPU device: the engine pins one shard
    per slot, so host-only stub fabrics can grow to ``n`` shards."""
    import jax
    return jax.local_devices()[:1] * n


def test_engine_scale_shards_and_elastic_deploy():
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(devices=_shared_cpu(4))
    try:
        eng.deploy(_StubEndpoint("ep"), pool_config=PoolConfig(
            max_instances=2, keep_alive=60.0), shards=2, elastic=True)
        assert eng.scale_shards(4) == 4
        # the elastic endpoint followed the fleet onto the new shards
        assert all(w.has_function("ep") for w in eng.cluster.workers)
        assert eng.submit("ep", tokens=None).result(timeout=5.0) == "ep"
        # shrink with drain: history survives, endpoint still serves
        assert eng.scale_shards(2) == 2
        assert eng.submit("ep", tokens=None).result(timeout=5.0) == "ep"
        assert eng.latency_summary("serving-elastic")["count"] == 2
        # a wider elastic deploy grows the fabric instead of raising
        eng.deploy(_StubEndpoint("wide"), pool_config=PoolConfig(
            max_instances=2, keep_alive=60.0), shards=3, elastic=True)
        assert eng.cluster.num_shards == 3
        # the non-elastic contract is unchanged
        with pytest.raises(ValueError, match="widest endpoint first"):
            eng.deploy(_StubEndpoint("wider"), shards=8)
    finally:
        eng.close()


def test_engine_latency_summary_keeps_drained_shard_history():
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(devices=_shared_cpu(4))
    try:
        eng.deploy(_StubEndpoint("ep"), pool_config=PoolConfig(
            max_instances=2, keep_alive=60.0), shards=3, elastic=True)
        for _ in range(6):
            eng.submit("ep", tokens=None).result(timeout=5.0)
        before = eng.latency_summary("serving-elastic")
        assert before["count"] == 6
        eng.scale_shards(1)                    # drain shards 1 and 2
        after = eng.latency_summary("serving-elastic")
        # the drained shards' samples survive in the retained ledgers
        assert after["count"] == 6
        assert after["p95"] == pytest.approx(before["p95"])
    finally:
        eng.close()


def test_engine_elastic_deploy_without_shards_joins_fabric():
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(devices=_shared_cpu(4))
    try:
        eng.scale_shards(2)
        eng.deploy(_StubEndpoint("ep"), pool_config=PoolConfig(
            max_instances=2, keep_alive=60.0), elastic=True)
        # joined the existing fabric cluster-wide, not the base scheduler
        assert all(w.has_function("ep") for w in eng.cluster.workers)
        eng.scale_shards(3)
        assert all(w.has_function("ep") for w in eng.cluster.workers)
        assert eng.submit("ep", tokens=None).result(timeout=5.0) == "ep"
    finally:
        eng.close()


def test_engine_fixed_width_deploy_after_elastic_churn():
    """Elastic churn leaves shard ids non-contiguous; a later non-elastic
    deploy(shards=N) must target the N lowest live shards, not
    range(N)."""
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(devices=_shared_cpu(4))
    try:
        eng.scale_shards(3)                    # ids {0, 1, 2}
        eng.scale_shards(2)                    # drains 2 -> {0, 1}
        eng.scale_shards(3)                    # adds 3  -> {0, 1, 3}
        assert sorted(w.shard_id for w in eng.cluster.workers) == [0, 1, 3]
        eng.deploy(_StubEndpoint("fixed"), pool_config=PoolConfig(
            max_instances=2, keep_alive=60.0), shards=3)
        assert all(w.has_function("fixed") for w in eng.cluster.workers)
        assert eng.submit("fixed", tokens=None).result(timeout=5.0) == "fixed"
    finally:
        eng.close()


def test_engine_scale_shards_builds_fabric_first_use():
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(devices=_shared_cpu(4))
    try:
        assert eng.scale_shards(1) == 1 and eng.cluster is None
        assert eng.scale_shards(2) == 2 and eng.cluster is not None
        eng.deploy(_StubEndpoint("late"), pool_config=PoolConfig(
            max_instances=2, keep_alive=60.0), shards=2, elastic=True)
        assert eng.submit("late", tokens=None).result(timeout=5.0) == "late"
        with pytest.raises(ValueError, match="at least one shard"):
            eng.scale_shards(0)
    finally:
        eng.close()
