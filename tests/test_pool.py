"""Multi-instance pool semantics: keep-alive scale-to-zero, queue-driven
burst scale-up, prewarm-aware freshen dispatch, queueing-delay/cold-start
accounting, and the concurrent scheduler router.

These are pure-core tests (no JAX) so they run fast and deterministically;
timing-sensitive cases use generous sleeps or fake clocks.
"""
import threading
import time
from concurrent.futures import wait

import pytest

from repro.core import (Accountant, FreshenScheduler, FunctionSpec,
                        InstancePool, PoolConfig, PoolSaturated, ServiceClass,
                        WarmthLevel)
from repro.core.freshen import Action, FreshenPlan, PlanEntry
from repro.core.pool import InstanceState


def _noop_spec(name="f", app="app"):
    return FunctionSpec(name, lambda ctx, args: args, app=app)


# module-level (picklable) function bodies: the backend-parametrized tests
# below also run under the subprocess backend, whose worker unpickles the
# spec by reference and imports this module
def _slow_code(ctx, args):
    time.sleep(0.05)


def _echo_code(ctx, args):
    return ("out", args)


def _pool_fetch():
    time.sleep(0.01)
    return "v"


def _pool_plan(rt):
    return FreshenPlan([PlanEntry("r0", Action.FETCH, _pool_fetch)])


def _planned_code(ctx, args):
    return ctx.fr_fetch(0)


def _noop_code(ctx, args):
    return args


def _planned_spec(name, fetched, value="v", cost=0.0, app="app"):
    def make_plan(rt):
        def fetch():
            if cost:
                time.sleep(cost)
            fetched["n"] += 1
            return value
        return FreshenPlan([PlanEntry("r0", Action.FETCH, fetch)])

    def code(ctx, args):
        return ctx.fr_fetch(0)

    return FunctionSpec(name, code, plan_factory=make_plan, app=app)


# ----------------------------------------------------------------------
# Keep-alive expiry / scale-to-zero
def test_keep_alive_reaps_pool_to_zero(fake_clock):
    pool = InstancePool(_noop_spec(), PoolConfig(max_instances=3,
                                                 keep_alive=10.0),
                        clock=fake_clock)
    insts = [pool.acquire()[0] for _ in range(3)]
    for i in insts:
        pool.release(i)
    assert pool.size() == 3 and pool.idle_count() == 3
    fake_clock.set(5.0)
    assert pool.reap() == 0                  # within keep-alive
    assert pool.size() == 3
    fake_clock.set(20.0)
    assert pool.reap() == 3                  # all idle past keep-alive
    assert pool.size() == 0 and pool.idle_count() == 0
    assert all(i.state is InstanceState.REAPED for i in insts)
    # traffic after scale-to-zero provisions fresh (cold) instances
    inst, _, cold = pool.acquire()
    assert cold and pool.size() == 1
    assert pool.stats()["reaped"] == 3


def test_reap_spares_busy_instances(fake_clock):
    pool = InstancePool(_noop_spec(), PoolConfig(max_instances=2,
                                                 keep_alive=1.0),
                        clock=fake_clock)
    busy, _, _ = pool.acquire()
    idle, _, _ = pool.acquire()
    pool.release(idle)
    fake_clock.set(100.0)
    assert pool.reap() == 1                  # only the idle one dies
    assert pool.size() == 1
    assert busy.state is InstanceState.BUSY
    pool.release(busy)                       # release after reap still works
    assert pool.idle_count() == 1


# ----------------------------------------------------------------------
# Burst traffic scale-up
@pytest.mark.parametrize("rep", range(3))
@pytest.mark.parametrize("backend", ["thread", "subprocess"])
def test_burst_scales_up_to_cap_and_queues(rep, backend):
    spec = FunctionSpec("slow", _slow_code, app="app")
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=3,
                                                    keep_alive=30.0,
                                                    backend=backend))
    sched.register(spec)
    futs = [sched.submit("slow", freshen_successors=False) for _ in range(8)]
    done, not_done = wait(futs, timeout=30)
    assert not not_done
    for f in futs:
        f.result()
    pool = sched.pool("slow")
    st = pool.stats()
    assert st["instances"] == 3              # scaled to the cap, not beyond
    assert st["cold_starts"] == 3
    assert st["queued_acquires"] >= 2        # 8 arrivals > 3 instances
    bill = sched.accountant.bill("app")
    assert bill.function_invocations == 8
    assert bill.cold_starts == 3
    assert bill.queue_seconds > 0            # queueing delay was accounted
    summary = sched.accountant.latency_summary("app")
    assert summary["count"] == 8
    assert summary["p99"] >= summary["p50"] > 0
    sched.shutdown()


def test_acquire_timeout_raises_when_saturated():
    pool = InstancePool(_noop_spec(), PoolConfig(max_instances=1))
    inst, _, cold = pool.acquire()
    assert cold
    inst.runtime.run(None)                   # boots the container
    with pytest.raises(PoolSaturated):
        pool.acquire(timeout=0.05)
    pool.release(inst)
    inst2, delay, cold2 = pool.acquire(timeout=1.0)
    assert inst2 is inst and not cold2       # warm container reuse


def test_scale_up_queue_depth_throttles_growth():
    """With depth=2 one waiter queues on the single busy instance; the pool
    only provisions instance #2 once a second simultaneous waiter arrives,
    and hands it to the head of the queue."""
    pool = InstancePool(_noop_spec(), PoolConfig(max_instances=4,
                                                 scale_up_queue_depth=2))
    a, _, _ = pool.acquire()
    assert pool.size() == 1                  # from zero: started one
    pool.release(a)
    b, _, _ = pool.acquire()
    assert b is a and pool.size() == 1       # reuse, no eager growth

    got = []

    def grab():
        inst, d, c = pool.acquire(timeout=10.0)
        got.append(inst)

    t1 = threading.Thread(target=grab)
    t1.start()                               # one waiter: below depth 2
    time.sleep(0.1)
    assert t1.is_alive() and pool.size() == 1
    t2 = threading.Thread(target=grab)
    t2.start()                               # second waiter crosses the depth
    t1.join(timeout=10.0)                    # FIFO: the head gets it
    assert not t1.is_alive() and pool.size() == 2   # scaled up for the burst
    pool.release(b)                          # frees the second waiter
    t2.join(timeout=10.0)
    assert not t2.is_alive() and len(got) == 2


# ----------------------------------------------------------------------
# Prewarm-aware freshen dispatch
@pytest.mark.parametrize("backend", ["thread", "subprocess"])
def test_prewarm_freshen_hits_across_backends(backend):
    """The prewarm→hit pipeline holds under both instance backends; under
    the subprocess backend the freshen hook runs inside the worker and its
    counters round-trip back through the pipe protocol."""
    sched = FreshenScheduler(pool_config=PoolConfig(backend=backend))
    sched.predictor.graph.add_chain(["pa", "pb"])
    sched.register(FunctionSpec("pa", _noop_code, app="app"))
    sched.register(FunctionSpec("pb", _planned_code,
                                plan_factory=_pool_plan, app="app"))
    try:
        sched.invoke("pa")                   # predicts pb -> prewarm dispatch
        sched.pool("pb").primary.join_freshen(timeout=30)
        out = sched.invoke("pb", freshen_successors=False)
        assert out == "v"
        st = sched.pool("pb").freshen_stats()
        assert st["freshened"] == 1          # background freshen did the work
        assert st["hits"] >= 1               # ...and the invocation consumed it
        assert st["inline"] == 0
        assert sched.pool("pb").stats()["prewarm_dispatches"] == 1
    finally:
        sched.shutdown()


@pytest.mark.parametrize("rep", range(3))
def test_prewarm_freshen_hits_on_next_invocation(rep):
    fetched = {"n": 0}
    sched = FreshenScheduler()
    sched.predictor.graph.add_chain(["fa", "fb"])
    sched.register(_noop_spec("fa"))
    sched.register(_planned_spec("fb", fetched))
    sched.invoke("fa")                       # predicts fb -> prewarm dispatch
    sched.pool("fb").primary.join_freshen(timeout=10)
    out = sched.invoke("fb", freshen_successors=False)
    assert out == "v" and fetched["n"] == 1
    st = sched.pool("fb").freshen_stats()
    assert st["freshened"] == 1              # background freshen did the work
    assert st["hits"] >= 1                   # ...and the invocation consumed it
    assert st["inline"] == 0
    assert sched.pool("fb").stats()["prewarm_dispatches"] == 1


def test_prewarm_provision_cold_starts_off_critical_path():
    """No idle instance + prewarm_provision: the pool cold-starts a new
    instance in the freshen thread, so a later arrival lands warm."""
    fetched = {"n": 0}
    spec = _planned_spec("fp", fetched)
    pool = InstancePool(spec, PoolConfig(max_instances=2,
                                         prewarm_provision=True))
    busy, _, _ = pool.acquire()              # the only instance is busy
    t0 = time.monotonic()
    threads = pool.prewarm_freshen()
    assert time.monotonic() - t0 < 0.5       # dispatch returned immediately
    assert len(threads) == 1 and pool.size() == 2
    assert pool.stats()["prewarm_provisioned"] == 1
    for th in threads:
        th.join(timeout=10)
    inst, _, cold = pool.acquire()           # lands on the provisioned one
    assert not cold                          # it was initialized off-path
    assert inst.runtime.run(None) == "v"
    assert fetched["n"] == 1                 # freshen prefetched it
    assert pool.freshen_stats()["hits"] >= 1
    pool.release(inst)
    pool.release(busy)


def test_prewarm_targets_most_recently_used_idle_instance():
    """LIFO: the idle instance a prewarm touches is the one the next
    acquire returns, so per-instance fr_state prewarming actually pays."""
    pool = InstancePool(_noop_spec(), PoolConfig(max_instances=3,
                                                 prewarm_fanout=1))
    a, _, _ = pool.acquire()
    b, _, _ = pool.acquire()
    pool.release(a)
    pool.release(b)                          # b is now most recently used
    targets = pool.prewarm_freshen()
    for th in targets:
        th.join(timeout=10)
    nxt, _, _ = pool.acquire()
    assert nxt is b
    assert b.runtime.freshen_count == 1 and a.runtime.freshen_count == 0


def test_scheduler_reports_no_idle_instance_event():
    sched = FreshenScheduler(accountant=Accountant())
    sched.accountant.service_class["app"] = ServiceClass.LATENCY_SENSITIVE
    sched.predictor.graph.add_chain(["ga", "gb"])
    sched.register(_noop_spec("ga"))
    sched.register(_noop_spec("gb"),
                   config=PoolConfig(max_instances=1,
                                     prewarm_busy_fallback=False))
    inst, _, _ = sched.pool("gb").acquire()  # gb's only instance busy
    sched.invoke("ga")
    assert any(e.reason == "no-idle-instance" and not e.dispatched
               for e in sched.events)
    sched.pool("gb").release(inst)


def test_prewarm_busy_fallback_freshens_busy_instance():
    """Seed-compatible: when the successor's only instance is mid-request,
    freshen still lands on it so the NEXT invocation hits (fr_state is
    thread-safe under the run hook)."""
    fetched = {"n": 0}
    pool = InstancePool(_planned_spec("fbsy", fetched),
                        PoolConfig(max_instances=1))
    inst, _, _ = pool.acquire()
    inst.runtime.run(None)                   # init + first fetch consumed
    threads = pool.prewarm_freshen()         # no idle instance -> busy one
    assert len(threads) == 1
    for th in threads:
        th.join(timeout=10)
    assert pool.stats()["prewarm_dispatches"] == 1
    pool.release(inst)


def test_reap_spares_instance_with_inflight_prewarm():
    """An idle instance being prewarm-freshened is predicted traffic — reap
    must not evict it mid-freshen even past keep-alive."""
    fetched = {"n": 0}
    pool = InstancePool(_planned_spec("fpw", fetched, cost=0.2),
                        PoolConfig(max_instances=2, keep_alive=30.0))
    inst, _, _ = pool.acquire()
    pool.release(inst)
    threads = pool.prewarm_freshen()         # slow fetch keeps it in flight
    pool.config.keep_alive = 0.0
    time.sleep(0.02)
    assert pool.reap() == 0                  # spared while freshen runs
    assert pool.size() == 1
    for th in threads:
        th.join(timeout=10)
    time.sleep(0.01)
    assert pool.reap() == 1                  # reapable once it settles
    assert pool.size() == 0


def test_runtimes_view_survives_reap():
    """scheduler.runtimes must be a live view: after keep-alive reaps the
    primary, indexing yields a runtime that is actually in the pool (not a
    detached REAPED instance)."""
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=2,
                                                    keep_alive=30.0))
    sched.register(_noop_spec("fv"))
    first = sched.runtimes["fv"]
    pool = sched.pool("fv")
    pool.config.keep_alive = 0.0
    time.sleep(0.01)
    assert pool.reap() == 1 and pool.size() == 0     # scaled to zero
    revived = sched.runtimes["fv"]
    assert revived is not first
    assert revived is pool.primary                   # attached to the pool
    assert sched.invoke("fv", 7, freshen_successors=False) == 7


# ----------------------------------------------------------------------
# Concurrent router correctness
@pytest.mark.parametrize("rep", range(3))
@pytest.mark.parametrize("backend", ["thread", "subprocess"])
def test_concurrent_submits_return_correct_results(rep, backend):
    spec = FunctionSpec("echo", _echo_code, app="app")
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=4,
                                                    backend=backend))
    sched.register(spec)
    futs = [sched.submit("echo", i, freshen_successors=False)
            for i in range(32)]
    outs = [f.result(timeout=30) for f in futs]
    assert outs == [("out", i) for i in range(32)]
    assert sched.accountant.bill("app").function_invocations == 32
    sched.shutdown()


def test_chain_submit_through_pools():
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=2))
    sched.predictor.graph.add_chain(["c1", "c2"])
    sched.register(FunctionSpec("c1", lambda ctx, a: a + 1, app="chain"))
    sched.register(FunctionSpec("c2", lambda ctx, a: a * 2, app="chain"))
    futs = [sched.submit_chain(["c1", "c2"], i, freshen=True)
            for i in range(8)]
    assert [f.result(timeout=30) for f in futs] == [(i + 1) * 2
                                                    for i in range(8)]
    sched.shutdown()


# ----------------------------------------------------------------------
# Daemon reap sweep + stats fallback
def test_adapt_daemon_step_reaps_idle_pools_without_traffic(fake_clock):
    """InstancePool.reap only runs inside acquire/prewarm_freshen, so a
    function that goes quiet would park instances forever; the daemon's
    per-pass sweep is the traffic-independent clock tick that returns the
    pool to zero."""
    from repro.workloads import AdaptDaemon

    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=2,
                                                    keep_alive=10.0))
    sched.register(_noop_spec("quiet"))
    pool = sched.pools["quiet"]
    pool.clock = fake_clock
    inst, _, _ = pool.acquire()
    pool.release(inst)
    assert pool.size() == 1
    daemon = AdaptDaemon(sched, adapt_pools=False)
    daemon.step()
    assert pool.size() == 1                  # within keep-alive: untouched
    fake_clock.set(20.0)                     # idle gap, zero traffic
    daemon.step()
    assert pool.size() == 0                  # swept to zero by the daemon
    assert daemon.reaped_swept == 1
    sched.shutdown()


def test_stats_and_measured_cold_start_agree_before_first_boot():
    """Both views fall back to the configured cold_start_cost until a
    boot has been measured — a dashboard reading stats() and a policy
    reading measured_cold_start() must see the same number."""
    pool = InstancePool(_noop_spec(), PoolConfig(cold_start_cost=0.15))
    assert pool.measured_cold_start() == 0.15
    assert pool.stats()["measured_init_mean"] == 0.15
    inst, _, _ = pool.acquire()
    inst.runtime.init()
    pool.release(inst)
    # once measured, both switch to the observed mean together
    assert pool.measured_cold_start() == pool.stats()["measured_init_mean"]
    assert pool.measured_cold_start() >= 0.15
    pool.close()


# ----------------------------------------------------------------------
# Graded warmth ladder (PR 7)
def _graded_cfg(**kw):
    base = dict(max_instances=2, keep_alive=8.0, graded_warmth=True,
                keep_alive_hot=2.0, keep_alive_initialized=4.0,
                keep_alive_process=6.0)
    base.update(kw)
    return PoolConfig(**base)


def test_graded_reap_demotes_one_rung_per_sweep(fake_clock):
    """Keep-alive expiry on a graded pool walks the ladder — HOT ->
    INITIALIZED -> PROCESS -> reaped — exactly one rung per sweep, with
    the idle timer restarting at each demotion."""
    pool = InstancePool(_noop_spec(), _graded_cfg(), clock=fake_clock)
    for th in pool.prewarm_freshen(max_dispatch=1, provision=True,
                                   level=WarmthLevel.HOT):
        th.join(5.0)
    inst = next(iter(pool._instances.values()))
    assert inst.runtime.warmth is WarmthLevel.HOT
    fake_clock.advance(3.0)                  # > hot rung (2), < init rung (4)
    assert pool.reap() == 0                  # demotion is not a death
    assert inst.runtime.warmth is WarmthLevel.INITIALIZED
    assert inst.runtime.fr_state is not None  # runtime survives, caches don't
    fake_clock.advance(5.0)                  # > init rung since demotion
    assert pool.reap() == 0
    assert inst.runtime.warmth is WarmthLevel.PROCESS
    assert inst.runtime.fr_state is None     # inited runtime torn down
    assert pool.warm_total_count() == 0      # no longer init-warm...
    assert pool.warm_total_count(WarmthLevel.PROCESS) == 1  # ...but resident
    fake_clock.advance(7.0)                  # > process rung: off the ladder
    assert pool.reap() == 1
    assert pool.size() == 0
    assert pool.stats()["demotions"] == 2


def test_binary_pool_never_demotes(fake_clock):
    """graded_warmth off: expiry stays a cliff (seed behavior)."""
    pool = InstancePool(_noop_spec(), PoolConfig(max_instances=1,
                                                 keep_alive=2.0),
                        clock=fake_clock)
    inst, _, _ = pool.acquire()
    inst.runtime.init()
    pool.release(inst)
    fake_clock.advance(3.0)
    assert pool.reap() == 1                  # reaped outright, no ladder
    assert pool.stats()["demotions"] == 0


def test_acquire_prefers_highest_rung_over_lifo():
    """LIFO says "most recently released"; the warmth ladder overrides it:
    an arrival lands on the warmest servable instance even when a colder
    one sits on top of the stack."""
    pool = InstancePool(_noop_spec(), PoolConfig(max_instances=2,
                                                 keep_alive=100.0))
    warm, _, _ = pool.acquire()
    cold, _, _ = pool.acquire()
    warm.runtime.init()
    pool.release(warm)                       # bottom of the LIFO stack
    pool.release(cold)                       # top of the stack, but COLD
    inst, _, was_cold = pool.acquire()
    assert inst is warm and not was_cold
    pool.close()


def test_process_standby_acquire_pays_partial_cold(fake_clock):
    """An arrival on a PROCESS standby is still billed a cold start (the
    init share remains), but the pool records it as partial — the sandbox
    share was prepaid by the ladder."""
    pool = InstancePool(_noop_spec(), _graded_cfg(max_instances=1),
                        clock=fake_clock)
    for th in pool.prewarm_freshen(max_dispatch=1, provision=True,
                                   level=WarmthLevel.PROCESS):
        th.join(5.0)
    assert pool.warm_total_count(WarmthLevel.PROCESS) == 1
    assert pool.warm_idle_count() == 0       # standby is not init-warm
    inst, _, was_cold = pool.acquire()
    assert was_cold
    assert inst.runtime.warmth is WarmthLevel.PROCESS
    s = pool.stats()
    assert s["cold_starts"] == 1 and s["partial_cold_starts"] == 1
    pool.close()


def test_lower_level_prewarm_never_demotes_warm_instances():
    """prewarm(level=PROCESS) on a pool whose idle instance is already
    INITIALIZED must not touch it — partial prewarm only promotes."""
    pool = InstancePool(_noop_spec(), _graded_cfg(max_instances=1))
    inst, _, _ = pool.acquire()
    inst.runtime.init()
    pool.release(inst)
    ths = pool.prewarm_freshen(max_dispatch=1, provision=True,
                               level=WarmthLevel.PROCESS)
    for th in ths:
        th.join(5.0)
    assert inst.runtime.warmth >= WarmthLevel.INITIALIZED
    pool.close()


def test_warm_idle_count_excludes_inflight_freshen():
    """Regression (PR 7 audit): warm_idle_count used to count instances
    whose freshen was still mid-flight, but acquire's warm path prefers
    to skip those — so routing saw warmth an arrival could not actually
    land on without blocking behind the fetch.  The signal now matches
    acquire's first preference."""
    gate = threading.Event()

    def make_plan(rt):
        def fetch():
            gate.wait(10.0)
            return "v"
        return FreshenPlan([PlanEntry("r0", Action.FETCH, fetch)])

    spec = FunctionSpec("f", lambda ctx, args: ctx.fr_fetch(0),
                        plan_factory=make_plan, app="app")
    pool = InstancePool(spec, PoolConfig(max_instances=2))
    inst, _, _ = pool.acquire()
    inst.runtime.init()
    pool.release(inst)
    assert pool.warm_idle_count() == 1
    ths = pool.prewarm_freshen(max_dispatch=1)   # blocks on the gated fetch
    try:
        assert pool.warm_idle_count() == 0       # mid-flight: not servable
        assert pool.warm_total_count() == 1      # ...but still resident
        assert pool.warmth_score() == 0.0        # routing signal agrees
    finally:
        gate.set()
        for th in ths:
            th.join(5.0)
    assert pool.warm_idle_count() == 1
    pool.close()
