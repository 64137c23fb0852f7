"""Single-submission hot path: non-blocking/closure-parked pool
admission and the one waiter queue it shares with the blocking acquire,
the scheduler fast path and its counters, chain tracing
parity, UnknownFunction, the pool-aware EndpointBatcher, daemon waiter
sweeps, and shutdown draining of parked admissions."""
import threading
import time

import pytest

from conftest import FakeClock, sync_batches

from repro.core import (FreshenScheduler, FunctionSpec, InstancePool,
                        PoolConfig, PoolSaturated, UnknownFunction)
from repro.core.pool import AcquireWaiter
from repro.serving.batching import EndpointBatcher
from repro.telemetry import Tracer
from repro.workloads import AdaptDaemon


def _spec(name="f", app="hot"):
    return FunctionSpec(name, lambda ctx, args: ("ok", args), app=app)


def _pool(cap=1, **kw):
    kw.setdefault("keep_alive", 60.0)
    return InstancePool(_spec(), PoolConfig(max_instances=cap, **kw))


# ----------------------------------------------------------------------
# try_acquire


def test_try_acquire_hit_miss_and_release_cycle():
    pool = _pool(cap=1)
    grabbed = pool.try_acquire()
    assert grabbed is not None
    inst, cold = grabbed
    assert cold                          # first touch boots the instance
    assert pool.try_acquire() is None    # cap reached, instance busy
    inst.runtime.init()                  # the runner boots it before running
    pool.release(inst)
    inst2, cold2 = pool.try_acquire()
    assert inst2 is inst and not cold2   # warm LIFO reuse
    pool.release(inst2)
    pool.close()


def test_try_acquire_scales_up_like_acquire():
    pool = _pool(cap=2)
    a = pool.try_acquire()
    b = pool.try_acquire()               # second arrival provisions
    assert a is not None and b is not None
    assert a[0] is not b[0]
    assert pool.try_acquire() is None
    pool.release(a[0])
    pool.release(b[0])
    pool.close()


def test_try_acquire_respects_keep_alive_expiry():
    """Regression: the fast path must reap an expired idle instance, not
    hand it out warm — keep-alive semantics cannot depend on which
    admission mode an arrival took."""
    clock = FakeClock()
    pool = InstancePool(_spec(), PoolConfig(max_instances=2, keep_alive=1.0),
                        clock=clock)
    inst, cold = pool.try_acquire()
    assert cold
    inst.runtime.init()
    pool.release(inst)
    clock.advance(2.0)                   # past keep-alive
    inst2, cold2 = pool.try_acquire()
    assert cold2, "expired instance must cold-start, not serve warm"
    pool.release(inst2)
    assert pool.stats()["reaped"] >= 1
    pool.close()


# ----------------------------------------------------------------------
# acquire_async


def _cb(record):
    def cb(inst, queue_delay, cold, error):
        record.append((inst, queue_delay, cold, error))
    return cb


def test_acquire_async_immediate_grant_fires_synchronously():
    pool = _pool(cap=1)
    got = []
    w = pool.acquire_async(_cb(got))
    assert isinstance(w, AcquireWaiter) and not w.pending
    assert len(got) == 1
    inst, _, cold, error = got[0]
    assert inst is not None and cold and error is None
    pool.release(inst)
    pool.close()


def test_release_hands_instance_to_waiters_in_admission_order():
    pool = _pool(cap=1)
    inst, _ = pool.try_acquire()
    first, second = [], []
    pool.acquire_async(_cb(first))
    pool.acquire_async(_cb(second))
    assert pool.waiting_count() == 2
    assert pool.try_acquire() is None    # no queue jumping past waiters
    pool.release(inst)
    assert len(first) == 1 and not second    # FIFO: head served first
    got = first[0][0]
    assert got is inst and first[0][1] >= 0.0
    pool.release(got)
    assert len(second) == 1 and second[0][0] is inst
    pool.release(second[0][0])
    pool.close()


def test_parked_waiter_reaching_scale_up_depth_provisions():
    """A waiter that lifts the queue to ``scale_up_queue_depth`` scales the
    pool up, and the new instance goes to the head of the queue."""
    pool = _pool(cap=2, scale_up_queue_depth=2)
    inst, _ = pool.try_acquire()
    first, second = [], []
    pool.acquire_async(_cb(first))       # demand 1: below the depth
    assert not first and pool.size() == 1
    pool.acquire_async(_cb(second))      # demand 2: provisions
    assert len(first) == 1 and first[0][0] is not inst and not second
    assert pool.size() == 2 and pool.waiting_count() == 1
    pool.release(inst)
    assert len(second) == 1 and second[0][0] is inst
    pool.release(first[0][0])
    pool.release(inst)
    pool.close()


def test_acquire_async_timeout_swept_with_saturation_error():
    pool = _pool(cap=1)
    inst, _ = pool.try_acquire()
    got = []
    pool.acquire_async(_cb(got), timeout=0.01)
    time.sleep(0.03)
    assert pool.sweep_waiters() == 1
    assert len(got) == 1
    assert isinstance(got[0][3], PoolSaturated)
    assert got[0][0] is None
    pool.release(inst)                   # nobody left to hand it to
    assert pool.idle_count() == 1
    pool.close()


def test_acquire_waiter_cancel_prevents_callback():
    pool = _pool(cap=1)
    inst, _ = pool.try_acquire()
    got = []
    w = pool.acquire_async(_cb(got))
    assert w.pending and w.cancel()
    assert not w.pending and not w.cancel()      # idempotent: already gone
    pool.release(inst)
    assert not got, "cancelled waiter must never fire"
    pool.close()


def test_concurrent_release_and_park_never_drops_a_waiter():
    """Hammer: parkers race releases; every parked callback must fire
    exactly once with an instance."""
    pool = _pool(cap=2)
    n = 60
    got, lock = [], threading.Lock()

    def cb(inst, qd, cold, error):
        assert error is None and inst is not None
        with lock:
            got.append(inst)
        # simulate a short run, then hand the instance back (serving the
        # next parked waiter directly under release's lock hold)
        threading.Timer(0.001, pool.release, args=(inst,)).start()

    threads = [threading.Thread(target=pool.acquire_async, args=(cb,))
               for _ in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    deadline = time.monotonic() + 10
    while len(got) < n and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(got) == n
    s = pool.stats()
    assert s["cold_starts"] + s["warm_acquires"] == n
    time.sleep(0.05)                     # let the last timer release land
    pool.close()


def test_retire_fails_parked_waiters():
    pool = _pool(cap=1)
    inst, _ = pool.try_acquire()
    got = []
    pool.acquire_async(_cb(got))
    pool.retire()
    assert len(got) == 1 and isinstance(got[0][3], PoolSaturated)
    pool.release(inst)                   # post-retire release closes it


# ----------------------------------------------------------------------
# scheduler fast path


def test_submit_fast_path_counter_and_result():
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=1))
    sched.register(_spec("f"))
    try:
        assert sched.submit("f", 1, freshen_successors=False).result(5) \
            == ("ok", 1)
        snap = sched.metrics_snapshot()
        assert snap["scheduler.invoke.fast_path"] == 1
        assert snap["scheduler.invoke.slow_path"] == 0
    finally:
        sched.shutdown()


def test_submit_slow_path_parks_closure_and_resolves():
    gate = threading.Event()
    spec = FunctionSpec("g", lambda ctx, args: (gate.wait(5), args)[1],
                        app="hot")
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=1))
    sched.register(spec)
    try:
        # the fast path acquires inline during submit, so the single
        # instance is already BUSY (gated) when this returns
        f1 = sched.submit("g", 1, freshen_successors=False)
        f2 = sched.submit("g", 2, freshen_successors=False)   # parks
        assert sched.pools["g"].waiting_count() == 1
        gate.set()
        assert f1.result(5) == 1 and f2.result(5) == 2
        snap = sched.metrics_snapshot()
        assert snap["scheduler.invoke.fast_path"] == 1
        assert snap["scheduler.invoke.slow_path"] == 1
        # the parked admission was billed with real queueing delay
        assert sched.accountant.bill("hot").queue_seconds > 0.0
    finally:
        sched.shutdown()


def test_blocking_acquire_and_callback_share_one_fifo():
    """A blocking ``acquire`` parks in the same queue as callbacks: a
    callback parked behind it waits its turn."""
    pool = _pool(cap=1)
    inst, _ = pool.try_acquire()
    held, granted, let_go = [], threading.Event(), threading.Event()

    def blocker():
        got, _, _ = pool.acquire(timeout=5.0)
        held.append(got)
        granted.set()
        let_go.wait(5)
        pool.release(got)

    th = threading.Thread(target=blocker)
    th.start()
    deadline = time.monotonic() + 5.0
    while pool.waiting_count() == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    later = []
    pool.acquire_async(_cb(later))
    assert pool.waiting_count() == 2
    pool.release(inst)
    assert granted.wait(5) and held == [inst]    # the head was the thread
    assert not later and pool.waiting_count() == 1
    let_go.set()
    th.join(5)
    assert len(later) == 1 and later[0][0] is inst and later[0][3] is None
    pool.release(inst)
    pool.close()


def test_timed_out_acquire_leaves_the_queue():
    """An ``acquire`` that times out raises ``PoolSaturated`` and leaves
    no waiter behind; the next release goes to the next waiter."""
    pool = _pool(cap=1)
    inst, _ = pool.try_acquire()
    with pytest.raises(PoolSaturated):
        pool.acquire(timeout=0.01)
    assert pool.waiting_count() == 0
    errors = []

    def blocker():
        try:
            pool.acquire(timeout=0.05)
        except PoolSaturated as e:
            errors.append(e)

    th = threading.Thread(target=blocker)
    th.start()
    deadline = time.monotonic() + 5.0
    while pool.waiting_count() == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    later = []
    pool.acquire_async(_cb(later))       # parked behind the blocked thread
    th.join(5)
    assert len(errors) == 1 and errors[0].queue_depth >= 1
    assert pool.waiting_count() == 1     # only the callback is left
    pool.release(inst)
    assert len(later) == 1 and later[0][0] is inst
    pool.release(inst)
    pool.close()


def test_unknown_function_raises_at_admission_time():
    sched = FreshenScheduler()
    try:
        with pytest.raises(UnknownFunction, match="register"):
            sched.submit("nope", 1)
        with pytest.raises(UnknownFunction):
            sched.invoke("nope", 1)
        with pytest.raises(UnknownFunction):
            sched.submit_chain(["nope"], 1)
        assert isinstance(UnknownFunction("x"), KeyError)   # legacy catch
    finally:
        sched.shutdown()


def test_shutdown_drains_parked_admissions():
    """Closure-parked admissions are not router tasks yet; shutdown must
    wait for them, not strand their futures."""
    gate = threading.Event()
    spec = FunctionSpec("g", lambda ctx, args: (gate.wait(5), args)[1],
                        app="hot")
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=1))
    sched.register(spec)
    futs = [sched.submit("g", i, freshen_successors=False) for i in range(3)]
    threading.Timer(0.05, gate.set).start()
    sched.shutdown(wait=True)
    assert [f.result(5) for f in futs] == [0, 1, 2]


def test_submit_chain_tracing_parity():
    """A chain traces like a submit: parent span stamps admission and the
    router hop as its queue phase; each link runs under a child span
    annotated with the parent id and link index."""
    tr = Tracer()
    sched = FreshenScheduler(tracer=tr)
    sched.register(_spec("a"))
    sched.register(FunctionSpec("b", lambda ctx, args: args, app="hot"))
    try:
        assert sched.submit_chain(["a", "b"], 7).result(5) == ("ok", 7)
    finally:
        sched.shutdown()
    spans = tr.spans()
    parent = [s for s in spans if s.fn == "chain:a->b"]
    assert len(parent) == 1
    parent = parent[0]
    assert parent.complete() and parent.attrs["chain"] == ["a", "b"]
    assert "queue" in parent.phase_seconds()      # admission hop stamped
    children = sorted((s for s in spans
                       if s.attrs.get("chain_parent") == parent.span_id),
                      key=lambda s: s.attrs["link"])
    assert [c.fn for c in children] == ["a", "b"]
    assert all(c.complete() for c in children)
    assert all("queue" in c.phase_seconds() for c in children)


# ----------------------------------------------------------------------
# daemon sweep


def test_daemon_step_sweeps_expired_waiters():
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=1))
    sched.register(_spec("f"))
    daemon = AdaptDaemon(sched, adapt_pools=False)
    pool = sched.pools["f"]
    inst, _ = pool.try_acquire()
    got = []
    pool.acquire_async(lambda i, qd, c, e: got.append(e), timeout=0.01)
    time.sleep(0.03)
    daemon.step()
    assert daemon.waiters_expired == 1
    assert len(got) == 1 and isinstance(got[0], PoolSaturated)
    pool.release(inst)
    sched.shutdown()


# ----------------------------------------------------------------------
# EndpointBatcher


def test_endpoint_batcher_fills_and_resolves_in_order():
    fills = []

    def handler(payloads):
        fills.append(len(payloads))
        return [p * 2 for p in payloads]

    b = EndpointBatcher("t", sync_batches(handler), batch_size=4,
                        max_wait=0.02)
    futs = [b.submit(i) for i in range(10)]
    assert [f.result(5) for f in futs] == [2 * i for i in range(10)]
    assert sum(fills) == 10
    assert max(fills) <= 4
    s = b.stats()
    assert s["requests"] == 10 and s["batches"] == len(fills)
    assert s["mean_fill"] == pytest.approx(10 / len(fills))
    b.close()


def _dispatch_adds_up(b):
    s = b.stats()
    assert sum(s["dispatch"].values()) == s["batches"]
    snap = b.metrics_snapshot()
    assert sum(snap[f"batcher.{b.name}.dispatch.{rule}"]
               for rule in EndpointBatcher.DISPATCH_RULES) == s["batches"]
    return s["dispatch"]


def test_endpoint_batcher_adapts_fill_to_fabric_capacity():
    """Queued rows spread over the free slots: 12 rows over 2 slots leave
    in batches of at most ceil(12 / 2); over 1 slot, of at most
    ``batch_size``."""
    for slots, expected in ((2, [6, 3, 2, 1]), (1, [8, 4])):
        fills = []
        entered, gate = threading.Event(), threading.Event()

        def handler(payloads, fills=fills, entered=entered, gate=gate):
            if payloads == ["hold"]:     # holds the worker in run_batch
                entered.set()
                gate.wait(5)
            else:
                fills.append(len(payloads))
            return list(payloads)

        b = EndpointBatcher("t", sync_batches(handler), batch_size=8,
                            max_wait=0.01, capacity=lambda s=slots: s)
        try:
            hold = b.submit("hold")
            assert entered.wait(5)       # the worker is inside run_batch
            futs = [b.submit(i) for i in range(12)]
            gate.set()
            assert hold.result(5) == "hold"
            assert [f.result(5) for f in futs] == list(range(12))
            assert fills == expected
            assert max(fills) <= min(8, -(-12 // slots))
            assert _dispatch_adds_up(b) == {"free": 1 + len(expected),
                                            "full": 0, "deadline": 0}
        finally:
            b.close()


def test_endpoint_batcher_backpressures_on_saturation():
    """PoolSaturated resolving a batch requeues it (admission order
    intact) instead of failing callers."""
    attempts = {"n": 0}

    def handler(payloads):
        attempts["n"] += 1
        if attempts["n"] <= 2:
            raise PoolSaturated("t", queue_depth=1)
        return list(payloads)

    b = EndpointBatcher("t", sync_batches(handler), batch_size=4,
                        max_wait=0.005, retry_interval=0.002)
    futs = [b.submit(i) for i in range(4)]
    assert [f.result(5) for f in futs] == list(range(4))
    assert b.stats()["backpressure"] >= 2
    assert b.metrics_snapshot()["batcher.t.backpressure"] >= 2
    b.close()


def test_endpoint_batcher_runs_batches_as_single_pooled_invocations():
    """End to end against a real scheduler: one batch = one acquire.  The
    instances stay busy until every row is queued, so rows gather."""
    gate = threading.Event()

    def fn(ctx, args):
        gate.wait(5)
        return [p + 1 for p in args]

    spec = FunctionSpec("m", fn, app="hot")
    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=2))
    sched.register(spec)
    pool = sched.pools["m"]

    b = EndpointBatcher(
        "m", lambda payloads: sched.submit("m", list(payloads),
                                           freshen_successors=False),
        batch_size=4, max_wait=0.02, capacity=pool.idle_capacity)
    try:
        futs = [b.submit(i) for i in range(8)]
        gate.set()
        assert [f.result(5) for f in futs] == [i + 1 for i in range(8)]
        s = pool.stats()
        invocations = s["cold_starts"] + s["warm_acquires"]
        # two rows take the two slots, the rest gather: at most one full
        # batch and one batch of the rows held behind it
        assert invocations == b.stats()["batches"] <= 4
        assert sum(b.batch_fill) == 8
        _dispatch_adds_up(b)
    finally:
        b.close()
        sched.shutdown()


@pytest.mark.parametrize("queued,expected", [(3, [1, 3]), (6, [1, 4, 2])])
def test_endpoint_batcher_holds_rows_behind_a_busy_instance(queued,
                                                            expected):
    """One instance (``max_instances`` 1) busy with the batcher's first
    row: rows queue behind it, a full batch waits in the pool's queue,
    and the rest leave as one batch of ``min(B, queued)`` once it is
    released, without waiting out ``max_wait``."""
    entered, gate = threading.Event(), threading.Event()

    def fn(ctx, args):
        entered.set()
        gate.wait(5)
        return [p + 1 for p in args]

    sched = FreshenScheduler(pool_config=PoolConfig(max_instances=1))
    sched.register(FunctionSpec("m", fn, app="hot"))
    pool = sched.pools["m"]
    b = EndpointBatcher(
        "m", lambda payloads: sched.submit("m", list(payloads),
                                           freshen_successors=False),
        batch_size=4, max_wait=5.0, capacity=pool.idle_capacity)
    try:
        first = b.submit(0)
        assert entered.wait(5)
        futs = [b.submit(i) for i in range(1, queued + 1)]
        deadline = time.monotonic() + 5
        while (b.queue_depth(), pool.waiting_count()) != (
                queued % 4, queued // 4):
            assert time.monotonic() < deadline, (b.queue_depth(),
                                                 pool.waiting_count())
            time.sleep(0.001)
        assert b.stats()["batches"] == 0     # nothing has left since
        t0 = time.monotonic()
        gate.set()
        assert first.result(5) == 1
        assert [f.result(5) for f in futs] == [i + 1 for i in
                                               range(1, queued + 1)]
        assert time.monotonic() - t0 < 2.0   # not the 5-s deadline
        assert sorted(b.batch_fill) == sorted(expected)
        assert _dispatch_adds_up(b) == {"free": 2, "full": queued // 4,
                                        "deadline": 0}
    finally:
        b.close()
        sched.shutdown()


def test_endpoint_batcher_free_slot_dispatches_at_once():
    """A free slot takes a lone row at once: ``max_wait`` is not waited."""
    b = EndpointBatcher("t", sync_batches(list), batch_size=4,
                        max_wait=5.0, capacity=lambda: 1)
    try:
        t0 = time.monotonic()
        assert b.submit(7).result(5) == 7
        assert time.monotonic() - t0 < 1.0
        assert _dispatch_adds_up(b) == {"free": 1, "full": 0,
                                        "deadline": 0}
    finally:
        b.close()


def test_endpoint_batcher_batch_of_one_dispatches_every_row_at_once():
    """At ``batch_size`` 1 every row is a full batch, slot or none."""
    b = EndpointBatcher("t", sync_batches(list), batch_size=1,
                        max_wait=5.0, capacity=lambda: 0)
    try:
        t0 = time.monotonic()
        assert [b.submit(i).result(5) for i in range(3)] == [0, 1, 2]
        assert time.monotonic() - t0 < 1.0
        assert list(b.batch_fill) == [1, 1, 1]
        assert _dispatch_adds_up(b) == {"free": 0, "full": 3,
                                        "deadline": 0}
    finally:
        b.close()


def test_endpoint_batcher_deadline_guard_without_free_slot():
    """No slot free and nothing of the batcher in flight (a prewarm or a
    direct submit holds the instance): the row still leaves after
    ``max_wait`` from its submit."""
    b = EndpointBatcher("t", sync_batches(list), batch_size=4,
                        max_wait=0.05, capacity=lambda: 0)
    try:
        t0 = time.monotonic()
        assert b.submit(3).result(5) == 3
        assert time.monotonic() - t0 >= 0.05
        assert _dispatch_adds_up(b) == {"free": 0, "full": 0,
                                        "deadline": 1}
    finally:
        b.close()


def test_endpoint_batcher_close_drains_pending():
    slow = threading.Event()

    def handler(payloads):
        slow.wait(0.01)
        return list(payloads)

    b = EndpointBatcher("t", sync_batches(handler), batch_size=2,
                        max_wait=0.5)    # long wait: close must not stall
    futs = [b.submit(i) for i in range(5)]
    b.close()
    assert [f.result(5) for f in futs] == list(range(5))
    with pytest.raises(RuntimeError):
        b.submit(99)
