"""Telemetry layer: span tracing, the metrics registry, exporters, and
the end-to-end guarantee the layer makes — every submitted invocation
yields exactly ONE complete span tree (closed envelope, no orphaned
phases), tracing on or off, success or failure, under concurrency and
under random pool interleavings.

FakeClock (tests/conftest.py) drives the tracer deterministically; the
stress tests interleave prewarm/acquire/kill the same way
tests/test_property.py exercises the pool state machine."""
import json
import random
import threading

import pytest
from conftest import FakeClock

from repro.core import (FreshenScheduler, FunctionSpec, PoolConfig,
                        ServiceClass, WarmthLevel)
from repro.telemetry import (NULL_SPAN, NULL_TRACER, PHASES, Counter, Gauge,
                             Histogram, MetricsRegistry, Tracer,
                             chrome_trace_events, current_span)


def _spec(name="f", app="t"):
    return FunctionSpec(name, lambda ctx, args: args, app=app)


# ----------------------------------------------------------------------
# Tracer unit tests (FakeClock-driven)

def test_span_phases_durations_and_complete(fake_clock):
    tr = Tracer(clock=fake_clock)
    span = tr.invocation("f", app="a")
    with span.phase("acquire"):
        fake_clock.advance(0.5)
    with span.phase("run", shard=3):
        fake_clock.advance(2.0)
    span.finish()
    assert span.complete()
    secs = span.phase_seconds()
    assert secs["acquire"] == pytest.approx(0.5)
    assert secs["run"] == pytest.approx(2.0)
    assert span.duration == pytest.approx(2.5)
    assert tr.spans() == [span]
    d = span.to_dict()
    assert d["phases"][1]["attrs"] == {"shard": 3}
    assert all(p["name"] in PHASES for p in d["phases"])


def test_phase_closed_on_error_and_error_annotated(fake_clock):
    tr = Tracer(clock=fake_clock)
    span = tr.invocation("f")
    with pytest.raises(ValueError):
        with span.phase("run"):
            fake_clock.advance(1.0)
            raise ValueError("boom")
    span.finish(error="ValueError")
    assert span.complete()                    # the phase still closed
    assert span.phases[0].attrs["error"] == "ValueError"
    assert span.attrs["error"] == "ValueError"


def test_finish_is_idempotent(fake_clock):
    tr = Tracer(clock=fake_clock)
    span = tr.invocation("f")
    span.finish()
    end = span.end
    fake_clock.advance(5.0)
    span.finish()
    assert span.end == end
    assert len(tr.spans()) == 1


def test_disabled_tracer_is_null_and_allocation_free(fake_clock):
    tr = Tracer(clock=fake_clock, enabled=False)
    span = tr.invocation("f")
    assert span is NULL_SPAN and not span
    assert tr.freshen("f") is NULL_SPAN
    # the null span's context managers are shared constants
    assert span.phase("run") is span.active() is NULL_SPAN.phase("x")
    with span.phase("run"):
        pass
    span.mark_submitted().annotate(x=1).finish()
    NULL_SPAN.dispatched().gated().dispatch_done()
    # plan-entry spans: the same null span, its children shared constants
    plan = tr.plan("f", "weights", "freshen")
    assert plan is NULL_SPAN and tr.invocation("f", start=1.0) is NULL_SPAN
    assert plan.child("read") is plan.active() is span.phase("run")
    assert plan.open_child("upload") is None
    plan.finish(error="x")
    assert tr.spans() == [] and tr.freshen_spans() == []
    assert tr.plan_spans() == []
    assert NULL_TRACER.invocation("g") is NULL_SPAN
    assert NULL_TRACER.plan("g", "weights", "inline") is NULL_SPAN


def test_active_span_is_thread_local_and_nests(fake_clock):
    tr = Tracer(clock=fake_clock)
    outer, inner = tr.invocation("a"), tr.invocation("b")
    assert current_span() is None
    with outer.active():
        assert current_span() is outer
        with inner.active():
            assert current_span() is inner
        assert current_span() is outer
    assert current_span() is None
    seen = []
    t = threading.Thread(target=lambda: seen.append(current_span()))
    with outer.active():
        t.start()
        t.join()
    assert seen == [None]                     # activation does not leak


def test_freshen_lands_on_nearest_anchor(fake_clock):
    tr = Tracer(clock=fake_clock, horizon=5.0)
    near = tr.freshen("f", confidence=0.9, expected_delay=1.0).dispatched()
    far = tr.freshen("f", confidence=0.9, expected_delay=4.0).dispatched()
    assert tr.pending_freshens() == 2
    fake_clock.advance(1.2)                   # nearest anchor: `near`
    inv = tr.invocation("f")
    inv.finish()
    assert near.outcome == "landed"
    assert near.linked_invocation == inv.span_id
    assert inv.linked_freshens == [near.span_id]
    assert far.outcome == "pending"           # future anchor survives
    assert tr.pending_freshens() == 1
    # the landed span is in the terminal ring, not lost
    assert near in tr.freshen_spans()


def test_freshen_expiry_sweep_and_gate(fake_clock):
    tr = Tracer(clock=fake_clock, horizon=2.0)
    fs = tr.freshen("f", expected_delay=0.0).dispatched()
    gated = tr.freshen("g").gated("policy-gated")
    assert gated.outcome == "gated" and gated.reason == "policy-gated"
    fake_clock.advance(10.0)
    assert tr.sweep_expired() == 1
    assert fs.outcome == "expired"
    outcomes = sorted(f.outcome for f in tr.freshen_spans())
    assert outcomes == ["expired", "gated"]
    assert tr.snapshot()["freshen_tally"] == {
        "landed": 0, "expired": 1, "gated": 1}


def test_arrival_expires_stale_anchors_in_passing(fake_clock):
    tr = Tracer(clock=fake_clock, horizon=1.0)
    stale = tr.freshen("f", expected_delay=0.0).dispatched()
    fake_clock.advance(50.0)
    tr.invocation("f").finish()               # way past the horizon
    assert stale.outcome == "expired"


def test_ring_buffer_bounded_and_dropped_counted(fake_clock):
    tr = Tracer(capacity=4, clock=fake_clock)
    for i in range(7):
        tr.invocation("f").finish()
    assert len(tr.spans()) == 4
    assert tr.dropped == 3
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_export_chrome_schema(fake_clock, tmp_path):
    tr = Tracer(clock=fake_clock)
    fs = tr.freshen("f", confidence=0.8, expected_delay=0.5).dispatched()
    fake_clock.advance(0.5)
    span = tr.invocation("f", app="a")
    with span.phase("run"):
        fake_clock.advance(0.1)
    span.finish()
    path = tmp_path / "trace.json"
    n = tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert len(events) == n
    inv = [e for e in events if e.get("cat") == "invocation"]
    phases = [e for e in events if e.get("cat") == "phase"]
    assert len(inv) == 1 and inv[0]["name"] == "invoke:f"
    # phases carry their owning span id (lane ids can collide)
    assert phases[0]["args"]["span"] == span.span_id
    # the landed freshen emits a flow arrow pair keyed by its id
    flows = sorted(e["ph"] for e in events if e.get("cat") == "freshen_link")
    assert flows == ["f", "s"]
    assert all(e["id"] == fs.span_id for e in events
               if e.get("cat") == "freshen_link")
    # timestamps are rebased: nothing starts before 0
    assert min(e["ts"] for e in events if "ts" in e) >= 0.0


def test_chrome_events_empty_inputs():
    assert all(e["ph"] == "M" for e in chrome_trace_events([], []))


# ----------------------------------------------------------------------
# Metrics registry

def test_counter_gauge_histogram_basics():
    c = Counter("n")
    c.inc()
    c.inc(4)
    assert c.value == 5 and int(c) == 5
    g = Gauge("g")
    g.set(2.5)
    assert g.value == 2.5
    g.set_fn(lambda: 7)
    assert g.value == 7.0
    g.set_fn(lambda: 1 / 0)                   # sampling must never raise
    assert g.value == 0.0
    h = Histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["sum"] == pytest.approx(10.0)
    assert s["min"] == 1.0 and s["max"] == 4.0
    assert h.percentile(0) == 1.0 and h.percentile(200) == 4.0   # clamped
    assert Histogram("e").summary()["p99"] == 0.0


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry("x.")
    c = reg.counter("hits")
    assert reg.counter("hits") is c
    with pytest.raises(TypeError):
        reg.gauge("hits")
    reg.gauge("depth").set(3)
    reg.histogram("lat").observe(0.25)
    snap = reg.snapshot()
    assert snap["x.hits"] == 0
    assert snap["x.depth"] == 3.0
    assert snap["x.lat"]["count"] == 1
    assert sorted(reg.names()) == ["x.depth", "x.hits", "x.lat"]


# ----------------------------------------------------------------------
# Fabric integration: scheduler, pool views, cluster

def test_scheduler_invocation_span_tree_cold_and_warm():
    tr = Tracer()
    sched = FreshenScheduler(tracer=tr)
    sched.register(_spec())
    try:
        assert sched.invoke("f", 1) == 1      # cold
        assert sched.invoke("f", 2) == 2      # warm
    finally:
        sched.shutdown()
    spans = tr.spans()
    assert len(spans) == 2
    assert all(s.complete() for s in spans)
    cold, warm = spans
    assert cold.attrs["cold"] and not warm.attrs["cold"]
    # the lazy boot path attached its phases to the cold invocation only
    assert "boot_init" in cold.phase_seconds()
    assert "boot_init" not in warm.phase_seconds()
    assert "run" in warm.phase_seconds()
    assert cold.app == "t"


def test_scheduler_failure_still_yields_complete_span():
    tr = Tracer()
    sched = FreshenScheduler(tracer=tr)
    def boom(ctx, args):
        raise RuntimeError("nope")
    sched.register(FunctionSpec("bad", boom, app="t"))
    try:
        with pytest.raises(RuntimeError):
            sched.invoke("bad", None)
    finally:
        sched.shutdown()
    (span,) = tr.spans()
    assert span.complete()
    assert span.attrs["error"] == "RuntimeError"


def test_submit_records_queue_phase_and_metrics():
    tr = Tracer()
    sched = FreshenScheduler(tracer=tr)
    sched.register(_spec())
    try:
        assert sched.submit("f", 9).result(timeout=10) == 9
    finally:
        sched.shutdown()
    (span,) = tr.spans()
    assert span.complete()
    assert "queue" in span.phase_seconds()
    snap = sched.metrics_snapshot()
    assert snap["scheduler.invoke.e2e_seconds"]["count"] == 1
    assert snap["pool.f.cold_starts"] == 1


def test_pool_counter_views_match_stats():
    sched = FreshenScheduler()
    sched.register(_spec())
    try:
        sched.invoke("f", 1)
        sched.invoke("f", 2)
    finally:
        sched.shutdown()
    pool = sched.pools["f"]
    s = pool.stats()
    assert pool.cold_starts == s["cold_starts"] == 1
    assert pool.warm_acquires == s["warm_acquires"] == 1
    assert s["cold_starts"] + s["warm_acquires"] == 2
    assert pool.metrics.snapshot()["pool.f.cold_starts"] == 1


def test_cluster_shared_tracer_links_cross_shard(tmp_path):
    from repro.cluster.router import ClusterRouter
    tr = Tracer()
    cluster = ClusterRouter.build(2, tracer=tr, pool_config=PoolConfig(
        max_instances=2, prewarm_provision=True))
    cluster.register(_spec("fr", app="bench"))
    cluster.predictor.graph.add_edge("fr", "fr", 1.0, 0.01)
    for w in cluster.workers:
        w.scheduler.accountant.service_class["bench"] = \
            ServiceClass.LATENCY_SENSITIVE
        assert w.scheduler.tracer is tr       # one tracer, whole fabric
    try:
        futs = [cluster.submit("fr", i) for i in range(8)]
        assert [f.result(timeout=30) for f in futs] == list(range(8))
    finally:
        cluster.shutdown()
    spans = tr.spans()
    assert len(spans) == 8
    assert all(s.complete() for s in spans)
    assert all("route" in s.phase_seconds() for s in spans)
    assert all(s.attrs.get("shard") in (0, 1) for s in spans)
    # at least one prewarm landed on a later arrival, linked both ways
    landed = [f for f in tr.freshen_spans() if f.outcome == "landed"]
    assert landed
    by_id = {s.span_id: s for s in spans}
    for fs in landed:
        assert fs.span_id in by_id[fs.linked_invocation].linked_freshens
    path = tmp_path / "cluster_trace.json"
    tr.export_chrome(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    assert any(e.get("cat") == "freshen_link" for e in events)


# ----------------------------------------------------------------------
# Stress: exactly one complete span tree per submitted invocation,
# across random prewarm/acquire/kill interleavings.

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_span_tree_invariant_random_interleavings(seed, fake_clock):
    rng = random.Random(seed)
    tr = Tracer(clock=fake_clock, capacity=8192)
    cfg = PoolConfig(max_instances=3, keep_alive=5.0, graded_warmth=True,
                     keep_alive_hot=2.0, keep_alive_initialized=4.0,
                     keep_alive_process=6.0)
    sched = FreshenScheduler(tracer=tr, pool_config=cfg)
    sched.register(_spec())
    sched.predictor.graph.add_edge("f", "f", 1.0, 0.01)
    pool = sched.pools["f"]
    pool.clock = fake_clock
    invoked = 0
    levels = [WarmthLevel.PROCESS, WarmthLevel.INITIALIZED, WarmthLevel.HOT]
    try:
        for _ in range(60):
            op = rng.choice(["invoke", "invoke", "prewarm", "kill",
                             "reap", "advance"])
            if op == "invoke":
                assert sched.invoke("f", invoked) == invoked
                invoked += 1
            elif op == "prewarm":
                for t in pool.prewarm_freshen(level=rng.choice(levels)):
                    t.join()
            elif op == "kill":
                idle = list(pool._idle)
                if idle:
                    pool.evict(rng.choice(idle))
            elif op == "reap":
                pool.reap()
            else:
                fake_clock.advance(rng.choice([0.5, 1.5, 3.0, 7.0]))
    finally:
        sched.shutdown()
    spans = tr.spans()
    assert len(spans) == invoked              # exactly one span per invoke
    assert all(s.complete() for s in spans)   # no orphaned phases
    assert all(set(s.phase_seconds()) <= set(PHASES) for s in spans)
    # freshen lifecycle is total: every span is terminal or still pending
    terminal = {"landed", "expired", "gated"}
    assert all(f.outcome in terminal for f in tr.freshen_spans())
    by_id = {s.span_id: s for s in spans}
    for fs in tr.freshen_spans():
        if fs.outcome == "landed":
            assert fs.span_id in by_id[fs.linked_invocation].linked_freshens


def test_span_tree_invariant_concurrent_submits():
    tr = Tracer(capacity=8192)
    sched = FreshenScheduler(tracer=tr, pool_config=PoolConfig(
        max_instances=3, prewarm_provision=True))
    sched.register(_spec())
    sched.predictor.graph.add_edge("f", "f", 1.0, 0.01)
    pool = sched.pools["f"]
    stop = threading.Event()

    def chaos():
        while not stop.is_set():
            idle = list(pool._idle)
            if idle:
                pool.evict(idle[0])
            pool.reap()

    killer = threading.Thread(target=chaos)
    killer.start()
    n = 40
    try:
        futs = [sched.submit("f", i) for i in range(n)]
        results = [f.result(timeout=30) for f in futs]
    finally:
        stop.set()
        killer.join()
        sched.shutdown()
    assert results == list(range(n))
    spans = tr.spans()
    assert len(spans) == n
    assert all(s.complete() for s in spans)
