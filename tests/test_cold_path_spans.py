"""Spans of the cold path and the batcher: a span per freshen-plan entry
(by the freshen hook or inline, linked to the invocation), the weights
entry's ``read``/``upload`` children, the ``fr_wait`` and ``batch_wait``
phases, and their ``fabric.*`` annotations on a real profiler's host
plane.  FakeClock drives the tracer wherever no real wait is timed."""
import dataclasses
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Action, FreshenPlan, FreshenState, PlanEntry
from repro.core.freshen import FrState
from repro.serving import ModelEndpoint, ServingEngine, WeightStore
from repro.serving.batching import EndpointBatcher
from repro.serving.engine import batch_span
from repro.telemetry import PHASES, Tracer, current_plan


def _wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.005)


def _state(tracer, thunks, fn="f"):
    plan = FreshenPlan([PlanEntry(name, Action.FETCH, thunk)
                        for name, thunk in thunks])
    return FreshenState(plan, clock=tracer.clock, tracer=tracer, fn=fn)


@pytest.fixture(scope="module")
def tiny_store(tmp_path_factory):
    cfg = dataclasses.replace(
        get_config("qwen2-0.5b").reduced(d_model=64), vocab_size=128)
    from repro.models import make_model
    params = make_model(cfg).init(jax.random.PRNGKey(0))
    store = WeightStore(str(tmp_path_factory.mktemp("w")))
    store.publish("tiny", params)
    return cfg, store, params


# ----------------------------------------------------------------------
# plan-entry spans

def test_plan_spans_by_freshen_and_inline(fake_clock):
    tr = Tracer(clock=fake_clock)

    def slow():
        fake_clock.advance(2.0)
        return "w"
    st = _state(tr, [("weights", slow), ("compiled", lambda: "c")])
    st.freshen()                                  # Algorithm 2: both entries
    st.invalidate(1)
    inv = tr.invocation("f")
    with inv.active():
        assert st.fr_fetch(0) == "w"              # a hit: no new span
        assert st.fr_fetch(1) == "c"              # inline
    inv.finish()
    plans = tr.plan_spans()
    assert [(p.entry, p.by) for p in plans] == [
        ("weights", "freshen"), ("compiled", "freshen"),
        ("compiled", "inline")]
    assert plans[0].invocation is None and plans[1].invocation is None
    assert plans[2].invocation == inv.span_id
    assert plans[0].duration == pytest.approx(2.0)
    assert all(p.complete() and p.fn == "f" for p in plans)
    assert inv.complete()
    d = plans[2].to_dict()
    assert d["kind"] == "plan" and d["by"] == "inline"
    assert tr.snapshot()["plans"][0]["entry"] == "weights"


def test_plan_span_of_a_failed_entry_is_closed(fake_clock):
    tr = Tracer(clock=fake_clock)

    def boom():
        raise ValueError("no weights")
    st = _state(tr, [("weights", boom)])
    st.freshen()                                  # freshen failure is not fatal
    with pytest.raises(ValueError):
        st.fr_fetch(0)
    plans = tr.plan_spans()
    assert [p.by for p in plans] == ["freshen", "inline"]
    assert all(p.complete() and p.attrs["error"] == "ValueError"
               for p in plans)


def test_open_child_holds_the_span_until_it_closes(fake_clock):
    tr = Tracer(clock=fake_clock)
    span = tr.plan("f", "weights", "freshen")
    with span.child("read"):
        fake_clock.advance(1.0)
    up = span.open_child("upload", bytes=8)
    span.finish()
    assert tr.plan_spans() == []                  # the upload is in flight
    fake_clock.advance(0.5)
    span.close_child(up)
    assert tr.plan_spans() == [span] and span.complete()
    assert span.child_seconds() == {"read": pytest.approx(1.0),
                                    "upload": pytest.approx(0.5)}
    assert up.end > span.end                      # outlasted the entry


def test_weights_entry_reads_and_uploads(tiny_store):
    cfg, store, params = tiny_store
    tr = Tracer()
    span = tr.plan("tiny", "weights", "freshen")
    with span.active():
        assert current_plan() is span
        loaded = store.load("tiny")
    assert current_plan() is None
    span.finish()
    _wait_for(lambda: tr.plan_spans())
    (got,) = tr.plan_spans()
    assert [c.name for c in got.children] == ["read", "upload"]
    read, upload = got.children
    assert upload.attrs["bytes"] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(loaded))
    assert upload.attrs["bytes"] == sum(
        np.asarray(leaf).nbytes for leaf in jax.tree.leaves(params))
    assert read.start <= read.end <= upload.start <= upload.end
    assert got.complete()
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_untraced_load_starts_no_upload_thread(tiny_store, monkeypatch):
    from repro.serving import weights
    cfg, store, params = tiny_store

    def traced(*a):
        raise AssertionError("an untraced load waited for its upload")
    monkeypatch.setattr(weights, "_timed_upload", traced)
    loaded = store.load("tiny")                   # no current plan
    assert len(jax.tree.leaves(loaded)) == len(jax.tree.leaves(params))


def test_endpoint_cold_start_spans_link_to_the_invocation(tiny_store):
    cfg, store, _ = tiny_store
    tr = Tracer()
    eng = ServingEngine(tracer=tr)
    try:
        ep = ModelEndpoint("tiny", cfg, store, batch_size=2, seq_len=8)
        eng.deploy(ep)                            # init only: plan not run
        eng.invoke("tiny", np.zeros((2, 8), np.int32),
                   freshen_successors=False)
        (inv,) = tr.spans()
        _wait_for(lambda: len(tr.plan_spans()) == 3)
        plans = tr.plan_spans()
        assert [p.entry for p in plans] == ["weights", "compiled", "warmup"]
        assert all(p.by == "inline" and p.invocation == inv.span_id
                   and p.complete() for p in plans)
        assert [c.name for c in plans[0].children] == ["read", "upload"]
        run = next(ph for ph in inv.phases if ph.name == "run")
        assert all(run.start <= p.start and p.end <= run.end for p in plans)
    finally:
        eng.close()


def test_untraced_plan_entries_allocate_nothing_in_the_tracer():
    import tracemalloc
    from repro.telemetry import tracer as tracer_mod
    st = _state(Tracer(enabled=False), [("weights", lambda: 1),
                                        ("warmup", lambda: None)])
    tracemalloc.start()
    try:
        for _ in range(200):
            st.invalidate()
            st.freshen()
            st.invalidate()
            st.fr_fetch(0)
            st.fr_warm(1)
            st.fr_wait(0)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    mine = snap.filter_traces([tracemalloc.Filter(True, tracer_mod.__file__)])
    assert mine.statistics("filename") == []
    assert st.stats()["freshened"] == st.stats()["inline"] == 400


# ----------------------------------------------------------------------
# fr_wait

def test_fr_wait_phase_while_a_freshen_runs():
    tr = Tracer()
    go = threading.Event()

    def load():
        go.wait(5.0)
        return "w"
    st = _state(tr, [("weights", load)])
    freshen = threading.Thread(target=st.freshen)
    freshen.start()
    _wait_for(lambda: st.entries[0].state is FrState.RUNNING)
    inv = tr.invocation("f")
    released = []

    def release():                     # once fr_fetch is in FrWait
        _wait_for(lambda: st.entries[0].wait_count)
        released.append(time.monotonic())
        go.set()
    threading.Thread(target=release).start()
    with inv.active():
        assert st.fr_fetch(0) == "w"
    inv.finish()
    freshen.join()
    (wait,) = [ph for ph in inv.phases if ph.name == "fr_wait"]
    assert wait.attrs == {"entry": "weights"}
    assert wait.start < released[0] <= wait.end   # blocked until the load
    assert inv.complete() and "fr_wait" in PHASES
    (plan,) = tr.plan_spans()
    assert plan.by == "freshen" and plan.invocation is None
    assert plan.end <= wait.end                   # waited for that entry


def test_untraced_fr_wait_records_nothing(fake_clock):
    st = _state(Tracer(clock=fake_clock, enabled=False),
                [("weights", lambda: 1)])
    st.entries[0].state = FrState.FINISHED
    st.fr_wait(0)                                 # no span, nothing to wait
    assert st.stats()["waits"] == 1


# ----------------------------------------------------------------------
# batch_wait

def test_batch_span_opens_at_the_oldest_row(fake_clock):
    tr = Tracer(clock=fake_clock)
    fake_clock.set(10.0)
    span = batch_span(tr, "e", "app", 3, [9.99, 9.995, 9.998])
    (wait,) = span.phases
    assert wait.name == "batch_wait" and span.start == 9.99
    assert wait.start == 9.99 and wait.end == 10.0
    assert wait.attrs["waits"] == pytest.approx([0.01, 0.005, 0.002])
    with span.phase("run"):
        fake_clock.advance(1.0)
    span.finish()
    assert span.complete()
    assert span.attrs == {"batch": True, "fill": 3}
    plain = batch_span(tr, "e", "app", 1)
    assert plain.start == fake_clock() and plain.phases == []


def test_batcher_hands_over_the_rows_submit_times():
    seen = []

    def run_batch(payloads, submitted=None):
        seen.append((list(payloads), submitted))
        from concurrent.futures import Future
        fut = Future()
        fut.set_result(list(payloads))
        return fut

    traced = EndpointBatcher("t", run_batch, batch_size=2, max_wait=5.0,
                             submit_times=True)  # fills at two rows
    plain = EndpointBatcher("p", run_batch, batch_size=2, max_wait=0.02)
    try:
        t0 = time.monotonic()
        futs = [traced.submit(i) for i in range(2)]
        t1 = time.monotonic()
        assert [f.result(5) for f in futs] == [0, 1]
        assert plain.submit(7).result(5) == 7
    finally:
        traced.close()
        plain.close()
    (rows, submitted), (prow, psub) = seen
    assert rows == [0, 1] and len(submitted) == 2
    assert t0 <= submitted[0] <= submitted[1] <= t1
    assert prow == [7] and psub is None


def test_engine_batches_carry_batch_wait(tiny_store):
    cfg, store, _ = tiny_store
    tr = Tracer()
    eng = ServingEngine(tracer=tr)
    try:
        ep = ModelEndpoint("tiny", cfg, store, batch_size=2, seq_len=8)
        eng.deploy(ep, batch_size=2, batch_max_wait=0.05)
        t0 = time.monotonic()
        futs = [eng.submit_request("tiny", np.zeros(8, np.int32))
                for _ in range(2)]
        for f in futs:
            f.result(60)
        spans = [s for s in tr.spans() if s.attrs.get("batch")]
        waits = [w for s in spans for ph in s.phases
                 if ph.name == "batch_wait" for w in ph.attrs["waits"]]
        assert len(waits) == 2 and all(0 <= w < 60 for w in waits)
        for s in spans:
            assert s.complete() and s.start >= t0
            assert s.phases[0].name == "batch_wait"
            assert s.phases[0].start == s.start
    finally:
        eng.close()


# ----------------------------------------------------------------------
# the profiler's host plane

def test_cold_start_annotations_on_the_profiler_host_plane(tiny_store,
                                                           tmp_path):
    cfg, store, _ = tiny_store
    tr = Tracer()
    eng = ServingEngine(tracer=tr)
    try:
        ep = ModelEndpoint("tiny", cfg, store, batch_size=2, seq_len=8)
        eng.deploy(ep)
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.invoke("tiny", np.zeros((2, 8), np.int32),
                       freshen_successors=False)
            _wait_for(lambda: len(tr.plan_spans()) == 3)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"fabric.plan.weights.read", "fabric.plan.weights.upload",
            "fabric.plan.weights", "fabric.plan.compiled", "fabric.run",
            "fabric.acquire"} <= names
    assert not any(n.startswith("fabric.queue") for n in names)
