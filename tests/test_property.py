"""Property-based tests (hypothesis) on system invariants:

1. Freshen exactly-once: under ANY interleaving of wrapper calls and freshen
   hooks, each fresh resource is executed exactly once and every fr_fetch
   returns the correct value.
2. Wrapper-result invariance: the function's observable result is identical
   whether freshen ran before, concurrently, or never (Figure 3).
3. Cache freshness: a get after TTL expiry never returns the stale value.
4. Markov predictor probabilities are a distribution and respect counts.
5. Connection model: warming never hurts; transfer time is monotone in size.
6. MoE dispatch equivalence: einsum and gather dispatch agree for any
   routing produced by random inputs.
7. Pool state machine (PR 7 warmth ladder): under ANY interleaving of
   prewarm(level)/acquire/release/reap/retire, warmth counts stay ordered
   (warm_idle <= warm_total <= size <= cap), graded reaping never skips a
   rung downward, acquire accounting balances, and every admitted future
   resolves.
8. Async admission (PR 9 hot path): under ANY interleaving of
   try_acquire/acquire_async/release/sweep/cancel, every parked callback
   fires exactly once (grant or PoolSaturated) or never if cancelled,
   grants follow admission order, waiters never starve next to idle
   capacity, and acquire accounting still balances.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional test dependency (see requirements-test.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.cache import FreshenCache
from repro.core.freshen import Action, FreshenPlan, FreshenState, PlanEntry
from repro.core.network import TIERS, Connection
from repro.core.prediction import MarkovPredictor


@settings(max_examples=25, deadline=None)
@given(n_resources=st.integers(1, 5),
       schedule=st.lists(st.sampled_from(["freshen", "fetch", "refetch"]),
                         min_size=1, max_size=8))
def test_exactly_once_any_schedule(n_resources, schedule):
    counts = [0] * n_resources

    def mk(i):
        def thunk():
            counts[i] += 1
            return f"value-{i}"
        return thunk

    plan = FreshenPlan([PlanEntry(f"r{i}", Action.FETCH, mk(i))
                        for i in range(n_resources)])
    stt = FreshenState(plan)
    for op in schedule:
        if op == "freshen":
            stt.freshen()
        else:
            for i in range(n_resources):
                assert stt.fr_fetch(i) == f"value-{i}"
    # regardless of schedule: each executed at most... exactly once if touched
    touched = any(op in ("fetch", "refetch", "freshen") for op in schedule)
    if touched:
        assert all(c == 1 for c in counts), counts


@settings(max_examples=15, deadline=None)
@given(freshen_delay_ms=st.integers(0, 20),
       call_delay_ms=st.integers(0, 20),
       run_freshen=st.booleans())
def test_result_invariant_to_freshen_timing(freshen_delay_ms, call_delay_ms,
                                            run_freshen):
    """Figure 3: whatever the relative timing, λ's result is the same."""
    def thunk():
        time.sleep(freshen_delay_ms / 1000.0)
        return 42

    stt = FreshenState(FreshenPlan([PlanEntry("r", Action.FETCH, thunk)]))
    if run_freshen:
        th = threading.Thread(target=stt.freshen, daemon=True)
        th.start()
    time.sleep(call_delay_ms / 1000.0)
    assert stt.fr_fetch(0) == 42
    if run_freshen:
        th.join()
    # and afterwards the entry is FINISHED exactly once
    s = stt.stats()
    assert s["freshened"] + s["inline"] == 1


@settings(max_examples=30, deadline=None)
@given(ttl=st.floats(0.1, 100.0), dt=st.floats(0.0, 200.0))
def test_cache_never_returns_expired(ttl, dt):
    now = [0.0]
    c = FreshenCache(clock=lambda: now[0])
    c.put("k", "old", ttl=ttl)
    now[0] = dt
    hit, val = c.get("k")
    if dt > ttl:
        assert not hit
    else:
        assert hit and val == "old"


@settings(max_examples=20, deadline=None)
@given(trace=st.lists(st.sampled_from("abc"), min_size=6, max_size=40))
def test_markov_probabilities_form_distribution(trace):
    m = MarkovPredictor(min_count=1)
    for i, fn in enumerate(trace):
        m.observe(fn, float(i))
    for fn in "abc":
        preds = m.successors(fn, top_k=10)
        if preds:
            total = sum(p.probability for p in preds)
            assert 0 < total <= 1.0 + 1e-9
            assert all(0 < p.probability <= 1 for p in preds)


@settings(max_examples=20, deadline=None)
@given(size_mb=st.floats(0.01, 50.0),
       tier=st.sampled_from(["local", "edge", "remote"]))
def test_warming_never_hurts(size_mb, tier):
    nbytes = size_mb * 1024 * 1024
    cold = Connection(TIERS[tier])
    cold.establish()
    t_cold = cold.transfer(nbytes)
    warm = Connection(TIERS[tier])
    warm.establish()
    warm.warm()
    t_warm = warm.transfer(nbytes)
    assert t_warm <= t_cold + 1e-9


@settings(max_examples=10, deadline=None)
@given(a_mb=st.floats(0.01, 10.0), b_mb=st.floats(0.01, 10.0))
def test_transfer_monotone_in_size(a_mb, b_mb):
    lo, hi = sorted([a_mb, b_mb])
    c1 = Connection(TIERS["edge"]); c1.establish()
    c2 = Connection(TIERS["edge"]); c2.establish()
    assert c1.transfer(lo * 2**20) <= c2.transfer(hi * 2**20) + 1e-9


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), toks=st.sampled_from([32, 64]))
def test_moe_dispatch_paths_agree(seed, toks):
    import dataclasses
    from repro.configs import get_config
    from repro.models.moe import init_moe, moe_apply
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                              dtype="float32")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, toks, cfg.d_model),
                          jnp.float32)
    out_e, _ = moe_apply(p, x, cfg)
    cfg_g = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="gather"))
    out_g, _ = moe_apply(p, x, cfg_g)
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_g),
                               atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# Pool state machine under random interleavings (PR 7 warmth ladder).
# FakeClock comes straight from conftest (hypothesis forbids
# function-scoped fixtures under @given); warm-up threads are joined
# after each op so the interleaving stays the one hypothesis chose.
from conftest import FakeClock  # noqa: E402

from repro.core import (FreshenScheduler, FunctionSpec, InstancePool,  # noqa: E402
                        PoolConfig, PoolSaturated, WarmthLevel)

_POOL_OPS = st.sampled_from(
    ["acquire", "release", "reap", "advance",
     "prewarm_process", "prewarm_init", "prewarm_hot"])


def _pool_invariants(pool, cap, acquires):
    size = pool.size()
    warm_idle = pool.warm_idle_count()
    warm_total = pool.warm_total_count()
    assert warm_idle <= warm_total <= size <= cap
    # the ladder is cumulative: counting from a lower rung up can only
    # see more instances
    assert (pool.warm_idle_count(WarmthLevel.PROCESS)
            >= pool.warm_idle_count(WarmthLevel.INITIALIZED)
            >= pool.warm_idle_count(WarmthLevel.HOT))
    s = pool.stats()
    assert sum(s["levels"].values()) == size
    # every admitted acquire was billed exactly once, cold or warm
    assert s["cold_starts"] + s["warm_acquires"] == acquires


@settings(max_examples=30, deadline=None)
@given(ops=st.lists(st.tuples(_POOL_OPS, st.integers(0, 7)),
                    min_size=1, max_size=40),
       graded=st.booleans())
def test_pool_state_machine_invariants(ops, graded):
    clock = FakeClock()
    cap = 3
    cfg = PoolConfig(max_instances=cap, keep_alive=10.0,
                     graded_warmth=graded, keep_alive_hot=4.0,
                     keep_alive_initialized=8.0, keep_alive_process=12.0)
    pool = InstancePool(FunctionSpec("p", lambda ctx, args: args, app="prop"),
                        cfg, clock=clock)
    levels = {"prewarm_process": WarmthLevel.PROCESS,
              "prewarm_init": WarmthLevel.INITIALIZED,
              "prewarm_hot": WarmthLevel.HOT}
    held, acquires = [], 0
    try:
        for op, k in ops:
            if op == "acquire":
                try:
                    inst, _, _ = pool.acquire(timeout=0.0)
                    held.append(inst)
                    acquires += 1
                except PoolSaturated:
                    pass
            elif op == "release":
                if held:
                    pool.release(held.pop(k % len(held)))
            elif op == "advance":
                clock.advance((1.0, 3.0, 5.0, 9.0, 13.0)[k % 5])
            elif op == "reap":
                before = {iid: inst.runtime.warmth
                          for iid, inst in pool._instances.items()}
                pool.reap()
                for iid, inst in pool._instances.items():
                    # graded expiry walks at most ONE rung per sweep;
                    # binary reaping never demotes at all
                    floor = before[iid] - 1 if graded else before[iid]
                    assert inst.runtime.warmth >= floor, \
                        (before[iid], inst.runtime.warmth)
            else:
                for th in pool.prewarm_freshen(max_dispatch=1,
                                               provision=True,
                                               level=levels[op]):
                    th.join(10.0)
            _pool_invariants(pool, cap, acquires)
    finally:
        pool.retire()
        for inst in held:
            pool.release(inst)
    assert pool.size() == 0 and pool.idle_count() == 0


@settings(max_examples=8, deadline=None)
@given(ops=st.lists(st.sampled_from(
    ["submit", "prewarm_hot", "prewarm_process", "sweep", "idle"]),
    min_size=1, max_size=12))
def test_scheduler_never_loses_admitted_futures(ops):
    """Whatever interleaving of traffic, partial/full prewarms and reap
    sweeps hits a graded pool, every future submit() admitted resolves to
    the right value — demotion and scale-to-zero may slow an arrival but
    can never drop or corrupt one."""
    sched = FreshenScheduler(pool_config=PoolConfig(
        max_instances=2, keep_alive=0.2, graded_warmth=True,
        keep_alive_hot=0.02, keep_alive_initialized=0.05,
        keep_alive_process=0.2, prewarm_provision=True))
    sched.register(FunctionSpec("g", lambda ctx, args: ("ok", args),
                                app="prop"))
    futs = []
    try:
        for i, op in enumerate(ops):
            if op == "submit":
                futs.append((i, sched.submit("g", i,
                                             freshen_successors=False)))
            elif op == "prewarm_hot":
                sched.prewarm("g", level=WarmthLevel.HOT)
            elif op == "prewarm_process":
                sched.prewarm("g", level=WarmthLevel.PROCESS)
            elif op == "sweep":
                sched.pools["g"].reap()
            else:
                time.sleep(0.03)       # let keep-alives expire for real
        for i, f in futs:
            assert f.result(timeout=30) == ("ok", i)
        s = sched.pools["g"].stats()
        assert s["cold_starts"] + s["warm_acquires"] == len(futs)
    finally:
        sched.shutdown()


# ----------------------------------------------------------------------
# Async admission machine under random interleavings (PR 9 hot path).
# Single-threaded on purpose: acquire_async fires callbacks synchronously
# on the driving thread (immediate grants) or on the releasing/sweeping
# thread (handoffs/expiries), so hypothesis fully controls the order.

_ASYNC_OPS = st.sampled_from(
    ["try", "park", "park_expired", "release", "sweep", "cancel"])


@settings(max_examples=40, deadline=None)
@given(ops=st.lists(st.tuples(_ASYNC_OPS, st.integers(0, 7)),
                    min_size=1, max_size=50))
def test_async_admission_interleavings(ops):
    cap = 2
    pool = InstancePool(FunctionSpec("p", lambda ctx, args: args, app="prop"),
                        PoolConfig(max_instances=cap, keep_alive=60.0))
    held = []          # instances this driver owns (try hits + grants)
    records = []       # one dict per acquire_async, in admission order
    seq, try_hits = [0], [0]
    served_order = []

    def park(timeout=None):
        rec = {"seq": seq[0], "fired": 0, "inst": None, "error": None,
               "cancelled": False}
        seq[0] += 1

        def cb(inst, queue_delay, cold, error):
            rec["fired"] += 1
            rec["inst"], rec["error"] = inst, error
            if inst is not None:
                held.append(inst)
                served_order.append(rec["seq"])
        rec["handle"] = pool.acquire_async(cb, timeout=timeout)
        records.append(rec)

    def check():
        # a parked waiter next to an idle instance means starvation:
        # release hands off directly and try_acquire never queue-jumps
        assert not (pool.idle_count() > 0 and pool.waiting_count() > 0)
        for r in records:
            assert r["fired"] <= 1                      # at most once, ever
            if r["cancelled"]:
                assert r["fired"] == 0                  # cancelled: never
        # grants are handed out in admission order
        assert served_order == sorted(served_order)

    try:
        for op, k in ops:
            if op == "try":
                got = pool.try_acquire()
                if got is not None:
                    held.append(got[0])
                    try_hits[0] += 1
            elif op == "park":
                park()
            elif op == "park_expired":
                park(timeout=0.0)       # expires on the next sweep
            elif op == "release":
                if held:
                    pool.release(held.pop(k % len(held)))
            elif op == "sweep":
                pool.sweep_waiters()
            else:   # cancel the oldest still-pending waiter
                for r in records:
                    if not r["cancelled"] and r["handle"].pending:
                        r["cancelled"] = r["handle"].cancel()
                        break
            check()

        # drain: hand everything back, then sweep out any zero-timeout
        # stragglers — no admitted waiter may be left unresolved
        while held:
            pool.release(held.pop())
            check()
        pool.sweep_waiters()
        pool.retire()                   # fails any remaining waiters
        for r in records:
            if r["cancelled"]:
                assert r["fired"] == 0
            else:
                assert r["fired"] == 1, "admitted waiter dropped"
                assert (r["inst"] is not None) ^ isinstance(r["error"],
                                                            PoolSaturated)
        s = pool.stats()
        grants = sum(1 for r in records if r["inst"] is not None)
        # every admission — inline hit or async grant — billed exactly once
        assert s["cold_starts"] + s["warm_acquires"] == grants + try_hits[0]
        assert pool.waiting_count() == 0
    finally:
        while held:
            pool.release(held.pop())
        pool.retire()
