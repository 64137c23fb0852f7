"""Serving substrate: endpoints, freshen integration end-to-end with REAL
XLA compiles and weight loads, batching, datastore, warm budget."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sync_batches

from repro.configs import get_config
from repro.serving import (EndpointBatcher, Executor, ModelEndpoint,
                           ServingEngine, TieredDatastore, WarmBudget,
                           WeightStore)


@pytest.fixture(scope="module")
def tiny_setup(tmp_path_factory):
    root = tmp_path_factory.mktemp("stores")
    cfg = get_config("qwen2-0.5b").reduced(d_model=128)
    cfg = dataclasses.replace(cfg, vocab_size=256)
    store = WeightStore(str(root / "weights"))
    from repro.models import make_model
    params = make_model(cfg).init(jax.random.PRNGKey(0))
    store.publish("tiny", params)
    return cfg, store, root


def test_executor_compile_cache(tiny_setup):
    cfg, store, root = tiny_setup
    ex = Executor()
    sds = jax.ShapeDtypeStruct

    def f(x):
        return x * 2.0

    c1, dt1 = ex.compile("f", f, (sds((4,), jnp.float32),))
    c2, dt2 = ex.compile("f", f, (sds((4,), jnp.float32),))
    assert dt1 > 0 and dt2 == 0.0 and c1 is c2
    assert ex.hit_count == 1
    out = c1(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_endpoint_cold_vs_freshened(tiny_setup):
    """The headline effect: freshen-before removes weight-load + compile +
    warmup from the invocation critical path (Figs 5/6 analogue, real XLA)."""
    cfg, store, root = tiny_setup
    ds = TieredDatastore(str(root / "data1"), tier="local")
    ds.put("embedding-table", {"v": 1})

    def make_ep(name):
        return ModelEndpoint(name, cfg, store, Executor(), batch_size=2,
                             seq_len=16, datastore=ds,
                             prefetch_key="embedding-table")

    toks = np.zeros((2, 16), np.int32)
    eng = ServingEngine()

    # cold endpoint, no freshen
    # NOTE: 'tiny' is the stored weight name; endpoint name must match
    ep_cold = make_ep("tiny")
    rt_cold = eng.deploy(ep_cold)
    out_cold = eng.invoke("tiny", toks, freshen_successors=False)
    t_cold = out_cold["timing"]["total"]

    # freshened endpoint (same everything, separate runtime+executor)
    ep_warm = ModelEndpoint("tiny", cfg, store, Executor(), batch_size=2,
                            seq_len=16, datastore=ds,
                            prefetch_key="embedding-table")
    eng2 = ServingEngine()
    rt_warm = eng2.deploy(ep_warm)
    rt_warm.freshen(blocking=True)
    out_warm = eng2.invoke("tiny", toks, freshen_successors=False)
    t_warm = out_warm["timing"]["total"]

    np.testing.assert_allclose(out_cold["logits"], out_warm["logits"],
                               atol=1e-5)
    assert t_warm < t_cold, (t_warm, t_cold)
    # compile dominated the cold path; it must be ~gone when freshened
    assert out_warm["timing"]["compile"] < 0.1 * out_cold["timing"]["compile"] \
        or out_warm["timing"]["compile"] < 0.01
    st = rt_warm.fr_state.stats()
    assert st["freshened"] >= 3 and st["inline"] == 0


def test_chain_freshen_next_stage(tiny_setup):
    """Two-stage pipeline: invoking stage1 freshens stage2 within the
    trigger window, so stage2's critical path is warm."""
    cfg, store, root = tiny_setup
    eng = ServingEngine()
    for name in ("stage1", "stage2"):
        store.publish(name, jax.tree.map(lambda x: x,  # reuse tiny weights
                                         _params(cfg)))
        eng.deploy(ModelEndpoint(name, cfg, store, Executor(),
                                 batch_size=2, seq_len=16))
    eng.chain(["stage1", "stage2"])
    toks = np.zeros((2, 16), np.int32)
    out1 = eng.invoke("stage1", toks)            # dispatches freshen(stage2)
    eng.scheduler.runtimes["stage2"].join_freshen(timeout=30)
    out2 = eng.invoke("stage2", toks, freshen_successors=False)
    assert out2["timing"]["compile"] < 0.05, out2["timing"]
    st = eng.scheduler.runtimes["stage2"].fr_state.stats()
    assert st["freshened"] >= 2
    assert st["hits"] >= 2


def _params(cfg):
    from repro.models import make_model
    return make_model(cfg).init(jax.random.PRNGKey(0))


def test_warm_budget_gating(tiny_setup):
    cfg, store, root = tiny_setup
    wb = WarmBudget(min_repetitions=2)
    key = ("m", 2, 16)
    assert not wb.allows(key)
    wb.observe(key); wb.observe(key)
    assert wb.allows(key)


def test_batcher_groups_requests():
    calls = []

    def handler(payloads):
        calls.append(len(payloads))
        return [p * 2 for p in payloads]

    b = EndpointBatcher("t", sync_batches(handler), batch_size=4,
                        max_wait=0.05)
    futs = [b.submit(i) for i in range(10)]
    results = [f.result(timeout=5) for f in futs]
    assert results == [i * 2 for i in range(10)]
    assert sum(calls) == 10
    assert max(calls) <= 4
    b.close()
    assert b.stats()["requests"] == 10


def test_datastore_versioning(tmp_path):
    ds = TieredDatastore(str(tmp_path / "ds"), tier="edge")
    ds.put("k", [1, 2, 3])
    v1 = ds.version("k")
    val, t = ds.get("k")
    assert val == [1, 2, 3] and t > 0
    ds.put("k", [4])
    assert ds.version("k") == v1 + 1


def test_weight_store_version_staleness(tiny_setup, tmp_path):
    """New published weights must be picked up via version_fn staleness."""
    cfg, _, _ = tiny_setup
    store = WeightStore(str(tmp_path / "w2"))
    p1 = _params(cfg)
    store.publish("m", p1)
    ep = ModelEndpoint("m", cfg, store, Executor(), batch_size=1, seq_len=8)
    eng = ServingEngine()
    rt = eng.deploy(ep)
    rt.freshen(blocking=True)
    toks = np.zeros((1, 8), np.int32)
    out1 = eng.invoke("m", toks, freshen_successors=False)
    # publish v2 with different weights
    p2 = jax.tree.map(lambda x: x + 0.01 * jnp.ones_like(x), p1)
    store.publish("m", p2)
    out2 = eng.invoke("m", toks, freshen_successors=False)  # stale -> reload
    assert not np.allclose(out1["logits"], out2["logits"])
    assert store.load_count >= 2
