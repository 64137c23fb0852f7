"""An expert layer that holds a share of the experts (``MoEConfig.held``),
dispatched without drops, at a reduced DeepSeek-V2 size in float32: the
shares add up to the whole layer, the worst routing drops nothing, the
published gates are not renormalised, and the rows routed to each held
expert reach the invocation span."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model, moe
from repro.models.layers import mlp_apply

EXPERTS, SHARES = 16, 4


def _cfg(**moe_kw):
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    e = dataclasses.replace(cfg.moe, n_experts=EXPERTS, top_k=6, n_shared=2,
                            d_ff=48, **moe_kw)
    return dataclasses.replace(cfg, dtype="float32", remat=False, moe=e)


def _share(p, first, count):
    return dict(p, **{k: p[k][first:first + count]
                      for k in ("wi_e", "wg_e", "wo_e")})


def _x(T=40, d=None, seed=1):
    d = d or _cfg().d_model
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T // 2, d))


def test_shares_add_up_to_the_whole_layer():
    whole = _cfg()
    p = moe.init_moe(jax.random.PRNGKey(0), whole)
    x = _x()
    size = EXPERTS // SHARES
    with jax.default_matmul_precision("highest"):
        want, _ = moe.moe_ref(p, x, whole)
        shared = mlp_apply(p["shared"], x, whole.activation)
        total, rows = shared, []
        for j in range(SHARES):
            cfg = _cfg(held=(j * size, size), dispatch="dropless")
            out, _, r = moe.moe_apply(_share(p, j * size, size), x, cfg,
                                      rows=True)
            total = total + (out - shared)     # the shared experts once
            rows.append(np.asarray(r))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.concatenate(rows).sum() == x.shape[0] * x.shape[1] * 6


def test_a_share_holds_and_counts_only_its_experts():
    cfg = _cfg(held=(8, 4), dispatch="dropless")
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    assert p["wi_e"].shape[0] == 4 and p["router"].shape[-1] == EXPERTS
    leaves = jax.tree.leaves(jax.eval_shape(
        lambda: make_model(cfg).init(jax.random.PRNGKey(0))))
    assert sum(int(np.prod(x.shape)) for x in leaves) == cfg.param_count()
    x = _x()
    with jax.default_matmul_precision("highest"):
        out, _, rows = moe.moe_apply(p, x, cfg, rows=True)
        want, _ = moe.moe_ref(p, x, cfg)
        _, idx, _ = moe._routing(p, x.reshape(-1, x.shape[-1]), cfg.moe)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=EXPERTS)[8:12]
    np.testing.assert_array_equal(np.asarray(rows), counts)


def test_a_share_needs_the_dropless_dispatch():
    cfg = _cfg(held=(0, 4))
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="dropless"):
        moe.moe_apply(p, _x(), cfg)


def test_dropless_under_the_worst_routing():
    """Zero router weights: every gate ties, every token picks experts
    0-5, all held here; a capacity dispatch drops most of those rows, the
    dropless one none."""
    cfg = _cfg(held=(0, 8), dispatch="dropless")
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    p["router"] = jnp.zeros_like(p["router"])
    x = _x(T=64)
    with jax.default_matmul_precision("highest"):
        out, _, rows = moe.moe_apply(p, x, cfg, rows=True)
        want, _ = moe.moe_ref(p, x, cfg)
        whole = _cfg(dispatch="gather")
        pw = moe.init_moe(jax.random.PRNGKey(0), whole)
        pw = dict(pw, router=jnp.zeros_like(pw["router"]),
                  **{k: pw[k].at[:8].set(p[k]) for k in ("wi_e", "wg_e",
                                                         "wo_e")})
        capped, _ = moe.moe_apply(pw, x, whole)
    np.testing.assert_array_equal(np.asarray(rows), [64] * 6 + [0, 0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(capped) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("norm_topk, scale", [(False, 1.0), (False, 2.5),
                                              (True, 1.0)])
def test_gates(norm_topk, scale):
    cfg = _cfg(norm_topk=norm_topk, routed_scale=scale)
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x2d = _x().reshape(-1, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        probs, idx, _ = moe._routing(p, x2d, cfg.moe)
        gates = jax.nn.softmax(x2d @ p["router"], axis=-1)
    top = np.take_along_axis(np.asarray(gates), np.asarray(idx), axis=-1)
    want = top / top.sum(-1, keepdims=True) if norm_topk else top * scale
    np.testing.assert_allclose(np.asarray(probs), want, rtol=1e-6)
    sums = np.asarray(probs).sum(-1)
    if norm_topk:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-6)
    else:                                  # the top-k of 16 gates, unscaled
        assert (sums < scale).all()


def test_forward_counts_rows_per_expert_layer():
    base = dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(
        n_layers=2), dtype="float32")
    cfg = dataclasses.replace(
        base, n_layers=3, segments=((("mla",), 1), (("mla_moe",), 2)),
        moe=dataclasses.replace(base.moe, n_experts=8, top_k=3,
                                held=(2, 4), dispatch="dropless"))
    m = make_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    x, _ = m.forward(params, toks)
    x2, _, rows = m.forward(params, toks, expert_rows=True)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x2))
    assert rows.shape == (2, 4) and rows.dtype == jnp.int32
    assert (np.asarray(rows).sum(-1) <= 2 * 32 * 3).all()
    assert np.asarray(rows).sum() > 0


def test_endpoint_records_expert_rows_on_its_span(tmp_path):
    from repro.serving import ModelEndpoint, ServingEngine, WeightStore
    from repro.telemetry import Tracer
    base = get_config("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_experts=8, top_k=3, held=(0, 4), dispatch="dropless"))
    store = WeightStore(str(tmp_path))
    store.publish("ds", make_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(tracer=Tracer(capacity=64))
    try:
        ep = ModelEndpoint("ds", cfg, store, batch_size=1, seq_len=16)
        engine.deploy(ep, batch_size=1)
        row = np.arange(16, dtype=np.int32)
        logits = engine.submit_request("ds", row).result(timeout=300)
        assert np.asarray(logits).shape[-1] == cfg.vocab_size
    finally:
        engine.close()
    spans = [s for s in engine.tracer.spans() if "expert_rows" in s.attrs]
    assert spans
    rows = np.asarray(spans[-1].attrs["expert_rows"])
    n_moe = sum(k == "mla_moe" for k in cfg.layer_kinds)
    assert rows.shape == (n_moe, 4) and rows.sum() > 0
