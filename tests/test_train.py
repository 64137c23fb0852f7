"""Training substrate: optimizer math, microbatching equivalence, loss
actually decreases, data pipeline determinism, checkpoint round-trip."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_metadata, load_pytree, save_pytree
from repro.configs import get_config
from repro.data import DataConfig, packed_batches
from repro.models import make_model
from repro.train import (OptimizerConfig, Trainer, TrainerConfig,
                         adamw_update, init_opt_state, make_train_step,
                         schedule)


def test_schedule_shape():
    cfg = OptimizerConfig(peak_lr=1e-3, min_lr=1e-4, warmup_steps=10,
                          total_steps=100)
    lrs = [float(schedule(cfg, jnp.asarray(s))) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 1e-3) < 1e-9            # peak after warmup
    assert lrs[-1] == pytest.approx(1e-4, rel=1e-3)
    assert all(a >= b - 1e-12 for a, b in zip(lrs[1:], lrs[2:]))  # decays


def test_adamw_moves_toward_minimum():
    cfg = OptimizerConfig(peak_lr=0.1, warmup_steps=0, total_steps=1000,
                          weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    st = init_opt_state(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}          # d/dw of w^2
        params, st, m = adamw_update(cfg, params, grads, st)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_clipping_bounds_update():
    cfg = OptimizerConfig(peak_lr=1.0, warmup_steps=0, clip_norm=1.0,
                          weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    st = init_opt_state(params)
    _, _, metrics = adamw_update(cfg, params, {"w": jnp.full(4, 1e6)}, st)
    assert float(metrics["grad_norm"]) > 1e5    # raw norm reported


def test_microbatching_matches_full_batch():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(d_model=128),
                              dtype="float32", vocab_size=256)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = OptimizerConfig(warmup_steps=0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, 256)
    batch = {"tokens": toks, "targets": toks}
    s1 = make_train_step(model, opt, num_microbatches=1)
    s2 = make_train_step(model, opt, num_microbatches=2)
    p1, _, m1 = jax.jit(s1)(params, init_opt_state(params), batch)
    p2, _, m2 = jax.jit(s2)(params, init_opt_state(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    # microbatching changes the gradient summation order, so float32
    # params drift by a few ULP-scale quanta (observed: 1/65536 elements
    # off by ~3e-5); the tolerance allows reduction-order noise while
    # still catching a wrong-by-a-factor accumulation bug
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_training_reduces_loss():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(d_model=128),
                              dtype="float32", vocab_size=128)
    model = make_model(cfg)
    dcfg = DataConfig(vocab_size=128, seq_len=64, batch_size=8, seed=3)
    data = packed_batches(dcfg)
    tr = Trainer(model, OptimizerConfig(peak_lr=3e-3, warmup_steps=5,
                                        total_steps=60),
                 TrainerConfig(steps=40), data)
    hist = tr.run()
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first - 0.3, (first, last)    # learned planted structure


def test_data_pipeline_determinism_and_sharding():
    dcfg = DataConfig(vocab_size=64, seq_len=32, batch_size=4, seed=7)
    a = next(packed_batches(dcfg))
    b = next(packed_batches(dcfg))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    s0 = next(packed_batches(dcfg, shard_id=0, num_shards=2))
    s1 = next(packed_batches(dcfg, shard_id=1, num_shards=2))
    assert not np.array_equal(s0["tokens"], s1["tokens"])
    # targets are tokens shifted by one
    full = next(packed_batches(dcfg))
    np.testing.assert_array_equal(full["tokens"][:, 1:],
                                  full["targets"][:, :-1])


def test_checkpoint_roundtrip(tmp_path):
    cfg = dataclasses.replace(get_config("xlstm-350m").reduced(d_model=128))
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "ck.ckpt")
    save_pytree(path, params, metadata={"step": 7})
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        params)
    loaded = load_pytree(path, like)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert load_metadata(path)["step"] == 7
