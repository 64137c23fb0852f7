"""The benchmark's yardstick on the CPU: operation and byte counts, the
peaks table, the configuration files, the traffic generator, the plain
reference against ``repro.models`` and the trace reduction on a trace
recorded on the chip."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import counts, model_config, peaks, reference, traffic  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.harness import ROOT, Bench  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.weights import make_params, param_shapes  # noqa: E402

DATA = Path(__file__).parent / "data"
REAL = [c["name"] for c in
        json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]]


def _spec(name):
    return Bench().config_file(name)


# -- counts ----------------------------------------------------------------
@pytest.mark.parametrize("name", REAL)
def test_param_count_matches_program_and_file(name):
    spec, arch = _spec(name), Bench().arch(name)
    cfg = arch.build(spec)
    z = arch.sizes_of(spec)
    assert arch.param_count(z) == cfg.param_count() == spec["params"]
    leaves = jax.tree.leaves(param_shapes(cfg))
    assert sum(int(np.prod(x.shape)) for x in leaves) == spec["params"]


def test_score_step_hand_count():
    z = reference.Sizes(hidden=8, heads=2, kv_heads=1, head_dim=4, ffn=16,
                        layers=3, vocab=10, rope_theta=1e4, eps=1e-6,
                        tied=False, qkv_bias=True)
    B, S = 2, 3
    T, pairs = B * S, 6
    per_layer = (2 * T * 8 * (8 + 8) + 2 * T * 8 * 8
                 + 4 * B * 2 * 4 * pairs + 2 * T * 3 * 8 * 16)
    step = counts.score_step(z, B, S)
    assert step["flops"] == 3 * per_layer + 2 * B * 8 * 10
    layer_w = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16 + 8 + 2 * 4
    weights = 3 * layer_w + T * 8 + 8 * 10        # gathered rows + head
    norms = 2 * 8 * 3 + 8
    assert step["bytes"] == 2 * weights + 4 * norms + 4 * T + 2 * B * 10


def test_qwen_step_is_the_published_size():
    z = reference.sizes_of(_spec("qwen2-0.5b"))
    step = counts.score_step(z, 4, 128)
    assert 0.36e12 < step["flops"] < 0.39e12
    assert 0.98e9 < step["bytes"] < 1.0e9


# -- peaks -----------------------------------------------------------------
def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v4")


# -- configuration files ---------------------------------------------------
def test_phi3_file_states_the_published_vocabulary():
    spec = _spec("phi3-medium-14b")
    from repro.configs import get_config
    assert spec["published"]["vocab_size"] == 32064
    assert get_config("phi3-medium-14b").vocab_size == 100352
    assert "100352" in spec["registry_fault"]
    cfg = model_config.build(spec)
    assert (cfg.vocab_size, cfg.n_layers, cfg.norm_eps) == (32064, 8, 1e-5)
    assert spec["reduced"] == ["num_hidden_layers"]


@pytest.mark.parametrize("arch, says", [
    (None, "no 'arch'"), ("moe", "'arch' 'moe' has no module"),
    ("../configs/qwen2-0.5b", "has no module")])
def test_config_file_needs_an_arch_module(tmp_path, arch, says):
    spec = _spec("qwen2-0.5b")
    del spec["arch"]
    if arch is not None:
        spec["arch"] = arch
    path = tmp_path / "c.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match=re.escape(says)):
        model_config.load_file(path)


def test_config_file_refuses_unlisted_change(tmp_path):
    spec = _spec("qwen2-0.5b")
    spec["config"] = dict(spec["config"], num_hidden_layers=4)
    path = tmp_path / "c.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="reduced"):
        model_config.load_file(path)


def test_benchmark_json_follows_its_rules():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) <= 5
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for w in spec["workloads"]:
        assert name.match(w["name"]) and w["chips"] == 1
        assert len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "cells" / f"{w['name']}.json").exists()
        reported = [m for m in spec["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in spec["per_layer"])
    for m in spec["end_to_end"]:
        if m["name"] != "setup_s":
            assert (ROOT / "bench" / "e2e" / f"{m['name']}.py").exists()
    for m in spec["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        stem = m["name"].split(".")[0]
        assert (ROOT / "bench" / "metrics" / f"{stem}.py").exists()
        for cell in m["workloads"]:
            e = next(x for x in spec["end_to_end"] if x["name"] == m["moves"])
            assert cell in e.get("workloads", [cell])
    for c in spec["configs"]:
        model_config.load_file(ROOT / c["file"])


# -- traffic ---------------------------------------------------------------
def _schedules(name, seconds):
    mix = traffic.load(ROOT / "bench" / "traffic" / f"{name}.json")
    a = traffic.open_schedule(mix, 5, seconds, 1000)
    b = traffic.open_schedule(mix, 5, seconds, 1000)
    c = traffic.open_schedule(mix, 2**40 + 3, seconds, 1000)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.prompts,
                                                           b.prompts)
    assert not np.array_equal(a.prompts, c.prompts)
    fixed = "arrivals_seed" in mix
    assert np.array_equal(a.due, c.due) == fixed
    assert len(a) == len(c) and a.after_idle.sum() == c.after_idle.sum()
    assert ((a.due >= 0) & (a.due < seconds)).all()
    return mix, a, c


def test_poisson_schedule_same_work_every_seed():
    mix, a, c = _schedules("steady", 45.0)
    assert len(a) == round(mix["rate_per_s"] * 45.0)
    gaps_a, gaps_c = np.sort(np.diff(a.due)), np.sort(np.diff(c.due))
    np.testing.assert_allclose(gaps_a, gaps_c, rtol=1e-6, atol=1e-3)
    cv = gaps_a.std() / gaps_a.mean()
    assert 0.9 < cv < 1.1                          # exponential gaps


def test_fleet_zipf_same_work_every_seed():
    mix, a, c = _schedules("fleet", 45.0)
    # the same arrivals for every seed: which arrivals find their endpoint
    # warm turns on the interleaving itself
    assert np.array_equal(a.entry, c.entry)
    assert np.array_equal(a.after_idle, c.after_idle)
    mix = {k: v for k, v in mix.items() if k != "arrivals_seed"}
    assert not np.array_equal(traffic.open_schedule(mix, 5, 45.0, 10).due,
                              traffic.open_schedule(mix, 6, 45.0, 10).due)
    for i in range(len(mix["entries"])):
        assert (a.entry == i).sum() == (c.entry == i).sum()
        assert (a.after_idle & (a.entry == i)).sum() == (
            c.after_idle & (c.entry == i)).sum()
    assert len(a) == round(mix["rate_per_s"] * 45.0)
    # the first arrival of each entry follows the set-up's idle wait
    for i in range(len(mix["entries"])):
        assert a.after_idle[np.flatnonzero(a.entry == i)[0]]


def test_zipf_spreads_the_rate():
    mix = {"rate_per_s": 25.0, "zipf_s": 1.0, "keep_alive_s": 1.0,
           "entries": ["w", "x", "y", "z"], "prompt_len": 4}
    s = traffic.open_schedule(mix, 1, 100.0, 100)
    n = np.bincount(s.entry, minlength=4)
    want = 2500 / np.arange(1, 5) / (1 + 1 / 2 + 1 / 3 + 1 / 4)
    np.testing.assert_allclose(n, want, atol=1)
    gaps = np.diff(s.due[s.entry == 3])
    assert 0.9 < gaps.std() / gaps.mean() < 1.1     # exponential gaps


# -- reference against the program ------------------------------------------
@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return Bench(tiny.build(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("name", list(tiny.CONFIGS))
def test_reference_matches_program_forward(tiny_bench, name):
    spec = tiny.build_spec(name, dtype="float32")
    arch = tiny_bench.arch(name)
    cfg = arch.build(spec)
    from repro.models import make_model
    model = make_model(cfg)
    params = arch.make_params(cfg, 3)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 16),
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = model.forward(params, jnp.asarray(toks))
        want = np.asarray(model._logits(params, x[:, -1:]))[:, 0]
    got = arch.reference_logits(arch.sizes_of(spec), params, toks)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    low = arch.reference_logits(arch.sizes_of(spec), params, toks,
                                quant="fp8")
    assert np.abs(low - want).max() > 10 * np.abs(got - want).max()


def test_weights_depend_on_every_word_of_the_seed():
    cfg = model_config.build(tiny.build_spec("tiny-qwen"))
    a = make_params(cfg, 7)["embed"]
    assert np.array_equal(a, make_params(cfg, 7)["embed"])
    assert not np.array_equal(a, make_params(cfg, 7 + 2**32)["embed"])
    assert not np.array_equal(a, make_params(cfg, 7, index=1)["embed"])


# -- the trace reduction ----------------------------------------------------
def _events(ops, modules=(), host=()):
    return {"devices": [{"plane": "/device:TPU:0", "ops": list(ops),
                         "modules": list(modules)}],
            "host": [(trace_mod.WINDOW, 0.0, 100.0)] + list(host)}


def test_reduce_by_hand():
    ops = [("a", 10, 20), ("b", 20, 20), ("a", 60, 10), ("c", 95, 10)]
    mods = [("jit_score(1)", 10, 30), ("jit_other", 60, 10)]
    host = [("bench.wait_due", 40, 20), ("bench.submit", 44, 5)]
    out = trace_mod.reduce(_events(ops, mods, host), "jit_score",
                           in_flight=[(0, 45)])
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx((30 + 10 + 5) * 1e-9)
    assert out["module_s"] == pytest.approx(30e-9)
    assert out["module_count"] == 1
    assert out["top_ops"][0] == ["a", pytest.approx(30e-9)]
    gaps = dict((round(s * 1e9), n) for n, s in out["idle_gaps"])
    assert gaps == {10: "bench.unannotated:in_flight",
                    20: "bench.wait_due:no_request",
                    25: "bench.unannotated:no_request"}


def test_reduce_recorded_chip_trace():
    path = DATA / "v5e_steady_trace.json.gz"
    events = json.loads(gzip.decompress(path.read_bytes()))
    out = trace_mod.reduce(events, "jit_score")
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 < out["module_s"] <= out["busy_s"] * 1.0001
    assert out["module_count"] > 0
    assert len(out["top_ops"]) == 10 and len(out["idle_gaps"]) == 10
    ops = events["devices"][0]["ops"]
    naive = sum(d for _, s, d in ops)
    assert out["busy_s"] <= naive * 1e-9 * 1.0001
