"""DeepSeek-V2's family on the CPU at reduced widths: the plain reference
against the program's forward, the float8 control, the score step's counts
by hand, and a tiny cell of the family through the harness."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import model_config  # noqa: E402
from bench.harness import CHECK, ROOT, Bench  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.tests.harness_util import fake_device, run  # noqa: E402

REAL = ROOT / "bench" / "configs" / "deepseek-v2-lite.json"
# the published keys at reduced widths; the router scores 16 experts, this
# chip holds 4 of them from expert 4 on, and the rope keeps YaRN
SMALL = {"hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "num_experts_per_tok": 4, "num_hidden_layers": 3,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "vocab_size": 512}


def small_spec(dtype="bfloat16", router=16, held=4, first=4):
    spec = json.loads(REAL.read_text())
    pub = dict(spec["published"], **SMALL, n_routed_experts=router)
    spec.update(pub, name="tiny-dsv2", published=pub,
                config=dict(pub, n_routed_experts=held), held_first=first,
                n_routed_experts=held,
                architecture=dict(spec["architecture"], dtype=dtype))
    return spec


@pytest.fixture(scope="module")
def arch():
    return Bench().arch("deepseek-v2-lite")


def test_file_is_the_catalog_config_with_eight_experts_held(arch):
    spec = model_config.load_file(REAL)
    pub, run_cfg = spec["published"], spec["config"]
    assert spec["reduced"] == ["n_routed_experts"]
    assert (pub["n_routed_experts"], run_cfg["n_routed_experts"]) == (64, 8)
    assert {k: spec[k] for k in run_cfg} == run_cfg   # the top level is run
    cfg = arch.build(spec)
    assert cfg.moe.held == (0, 8) and cfg.moe.n_experts == 64
    assert cfg.moe.dispatch == "dropless" and not cfg.moe.norm_topk
    assert (cfg.moe.top_k, cfg.moe.d_ff, cfg.moe.n_shared) == (6, 1408, 2)
    assert cfg.mla.yarn.factor == 40 and cfg.mla.q_lora_rank is None
    assert cfg.segments == ((("mla",), 1), (("mla_moe",), 26))
    from repro.configs import get_config
    whole = get_config(spec["registry"])
    assert whole.mla == cfg.mla and whole.param_count() == 15_706_484_224


@pytest.mark.parametrize("first", [0, 4])
def test_reference_matches_program_forward(arch, first):
    spec = small_spec("float32", first=first)
    cfg = arch.build(spec)
    from repro.models import make_model
    model = make_model(cfg)
    params = arch.make_params(cfg, 3)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 16),
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = model.forward(params, jnp.asarray(toks))
        want = np.asarray(model._logits(params, x[:, -1:]))[:, 0]
    z = arch.sizes_of(spec)
    got = arch.reference_logits(z, params, toks)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    low = arch.reference_logits(z, params, toks, quant="fp8")
    assert np.abs(low - want).max() > 10 * np.abs(got - want).max()


def test_score_step_hand_count(arch):
    z = arch.sizes_of(small_spec())
    B, S = 2, 8
    T, pairs = B * S, S * (S + 1) // 2
    d, H = 64, 4
    attn_w = d * H * 24 + d * (32 + 8) + 32 * H * 32 + H * 16 * d
    per_layer = 2 * T * attn_w + 2 * B * H * (24 + 16) * pairs
    routed_rows = T * 4 * 4 // 16                      # tokens x k x held / E
    expert = 2 * T * d * 16 + 2 * 3 * d * 32 * (2 * T + routed_rows)
    flops = 3 * per_layer + 2 * T * 3 * d * 96 + 2 * expert + 2 * B * d * 512
    step = arch.score_step(z, B, S)
    assert step["flops"] == flops
    weights = (3 * attn_w + 3 * d * 96 + 2 * 3 * d * 32 * (4 + 2)
               + T * d + d * 512)                      # gathered rows + head
    floats = 3 * (2 * d + 32) + d + 2 * d * 16        # norms, f32 router
    assert step["bytes"] == 2 * weights + 4 * floats + 4 * T + 2 * B * 512


def test_score4k_step_is_the_published_size(arch):
    z = arch.sizes_of(model_config.load_file(REAL))
    step = arch.score_step(z, 1, 4096)
    assert 11.0e12 < step["flops"] < 11.02e12        # 55.9 ms at 197 TFLOP/s
    assert step["bytes"] / 819e9 < step["flops"] / 197e12 / 5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A tiny root with one cell of the family: the score4k mix at 64
    tokens, the real per-layer entries of score4k pointed at it."""
    root = tiny.build(tmp_path_factory.mktemp("bench"))
    b = root / "bench"
    (b / "configs" / "tiny-dsv2.json").write_text(json.dumps(small_spec()))
    mix = json.loads((ROOT / "bench" / "traffic" / "score4k.json")
                     .read_text())
    mix.update(clients=2, prompt_len=64, prompt_pool=8, check=4,
               check_from_first=6)
    (b / "traffic" / "score4k.json").write_text(json.dumps(mix))
    (b / "cells" / "tiny-dsv2.score4k.json").write_text(
        json.dumps({"limits": {CHECK: 0.05}}))
    top = json.loads((root / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "tiny-dsv2", "source": "test",
                           "file": "bench/configs/tiny-dsv2.json",
                           "reduced": ["n_routed_experts"], "why": "test"})
    top["workloads"].append({"name": "tiny-dsv2.score4k",
                             "config": "tiny-dsv2", "traffic": "score4k",
                             "chips": 1, "why": "test"})
    for m in top["end_to_end"] + top["per_layer"]:
        if m["name"] == "tokens_per_s" or m["name"].endswith(".score4k"):
            m["workloads"].append("tiny-dsv2.score4k")
    (root / "BENCHMARK.json").write_text(json.dumps(top))
    return root


def test_tiny_cell_through_the_harness(root, monkeypatch):
    res = run(root, "tiny-dsv2.score4k", control=True)
    assert res["correct"] and res["failed"] == 0 and res["compared_rows"]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["control"]["correct"] is False
    fake_device(monkeypatch)
    res = run(root, "tiny-dsv2.score4k", trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert set(m) == {"execute_ms.score4k", "score_step_roofline.score4k",
                      "score_step_mfu.score4k", "expert_imbalance.score4k"}
    assert 1.0 <= m["expert_imbalance.score4k"]["value"] <= 4.0
