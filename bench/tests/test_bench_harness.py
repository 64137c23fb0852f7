"""The harness on the CPU at reduced widths: the last line of each mix,
the refusal of a CPU device, a new cell added as data alone and a new
family of architectures added as files alone."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import hashlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

from bench.harness import CHECK, ROOT, Bench  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.tests.harness_util import fake_device, run  # noqa: E402

CELLS = [c for c, _, _ in tiny.CELLS]
HEAD = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp("bench"))


def _shape(res, want):
    assert list(res)[:5] == HEAD and list(res)[-1] == "checks"
    json.dumps(res)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) <= want
    for m in res["metrics"].values():
        assert m["value"] >= 0 and m["unit"]
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(root, cell):
    res = run(root, cell)
    want = {m["name"] for m in Bench(root).metrics(cell, per_layer=False)}
    _shape(res, want)
    assert set(res["metrics"]) == want
    assert res["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line(root, cell, monkeypatch):
    fake_device(monkeypatch)
    res = run(root, cell, trace=True)
    want = {m["name"] for m in Bench(root).metrics(cell, per_layer=True)}
    _shape(res, want)
    assert res["metrics"], "a traced run reads at least one layer"
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(res["breakdown"][key]) <= 10
    for m in res["metrics"].values():
        if m["unit"] == "%":
            assert m["value"] <= 105.0


def test_new_cell_is_data_alone(tmp_path, monkeypatch):
    root = tiny.build(tmp_path)
    b = root / "bench"
    spec = tiny.build_spec("tiny-qwen")
    spec = dict(spec, name="tiny-extra")
    spec["published"] = spec["config"] = dict(spec["config"],
                                              num_hidden_layers=3)
    (b / "configs" / "tiny-extra.json").write_text(json.dumps(spec))
    mix = json.loads((b / "traffic" / "steady.json").read_text())
    mix.update(rate_per_s=30.0, entries=["x", "y"], zipf_s=1.0)
    (b / "traffic" / "pulse.json").write_text(json.dumps(mix))
    (b / "e2e" / "latency_p95_ms.py").write_text(
        "import numpy as np\n\n\ndef read(run):\n"
        "    return float(np.percentile(run.lat_ms[run.ok], 95))\n")
    (b / "cells" / "tiny-extra.pulse.json").write_text(
        json.dumps({"limits": {"logit_rel_err": 0.05}}))
    (b / "metrics" / "rows_served.py").write_text(
        "def read(run):\n    return float(run.ok.sum())\n")
    top = json.loads((root / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "tiny-extra", "source": "test",
                           "file": "bench/configs/tiny-extra.json",
                           "reduced": [], "why": "test"})
    top["workloads"].append({"name": "tiny-extra.pulse",
                             "config": "tiny-extra", "traffic": "pulse",
                             "chips": 1, "why": "test"})
    for m in top["end_to_end"]:
        if m["name"] == "latency_p50_ms":
            m["workloads"].append("tiny-extra.pulse")
    top["end_to_end"].append({"name": "latency_p95_ms", "unit": "ms",
                              "better": "lower", "bound": 0.25,
                              "source": "host_clock",
                              "workloads": ["tiny-extra.pulse"]})
    top["per_layer"].append({"name": "rows_served.pulse", "unit": "rows",
                             "better": "higher", "source": "program_counter",
                             "layer": "client", "moves": "latency_p95_ms",
                             "workloads": ["tiny-extra.pulse"]})
    (root / "BENCHMARK.json").write_text(json.dumps(top))
    res = run(root, "tiny-extra.pulse")
    assert set(res["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    m = res["metrics"]
    assert m["latency_p95_ms"]["value"] >= m["latency_p50_ms"]["value"]
    fake_device(monkeypatch)
    res = run(root, "tiny-extra.pulse", trace=True)
    assert res["metrics"]["rows_served.pulse"]["value"] == res["attempted"]


PROBE = '''"""Dense's architecture family, each call recorded beside this file
and the reference's logits moved by SHIFT."""
from pathlib import Path

from bench.archs import dense

SHIFT = {shift}
CALLS = Path(__file__).with_name("probe.calls")


def _recorded(name):
    def call(*args, **kw):
        with CALLS.open("a") as f:
            f.write(name + "\\n")
        return getattr(dense, name)(*args, **kw)
    return call


build = _recorded("build")
sizes_of = _recorded("sizes_of")
param_count = _recorded("param_count")
make_params = _recorded("make_params")
score_step = _recorded("score_step")


def reference_logits(sizes, params, tokens, quant=None):
    return _recorded("reference_logits")(sizes, params, tokens, quant) + SHIFT
'''
ARCH_NAMES = {"build", "sizes_of", "param_count", "make_params",
              "reference_logits", "score_step"}


def _tree_digest():
    """Every file of the real benchmark, by content."""
    files = [ROOT / "BENCHMARK.json"] + sorted(
        p for p in (ROOT / "bench").rglob("*")
        if p.is_file() and "__pycache__" not in p.parts)
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in files}


def test_new_architecture_is_files_alone(tmp_path):
    root = tiny.build(tmp_path)
    b = root / "bench"
    probe = b / "archs" / "probe.py"
    probe.write_text(PROBE.format(shift=0.0))
    spec = dict(tiny.build_spec("tiny-qwen"), name="tiny-probe", arch="probe")
    (b / "configs" / "tiny-probe.json").write_text(json.dumps(spec))
    (b / "cells" / "tiny-probe.steady.json").write_text(
        json.dumps({"limits": {CHECK: 0.05}}))
    top = json.loads((root / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "tiny-probe", "source": "test",
                           "file": "bench/configs/tiny-probe.json",
                           "reduced": [], "why": "test"})
    top["workloads"].append({"name": "tiny-probe.steady",
                             "config": "tiny-probe", "traffic": "steady",
                             "chips": 1, "why": "test"})
    for m in top["end_to_end"]:
        if m["name"] == "latency_p50_ms":
            m["workloads"].append("tiny-probe.steady")
    (root / "BENCHMARK.json").write_text(json.dumps(top))
    before = _tree_digest()

    res = run(root, "tiny-probe.steady")
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p50_ms", "setup_s"}
    calls = probe.with_name("probe.calls").read_text().split()
    assert set(calls) == ARCH_NAMES
    dense = Bench(root).arch("tiny-qwen")
    assert res["params"] == dense.param_count(dense.sizes_of(spec))
    assert _tree_digest() == before

    # the verdict is the module's reference's
    probe.write_text(PROBE.format(shift=1.0))
    res = run(root, "tiny-probe.steady")
    assert res["correct"] is False and res["failed"] == 0
    assert res["checks"][CHECK]["value"] > res["checks"][CHECK]["limit"]


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _command(ROOT, env)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_command_needs_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = _command(tmp_path, env)
    assert out.returncode != 0 and out.stdout.strip() == ""
