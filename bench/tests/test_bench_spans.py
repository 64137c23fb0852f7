"""The readers of the program's cold-path and batcher spans: a traced run
of the tiny root's fleet and steady cells prints them, and a program that
records none of these spans (no plan spans, no ``fr_wait`` or
``batch_wait`` phases) gives no reading instead of a wrong one."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import types  # noqa: E402

import pytest  # noqa: E402

from bench.harness import Bench  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.tests.harness_util import fake_device, run  # noqa: E402

FLEET = ("fr_wait_ms.fleet", "weights_read_ms.fleet",
         "weights_upload_gbps.fleet")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.build(tmp_path_factory.mktemp("bench"))
    # start the profiler half a second in, so that steady has spans begun
    # before it, which is what its batcher and admission readers read
    path = root / "bench" / "traffic" / "steady.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    trace_seconds=0.5)))
    return root


@pytest.mark.parametrize("cell,names", [
    ("tiny-qwen.fleet", FLEET),
    ("tiny-qwen.steady", ("batch_wait_ms.steady", "admit_ms.steady")),
])
def test_traced_cell_prints_the_span_metrics(root, cell, names,
                                             monkeypatch):
    fake_device(monkeypatch)
    res = run(root, cell, trace=True)
    assert res["correct"]
    want = {m["name"] for m in Bench(root).metrics(cell, per_layer=True)}
    assert set(names) <= want
    for name in names:
        value = res["metrics"][name]["value"]
        assert value >= 0 and res["metrics"][name]["unit"]
    if cell.endswith("fleet"):
        assert res["metrics"]["weights_read_ms.fleet"]["value"] > 0
        assert res["metrics"]["weights_upload_gbps.fleet"]["value"] > 0
    else:
        # a row waits at most the batcher's deadline and a dispatch
        assert res["metrics"]["batch_wait_ms.steady"]["value"] < 1e3


def _reader(name):
    return Bench().reader(name, per_layer=True)


def test_a_program_without_the_spans_gives_no_reading(monkeypatch):
    phase = types.SimpleNamespace(name="run", duration=0.1, attrs={})
    span = types.SimpleNamespace(tracer=object(), start=1.0,
                                 phases=[phase])
    r = types.SimpleNamespace(spans=[span], t0=0.0, seconds=10.0,
                              trace_mark=None)
    for name in ("batch_wait_ms.steady", "weights_read_ms.fleet",
                 "weights_upload_gbps.fleet"):
        assert _reader(name)(r) is None
    from repro.telemetry import tracer
    monkeypatch.setattr(tracer, "PHASES", ("route", "run"))
    assert _reader("fr_wait_ms.fleet")(r) is None
    empty = types.SimpleNamespace(spans=[], t0=0.0, seconds=10.0,
                                  trace_mark=None)
    for name in FLEET + ("batch_wait_ms.steady",):
        assert _reader(name)(empty) is None
