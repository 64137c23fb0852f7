"""The correctness check fails what it must: the float8 control, and a run
whose timed path is broken underneath, on every mix (CPU, reduced widths)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.harness import CHECK, ROOT  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.tests.harness_util import run  # noqa: E402

CELLS = [c for c, _, _ in tiny.CELLS]


def _limit(cell):
    real = {"fleet": "qwen2-0.5b.fleet", "steady": "qwen2-0.5b.steady",
            "batch": "phi3-medium-14b.batch"}[cell.split(".")[1]]
    path = ROOT / "bench" / "cells" / f"{real}.json"
    return json.loads(path.read_text())["limits"][CHECK]


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    return {c: tiny.build(tmp_path_factory.mktemp("bench"), _limit(c))
            for c in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(roots, cell):
    res = run(roots[cell], cell, control=True)
    limit = res["checks"][CHECK]["limit"]
    assert res["correct"] and res["checks"][CHECK]["value"] <= limit
    # the control, judged by the harness in the program's place
    assert res["control"]["correct"] is False
    assert res["control"][CHECK] > limit


def _alter_answer(monkeypatch):
    from repro.serving.engine import ModelEndpoint
    code = ModelEndpoint.code

    def altered(self, ctx, args):
        out = code(self, ctx, args)
        out["logits"] = out["logits"] * np.asarray(1.25, out["logits"].dtype)
        return out
    monkeypatch.setattr(ModelEndpoint, "code", altered)


def _alter_tokens(monkeypatch):
    from repro.serving import engine
    pad = engine.pad_batch
    monkeypatch.setattr(engine, "pad_batch",
                        lambda rows, size: (pad(rows, size) + 1) % 512)


@pytest.mark.parametrize("fault", [_alter_answer, _alter_tokens])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(roots, cell, fault, monkeypatch):
    fault(monkeypatch)
    res = run(roots[cell], cell)
    assert res["correct"] is False
    assert res["checks"][CHECK]["value"] > res["checks"][CHECK]["limit"]
