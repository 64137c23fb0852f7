"""A benchmark root at reduced widths for the CPU tests: the real mixes'
shapes with small models, short keep-alives and low rates, built as data
beside a copy of the real metric readers and architecture modules."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench.harness import ROOT

TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True,
}

CONFIGS = {
    "tiny-qwen": dict(registry="qwen2-0.5b", config=TINY, qkv_bias=True),
    "tiny-phi": dict(registry="phi3-medium-14b",
                     config=dict(TINY, tie_word_embeddings=False,
                                 rms_norm_eps=1e-05, rope_theta=10000.0),
                     qkv_bias=False),
}

MIXES = {
    "fleet": dict(keep_alive_s=0.3, rate_per_s=8.0),
    "steady": dict(rate_per_s=20.0, check=8),
    "batch": dict(clients=6, prompt_len=64, prompt_pool=16, check=4,
                  check_from_first=6),
}

CELLS = [("tiny-qwen.fleet", "tiny-qwen", "fleet"),
         ("tiny-qwen.steady", "tiny-qwen", "steady"),
         ("tiny-phi.batch", "tiny-phi", "batch")]


def build_spec(name: str, dtype: str = "bfloat16") -> dict:
    """The configuration file of tiny config ``name``."""
    c = CONFIGS[name]
    return {"name": name, "arch": "dense", "registry": c["registry"],
            "published": c["config"],
            "config": c["config"], "reduced": [],
            "architecture": {"qkv_bias": c["qkv_bias"], "activation": "swiglu",
                             "dtype": dtype}}


def build(root: Path, limit: float = 0.05) -> Path:
    """Write a benchmark root under ``root``; returns it."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "bench"
    for sub in ("configs", "traffic", "cells"):
        (b / sub).mkdir(parents=True, exist_ok=True)
    for kind in ("metrics", "e2e", "archs"):
        shutil.copytree(ROOT / "bench" / kind, b / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    configs = []
    for name in CONFIGS:
        path = b / "configs" / f"{name}.json"
        path.write_text(json.dumps(build_spec(name)))
        configs.append({"name": name, "source": "test", "reduced": [],
                        "file": f"bench/configs/{name}.json", "why": "test"})
    for mix, over in MIXES.items():
        spec = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                          .read_text())
        spec.update(over)
        (b / "traffic" / f"{mix}.json").write_text(json.dumps(spec))
    workloads = []
    for cell, cfg, mix in CELLS:
        workloads.append({"name": cell, "config": cfg, "traffic": mix,
                          "chips": 1, "why": "test"})
        (b / "cells" / f"{cell}.json").write_text(
            json.dumps({"limits": {"logit_rel_err": limit}}))
    real_cells = {w["name"]: w["traffic"] for w in real["workloads"]}

    def retarget(names):
        return [c for c, _, mix in CELLS
                if mix in {real_cells[n] for n in names}]
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] = retarget(m["workloads"])
    spec = dict(real, configs=configs, workloads=workloads)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
