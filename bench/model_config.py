"""A configuration file (``bench/configs/<name>.json``) and the
``ModelConfig`` the program is given for it.

The file states the model in the key names of the published
``config.json``: ``published`` as released, ``config`` as it is run here.
``reduced`` lists every key where the two differ.  The served
``ModelConfig`` is the program's registry entry (``registry``) with every
size replaced by the file's; ``architecture`` holds what the published
config implies without a key of its own (QKV bias, activation, dtype).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

# published config.json key -> ModelConfig field
HF_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
ARCH_FIELDS = ("qkv_bias", "activation", "dtype")


def load_file(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    pub, run = spec["published"], spec["config"]
    changed = sorted(k for k in set(pub) | set(run) if pub.get(k) != run.get(k))
    if changed != sorted(spec["reduced"]):
        raise ValueError(f"{path}: keys changed from the published config "
                         f"{changed} are not what 'reduced' lists "
                         f"{sorted(spec['reduced'])}")
    return spec


def build(spec: dict):
    """The ``ModelConfig`` served for a configuration file's contents."""
    from repro.configs import get_config
    base = get_config(spec["registry"])
    run = spec["config"]
    kw = {HF_FIELDS[k]: v for k, v in run.items() if k in HF_FIELDS}
    kw.update({k: spec["architecture"][k] for k in ARCH_FIELDS})
    n_layers = run["num_hidden_layers"]
    if base.segments != ((("attn",), base.n_layers),):
        raise ValueError(f"{spec['registry']}: only plain decoders of "
                         f"'attn' layers are benchmarked so far")
    kw["segments"] = ((("attn",), n_layers),)
    cfg = dataclasses.replace(base, name=spec["name"], **kw)
    for key, field in HF_FIELDS.items():
        if key in run and getattr(cfg, field) != run[key]:
            raise ValueError(f"{spec['name']}: {field}={getattr(cfg, field)}"
                             f" but the file runs {key}={run[key]}")
    return cfg
