"""A configuration file (``bench/configs/<name>.json``) and the
``ModelConfig`` the program is given for it.

The file states the model in the key names of the published
``config.json``: ``published`` as released, ``config`` as it is run here.
``reduced`` lists every key where the two differ.  ``arch`` names the
module of its family of architectures, ``bench/archs/<arch>.py``, which
builds the served ``ModelConfig`` and holds the reference, the weights and
the counts.  For the dense family (``bench/archs/dense.py``) the served
``ModelConfig`` is the program's registry entry (``registry``) with every
size replaced by the file's; ``architecture`` holds what the published
config implies without a key of its own (QKV bias, activation, dtype).
"""
from __future__ import annotations

import dataclasses
import json
import re
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
ARCHS = Path(__file__).resolve().parent / "archs"
ARCH_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_-]{0,63}")


def load_file(path: Path, archs: Path = ARCHS) -> dict:
    """A configuration file's contents, refused where its ``arch`` names no
    module in ``archs`` or a key changed from the published config is not
    in ``reduced``."""
    spec = json.loads(Path(path).read_text())
    if "arch" not in spec:
        raise ValueError(f"{path}: no 'arch': name the module of its "
                         f"architecture family, bench/archs/<arch>.py")
    arch = spec["arch"]
    if not (isinstance(arch, str) and ARCH_NAME.fullmatch(arch)
            and (Path(archs) / f"{arch}.py").is_file()):
        raise ValueError(f"{path}: 'arch' {arch!r} has no module "
                         f"<arch>.py in {archs}")
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
