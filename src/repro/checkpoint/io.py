"""Pytree checkpointing: flatten params by key-path and store as .npz, with
a version counter and atomic writes.  No external deps (orbax not available);
this is the substrate WeightStore and the trainer build on."""
from __future__ import annotations

import json
import os
import tempfile
from typing import Any, Tuple

import jax
import numpy as np


def _flatten_with_paths(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(_path_str(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"[{p.idx}]"
    return str(p)


def save_pytree(path: str, tree, metadata: dict | None = None) -> None:
    """Atomic save: write to a temp file in the same dir, then rename.
    bfloat16 (unknown to vanilla numpy IO) is stored as a uint16 view with
    the true dtype recorded in the metadata."""
    flat = _flatten_with_paths(tree)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    treedef = jax.tree_util.tree_structure(tree)
    dtype_map = {}
    for k, v in list(flat.items()):
        if v.dtype.kind not in "biufc":          # e.g. bfloat16 -> void
            dtype_map[k] = str(v.dtype)
            flat[k] = v.view(np.uint16)
    meta = {"treedef": str(treedef), "dtype_map": dtype_map,
            **(metadata or {})}
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)),
                               suffix=".tmp")
    os.close(fd)
    try:
        with open(tmp, "wb") as f:
            np.savez(f, __meta__=json.dumps(meta), **flat)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def read_flat(path: str) -> dict:
    """Read every leaf of a checkpoint into host memory, keyed by path,
    each in its true dtype (bfloat16 and kin viewed back from uint16)."""
    import ml_dtypes
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        flat = {k: z[k] for k in z.files if k != "__meta__"}
    dtype_map = meta.get("dtype_map", {})
    for k, dt in dtype_map.items():
        flat[k] = flat[k].view(np.dtype(getattr(ml_dtypes, dt)))
    return flat


def place_flat(flat: dict, like) -> Any:
    """Put the leaves of ``flat`` on the device in the structure of
    ``like`` (a template pytree or its eval_shape), matched by key path.
    Each transfer is issued leaf by leaf and may still be in flight when
    this returns."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for path_elems, leaf in paths:
        key = "/".join(_path_str(p) for p in path_elems)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = flat[key]
        want = np.dtype(leaf.dtype)
        leaves.append(jax.numpy.asarray(arr).astype(want))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def load_pytree(path: str, like) -> Any:
    """Load into the structure of ``like`` (a template pytree or its
    eval_shape); leaf order is matched by key path."""
    return place_flat(read_flat(path), like)


def load_metadata(path: str) -> dict:
    with np.load(path, allow_pickle=False) as z:
        return json.loads(str(z["__meta__"]))
