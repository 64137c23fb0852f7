"""Pytree checkpointing: flatten params by key-path into one file that a
load maps instead of reading, with atomic writes.  No external deps (orbax
not available); this is the substrate WeightStore and the trainer build on.

The file: ``MAGIC``, a u16 format version and a u64 header length (all
little-endian), then a JSON header ``{"meta": {...}, "leaves": {key:
{"dtype", "shape", "offset"}}}``, then each leaf's bytes in C order.  The
data section starts at the first multiple of ``ALIGN`` after the header
and every leaf's ``offset`` into it is a multiple of ``ALIGN``, so each
leaf is a page-aligned, DMA-ready slice of a mapping of the file.
"""
from __future__ import annotations

import json
import mmap
import os
import struct
import tempfile
from typing import Any, Tuple

import jax
import ml_dtypes
import numpy as np

MAGIC = b"PYTREE"
VERSION = 1
ALIGN = 4096
_PREFIX = struct.Struct("<6sHQ")


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


def _align(n: int) -> int:
    return -(-n // ALIGN) * ALIGN


def _dtype(name: str) -> np.dtype:
    """A dtype by its name, bfloat16 and the other ml_dtypes included."""
    return np.dtype(getattr(ml_dtypes, name, name))


def save_pytree(path: str, tree, metadata: dict | None = None) -> None:
    """Atomic save: write to a temp file in the same dir, then rename, so a
    reader (or a mapping) sees the old file or the new one, never a mix."""
    flat = _flatten_with_paths(tree)
    treedef = jax.tree_util.tree_structure(tree)
    leaves, end, used = {}, 0, 0
    for k, v in flat.items():
        leaves[k] = {"dtype": v.dtype.name, "shape": list(v.shape),
                     "offset": end}
        used = end + v.nbytes
        end = _align(used)
    header = json.dumps({"meta": {"treedef": str(treedef),
                                  **(metadata or {})},
                         "leaves": leaves}).encode()
    base = _align(_PREFIX.size + len(header))
    directory = os.path.dirname(os.path.abspath(path))
    os.makedirs(directory, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(_PREFIX.pack(MAGIC, VERSION, len(header)))
            f.write(header)
            for k, v in flat.items():
                f.seek(base + leaves[k]["offset"])
                f.write(np.ascontiguousarray(v).reshape(-1).view(np.uint8))
            f.truncate(base + used)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _read_header(f, path: str) -> Tuple[dict, int]:
    """The header of the checkpoint open as ``f``, and where its data
    section starts."""
    prefix = f.read(_PREFIX.size)
    if len(prefix) < _PREFIX.size:
        raise ValueError(f"{path}: truncated checkpoint header")
    magic, version, n = _PREFIX.unpack(prefix)
    if magic != MAGIC or version != VERSION:
        raise ValueError(f"{path}: not a version-{VERSION} checkpoint")
    raw = f.read(n)
    if len(raw) < n:
        raise ValueError(f"{path}: truncated checkpoint header")
    return json.loads(raw), _align(_PREFIX.size + n)


def read_flat(path: str) -> dict:
    """Every leaf of a checkpoint, keyed by path, in its true dtype, as a
    read-only view of one shared mapping of the file: nothing is copied,
    and a view's ``base`` keeps the mapping alive.  The mapping is
    populated up front (``MAP_POPULATE`` where the OS has it), so its
    page-table work is done here and not in the upload that follows."""
    with open(path, "rb") as f:
        header, base = _read_header(f, path)
        size = os.fstat(f.fileno()).st_size
        mapping = mmap.mmap(
            f.fileno(), size, prot=mmap.PROT_READ,
            flags=mmap.MAP_SHARED | getattr(mmap, "MAP_POPULATE", 0))
    flat = {}
    for k, leaf in header["leaves"].items():
        dtype, shape = _dtype(leaf["dtype"]), tuple(leaf["shape"])
        count = int(np.prod(shape))
        start = base + leaf["offset"]
        if start + count * dtype.itemsize > size:
            raise ValueError(f"{path}: truncated at leaf {k!r}")
        flat[k] = np.frombuffer(mapping, dtype, count, start).reshape(shape)
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
    with open(path, "rb") as f:
        return _read_header(f, path)[0]["meta"]
