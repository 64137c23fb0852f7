"""Versioned weight store: "downloading the ML model from a server", the
paper's canonical redundant overhead.  Weights are real checkpoints on disk
(repro.checkpoint, one ``.ckpt`` file per name); a load maps the current
version's file read-only and uploads it to the device leaf by leaf, each
leaf straight from its view of the mapping.  Publishing replaces the file
atomically, so a load that races a publish maps one version or the other.

Inside a traced freshen-plan entry (``repro.telemetry.current_plan``) a
load times its two parts as the entry's children: ``read`` (mapping the
file and viewing its leaves) and ``upload`` (from the first leaf's transfer
until every leaf is on the device, with the leaves' ``bytes``).
"""
from __future__ import annotations

import os
import queue
import threading
from typing import Any

import jax

from repro.checkpoint import place_flat, read_flat, save_pytree
from repro.telemetry import NULL_SPAN, current_plan


class WeightStore:
    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self._versions: dict[str, int] = {}
        self._templates: dict[str, Any] = {}
        self._lock = threading.Lock()
        self.load_count = 0

    def _path(self, name: str) -> str:
        return os.path.join(self.root, f"{name}.ckpt")

    # ------------------------------------------------------------------
    def publish(self, name: str, params) -> int:
        """Store a new weight version; returns the version number."""
        with self._lock:
            v = self._versions.get(name, 0) + 1
            self._versions[name] = v
        save_pytree(self._path(name), params, metadata={"version": v})
        with self._lock:
            self._templates[name] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        return v

    def version(self, name: str) -> int:
        with self._lock:
            return self._versions.get(name, 0)

    def load(self, name: str) -> Any:
        """The params of ``name``'s current version, their uploads issued
        (the leaves may still be in flight to the device)."""
        with self._lock:
            template = self._templates[name]
        plan = current_plan() or NULL_SPAN
        with plan.child("read"):
            flat = read_flat(self._path(name))
        upload = plan.open_child("upload")
        if upload is None:
            params = place_flat(flat, template)
        else:
            params = _timed_upload(plan, upload, flat, template)
        with self._lock:
            self.load_count += 1
        return params

    def nbytes(self, name: str) -> int:
        return os.path.getsize(self._path(name))


def _timed_upload(plan, upload, flat, template):
    """``place_flat`` with the ``upload`` child closed when every leaf is
    on the device.  A thread started here waits for that, so the caller
    returns as soon as the transfers are issued, as it does untraced, and
    the entries after it still overlap the upload."""
    handoff: queue.SimpleQueue = queue.SimpleQueue()

    def wait_ready():
        try:
            with plan.annotation(upload.name):
                params = handoff.get()
                if params is not None:
                    jax.block_until_ready(params)
        finally:
            plan.close_child(upload)

    threading.Thread(target=wait_ready, daemon=True,
                     name=f"upload-ready-{plan.fn}").start()
    params = None
    try:
        params = place_flat(flat, template)
        upload.annotate(bytes=sum(int(leaf.nbytes)
                                  for leaf in jax.tree.leaves(params)))
    finally:
        handoff.put(params)
    return params
