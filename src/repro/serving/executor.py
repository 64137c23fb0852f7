"""Executor: the XLA compile cache + warm-up machinery.

``jit`` compilation is the TPU/JAX cold start (seconds of wall time) —
``CompileResource`` freshens it by compiling ahead of the predicted
invocation.  The cache is keyed by (name, shapes, target device) and is
runtime-scoped: an executable compiled under one shard's device pin is
never handed to a shard pinned elsewhere (it would silently move that
shard's arguments to the first device).
"""
from __future__ import annotations
# fabriclint: allow-file[clock] -- compile/warmup seconds are measured
# wall-clock costs fed to the freshen planner.

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


class Executor:
    def __init__(self):
        self._cache: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self.compile_seconds: Dict[Tuple, float] = {}
        self.compile_count = 0
        self.hit_count = 0

    @staticmethod
    def _key(name: str, specs) -> Tuple:
        leaves = jax.tree.leaves(specs)
        # the thread's jax default device decides where the AOT compile
        # places the program (None = the backend's first device)
        return ((name, jax.config.jax_default_device)
                + tuple((tuple(l.shape), str(l.dtype)) for l in leaves))

    # ------------------------------------------------------------------
    def compile(self, name: str, fn: Callable, specs, *,
                donate_argnums=()) -> Tuple[Any, float]:
        """AOT lower+compile for the given ShapeDtypeStructs; cached.
        Returns (compiled, seconds_spent_now)."""
        key = self._key(name, specs)
        with self._lock:
            if key in self._cache:
                self.hit_count += 1
                return self._cache[key], 0.0
        t0 = time.monotonic()
        jitted = jax.jit(fn, donate_argnums=donate_argnums)
        lowered = jitted.lower(*specs) if isinstance(specs, (list, tuple)) \
            else jitted.lower(specs)
        compiled = lowered.compile()
        dt = time.monotonic() - t0
        with self._lock:
            self._cache[key] = compiled
            self.compile_seconds[key] = dt
            self.compile_count += 1
        return compiled, dt

    def get(self, name: str, specs) -> Optional[Any]:
        with self._lock:
            return self._cache.get(self._key(name, specs))

    # ------------------------------------------------------------------
    def warmup(self, compiled, specs) -> float:
        """Run the compiled executable once on zeros: warms the dispatch
        path, allocator arenas, and (on TPU) collective channels — the
        CWND-warming analogue."""
        args = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), specs)
        t0 = time.monotonic()
        out = compiled(*args) if isinstance(args, (list, tuple)) \
            else compiled(args)
        jax.block_until_ready(out)
        return time.monotonic() - t0
