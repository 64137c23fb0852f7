"""Weights made from the seed, on the device, in the type they are served in.

The benchmark makes the weights, not the program: the program is only told
the tree's shapes (``jax.eval_shape`` of its ``init``), and each leaf is
filled by a rule on its name.  One jitted call makes one endpoint's whole
tree, so the persistent compile cache serves it after the first run, and the
reference makes the very same tree again from the same seed after the window.

Seeds may exceed 32 bits: the seed's low and high words and the endpoint's
index are folded into one threefry key.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MASK32 = 0xFFFFFFFF


def param_shapes(cfg):
    from repro.models import make_model
    return jax.eval_shape(make_model(cfg).init, jax.random.PRNGKey(0))


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _fill(key, path, spec):
    """One leaf: norm scales near 1, biases small, matrices scaled by
    their fan-in (the stacked layer axis, if any, comes first)."""
    name = _leaf_name(path)
    noise = jax.random.normal(key, spec.shape, jnp.float32)
    if name == "scale":
        out = 1.0 + 0.1 * noise
    elif name.startswith("b"):
        out = 0.1 * noise
    elif name == "embed":                       # (vocab, d_model)
        out = noise * (1.0 / math.sqrt(spec.shape[-1]))
    else:
        out = noise * (1.0 / math.sqrt(spec.shape[-2]))
    return out.astype(spec.dtype)


@functools.lru_cache(maxsize=None)
def _maker(cfg):
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(words):
        key = jax.random.wrap_key_data(words[:2])
        key = jax.random.fold_in(key, words[2])
        keys = jax.random.split(key, len(flat))
        leaves = [_fill(keys[i], path, spec)
                  for i, (path, spec) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return jax.jit(make)


def make_params(cfg, seed: int, index: int = 0):
    """Endpoint ``index``'s weights for ``seed``, on the default device."""
    words = np.array([(seed >> 32) & MASK32, seed & MASK32, index & MASK32],
                     np.uint32)
    return _maker(cfg)(words)

