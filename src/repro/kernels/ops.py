"""Jit'd public wrappers for the Pallas kernels.

On a TPU backend these dispatch to the Mosaic-lowered kernels.  On the CPU
backend ``interpret=True`` executes the kernel body in Python for
correctness validation; any other backend is refused rather than silently
interpreted.  The model substrate defaults to the pure-jnp blockwise
implementations (the paper has no kernel contribution; kernels serve the
framework's serving hot paths).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.rglru_scan import rglru_scan_kernel


def _interpret(interpret) -> bool:
    """Explicit ``interpret`` wins; otherwise compile on TPU, interpret on
    CPU, and raise anywhere else (a GPU would silently run the slow
    interpreter and report its numbers as the kernel's)."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                       f"{backend!r}; pass interpret=True to emulate them")


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                                   "q_blk", "kv_blk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, q_blk=128, kv_blk=128, interpret=None):
    interpret = _interpret(interpret)
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_blk=q_blk, kv_blk=kv_blk, interpret=interpret)


@partial(jax.jit, static_argnames=("window", "softcap", "scale", "kv_blk",
                                   "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, window=None, softcap=None,
                     scale=None, kv_blk=256, interpret=None):
    interpret = _interpret(interpret)
    return decode_attention_kernel(
        q, k_cache, v_cache, pos, window=window, softcap=softcap, scale=scale,
        kv_blk=kv_blk, interpret=interpret)


@partial(jax.jit, static_argnames=("r_blk", "interpret"))
def rglru_scan(a, b, h0=None, *, r_blk=256, interpret=None):
    interpret = _interpret(interpret)
    return rglru_scan_kernel(a, b, h0, r_blk=r_blk, interpret=interpret)
