"""Pallas TPU kernel for the RG-LRU sequential scan.

The gate computations (block-diagonal matmuls + sigmoids) are cheap and fuse
well in XLA, so the kernel takes the precomputed per-step decay ``a`` and
input ``b`` (both f32) and runs the recurrence  h_t = a_t * h_{t-1} + b_t
sequentially in VMEM.  Grid = (B_tiles, r_tiles); each program holds its
(S × r_blk) slice of a/b in VMEM (2·S·r_blk·4 bytes — r_blk chosen so this
fits) and carries h in a VMEM scratch row.

On TPU this trades the log(S)-depth associative scan (which materializes
O(S·r) intermediates in HBM at every level) for a single VMEM-resident pass;
it is also the decode-friendly formulation.  Validated in interpret mode
against ``repro.models.rglru.rglru_scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, hT_ref, *, seq_len):
    def step(t, h):                                     # h: (1, r_blk) f32
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        y_ref[0, row, :] = h
        return h

    hT_ref[0] = jax.lax.fori_loop(0, seq_len, step, h0_ref[0])


def rglru_scan_kernel(a, b, h0=None, *, b_blk=1, r_blk=256, interpret=False):
    """a, b: (B, S, r) f32 decay/input sequences; h0: (B, r) initial state.

    Returns (y: (B, S, r) f32, h_final: (B, r) f32).
    """
    B, S, r = a.shape
    r_blk = min(r_blk, r)
    assert r % r_blk == 0
    if h0 is None:
        h0 = jnp.zeros((B, r), jnp.float32)

    # the carried state travels as (B, 1, r): a (1, r_blk) block then
    # equals the array's second-minor dim, as Mosaic's (8, 128) rule asks
    kernel = functools.partial(_rglru_kernel, seq_len=S)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, r // r_blk),
        in_specs=[
            pl.BlockSpec((1, S, r_blk), lambda bi, ri: (bi, 0, ri)),
            pl.BlockSpec((1, S, r_blk), lambda bi, ri: (bi, 0, ri)),
            pl.BlockSpec((1, 1, r_blk), lambda bi, ri: (bi, 0, ri)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, r_blk), lambda bi, ri: (bi, 0, ri)),
            pl.BlockSpec((1, 1, r_blk), lambda bi, ri: (bi, 0, ri)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, r), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, r), jnp.float32),
        ],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32),
      h0.astype(jnp.float32)[:, None, :])
    return y, hT[:, 0]
