"""Pallas TPU single-token (decode) attention kernel over a KV cache.

Grid = (B, Hq, nk), KV innermost; per-(b,h) running (m, l, acc) scalars/rows
in VMEM scratch.  Supports ring-buffered local windows: validity of slot j is
derived from the current position (SMEM-prefetched per-row scalar), matching
``repro.models.layers.decode_attention`` semantics exactly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale, softcap, window, ring, kv_blk, nk):
    bi, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (1, dh)
    k = k_ref[0, 0].astype(jnp.float32)                 # (kv_blk, dh)
    v = v_ref[0, 0].astype(jnp.float32)                 # (kv_blk, dv)
    pos = pos_ref[bi]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    slots = ki * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (1, kv_blk), 1)
    if ring:
        # slot j holds absolute position pos - ((pos - j) mod W); true mod
        delta = jax.lax.rem(jax.lax.rem(pos - slots, window) + window, window)
        valid = (pos - delta) >= 0
    else:
        valid = slots <= pos
        if window is not None:
            valid &= (pos - slots) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention_kernel(q, k_cache, v_cache, pos, *, window=None,
                            softcap=None, scale=None, kv_blk=256,
                            interpret=False):
    """q: (B, 1, Hq, dh); k/v_cache: (B, S, Hkv, dh|dv); pos: (B,) int32."""
    B, _, Hq, dh = q.shape
    _, S, Hkv, dv = v_cache.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    kv_blk = min(kv_blk, S)
    assert S % kv_blk == 0
    nk = S // kv_blk
    ring = window is not None and S == window

    qh = q.transpose(0, 2, 1, 3)                        # (B, Hq, 1, dh)
    kh = k_cache.transpose(0, 2, 1, 3)
    vh = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=softcap, window=window,
        ring=ring, kv_blk=kv_blk, nk=nk)

    # pos is a scalar-prefetch operand: the whole (B,) vector sits in
    # SMEM and each program reads its row's entry (index maps receive the
    # prefetched ref as a trailing argument)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, ki, _: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, kv_blk, dh),
                         lambda b, h, ki, _, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, kv_blk, dv),
                         lambda b, h, ki, _, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dv),
                               lambda b, h, ki, _: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, dv), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
