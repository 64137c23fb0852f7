"""Core neural-net building blocks: norms, RoPE, linear, blockwise (flash)
attention, decode attention, MLP variants.

Everything is functional: ``init_*`` returns a param pytree, ``*_apply``
consumes it.  Attention is written blockwise (online softmax over KV chunks
inside a scan) so peak memory is bounded by chunk size — this same function is
the pure-jnp oracle for the Pallas flash kernel.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def cast(x, dtype):
    return x.astype(dtype) if x.dtype != dtype else x


# ----------------------------------------------------------------------
# init helpers
def dense_init(key, in_dim, out_dim, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_apply(params, x, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return x.astype(dtype)


# ----------------------------------------------------------------------
# RoPE
def rope_frequencies(head_dim, theta):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)                      # (head_dim/2,)


def apply_rope(x, positions, theta, inv_freq=None):
    """x: (..., S, H, hd); positions: broadcastable to (..., S).
    ``inv_freq`` (hd/2,) replaces the plain ``theta`` frequencies (YaRN)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                          # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Blockwise (flash) attention — pure jnp; also the Pallas kernel oracle.
def _softcap(scores, softcap):
    if softcap is None:
        return scores
    return softcap * jnp.tanh(scores / softcap)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, q_chunk=512, kv_chunk=1024,
                    q_offset=0):
    """Online-softmax attention with a flash-style custom VJP.

    q: (B, Sq, Hq, dh) — Hq must be a multiple of Hkv (GQA).
    k: (B, Sk, Hkv, dh); v: (B, Sk, Hkv, dv).
    ``q_offset``: absolute position of q[0] (so Sq may be a suffix of Sk).
    Returns (B, Sq, Hq, dv).

    The custom VJP recomputes score blocks in the backward pass (residuals
    are only q/k/v/out + the per-row logsumexp), keeping peak memory at
    O(chunk²) instead of O(Sq·Sk) — without it, grad-of-scan saves every
    probability block (observed ~8 GB/device/layer at 4k train).
    """
    return _flash_vjp(q, k, v, causal, window, softcap, scale,
                      q_chunk, kv_chunk, q_offset)


def _flash_layout(q, k, v, q_chunk, kv_chunk):
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, dv = v.shape
    G = Hq // Hkv
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    qh = q.reshape(B, Sq, Hkv, G, dh).transpose(0, 2, 3, 1, 4)
    qh = qh.reshape(B, Hkv, G, nq, q_chunk, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B, Hkv, nk, kv_chunk, dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B, Hkv, nk, kv_chunk, dv)
    return qh, kh, vh, (B, Hkv, G, nq, nk, dh, dv)


def _block_mask(q_pos, kv_pos, causal, window):
    mask = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        mask &= q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - kv_pos[None, :]) < window
    return mask


def _flash_fwd_impl(q, k, v, causal, window, softcap, scale, q_chunk,
                    kv_chunk, q_offset):
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, _ = k.shape
    dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0, (Sq, q_chunk, Sk, kv_chunk)
    qh, kh, vh, (B, Hkv, G, nq, nk, dh, dv) = _flash_layout(
        q, k, v, q_chunk, kv_chunk)

    def q_step(_, qi):
        q_blk = qh[:, :, :, qi]                            # (B,Hkv,G,qc,dh)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m, l, acc = carry
            k_blk = kh[:, :, ki]
            v_blk = vh[:, :, ki]
            kv_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            s = jnp.where(_block_mask(q_pos, kv_pos, causal, window),
                          s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((B, Hkv, G, q_chunk), jnp.float32),
                jnp.zeros((B, Hkv, G, q_chunk, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))           # logsumexp rows
        return None, (out.astype(q.dtype), lse)

    _, (out, lse) = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hkv, G, Sq, dv)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, dv)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, Hkv, G, Sq)
    return out, lse


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_vjp(q, k, v, causal, window, softcap, scale, q_chunk, kv_chunk,
               q_offset):
    return _flash_fwd_impl(q, k, v, causal, window, softcap, scale,
                           q_chunk, kv_chunk, q_offset)[0]


def _flash_fwd_rule(q, k, v, causal, window, softcap, scale, q_chunk,
                    kv_chunk, q_offset):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, softcap, scale,
                               q_chunk, kv_chunk, q_offset)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, softcap, scale, q_chunk, kv_chunk,
                    q_offset, res, do):
    q, k, v, out, lse = res
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, _ = k.shape
    dv = v.shape[-1]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(dh)
    q_chunk_ = min(q_chunk, Sq)
    kv_chunk_ = min(kv_chunk, Sk)
    nq, nk = Sq // q_chunk_, Sk // kv_chunk_
    qh, kh, vh, (B, Hkv, G, nq, nk, dh, dv) = _flash_layout(
        q, k, v, q_chunk_, kv_chunk_)
    doh = do.reshape(B, Sq, Hkv, G, dv).transpose(0, 2, 3, 1, 4)
    doh = doh.reshape(B, Hkv, G, nq, q_chunk_, dv).astype(jnp.float32)
    oh = out.reshape(B, Sq, Hkv, G, dv).transpose(0, 2, 3, 1, 4)
    oh = oh.reshape(B, Hkv, G, nq, q_chunk_, dv).astype(jnp.float32)
    lseh = lse.reshape(B, Hkv, G, nq, q_chunk_)
    # D_i = sum_k p_ik dp_ik = do_i · o_i
    Dh = jnp.sum(doh * oh, axis=-1)                        # (B,Hkv,G,nq,qc)

    def q_step(carry, qi):
        dk_acc, dv_acc = carry                             # (B,Hkv,Sk,·) f32
        q_blk = qh[:, :, :, qi].astype(jnp.float32)
        do_blk = doh[:, :, :, qi]
        L_blk = lseh[:, :, :, qi]
        D_blk = Dh[:, :, :, qi]
        q_pos = q_offset + qi * q_chunk_ + jnp.arange(q_chunk_)

        def kv_step(inner, ki):
            dq_blk, dk_acc, dv_acc = inner
            k_blk = kh[:, :, ki].astype(jnp.float32)
            v_blk = vh[:, :, ki].astype(jnp.float32)
            kv_pos = ki * kv_chunk_ + jnp.arange(kv_chunk_)
            s_raw = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                               preferred_element_type=jnp.float32) * scale_v
            if softcap is not None:
                t = jnp.tanh(s_raw / softcap)
                s = softcap * t
            else:
                s = s_raw
            mask = _block_mask(q_pos, kv_pos, causal, window)
            p = jnp.where(mask, jnp.exp(s - L_blk[..., None]), 0.0)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", do_blk, v_blk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - D_blk[..., None])
            if softcap is not None:
                ds = ds * (1.0 - jnp.square(t))
            dq_blk = dq_blk + jnp.einsum(
                "bhgqk,bhkd->bhgqd", ds, k_blk,
                preferred_element_type=jnp.float32) * scale_v
            dk_blk = jnp.einsum("bhgqk,bhgqd->bhkd", ds, q_blk,
                                preferred_element_type=jnp.float32) * scale_v
            dv_blk = jnp.einsum("bhgqk,bhgqd->bhkd", p, do_blk,
                                preferred_element_type=jnp.float32)
            sl = ki * kv_chunk_
            dk_acc = jax.lax.dynamic_update_slice_in_dim(
                dk_acc, jax.lax.dynamic_slice_in_dim(dk_acc, sl, kv_chunk_, 2)
                + dk_blk, sl, axis=2)
            dv_acc = jax.lax.dynamic_update_slice_in_dim(
                dv_acc, jax.lax.dynamic_slice_in_dim(dv_acc, sl, kv_chunk_, 2)
                + dv_blk, sl, axis=2)
            return (dq_blk, dk_acc, dv_acc), None

        dq0 = jnp.zeros((B, Hkv, G, q_chunk_, dh), jnp.float32)
        (dq_blk, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), jnp.arange(nk))
        return (dk_acc, dv_acc), dq_blk

    dk0 = jnp.zeros((B, Hkv, Sk, dh), jnp.float32)
    dv0 = jnp.zeros((B, Hkv, Sk, dv), jnp.float32)
    (dk_f, dv_f), dq_chunks = jax.lax.scan(q_step, (dk0, dv0),
                                           jnp.arange(nq))
    dq = dq_chunks.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hkv, G, Sq, dh)
    dq = dq.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, dh).astype(q.dtype)
    dk = dk_f.transpose(0, 2, 1, 3).astype(k.dtype)
    dvv = dv_f.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dvv


_flash_vjp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_tri(q, k, v, *, causal=True, window=None, softcap=None,
                        scale=None, q_chunk=512, kv_chunk=1024, q_offset=0):
    """Causality-aware variant: a python loop over q chunks where each chunk
    only attends to the structurally-unmasked KV prefix (and, for windows,
    skips the fully-masked left blocks) — ~2x fewer attention FLOPs for
    causal prefill/training.  Each chunk call is the custom-VJP
    :func:`flash_attention`, so memory stays flash-bounded under grad.
    Numerically identical to :func:`flash_attention`.  Beyond-paper perf
    optimization (EXPERIMENTS.md §Perf)."""
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, _ = k.shape
    dv = v.shape[-1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    gran = q_chunk                      # prefix granularity for skipping
    outs = []
    for qi in range(nq):
        q_blk = q[:, qi * q_chunk:(qi + 1) * q_chunk]
        q_lo = q_offset + qi * q_chunk
        q_hi = q_lo + q_chunk - 1
        k_hi = Sk if not causal else max(0, min(Sk, (q_hi // gran + 1) * gran))
        k_lo = 0
        if window is not None:
            k_lo = (max(0, q_lo - window + 1) // gran) * gran
        if k_hi <= k_lo:
            outs.append(jnp.zeros((B, q_chunk, Hq, dv), q.dtype))
            continue
        ks = k[:, k_lo:k_hi]
        vs = v[:, k_lo:k_hi]
        kc = min(kv_chunk, k_hi - k_lo)
        if (k_hi - k_lo) % kc:
            kc = q_chunk                # slice is always a q_chunk multiple
        out = flash_attention(
            q_blk, ks, vs, causal=causal, window=window, softcap=softcap,
            scale=scale, q_chunk=q_chunk, kv_chunk=kc,
            q_offset=q_lo - k_lo)
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


def decode_attention(q, k_cache, v_cache, pos, *, window=None, softcap=None,
                     scale=None):
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, Hq, dh); k_cache/v_cache: (B, S_cache, Hkv, dh/dv);
    pos: (B,) absolute position of the current token.
    For ring buffers (window is not None and S_cache == window) slot ``j``
    holds absolute position ``pos - ((pos - j) mod W)``.
    Returns (B, 1, Hq, dv).
    """
    B, _, Hq, dh = q.shape
    _, S, Hkv, _ = k_cache.shape
    dv = v_cache.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)

    qh = q.reshape(B, Hkv, G, dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)
    slots = jnp.arange(S)
    if window is not None and S == window:
        abs_pos = pos[:, None] - jnp.mod(pos[:, None] - slots[None, :], window)
        valid = abs_pos >= 0
    else:
        valid = slots[None, :] <= pos[:, None]
        if window is not None:
            valid &= (pos[:, None] - slots[None, :]) < window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", s.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(B, 1, Hq, dv)


# ----------------------------------------------------------------------
# GQA attention layer (init + apply for prefill/train and decode)
def init_attention(key, cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def attention_qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_prefill(p, x, cfg, *, local, positions=None, use_tri=False):
    """Returns (out, (k, v)) — k/v post-RoPE for cache seeding."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = attention_qkv(p, x, cfg, positions)
    window = cfg.window_size if local else None
    fn = flash_attention_tri if use_tri else flash_attention
    out = fn(q, k, v, causal=True, window=window, softcap=cfg.attn_softcap,
             scale=cfg.query_scale, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    out = out.reshape(B, S, -1) @ p["wo"]
    return out, (k, v)


def attention_decode(p, x, cfg, cache, pos, *, local, use_pallas=False):
    """x: (B,1,d); cache: {"k","v"}; pos: (B,).  Returns (out, new_cache).

    ``use_pallas``: dispatch the cache-attention to the Pallas TPU kernel
    (``repro.kernels.decode_attention``); on CPU it runs interpret=True.
    Off by default here because the jnp path lowers on any backend; the
    serving engine flips it on TPU."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = attention_qkv(p, x, cfg, pos[:, None])
    S_cache = cache["k"].shape[1]
    window = cfg.window_size if local else None
    if window is not None and S_cache == window:
        slot = jnp.mod(pos, window)
    else:
        slot = pos
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
    v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
    if use_pallas:
        from repro.kernels.ops import decode_attention as decode_kernel
        out = decode_kernel(q, k_cache, v_cache, pos, window=window,
                            softcap=cfg.attn_softcap, scale=cfg.query_scale)
    else:
        out = decode_attention(q, k_cache, v_cache, pos, window=window,
                               softcap=cfg.attn_softcap,
                               scale=cfg.query_scale)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache}


# ----------------------------------------------------------------------
# MLPs
def init_mlp(key, cfg, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu":
        return {"wi": dense_init(ks[0], d, f, dtype),
                "wg": dense_init(ks[1], d, f, dtype),
                "wo": dense_init(ks[2], f, d, dtype)}
    return {"wi": dense_init(ks[0], d, f, dtype),
            "wo": dense_init(ks[2], f, d, dtype)}


def mlp_apply(p, x, activation):
    if activation == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    elif activation == "squared_relu":
        h = jnp.square(jax.nn.relu(x @ p["wi"]))
    elif activation == "gelu":
        h = jax.nn.gelu(x @ p["wi"])
    else:
        raise ValueError(activation)
    return h @ p["wo"]
