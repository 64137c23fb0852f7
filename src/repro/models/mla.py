"""DeepSeek-V2 Multi-head Latent Attention (MLA).

The KV cache stores only the compressed latent ``c_kv`` (kv_lora_rank) plus a
single shared RoPE key head — the paper's memory win.  Two decode paths:

* ``naive``    — re-expand the cached latents to full K/V each step.
* ``absorbed`` — fold W_UK into the query and W_UV into the output so decode
  attends directly over latents (DeepSeek's deployment optimization; our
  beyond-paper perf variant for decode shapes).

With ``MLAConfig.yarn`` set, the rope part of q and k uses YaRN's blended
frequencies and the softmax scale carries mscale(mscale_all_dim)^2, as
DeepSeek-V2 publishes them; every path (prefill, both decodes) shares
``rope_inv_freq`` and ``softmax_scale``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import (NEG_INF, apply_rope, dense_init, init_rmsnorm,
                                 flash_attention, flash_attention_tri,
                                 rmsnorm_apply, rope_frequencies)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(yarn, dim: int, theta: float):
    """The rope dims (of ``dim``) between which YaRN's ramp runs: those
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context, floored and ceiled."""
    def dim_of(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(dim_of(yarn.beta_fast))
    high = math.ceil(dim_of(yarn.beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_inv_freq(cfg):
    """(rope_head_dim / 2,) inverse frequencies of the rope part."""
    m = cfg.mla
    extra = rope_frequencies(m.rope_head_dim, cfg.rope_theta)
    if m.yarn is None:
        return extra
    low, high = yarn_correction_range(m.yarn, m.rope_head_dim, cfg.rope_theta)
    ramp = jnp.clip((jnp.arange(m.rope_head_dim // 2, dtype=jnp.float32)
                     - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / m.yarn.factor * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg) -> float:
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    if m.yarn is not None and m.yarn.mscale_all_dim:
        scale *= yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg):
    """x: (..., S, H, rope_head_dim) rotated, then scaled by YaRN's
    mscale(mscale) / mscale(mscale_all_dim) where that is not 1."""
    out = apply_rope(x, positions, cfg.rope_theta, rope_inv_freq(cfg))
    y = cfg.mla.yarn
    if y is not None:
        gain = (yarn_mscale(y.factor, y.mscale)
                / yarn_mscale(y.factor, y.mscale_all_dim))
        if gain != 1.0:
            out = (out.astype(jnp.float32) * gain).astype(out.dtype)
    return out


def init_mla(key, cfg):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    qd = m.nope_head_dim + m.rope_head_dim
    p = {
        "wkv_a": dense_init(ks[1], d, m.kv_lora_rank + m.rope_head_dim, dtype),
        "ckv_norm": init_rmsnorm(m.kv_lora_rank),
        "wkv_b": dense_init(ks[2], m.kv_lora_rank,
                            H * (m.nope_head_dim + m.v_head_dim), dtype),
        "wo": dense_init(ks[3], H * m.v_head_dim, d, dtype),
    }
    if m.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, m.q_lora_rank, dtype)
        p["q_norm"] = init_rmsnorm(m.q_lora_rank)
        p["wq_b"] = dense_init(ks[4], m.q_lora_rank, H * qd, dtype)
    else:
        p["wq"] = dense_init(ks[0], d, H * qd, dtype)
    return p


def _mla_q(p, x, cfg, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank:
        q = rmsnorm_apply(p["q_norm"], x @ p["wq_a"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, qd)
    qn, qr = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    qr = _rope(qr, positions, cfg)
    return qn, qr


def _mla_latents(p, x, cfg, positions):
    m = cfg.mla
    kv = x @ p["wkv_a"]
    ckv, kr = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    ckv = rmsnorm_apply(p["ckv_norm"], ckv, cfg.norm_eps)
    kr = _rope(kr[:, :, None, :], positions, cfg)[:, :, 0, :]
    return ckv, kr


def _expand(p, ckv, cfg):
    """latents (B,S,lora) -> k_nope (B,S,H,nope), v (B,S,H,v)."""
    m = cfg.mla
    B, S, _ = ckv.shape
    H = cfg.n_heads
    kvb = (ckv @ p["wkv_b"]).reshape(B, S, H, m.nope_head_dim + m.v_head_dim)
    return kvb[..., :m.nope_head_dim], kvb[..., m.nope_head_dim:]


@jax.named_scope("mla")
def mla_prefill(p, x, cfg, *, positions=None, use_tri=False):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    qn, qr = _mla_q(p, x, cfg, positions)
    ckv, kr = _mla_latents(p, x, cfg, positions)
    kn, v = _expand(p, ckv, cfg)
    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, :, None, :], qr.shape)], axis=-1)
    fn = flash_attention_tri if use_tri else flash_attention
    out = fn(q, k, v, causal=True, scale=softmax_scale(cfg),
             q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    out = out.reshape(B, S, -1) @ p["wo"]
    return out, (ckv, kr)


@jax.named_scope("mla")
def mla_decode(p, x, cfg, cache, pos):
    """cache: {"ckv": (B,S,lora), "kr": (B,S,rope_dim)}; pos: (B,)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    qn, qr = _mla_q(p, x, cfg, pos[:, None])
    ckv_t, kr_t = _mla_latents(p, x, cfg, pos[:, None])
    bidx = jnp.arange(B)
    ckv = cache["ckv"].at[bidx, pos].set(ckv_t[:, 0])
    kr = cache["kr"].at[bidx, pos].set(kr_t[:, 0])
    S = ckv.shape[1]
    scale = softmax_scale(cfg)
    valid = jnp.arange(S)[None, :] <= pos[:, None]

    if m.decode_mode == "absorbed":
        wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, -1)
        w_uk = wkv_b[..., :m.nope_head_dim]                 # (lora,H,nope)
        w_uv = wkv_b[..., m.nope_head_dim:]                 # (lora,H,v)
        q_lat = jnp.einsum("bqhn,lhn->bqhl", qn, w_uk)
        s = (jnp.einsum("bqhl,bsl->bhqs", q_lat, ckv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bsr->bhqs", qr, kr,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhqs,bsl->bqhl", probs, ckv)
        out = jnp.einsum("bqhl,lhv->bqhv", o_lat, w_uv)
    else:
        kn, v = _expand(p, ckv, cfg)
        q = jnp.concatenate([qn, qr], axis=-1)              # (B,1,H,qd)
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr[:, :, None, :],
                                  (B, S, H, m.rope_head_dim))], axis=-1)
        s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqs,bshv->bqhv", probs, v)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, {"ckv": ckv, "kr": kr}
