"""Block assembly: per-kind init/apply, super-block (pattern) execution, and
segment scan.  Segments are ``lax.scan``s over stacked super-block params so
HLO size is independent of depth.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models import layers, mla, moe, rglru, xlstm
from repro.models.layers import init_rmsnorm, rmsnorm_apply


# ----------------------------------------------------------------------
# per-block init
def init_block(key, kind, cfg):
    ks = jax.random.split(key, 4)
    p = {"norm1": init_rmsnorm(cfg.d_model)}
    if cfg.use_post_norm:
        p["post_norm1"] = init_rmsnorm(cfg.d_model)
    if kind in ("attn", "attn_local", "attn_moe"):
        p["mixer"] = layers.init_attention(ks[0], cfg)
    elif kind in ("mla", "mla_moe"):
        p["mixer"] = mla.init_mla(ks[0], cfg)
    elif kind == "rglru":
        p["mixer"] = rglru.init_rglru_block(ks[0], cfg)
    elif kind == "mlstm":
        p["mixer"] = xlstm.init_mlstm_block(ks[0], cfg)
    elif kind == "slstm":
        p["mixer"] = xlstm.init_slstm_block(ks[0], cfg)
    else:
        raise ValueError(kind)
    if _has_ffn(kind, cfg):
        p["norm2"] = init_rmsnorm(cfg.d_model)
        if cfg.use_post_norm:
            p["post_norm2"] = init_rmsnorm(cfg.d_model)
        if kind in ("attn_moe", "mla_moe"):
            p["ffn"] = moe.init_moe(ks[1], cfg)
        else:
            p["ffn"] = layers.init_mlp(ks[1], cfg)
    return p


def _has_ffn(kind, cfg):
    if kind in ("mlstm", "slstm"):
        return False
    if kind in ("attn_moe", "mla_moe"):
        return True
    return cfg.d_ff > 0


# ----------------------------------------------------------------------
# per-block apply
def block_apply(kind, cfg, p, x, *, cache=None, pos=None, decode=False,
                use_tri=False, expert_rows=False):
    """Returns (x, new_cache_or_None, aux_scalar); with ``expert_rows``
    also the rows routed to each held expert, (held,) int32 (None for a
    block without experts)."""
    aux = jnp.zeros((), jnp.float32)
    rows = None
    if decode and cache is not None:
        # keep per-layer cache slices opaque: without this barrier XLA:CPU
        # hoists an f32 convert of the ENTIRE stacked cache out of the
        # layer scan (3-13 GB/device of pure lowering artifact)
        cache = jax.tree.map(jax.lax.optimization_barrier, cache)
    h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)

    if kind in ("attn", "attn_local", "attn_moe"):
        local = kind == "attn_local"
        if decode:
            out, new_cache = layers.attention_decode(
                p["mixer"], h, cfg, cache, pos, local=local)
        else:
            out, kv = layers.attention_prefill(
                p["mixer"], h, cfg, local=local, use_tri=use_tri)
            new_cache = kv                      # (k, v) — assembled by caller
    elif kind in ("mla", "mla_moe"):
        if decode:
            out, new_cache = mla.mla_decode(p["mixer"], h, cfg, cache, pos)
        else:
            out, new_cache = mla.mla_prefill(p["mixer"], h, cfg,
                                             use_tri=use_tri)
    elif kind == "rglru":
        out, new_cache = rglru.rglru_block_apply(
            p["mixer"], h, cfg, cache=cache if decode else None)
    elif kind == "mlstm":
        out, new_cache = xlstm.mlstm_block_apply(
            p["mixer"], h, cfg, cache=cache if decode else None)
    elif kind == "slstm":
        out, new_cache = xlstm.slstm_block_apply(
            p["mixer"], h, cfg, cache=cache if decode else None)
    else:
        raise ValueError(kind)

    if cfg.use_post_norm:
        out = rmsnorm_apply(p["post_norm1"], out, cfg.norm_eps)
    x = x + out

    if _has_ffn(kind, cfg):
        h2 = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if kind in ("attn_moe", "mla_moe"):
            out2, aux, *held = moe.moe_apply(p["ffn"], h2, cfg,
                                             rows=expert_rows)
            rows = held[0] if held else None
        else:
            out2 = layers.mlp_apply(p["ffn"], h2, cfg.activation)
        if cfg.use_post_norm:
            out2 = rmsnorm_apply(p["post_norm2"], out2, cfg.norm_eps)
        x = x + out2
    if expert_rows:
        return x, new_cache, aux, rows
    return x, new_cache, aux


# ----------------------------------------------------------------------
# cache construction
def init_block_cache(kind, cfg, batch, max_len):
    dtype = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    if kind in ("attn", "attn_moe"):
        return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype)}
    if kind == "attn_local":
        L = min(max_len, cfg.window_size)
        return {"k": jnp.zeros((batch, L, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, L, cfg.n_kv_heads, hd), dtype)}
    if kind in ("mla", "mla_moe"):
        m = cfg.mla
        return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, max_len, m.rope_head_dim), dtype)}
    if kind == "rglru":
        return rglru.init_rglru_cache(cfg, batch)
    if kind == "mlstm":
        return xlstm.init_mlstm_cache(cfg, batch)
    if kind == "slstm":
        return xlstm.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def seed_block_cache(kind, cfg, empty_cache, prefill_out, seq_len):
    """Place prefill products into an (empty) decode cache."""
    if kind in ("attn", "attn_moe", "attn_local", "mla", "mla_moe"):
        if kind in ("mla", "mla_moe"):
            parts = {"ckv": prefill_out[0], "kr": prefill_out[1]}
        else:
            parts = {"k": prefill_out[0], "v": prefill_out[1]}
        out = {}
        for name, val in parts.items():
            buf = empty_cache[name]
            L = buf.shape[1]
            if seq_len == L:                    # exact fit: the values ARE
                out[name] = val.astype(buf.dtype)   # the cache (no scatter)
            elif seq_len > L:                   # ring buffer wrap
                tail = val[:, seq_len - L:]
                slots = jnp.mod(jnp.arange(seq_len - L, seq_len), L)
                out[name] = buf.at[:, slots].set(tail.astype(buf.dtype))
            else:
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    buf, val.astype(buf.dtype), 0, axis=1)
        return out
    return prefill_out                          # recurrent states pass through


# ----------------------------------------------------------------------
# super-block (one pattern instance) + segments
def init_segment(key, pattern, repeats, cfg):
    """Stacked params: tuple over pattern positions, leaves (repeats, ...)."""
    def one(key):
        ks = jax.random.split(key, len(pattern))
        return tuple(init_block(ks[i], kind, cfg)
                     for i, kind in enumerate(pattern))
    keys = jax.random.split(key, repeats)
    per = [one(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per) if repeats > 1 \
        else jax.tree.map(lambda x: x[None], per[0])


def superblock_apply(pattern, cfg, params_tuple, x, caches_tuple=None,
                     pos=None, decode=False, use_tri=False, constrain=None,
                     expert_rows=False):
    new_caches, aux_total, rows = [], jnp.zeros((), jnp.float32), []
    for i, kind in enumerate(pattern):
        cache_i = caches_tuple[i] if caches_tuple is not None else None
        x, nc, aux, *r = block_apply(kind, cfg, params_tuple[i], x,
                                     cache=cache_i, pos=pos, decode=decode,
                                     use_tri=use_tri, expert_rows=expert_rows)
        if constrain is not None:
            x = constrain(x, "activation")
        new_caches.append(nc)
        aux_total = aux_total + aux
        rows += r
    if expert_rows:
        return x, tuple(new_caches), aux_total, tuple(rows)
    return x, tuple(new_caches), aux_total


def _sqrt_divisor(n: int) -> int:
    """Largest divisor of n not exceeding sqrt(n)+1 (for 2-level remat)."""
    best = 1
    d = 1
    while d * d <= n + 1:
        if n % d == 0:
            best = d
        d += 1
    return best


def segment_scan(pattern, repeats, cfg, seg_params, x, *, seg_caches=None,
                 pos=None, decode=False, use_tri=False, remat=False,
                 collect_cache=False, constrain=None, expert_rows=False):
    """Run ``repeats`` stacked super-blocks.  Returns (x, caches, aux), and
    with ``expert_rows`` (no caches) also a tuple over the pattern of each
    block's (repeats, held) routed rows, None for a block without experts.

    Training (remat=True, no caches) uses TWO-LEVEL sqrt(R) checkpointing:
    an outer scan over groups saves only R/k boundaries; the rematted inner
    scan over k blocks recomputes within each group — peak saved activations
    drop from R to ~2*sqrt(R) layer boundaries.
    """
    assert not (expert_rows and (collect_cache or seg_caches is not None))

    def body(carry, xs):
        x, aux = carry
        if seg_caches is not None:
            p, c = xs
        else:
            p, c = xs, None
        x, nc, a, *rows = superblock_apply(
            pattern, cfg, p, x, caches_tuple=c, pos=pos, decode=decode,
            use_tri=use_tri, constrain=constrain, expert_rows=expert_rows)
        out = nc if (collect_cache or seg_caches is not None) else None
        return (x, aux + a), (rows[0] if expert_rows else out)

    if remat and seg_caches is None and not collect_cache and repeats >= 4:
        k = _sqrt_divisor(repeats)
        if k > 1:
            grouped = jax.tree.map(
                lambda l: l.reshape(repeats // k, k, *l.shape[1:]),
                seg_params)

            @jax.checkpoint
            def outer_body(carry, p_grp):
                return jax.lax.scan(jax.checkpoint(body), carry, p_grp)

            (x, aux), rows = jax.lax.scan(
                outer_body, (x, jnp.zeros((), jnp.float32)), grouped)
            if expert_rows:
                return x, None, aux, jax.tree.map(
                    lambda l: l.reshape(repeats, *l.shape[2:]), rows)
            return x, None, aux

    if remat:
        body = jax.checkpoint(body)
    xs = (seg_params, seg_caches) if seg_caches is not None else seg_params
    (x, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    if expert_rows:
        return x, None, aux, ys
    return x, ys, aux
