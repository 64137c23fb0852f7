"""Plain float32 forward of a dense decoder, written from the published
architecture and not through the program: RMSNorm, RoPE, grouped-query
attention with an optional QKV bias, SwiGLU, a tied or untied head.

It imports nothing of the program.  It is given the sizes from the
configuration file and a weight tree in the program's layout (made by
``bench/weights.py``, not by the program), and it upcasts one layer at a
time, in blocks of rows, so that it fits beside the bfloat16 weights.
Every matrix product runs at ``Precision.HIGHEST``.

``quant="fp8"`` is the control: the same forward with every operand of the
linear layers and the head rounded to float8 (e4m3, one scale per row of
activations and per output column of weights), the step below bfloat16
that would tempt a change.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 448.0                  # largest finite float8_e4m3fn
TOKENS_PER_BLOCK = 4096


class Sizes(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    layers: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    qkv_bias: bool


def sizes_of(config_file: dict) -> Sizes:
    """The reference's sizes, read from a ``bench/configs`` file."""
    c = config_file["config"]
    return Sizes(c["hidden_size"], c["num_attention_heads"],
                 c["num_key_value_heads"], c["head_dim"],
                 c["intermediate_size"],
                 c["num_hidden_layers"], c["vocab_size"],
                 float(c["rope_theta"]), float(c["rms_norm_eps"]),
                 bool(c["tie_word_embeddings"]),
                 bool(config_file["architecture"]["qkv_bias"]))


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _linear(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def _layer(stacked, i, x, *, z: Sizes, quant):
    p = jax.tree.map(lambda a: a[i].astype(F32), stacked)
    a, m = p["mixer"], p["ffn"]
    B, S, _ = x.shape
    H, KV, hd = z.heads, z.kv_heads, z.head_dim
    inv = 1.0 / z.rope_theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(t):                                       # (B, S, heads, hd)
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                               axis=-1)

    def proj(h, name):
        out = _linear(h, a["w" + name], quant)
        return out + a["b" + name] if z.qkv_bias else out

    h = _norm(x, p["norm1"]["scale"], z.eps)
    q = rope(proj(h, "q").reshape(B, S, H, hd))
    k = rope(proj(h, "k").reshape(B, S, KV, hd))
    v = proj(h, "v").reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)        # query head h reads kv head
    v = jnp.repeat(v, H // KV, axis=2)        # h // (H / KV)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    x = x + _linear(o.reshape(B, S, H * hd), a["wo"], quant)
    h = _norm(x, p["norm2"]["scale"], z.eps)
    g = jax.nn.silu(_linear(h, m["wg"], quant)) * _linear(h, m["wi"], quant)
    return x + _linear(g, m["wo"], quant)


@functools.partial(jax.jit, static_argnames=("z",))
def _embed(table, tokens, *, z: Sizes):
    return jnp.take(table, tokens, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def _head(params, x, *, z: Sizes, quant):
    x = _norm(x[:, -1], params["final_norm"]["scale"].astype(F32), z.eps)
    table = params["embed"].T if z.tied else params["unembed"]
    return _linear(x, table.astype(F32), quant)


def reference_logits(z: Sizes, params, tokens,
                     quant: Optional[str] = None) -> np.ndarray:
    """Last-position logits, float32, shape (rows, vocab), for int token
    rows ``tokens`` of shape (rows, seq)."""
    tokens = np.asarray(tokens, np.int32)
    rows, seq = tokens.shape
    stacked = params["seg0"][0]
    block = max(1, TOKENS_PER_BLOCK // seq)
    out = []
    for r0 in range(0, rows, block):
        x = _embed(params["embed"], jnp.asarray(tokens[r0:r0 + block]), z=z)
        for i in range(z.layers):
            x = _layer(stacked, i, x, z=z, quant=quant)
        out.append(np.asarray(_head(params, x, z=z, quant=quant)))
    return np.concatenate(out, axis=0)
