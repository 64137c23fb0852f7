"""DeepSeek-V2's family: multi-head latent attention with YaRN rope in every
layer, a dense SwiGLU layer first, then layers of routed and shared
experts, an untied head.

The module of an architecture family, found by the name in a
configuration file's ``arch``, with the six names every such module has.
A configuration file of this family states the published ``config.json``
keys; its ``n_routed_experts`` in ``config`` is the number of experts one
chip holds of each expert layer, from ``held_first`` on, while the router
keeps the published count (``published``).  The served ``ModelConfig``
holds just those experts, routes over all of them and dispatches without
dropping (``build``); the reference is given the same share.

The plain reference (``reference_logits``) imports nothing of the program.
It runs in float32 at ``Precision.HIGHEST`` on a weight tree in the
program's layout, made by ``bench/weights.py``, one layer at a time and
attention in blocks of queries, so that a 4096-token row fits beside the
bfloat16 weights.  Its departures from the published model:

- rope rotates the two halves of the 64 rope dims (``x[:32]``, ``x[32:]``)
  where the published code rotates interleaved pairs; with random weights
  that is a fixed permutation of the rope columns of ``wq`` and ``wkv_a``;
- the experts that this chip does not hold add nothing (the chip's share of
  the layer), as in the program.

``quant="fp8"`` is the control, as in ``bench/reference.py``: every operand
of the linear layers (router and experts included) and of the head rounded
to float8 e4m3, one scale per row of activations and per output column of
weights.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import make_params

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 448.0                  # largest finite float8_e4m3fn
TOKENS_PER_BLOCK = 4096          # rows of tokens per reference pass
QUERY_BLOCK = 512                # queries per attention block
BF16, F32_BYTES, I32 = 2, 4, 4

__all__ = ["build", "sizes_of", "param_count", "make_params",
           "reference_logits", "score_step"]


class Sizes(NamedTuple):
    hidden: int
    heads: int
    nope: int                    # qk_nope_head_dim
    rope: int                    # qk_rope_head_dim
    v_dim: int
    kv_lora: int
    dense_ffn: int               # intermediate_size of the dense layers
    expert_ffn: int              # moe_intermediate_size
    router: int                  # experts the router scores (published)
    held_first: int
    held: int                    # experts held here, from held_first
    top_k: int
    shared: int                  # shared experts, one SwiGLU of shared x width
    norm_topk: bool
    routed_scale: float
    dense_layers: int            # first_k_dense_replace
    layers: int
    vocab: int
    rope_theta: float
    eps: float
    yarn: Optional[Tuple[float, int, float, float, float, float]]
    tied: bool


def sizes_of(config_file: dict) -> Sizes:
    """The reference's sizes, read from a ``bench/configs`` file."""
    c, pub = config_file["config"], config_file["published"]
    if c.get("q_lora_rank") is not None:
        raise ValueError(f"{config_file['name']}: q_lora_rank is not "
                         f"served yet (DeepSeek-V2-Lite has none)")
    if c.get("scoring_func", "softmax") != "softmax" or c.get(
            "topk_method", "greedy") != "greedy":
        raise ValueError(f"{config_file['name']}: only greedy softmax "
                         f"routing is served")
    y = c.get("rope_scaling")
    if y is not None and y["type"] != "yarn":
        raise ValueError(f"{config_file['name']}: rope_scaling {y['type']!r}")
    yarn = None if y is None else (
        float(y["factor"]), int(y["original_max_position_embeddings"]),
        float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
        float(y["mscale_all_dim"]))
    return Sizes(
        c["hidden_size"], c["num_attention_heads"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"],
        c["intermediate_size"], c["moe_intermediate_size"],
        pub["n_routed_experts"], config_file["held_first"],
        c["n_routed_experts"], c["num_experts_per_tok"],
        c["n_shared_experts"], bool(c["norm_topk_prob"]),
        float(c["routed_scaling_factor"]), c["first_k_dense_replace"],
        c["num_hidden_layers"], c["vocab_size"], float(c["rope_theta"]),
        float(c["rms_norm_eps"]), yarn, bool(c["tie_word_embeddings"]))


def build(spec: dict):
    """The ``ModelConfig`` served for a configuration file's contents: the
    registry entry with every size from the file, the held experts of
    each expert layer, dropless dispatch and the published routing."""
    from repro.configs import get_config
    from repro.configs.base import YaRNConfig
    z = sizes_of(spec)
    base = get_config(spec["registry"])
    yarn = None if z.yarn is None else YaRNConfig(*z.yarn)
    mla = dataclasses.replace(
        base.mla, kv_lora_rank=z.kv_lora, q_lora_rank=None,
        nope_head_dim=z.nope, rope_head_dim=z.rope, v_head_dim=z.v_dim,
        yarn=yarn)
    moe = dataclasses.replace(
        base.moe, n_experts=z.router, top_k=z.top_k, d_ff=z.expert_ffn,
        n_shared=z.shared, held=(z.held_first, z.held), dispatch="dropless",
        norm_topk=z.norm_topk, routed_scale=z.routed_scale)
    return dataclasses.replace(
        base, name=spec["name"], n_layers=z.layers, d_model=z.hidden,
        n_heads=z.heads, n_kv_heads=z.heads, head_dim=z.v_dim,
        d_ff=z.dense_ffn, vocab_size=z.vocab,
        segments=((("mla",), z.dense_layers),
                  (("mla_moe",), z.layers - z.dense_layers)),
        rope_theta=z.rope_theta, norm_eps=z.eps, tie_embeddings=z.tied,
        activation="swiglu", dtype=spec["architecture"]["dtype"],
        mla=mla, moe=moe)


# -- counts ------------------------------------------------------------------
def _attention_params(z: Sizes) -> int:
    return (z.hidden * z.heads * (z.nope + z.rope)              # wq
            + z.hidden * (z.kv_lora + z.rope)                   # wkv_a
            + z.kv_lora * z.heads * (z.nope + z.v_dim)          # wkv_b
            + z.heads * z.v_dim * z.hidden)                     # wo


def _expert_layer_weights(z: Sizes) -> int:
    """bfloat16 weights of one expert layer's feed-forward part."""
    return 3 * z.hidden * z.expert_ffn * (z.held + z.shared)


def param_count(z: Sizes) -> int:
    norms = 2 * z.hidden + z.kv_lora
    dense = _attention_params(z) + norms + 3 * z.hidden * z.dense_ffn
    expert = (_attention_params(z) + norms + _expert_layer_weights(z)
              + z.hidden * z.router)
    embed = z.vocab * z.hidden * (1 if z.tied else 2)
    return (z.dense_layers * dense + (z.layers - z.dense_layers) * expert
            + embed + z.hidden)


def score_step(z: Sizes, batch: int, seq: int) -> dict:
    """``{"flops", "bytes"}`` of one score step over (batch, seq), lower
    bounds as in ``bench/counts.py``: every projection over all tokens,
    causal attention over the seq (seq + 1) / 2 pairs it needs (q·k over
    nope + rope dims, p·v over the value dims), the router, the shared
    experts, the held experts over their expected rows, tokens x top_k x
    held / router, the dense layers, and the head for the last position.
    Bytes: every weight read once (the router in float32, as served),
    the gathered rows of the untied embedding, the token ids and the
    bfloat16 logits."""
    d, tokens = z.hidden, batch * seq
    experts_layers = z.layers - z.dense_layers
    pairs = seq * (seq + 1) // 2
    attention = (2 * tokens * _attention_params(z)
                 + 2 * batch * z.heads * (z.nope + z.rope + z.v_dim) * pairs)
    routed_rows = tokens * z.top_k * z.held // z.router
    expert = (2 * tokens * d * z.router
              + 2 * 3 * d * z.expert_ffn * (tokens * z.shared + routed_rows))
    flops = (z.layers * attention + z.dense_layers * 2 * tokens * 3 * d
             * z.dense_ffn + experts_layers * expert + 2 * batch * d * z.vocab)

    weights = (z.layers * _attention_params(z)
               + z.dense_layers * 3 * d * z.dense_ffn
               + experts_layers * _expert_layer_weights(z))
    weights += (z.vocab * d if z.tied
                else min(tokens, z.vocab) * d + d * z.vocab)
    floats = (z.layers * (2 * d + z.kv_lora) + d
              + experts_layers * d * z.router)
    nbytes = (BF16 * weights + F32_BYTES * floats + I32 * tokens
              + BF16 * batch * z.vocab)
    return {"flops": flops, "bytes": nbytes}


# -- the plain reference -------------------------------------------------------
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


def _swiglu(m, h, quant):
    g = jax.nn.silu(_linear(h, m["wg"], quant)) * _linear(h, m["wi"], quant)
    return _linear(g, m["wo"], quant)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope_tables(z: Sizes, seq: int):
    """cos and sin, (seq, 1, rope / 2), of DeepSeek-V2's YaRN rope, and
    the attention softmax scale, computed in float64."""
    dim, theta = z.rope, z.rope_theta
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    gain = 1.0
    scale = (z.nope + z.rope) ** -0.5
    if z.yarn is not None:
        factor, orig, beta_fast, beta_slow, ms, ms_all = z.yarn

        def corr_dim(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))
        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        extrapolated = 1.0 - ramp
        inv = inv / factor * (1 - extrapolated) + inv * extrapolated
        gain = _mscale(factor, ms) / _mscale(factor, ms_all)
        if ms_all:
            scale *= _mscale(factor, ms_all) ** 2
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv
    cos = (np.cos(ang) * gain).astype(np.float32)[:, None]
    sin = (np.sin(ang) * gain).astype(np.float32)[:, None]
    return cos, sin, scale


def _attention(a, h, z: Sizes, quant):
    B, S, _ = h.shape
    H, nope, vd = z.heads, z.nope, z.v_dim
    cos, sin, scale = _rope_tables(z, S)

    def rope(t):                                       # (B, S, heads, rope)
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                               axis=-1)

    q = _linear(h, a["wq"], quant).reshape(B, S, H, nope + z.rope)
    kv = _linear(h, a["wkv_a"], quant)
    latent = _norm(kv[..., :z.kv_lora], a["ckv_norm"]["scale"], z.eps)
    k_rope = rope(kv[..., None, z.kv_lora:])           # one key for all heads
    kvb = _linear(latent, a["wkv_b"], quant).reshape(B, S, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], axis=-1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_rope, (B, S, H, z.rope))], axis=-1)
    v = kvb[..., nope:]
    block = min(QUERY_BLOCK, S)
    outs = []
    for q0 in range(0, S, block):                      # causal: keys to q0+block
        n = min(q0 + block, S)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:n], k[:, :n],
                       precision=HIGHEST) * scale
        causal = (q0 + jnp.arange(n - q0))[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                               v[:, :n], precision=HIGHEST))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, H * vd)
    return _linear(o, a["wo"], quant)


def _experts(f, h, z: Sizes, quant):
    """Softmax gates over all ``router`` experts in float32, the top_k of
    each token, and the held experts' part: each held expert over every
    token, weighted by its gate where the token chose it and 0 elsewhere;
    then the shared experts for every token."""
    gates = jax.nn.softmax(_linear(h, f["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(gates, z.top_k)
    if z.norm_topk:
        top = top / top.sum(-1, keepdims=True)
    else:
        top = top * z.routed_scale
    out = _swiglu(f["shared"], h, quant)
    for j in range(z.held):
        w = jnp.sum(jnp.where(idx == z.held_first + j, top, 0.0), axis=-1)
        e = {name[:2]: f[name][j] for name in ("wi_e", "wg_e", "wo_e")}
        out = out + w[..., None] * _swiglu(e, h, quant)
    return out


@functools.partial(jax.jit, static_argnames=("z", "quant", "experts"))
def _layer(stacked, i, x, *, z: Sizes, quant, experts: bool):
    p = jax.tree.map(lambda a: a[i].astype(F32), stacked)
    h = _norm(x, p["norm1"]["scale"], z.eps)
    x = x + _attention(p["mixer"], h, z, quant)
    h = _norm(x, p["norm2"]["scale"], z.eps)
    if experts:
        return x + _experts(p["ffn"], h, z, quant)
    return x + _swiglu(p["ffn"], h, quant)


@jax.jit
def _embed(table, tokens):
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
    segments = ((params["seg0"][0], z.dense_layers, False),
                (params["seg1"][0], z.layers - z.dense_layers, True))
    block = max(1, TOKENS_PER_BLOCK // seq)
    out = []
    for r0 in range(0, rows, block):
        x = _embed(params["embed"], jnp.asarray(tokens[r0:r0 + block]))
        for stacked, n, experts in segments:
            for i in range(n):
                x = _layer(stacked, i, x, z=z, quant=quant, experts=experts)
        out.append(np.asarray(_head(params, x, z=z, quant=quant)))
    return np.concatenate(out, axis=0)
