"""Operations and bytes of one score step, from the configuration's sizes
and the shapes alone.

The score step is one forward over a (batch, seq) block of tokens that
returns the last position's logits.  Operations count each multiply-add as
two and follow what the step needs: every projection and feed-forward
product over all batch x seq tokens, causal attention over the
seq (seq + 1) / 2 query-key pairs it needs, and the head for the last
position only.  Norms, RoPE and softmax are left out (well under one per
cent here), so the count is a lower bound.

Bytes are a lower bound too: every weight read once, except the rows of an
untied embedding table that the gather does not touch, plus the token ids
in and the bfloat16 logits out.
"""
from __future__ import annotations

from bench.reference import Sizes

BF16, F32, I32 = 2, 4, 4


def layer_params(z: Sizes) -> int:
    d, qd, kvd = z.hidden, z.heads * z.head_dim, z.kv_heads * z.head_dim
    n = d * qd + 2 * d * kvd + qd * d + 3 * d * z.ffn + 2 * d
    if z.qkv_bias:
        n += qd + 2 * kvd
    return n


def param_count(z: Sizes) -> int:
    embed = z.vocab * z.hidden * (1 if z.tied else 2)
    return z.layers * layer_params(z) + embed + z.hidden


def score_step(z: Sizes, batch: int, seq: int) -> dict:
    """``{"flops", "bytes"}`` of one score step over (batch, seq)."""
    d, qd, kvd = z.hidden, z.heads * z.head_dim, z.kv_heads * z.head_dim
    tokens = batch * seq
    pairs = seq * (seq + 1) // 2
    per_layer = (2 * tokens * d * (qd + 2 * kvd)        # q, k, v
                 + 2 * tokens * qd * d                   # output projection
                 + 4 * batch * z.heads * z.head_dim * pairs  # QK^T and PV
                 + 2 * tokens * 3 * d * z.ffn)           # SwiGLU
    flops = z.layers * per_layer + 2 * batch * d * z.vocab

    norms = 2 * d * z.layers + d                         # float32 scales
    weights = z.layers * (layer_params(z) - 2 * d) + (z.vocab * d
                                                      if z.tied else 0)
    if not z.tied:
        weights += min(tokens, z.vocab) * d + d * z.vocab
    nbytes = BF16 * weights + F32 * norms + I32 * tokens + BF16 * batch * z.vocab
    return {"flops": flops, "bytes": nbytes}


def least_seconds(step: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(step["flops"] / peak["bf16_flops_per_s"],
               step["bytes"] / peak["hbm_bytes_per_s"])
