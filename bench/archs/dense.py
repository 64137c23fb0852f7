"""A dense decoder of ``attn`` layers: RMSNorm, RoPE, grouped-query
attention with an optional QKV bias, SwiGLU, a tied or untied head.

The module of an architecture family, found by the name in a
configuration file's ``arch``.  It gives the harness the six names every
such module has; ``sizes_of`` returns the hashable value that the others
take back.
"""
from bench.counts import param_count, score_step
from bench.model_config import build
from bench.reference import reference_logits, sizes_of
from bench.weights import make_params

__all__ = ["build", "sizes_of", "param_count", "make_params",
           "reference_logits", "score_step"]
