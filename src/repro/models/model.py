"""Top-level model API: init / loss / prefill / decode_step / input_specs.

A ``Model`` interprets a ``ModelConfig``.  All entry points are pure
functions of (params, inputs) and are pjit-compatible; sharding is decided by
the launch layer (``repro.sharding`` + ``repro.launch``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import transformer
from repro.models.layers import dense_init, init_rmsnorm, rmsnorm_apply


def _softcap(x, cap):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


class Model:
    def __init__(self, cfg: ModelConfig, use_tri: bool = False,
                 constrain=None):
        self.cfg = cfg
        self.use_tri = use_tri      # causality-aware flash variant (perf)
        # optional sharding-constraint hook: constrain(x, tag) applied to
        # activations at block boundaries and to loss logits (launch layer
        # injects lax.with_sharding_constraint closures over the mesh)
        self.constrain = constrain

    # ------------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, len(cfg.segments) + 3)
        params: dict[str, Any] = {
            "embed": (jax.random.normal(
                keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
                * cfg.d_model ** -0.5).astype(dtype),
            "final_norm": init_rmsnorm(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = dense_init(keys[1], cfg.d_model,
                                           cfg.vocab_size, dtype)
        for i, (pattern, repeats) in enumerate(cfg.segments):
            params[f"seg{i}"] = transformer.init_segment(
                keys[2 + i], pattern, repeats, cfg)
        return params

    # ------------------------------------------------------------------
    def _embed(self, params, tokens, frontend_embeds=None, frontend_mask=None):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.scale_embedding:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if frontend_embeds is not None:
            x = jnp.where(frontend_mask[..., None], frontend_embeds.astype(x.dtype), x)
        return x

    def _logits(self, params, x, constrain=None):
        cfg = self.cfg
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = x @ table.astype(x.dtype)
        if constrain is not None:
            logits = constrain(logits, "logits")
        return _softcap(logits, cfg.final_softcap)

    # ------------------------------------------------------------------
    def forward(self, params, tokens, frontend_embeds=None,
                frontend_mask=None, expert_rows=False):
        """Full-sequence forward to hidden states; returns (x, aux), and
        with ``expert_rows`` also the rows routed to each held expert of
        every expert layer, in layer order: (expert layers, held) int32."""
        cfg = self.cfg
        x = self._embed(params, tokens, frontend_embeds, frontend_mask)
        if self.constrain is not None:
            x = self.constrain(x, "activation")
        aux = jnp.zeros((), jnp.float32)
        rows = []
        for i, (pattern, repeats) in enumerate(cfg.segments):
            x, _, a, *seg_rows = transformer.segment_scan(
                pattern, repeats, cfg, params[f"seg{i}"], x,
                use_tri=self.use_tri, remat=cfg.remat,
                constrain=self.constrain, expert_rows=expert_rows)
            aux = aux + a
            held = [r for r in (seg_rows[0] if seg_rows else ())
                    if r is not None]              # each (repeats, held)
            if held:
                rows.append(jnp.stack(held, axis=1).reshape(
                    -1, held[0].shape[-1]))
        if expert_rows:
            return x, aux, jnp.concatenate(rows)
        return x, aux

    def loss(self, params, batch, constrain=None, seq_chunk=512):
        """Next-token cross-entropy, chunked over the sequence so the full
        (B, S, vocab) logits tensor is never materialized."""
        cfg = self.cfg
        constrain = constrain if constrain is not None else self.constrain
        x, aux = self.forward(params, batch["tokens"],
                              batch.get("frontend_embeds"),
                              batch.get("frontend_mask"))
        targets = batch["targets"]
        B, S = targets.shape
        seq_chunk = min(seq_chunk, S)
        assert S % seq_chunk == 0
        nc = S // seq_chunk
        xc = x.reshape(B, nc, seq_chunk, cfg.d_model).transpose(1, 0, 2, 3)
        tc = targets.reshape(B, nc, seq_chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def chunk_xent(xb, tb):
            # rematerialized: the (B, chunk, vocab) logits are recomputed in
            # the backward pass instead of being saved per scan iteration
            logits = self._logits(params, xb, constrain).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
            return jnp.sum(logz - gold)

        def chunk_loss(carry, xs):
            xb, tb = xs
            return carry + chunk_xent(xb, tb), None

        total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32),
                                (xc, tc))
        loss = total / (B * S)
        return loss + aux, {"xent": loss, "aux": aux}

    # ------------------------------------------------------------------
    def init_cache(self, batch, max_len):
        cfg = self.cfg
        caches = []
        for pattern, repeats in cfg.segments:
            per_pos = tuple(
                jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (repeats,) + x.shape),
                    transformer.init_block_cache(kind, cfg, batch, max_len))
                for kind in pattern)
            caches.append(per_pos)
        return tuple(caches)

    def prefill(self, params, tokens, max_len=None, frontend_embeds=None,
                frontend_mask=None):
        """Run the prompt; returns (last-token logits, decode cache)."""
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S
        x = self._embed(params, tokens, frontend_embeds, frontend_mask)
        if self.constrain is not None:
            x = self.constrain(x, "activation")
        caches = []
        for i, (pattern, repeats) in enumerate(cfg.segments):
            x, raw, _ = transformer.segment_scan(
                pattern, repeats, cfg, params[f"seg{i}"], x,
                use_tri=self.use_tri, remat=False, collect_cache=True,
                constrain=self.constrain)
            empty = tuple(
                jax.tree.map(
                    lambda l: jnp.broadcast_to(l[None], (repeats,) + l.shape),
                    transformer.init_block_cache(kind, cfg, B, max_len))
                for kind in pattern)
            seeded = tuple(
                jax.vmap(lambda e, r, kind=kind: transformer.seed_block_cache(
                    kind, cfg, e, r, S))(empty[j], raw[j])
                for j, kind in enumerate(pattern))
            caches.append(seeded)
        logits = self._logits(params, x[:, -1:])
        return logits, tuple(caches)

    def decode_step(self, params, cache, token, pos):
        """token: (B,1) int32; pos: (B,) int32.  Returns (logits, cache)."""
        cfg = self.cfg
        x = self._embed(params, token)
        if self.constrain is not None:
            x = self.constrain(x, "activation")
        new_caches = []
        for i, (pattern, repeats) in enumerate(cfg.segments):
            x, nc, _ = transformer.segment_scan(
                pattern, repeats, cfg, params[f"seg{i}"], x,
                seg_caches=cache[i], pos=pos, decode=True,
                constrain=self.constrain)
            new_caches.append(nc)
        logits = self._logits(params, x)
        return logits, tuple(new_caches)

    # ------------------------------------------------------------------
    def input_specs(self, shape: InputShape, param_dtype=None):
        """ShapeDtypeStruct stand-ins for every input of the lowered step."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if shape.mode == "train":
            batch = {"tokens": sds((B, S), i32), "targets": sds((B, S), i32)}
            if cfg.frontend != "none":
                batch["frontend_embeds"] = sds((B, S, cfg.d_model),
                                               jnp.dtype(cfg.dtype))
                batch["frontend_mask"] = sds((B, S), jnp.bool_)
            return batch
        if shape.mode == "prefill":
            batch = {"tokens": sds((B, S), i32)}
            if cfg.frontend != "none":
                batch["frontend_embeds"] = sds((B, S, cfg.d_model),
                                               jnp.dtype(cfg.dtype))
                batch["frontend_mask"] = sds((B, S), jnp.bool_)
            return batch
        if shape.mode == "decode":
            cache = jax.eval_shape(lambda: self.init_cache(B, S))
            return {"token": sds((B, 1), i32), "pos": sds((B,), i32),
                    "cache": cache}
        raise ValueError(shape.mode)


def make_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
