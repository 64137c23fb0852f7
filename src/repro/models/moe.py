"""Mixture-of-Experts layer with top-k routing, optional shared experts, and
two dispatch strategies:

* ``einsum``  — GShard-style one-hot dispatch/combine einsums (baseline;
  matches the reference formulation, but the dispatch einsums carry phantom
  FLOPs proportional to E·C).
* ``gather``  — scatter/gather dispatch: tokens are placed into a dense
  (E·C, d) buffer by slot index and combined back by gather.  FLOP-free
  dispatch; the beyond-paper perf variant (see EXPERIMENTS.md §Perf).
* ``dropless`` — the (token, slot) pairs routed to held experts are sorted
  by expert into a buffer of the static worst case, T·min(k, held) rows,
  and each projection is one grouped matmul (``lax.ragged_dot``) over the
  groups' real sizes: nothing is dropped, whatever the imbalance, and the
  work follows the routed rows.

Expert weights are stacked on a leading E axis => expert parallelism is a
sharding rule (experts over the "model"/"expert" mesh axis), and XLA inserts
the all-to-alls.  ``MoEConfig.held`` gives a layer one device's share of
that axis instead: the router still scores all ``n_experts``, the layer
holds and computes only its own experts, and pairs routed elsewhere add
nothing here (the shared experts are added for every token).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_moe(key, cfg):
    e = cfg.moe
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    held = e.held_range[1]
    def expert_stack(k, in_dim, out_dim):
        kk = jax.random.split(k, held)
        return jnp.stack([dense_init(kk[i], in_dim, out_dim, dtype)
                          for i in range(held)])
    # expert weights use distinct names (wi_e/...) so sharding rules can
    # target the expert-stacked 3D layout without colliding with dense MLPs
    p = {"router": dense_init(ks[0], d, e.n_experts, jnp.float32)}
    if cfg.activation == "swiglu":
        p["wi_e"] = expert_stack(ks[1], d, e.d_ff)
        p["wg_e"] = expert_stack(ks[2], d, e.d_ff)
        p["wo_e"] = expert_stack(ks[3], e.d_ff, d)
    else:
        p["wi_e"] = expert_stack(ks[1], d, e.d_ff)
        p["wo_e"] = expert_stack(ks[3], e.d_ff, d)
    if e.n_shared:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], cfg, d_ff=e.d_ff * e.n_shared)
    return p


def _expert_ffn(p, x, activation):
    """x: (E, C*, d) -> (E, C*, d) via per-expert weights."""
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["wg_e"])) * \
            jnp.einsum("ecd,edf->ecf", x, p["wi_e"])
    elif activation == "squared_relu":
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", x, p["wi_e"])))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, p["wi_e"]))
    return jnp.einsum("ecf,efd->ecd", h, p["wo_e"])


def _routing(p, x2d, e):
    """x2d: (T, d) -> (probs (T,k), idx (T,k), aux_loss scalar).  The
    gate runs in float32 (``HIGHEST``: a TPU's default would round the
    operands to bfloat16 and flip near-tied top-k choices)."""
    logits = jnp.matmul(x2d.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)    # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    probs, idx = jax.lax.top_k(gates, e.top_k)                # (T, k)
    if e.norm_topk:
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    elif e.routed_scale != 1.0:
        probs = probs * e.routed_scale
    # Switch/GShard load-balance aux loss
    me = gates.mean(axis=0)                                   # (E,)
    onehot = jax.nn.one_hot(idx, e.n_experts, dtype=jnp.float32)  # (T,k,E)
    ce = onehot.sum(axis=(0, 1)) / (x2d.shape[0] * e.top_k)
    aux = e.n_experts * jnp.sum(me * ce) * e.load_balance_coef
    return probs, idx, aux


def _capacity(tokens_per_group, e):
    c = int(tokens_per_group * e.top_k * e.capacity_factor / e.n_experts)
    return max(4, -(-c // 4) * 4)                             # round up to 4


def _held_key(idx, e):
    """(T, k) global expert ids -> each pair's held expert, ``count`` for
    pairs routed to experts this layer does not hold."""
    first, count = e.held_range
    local = idx - first
    return jnp.where((local >= 0) & (local < count), local, count)


def _held_rows(key, count):
    """Pairs routed to each held expert: (count,) int32."""
    return jnp.zeros((count + 1,), jnp.int32).at[key.reshape(-1)].add(1)[:count]


def _grouped_ffn(p, xs, sizes, activation):
    """xs: (R, d) rows sorted by expert, ``sizes`` (held,) rows per expert
    -> (R, d); rows past ``sizes.sum()`` are left undefined."""
    def mm(a, w):
        return jax.lax.ragged_dot(a, w, sizes)
    if activation == "swiglu":
        h = jax.nn.silu(mm(xs, p["wg_e"])) * mm(xs, p["wi_e"])
    elif activation == "squared_relu":
        h = jnp.square(jax.nn.relu(mm(xs, p["wi_e"])))
    else:
        h = jax.nn.gelu(mm(xs, p["wi_e"]))
    return mm(h, p["wo_e"])


def _dropless(p, x2d, probs, key, e, activation):
    """Every pair routed to a held expert, computed: (T, d) routed output."""
    T, d = x2d.shape
    k = e.top_k
    count = e.held_range[1]
    rows = T * min(k, count)           # a token's k experts are distinct
    flat = key.reshape(-1)                                     # (T*k,)
    sizes = _held_rows(key, count)
    with jax.named_scope("moe.experts"):
        order = jnp.argsort(flat, stable=True)[:rows]          # by expert
        ys = _grouped_ffn(p, x2d[order // k], sizes, activation)
    with jax.named_scope("moe.combine"):
        pos = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32)).reshape(T, k)
        held = key < count
        w = jnp.where(held, probs, 0.0)[..., None]
        picked = jnp.where(held[..., None], ys[pos].astype(jnp.float32), 0.0)
        return jnp.sum(picked * w, axis=1).astype(x2d.dtype)


def moe_apply(p, x, cfg, *, rows=False):
    """x: (B, S, d) -> (out, aux_loss), and with ``rows`` the pairs routed
    to each held expert, (held,) int32."""
    e = cfg.moe
    B, S, d = x.shape
    T = B * S
    x2d = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        probs, idx, aux = _routing(p, x2d, e)
        key = _held_key(idx, e)
    if e.dispatch == "dropless":
        out = _dropless(p, x2d, probs, key, e, cfg.activation)
        return _finish(p, x, out.reshape(B, S, d), aux, cfg, key, rows)
    if e.held is not None:
        raise ValueError(f"{cfg.name}: a share of the experts "
                         f"(held={e.held}) needs the 'dropless' dispatch")

    gs = min(e.group_size, T)
    assert T % gs == 0, (T, gs)
    G = T // gs
    C = _capacity(gs, e)

    xg = x2d.reshape(G, gs, d)
    idx_g = idx.reshape(G, gs, e.top_k)
    probs_g = probs.reshape(G, gs, e.top_k)

    # position of each (token, k-slot) within its expert, k-major priority
    onehot = jax.nn.one_hot(idx_g, e.n_experts, dtype=jnp.int32)  # (G,gs,k,E)
    flat = onehot.transpose(0, 2, 1, 3).reshape(G, gs * e.top_k, e.n_experts)
    pos_in_expert = jnp.cumsum(flat, axis=1) - 1                  # (G,gs*k,E)
    pos_in_expert = pos_in_expert.transpose(0, 2, 1).reshape(
        G, e.n_experts, e.top_k, gs).transpose(0, 3, 2, 1)        # (G,gs,k,E)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)                # (G,gs,k)
    keep = pos < C

    if e.dispatch == "einsum":
        # (G, gs, k, E, C) one-hot dispatch tensor
        disp = (jax.nn.one_hot(idx_g, e.n_experts, dtype=x.dtype)[..., :, None]
                * jax.nn.one_hot(pos, C, dtype=x.dtype)[..., None, :])
        disp = disp * keep[..., None, None].astype(x.dtype)
        disp_tok = disp.sum(axis=2)                               # (G,gs,E,C)
        expert_in = jnp.einsum("gsec,gsd->gecd", disp_tok, xg)
        expert_in = expert_in.transpose(1, 0, 2, 3).reshape(e.n_experts, G * C, d)
        expert_out = _expert_ffn(p, expert_in, cfg.activation)
        expert_out = expert_out.reshape(e.n_experts, G, C, d).transpose(1, 0, 2, 3)
        combine = (disp * probs_g[..., None, None].astype(x.dtype)).sum(axis=2)
        out2d = jnp.einsum("gsec,gecd->gsd", combine, expert_out)
    elif e.dispatch == "gather":
        slot = idx_g * C + pos                                     # (G,gs,k)
        slot = jnp.where(keep, slot, e.n_experts * C)              # overflow row
        buf = jnp.zeros((G, e.n_experts * C + 1, d), x.dtype)
        src = jnp.broadcast_to(xg[:, :, None, :], (G, gs, e.top_k, d))
        buf = buf.at[jnp.arange(G)[:, None, None], slot].set(
            src, mode="drop")
        expert_in = buf[:, :-1].reshape(G, e.n_experts, C, d)
        expert_in = expert_in.transpose(1, 0, 2, 3).reshape(e.n_experts, G * C, d)
        expert_out = _expert_ffn(p, expert_in, cfg.activation)
        expert_out = expert_out.reshape(e.n_experts, G, C, d).transpose(1, 0, 2, 3)
        ybuf = expert_out.reshape(G, e.n_experts * C, d)
        ybuf = jnp.concatenate([ybuf, jnp.zeros((G, 1, d), x.dtype)], axis=1)
        gathered = ybuf[jnp.arange(G)[:, None, None], slot]        # (G,gs,k,d)
        out2d = jnp.sum(gathered * probs_g[..., None].astype(x.dtype), axis=2)
    else:
        raise ValueError(e.dispatch)
    return _finish(p, x, out2d.reshape(B, S, d), aux, cfg, key, rows)


def _finish(p, x, out, aux, cfg, key, rows):
    """Add the shared experts; append the held experts' rows if asked."""
    e = cfg.moe
    if e.n_shared:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(p["shared"], x, cfg.activation)
    if rows:
        return out, aux, _held_rows(key, e.held_range[1])
    return out, aux


def moe_ref(p, x, cfg):
    """Dense oracle: every token through its top-k experts exactly (no
    capacity drops); of a share (``held``), the held experts' part.  Used
    in tests to bound dispatch-path error."""
    e = cfg.moe
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    probs, idx, aux = _routing(p, x2d, e)
    key = _held_key(idx, e)
    count = e.held_range[1]
    outs = []
    for j in range(count):
        xin = x2d[None]                                            # (1,T,d)
        y = _expert_ffn({k: v[j:j + 1] for k, v in p.items()
                         if k in ("wi_e", "wg_e", "wo_e")}, xin,
                        cfg.activation)[0]
        outs.append(y)
    outs.append(jnp.zeros_like(x2d))                # experts not held here
    ys = jnp.stack(outs)                                        # (held+1,T,d)
    sel = jnp.take_along_axis(
        ys.transpose(1, 0, 2), key[..., None].astype(jnp.int32), axis=1)
    out2d = jnp.sum(sel * probs[..., None].astype(x.dtype), axis=1)
    out = out2d.reshape(B, S, d)
    if e.n_shared:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(p["shared"], x, cfg.activation)
    return out, aux
