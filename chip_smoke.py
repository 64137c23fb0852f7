"""Smoke test of the serving path on the TPU, at qwen2-0.5b's published widths.

    python chip_smoke.py [--seed N]        # one chip: serving + kernels
    python chip_smoke.py --chips 4         # four one-chip replicas only

One chip: qwen2-0.5b parameters are made from the seed, published to a
``WeightStore`` and served by two endpoints chained on a ``ServingEngine``
(``InstancePool`` -> ``Runtime`` freshen plan -> ``Executor``).  Batches go
through ``submit`` and single rows through ``submit_request``; invoking the
first stage freshens the second.  Served logits are checked against a plain
f32 forward written out from the published architecture.  Then each Pallas
kernel runs compiled at real widths and is checked against
``repro.kernels.ref``.

Four chips (``--chips 4``): the same endpoint deployed with ``shards=4``,
one replica per chip behind the ``ClusterRouter``; every shard's weights
and executable must sit on its own chip and its logits must equal the
one-chip result computed in this process.

Refuses to run unless JAX's first device is a TPU.  Any failed phase exits
non-zero.  The last line of stdout is the JSON result, printed only on
success: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations
# fabriclint: allow-file[clock] -- a smoke harness: wall-clock stamps
# feed the printed per-phase seconds only.

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
BATCH, SEQ = 4, 64
# served (bf16) vs reference (f32) logits: the largest absolute difference
# may be at most this share of the reference's largest |logit|; a row's
# argmax may differ only where the reference ranks the served choice
# within that same tolerance of its top logit
LOGIT_REL_TOL = 0.05
RESULT_TIMEOUT = 600.0


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# reference and checks
def reference_logits(cfg, params, tokens):
    """Plain f32 forward of a dense Qwen2-style decoder (RMSNorm, RoPE, GQA
    with QKV bias, SwiGLU, tied embeddings), written from the published
    architecture rather than through ``repro.models``.  Returns the
    last-position logits, shape (B, 1, vocab)."""
    import jax
    import jax.numpy as jnp
    if not (cfg.segments == ((("attn",), cfg.n_layers),)
            and cfg.activation == "swiglu" and cfg.qkv_bias
            and cfg.tie_embeddings and not cfg.scale_embedding
            and cfg.attn_softcap is None and cfg.final_softcap is None
            and cfg.query_scale is None and not cfg.use_post_norm):
        raise ValueError(f"{cfg.name}: the reference covers plain "
                         f"Qwen2-style decoders only")
    B, S = tokens.shape
    H, KV, hd, eps = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                      cfg.norm_eps)

    def forward(params, tokens):
        f = jax.tree.map(lambda a: a.astype(jnp.float32), params)

        def norm(x, scale):
            var = jnp.mean(x * x, axis=-1, keepdims=True)
            return x * jax.lax.rsqrt(var + eps) * scale

        inv = 1.0 / cfg.rope_theta ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

        def rope(t):                                   # (B, S, heads, hd)
            t1, t2 = jnp.split(t, 2, axis=-1)
            return jnp.concatenate([t1 * cos - t2 * sin,
                                    t2 * cos + t1 * sin], axis=-1)

        causal = jnp.tril(jnp.ones((S, S), bool))

        def layer(x, p):
            a, m = p["mixer"], p["ffn"]
            h = norm(x, p["norm1"]["scale"])
            q = rope((h @ a["wq"] + a["bq"]).reshape(B, S, H, hd))
            k = rope((h @ a["wk"] + a["bk"]).reshape(B, S, KV, hd))
            v = (h @ a["wv"] + a["bv"]).reshape(B, S, KV, hd)
            k = jnp.repeat(k, H // KV, axis=2)          # query head h reads
            v = jnp.repeat(v, H // KV, axis=2)          # kv head h // (H/KV)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
            s = jnp.where(causal, s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
            x = x + o.reshape(B, S, H * hd) @ a["wo"]
            h = norm(x, p["norm2"]["scale"])
            x = x + (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]
            return x, None

        x = f["embed"][tokens]
        x, _ = jax.lax.scan(layer, x, f["seg0"][0])
        x = norm(x[:, -1:], f["final_norm"]["scale"])
        return x @ f["embed"].T

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(forward)(params, jnp.asarray(tokens)))


def check_logits(served, ref, rel_tol: float = LOGIT_REL_TOL) -> dict:
    """Served vs reference last-position logits (any leading shape)."""
    served = np.asarray(served, np.float32).reshape(-1, ref.shape[-1])
    ref = np.asarray(ref, np.float32).reshape(-1, ref.shape[-1])
    tol = rel_tol * float(np.abs(ref).max())
    diff = float(np.abs(served - ref).max())
    got = served.argmax(-1)
    agree = got == ref.argmax(-1)
    near = ref[np.arange(len(ref)), got] >= ref.max(-1) - tol
    return {"rows": len(ref), "argmax_agree": int(agree.sum()),
            "max_abs_diff": diff, "tol": tol,
            "ok": bool(diff <= tol and np.all(agree | near))}


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _make_params(cfg, seed: int):
    import jax
    from repro.models import make_model
    return jax.jit(make_model(cfg).init)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# phase: two chained endpoints on one device
def serve_phase(cfg, seed: int, *, batch: int = BATCH, seq: int = SEQ,
                n_batches: int = 3, n_rows: int = 4) -> dict:
    """Deploy two chained endpoints of ``cfg`` on the default device, send
    batches through ``submit`` and rows through ``submit_request``, and
    check the served logits against ``reference_logits``.  Raises on any
    failed check; returns the report it printed."""
    import jax
    from repro.serving import ModelEndpoint, ServingEngine, WeightStore

    stages = ["stage0", "stage1"]
    report: dict = {}
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
               for _ in range(n_batches)]
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
        t0 = time.monotonic()
        store = WeightStore(root)
        params = {}
        for i, name in enumerate(stages):
            params[name] = _make_params(cfg, seed + i)
            store.publish(name, params[name])
        n_params = sum(int(x.size) for x in jax.tree.leaves(params[stages[0]]))
        report["params_per_stage"] = n_params
        log(f"setup: {cfg.name} {n_params:,} params/stage, batch={batch} "
            f"seq={seq}, published in {time.monotonic() - t0:.3f}s")

        eng = ServingEngine()
        try:
            eps = {}
            for name in stages:
                eps[name] = ModelEndpoint(name, cfg, store, batch_size=batch,
                                          seq_len=seq)
                eng.deploy(eps[name], batch_size=batch)
            eng.chain(stages)

            served = {name: [] for name in stages}
            t0 = time.monotonic()
            for i, toks in enumerate(batches):
                out = eng.submit(stages[0], toks).result(RESULT_TIMEOUT)
                served[stages[0]].append(out["logits"])
                if i == 0:      # the chain's freshen of stage1 is in flight
                    eng.scheduler.runtimes[stages[1]].join_freshen(
                        timeout=RESULT_TIMEOUT)
                out = eng.submit(stages[1], toks).result(RESULT_TIMEOUT)
                served[stages[1]].append(out["logits"])
            futs = [eng.submit_request(stages[0], row)
                    for row in batches[0][:n_rows]]
            rows = np.stack([f.result(RESULT_TIMEOUT) for f in futs])
            log(f"traffic: {n_batches} batches x {len(stages)} stages via "
                f"submit, {n_rows} rows via submit_request, "
                f"{time.monotonic() - t0:.3f}s")

            for name in stages:
                ep = eps[name]
                for label, t in (("first", ep.timings[0]),
                                 ("last", ep.timings[-1])):
                    log(f"phases[{name}/{label}]: " + " ".join(
                        f"{k}={t[k]:.6f}s" for k in
                        ("weights", "compile", "warmup", "execute", "total")))
                compiled = sorted(ep.executor.compile_seconds.values())
                log(f"executor[{name}]: compiles={ep.executor.compile_count} "
                    f"seconds={[round(c, 6) for c in compiled]}")
            stats = eng.platform_stats()
            for name in stages:
                ps = stats[name]
                log(f"pool[{name}]: instances={ps['instances']} "
                    f"cold={ps['cold_starts']} freshened={ps['freshened']} "
                    f"hits={ps['hits']} inline={ps['inline']}")
            report["downstream_inline"] = stats[stages[1]]["inline"]
            _require(stats[stages[1]]["inline"] == 0,
                     f"{stages[1]} did work inline: the chain's freshen did "
                     f"not prepare it before its first run")
            _require(stats[stages[1]]["freshened"] > 0,
                     f"{stages[1]} was never freshened")
        finally:
            eng.close()

        dev = jax.devices()[0]
        mem = dev.memory_stats() or {}
        report["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
        log(f"memory: peak_bytes_in_use={report['peak_bytes_in_use']}")

        for name in stages:
            ref = reference_logits(cfg, params[name], batches[0])
            chk = check_logits(served[name][0], ref)
            report[f"logits[{name}]"] = chk
            log(f"logits[{name}]: {json.dumps(chk)}")
            _require(chk["ok"], f"{name} logits disagree with the f32 "
                     f"reference beyond tolerance")
            if name == stages[0]:
                chk = check_logits(rows, ref[:n_rows])
                report["logits[rows]"] = chk
                log(f"logits[submit_request rows]: {json.dumps(chk)}")
                _require(chk["ok"], "submit_request rows disagree with "
                         "the f32 reference beyond tolerance")
    return report


# ---------------------------------------------------------------------------
# phase: each Pallas kernel compiled at real widths
def kernel_phase(seed: int) -> dict:
    """Run each Pallas kernel compiled (``tpu_custom_call`` in the program)
    at real widths — qwen2-0.5b heads for attention, the recurrentgemma-2b
    recurrence width for the RG-LRU scan — against ``repro.kernels.ref``."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops, ref

    qwen = get_config(ARCH)
    Hq, Hkv, dh = qwen.n_heads, qwen.n_kv_heads, qwen.resolved_head_dim
    rg = get_config("recurrentgemma-2b")
    r = rg.rglru.d_rnn or rg.d_model
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(k, shape, dtype):
        return jax.random.normal(k, shape, f32).astype(dtype)

    B, S = 2, 512
    q, k, v = (normal(ks[0], (B, S, Hq, dh), bf16),
               normal(ks[1], (B, S, Hkv, dh), bf16),
               normal(ks[2], (B, S, Hkv, dh), bf16))
    Bd, L = 4, 1024
    qd = normal(ks[3], (Bd, 1, Hq, dh), bf16)
    kc = normal(ks[4], (Bd, L, Hkv, dh), bf16)
    vc = normal(ks[5], (Bd, L, Hkv, dh), bf16)
    pos = jax.random.randint(ks[6], (Bd,), 1, L)
    Br, Sr = 2, 256
    ka, kb, kh = jax.random.split(ks[7], 3)
    a = jax.nn.sigmoid(normal(ka, (Br, Sr, r), f32))
    b, h0 = normal(kb, (Br, Sr, r), f32), normal(kh, (Br, r), f32)

    cases = [
        ("flash_attention", ops.flash_attention, (q, k, v),
         lambda: ref.ref_attention(q, k, v), 3e-2),
        ("decode_attention", ops.decode_attention, (qd, kc, vc, pos),
         lambda: ref.ref_decode_attention(qd, kc, vc, pos), 3e-2),
        ("rglru_scan", ops.rglru_scan, (a, b, h0),
         lambda: ref.ref_rglru_scan(a, b, h0), 1e-5),
    ]
    report = {}
    for name, fn, args, oracle, tol in cases:
        t0 = time.monotonic()
        compiled = fn.lower(*args).compile()
        t_compile = time.monotonic() - t0
        _require("tpu_custom_call" in compiled.as_text(),
                 f"{name}: no tpu_custom_call in the compiled program")
        t0 = time.monotonic()
        out = jax.block_until_ready(compiled(*args))
        t_run = time.monotonic() - t0
        with jax.default_matmul_precision("highest"):
            want = oracle()
        diffs = [float(np.abs(np.asarray(o, np.float32)
                              - np.asarray(w, np.float32)).max())
                 for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want))]
        report[name] = {"compile_s": t_compile, "first_run_s": t_run,
                        "max_abs_diff": max(diffs), "tol": tol}
        log(f"kernel[{name}]: tpu_custom_call=yes compile={t_compile:.6f}s "
            f"first_run={t_run:.6f}s max_abs_diff={max(diffs):.3e} "
            f"(tol {tol:g})")
        for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(o, np.float32),
                                       np.asarray(w, np.float32),
                                       atol=tol, rtol=tol, err_msg=name)
    return report


# ---------------------------------------------------------------------------
# phase: one replica per device behind the ClusterRouter
def replica_phase(cfg, seed: int, shards: int, *, batch: int = BATCH,
                  seq: int = SEQ) -> dict:
    """``deploy(ep, shards=N)`` over ``jax.local_devices()``: check that
    shard k's weights and executable sit on device k and that its logits
    equal the one-device result for the same seed and tokens."""
    import jax
    from repro.serving import ModelEndpoint, ServingEngine, WeightStore

    name = "replica"
    devices = jax.local_devices()
    _require(len(devices) >= shards,
             f"{shards} replicas need {shards} devices; found {len(devices)}")
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    report: dict = {}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
        store = WeightStore(root)
        store.publish(name, _make_params(cfg, seed))

        one = ServingEngine()
        try:
            one.deploy(ModelEndpoint(name, cfg, store, batch_size=batch,
                                     seq_len=seq))
            want = one.invoke(name, toks)["logits"]
        finally:
            one.close()

        eng = ServingEngine()
        try:
            t0 = time.monotonic()
            eng.deploy(ModelEndpoint(name, cfg, store, batch_size=batch,
                                     seq_len=seq), shards=shards)
            got = {}
            for w in eng.cluster.workers:
                got[w.shard_id] = w.invoke(name, {"tokens": toks})["logits"]
            routed = [eng.submit(name, toks) for _ in range(2 * shards)]
            routed = [f.result(RESULT_TIMEOUT)["logits"] for f in routed]
            log(f"replicas: {shards} shards served {shards} direct + "
                f"{len(routed)} routed batches in "
                f"{time.monotonic() - t0:.3f}s")
            for w in eng.cluster.workers:
                dev = w.devices[0]
                entries = w.scheduler.runtimes[name].fr_state.entries
                leaves = jax.tree.leaves(entries[0].result)
                on = {d for leaf in leaves for d in leaf.devices()}
                exe = {d for s in jax.tree.leaves(
                           entries[1].result.input_shardings)
                       for d in s.device_set}
                diff = float(np.abs(got[w.shard_id].astype(np.float32)
                                    - want.astype(np.float32)).max())
                log(f"shard[{w.shard_id}]: device={dev} "
                    f"params_on={sorted(map(str, on))} "
                    f"executable_on={sorted(map(str, exe))} "
                    f"max_abs_diff_vs_one_device={diff}")
                _require(on == {dev}, f"shard {w.shard_id}: params on {on}, "
                         f"not on its device {dev}")
                _require(exe == {dev}, f"shard {w.shard_id}: executable "
                         f"placed on {exe}, not on {dev}")
                _require(np.array_equal(got[w.shard_id], want),
                         f"shard {w.shard_id}: logits differ from the "
                         f"one-device run (max |diff| {diff})")
                report[w.shard_id] = {"device": str(dev), "max_abs_diff": diff}
            _require(len({str(w.devices[0]) for w in eng.cluster.workers})
                     == shards, "shards share a device")
            for out in routed:
                _require(np.array_equal(out, want),
                         "a routed batch differs from the one-device run")
            for key, ps in eng.platform_stats().items():
                log(f"pool[{key}]: instances={ps['instances']} "
                    f"cold={ps['cold_starts']} hits={ps['hits']} "
                    f"inline={ps['inline']}")
        finally:
            eng.close()
    return report


# ---------------------------------------------------------------------------
def _count_cache_events():
    import jax
    counts = {"requests": 0, "hits": 0}

    def listener(event, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
    jax.monitoring.register_event_listener(listener)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica path")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX's first device is {platform!r}, not a TPU; "
              f"this smoke test runs on the chip only", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    cached = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    log(f"device: {json.dumps(device)}")
    log(f"compile_cache: dir={cache_dir} entries_at_start={cached}")
    cache_events = _count_cache_events()

    from repro.configs import get_config
    cfg = get_config(ARCH)
    if args.chips == 4:
        phases = [("replicas", lambda: replica_phase(cfg, args.seed, 4))]
    else:
        phases = [("serve", lambda: serve_phase(cfg, args.seed)),
                  ("kernels", lambda: kernel_phase(args.seed))]
    failed = []
    for name, phase in phases:
        t0 = time.monotonic()
        try:
            phase()
            log(f"phase[{name}]: ok in {time.monotonic() - t0:.3f}s")
        except Exception:                               # noqa: BLE001
            traceback.print_exc()
            log(f"phase[{name}]: FAILED after {time.monotonic() - t0:.3f}s")
            failed.append(name)
    log(f"compile_cache: requests={cache_events['requests']} "
        f"hits={cache_events['hits']}")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
