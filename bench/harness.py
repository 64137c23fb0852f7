"""Run one cell of ``BENCHMARK.json`` once and print its result line.

A run: make each endpoint's weights on the device from the seed and
publish them to a temporary ``WeightStore``; deploy the endpoints on a
``ServingEngine`` with the mix's pool and batcher settings; serve one
request to each endpoint (set-up ends here, after the keep-alive is waited
out where the mix asks for it); drive the window through
``ServingEngine.submit_request`` from the mix's schedule or closed loop;
wait for every request due in the window; read the device's memory peak;
free the program's state; check the served logits of the sampled requests
against the plain reference; print the metrics.  What belongs to one
family of architectures (the served ``ModelConfig``, the weights, the
reference, the operation and byte counts) comes from the configuration's
module in ``bench/archs`` (``Bench.arch``).

End-to-end metrics (``--trace 0``) are taken on the client's side with the
program's tracer off.  A traced run (``--trace 1``) turns the tracer on,
records part of the window with the JAX profiler and prints the per-layer
metrics.  Each metric is computed by its own reader: ``bench/e2e`` for the
end-to-end ones, ``bench/metrics`` for the per-layer ones.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import model_config, peaks, traffic
from bench import trace as trace_mod

ROOT = Path(__file__).resolve().parents[1]
RESULT_GRACE_S = 60.0          # a request due in the window may finish late
CHECK = "logit_rel_err"


class NoChip(RuntimeError):
    pass


class Bench:
    """The benchmark's data under ``root``, found by name."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config_file(self, name: str) -> dict:
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        return model_config.load_file(self.root / entry["file"],
                                      self.root / "bench" / "archs")

    def mix(self, name: str) -> dict:
        return traffic.load(self.root / "bench" / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        path = self.root / "bench" / "cells" / f"{cell}.json"
        return json.loads(path.read_text())["limits"]

    def metrics(self, cell: str, per_layer: bool) -> List[dict]:
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not per_layer:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", [cell]) and m["moves"] in moved]

    def reader(self, metric: str, per_layer: bool):
        """The ``read(run)`` of ``metric``: ``bench/metrics/<stem>.py`` for
        a per-layer metric, ``bench/e2e/<stem>.py`` for an end-to-end one,
        where a name ``a.b`` has the stem ``a``."""
        return self._module("metrics" if per_layer else "e2e",
                            metric.split(".")[0]).read

    def arch(self, config: str):
        """The module of ``config``'s architecture family,
        ``bench/archs/<arch>.py`` where ``<arch>`` is the configuration
        file's ``arch``: ``build``, ``sizes_of``, ``param_count``,
        ``make_params``, ``reference_logits`` and ``score_step``."""
        return self._module("archs", self.config_file(config)["arch"])

    def _module(self, kind: str, stem: str):
        path = self.root / "bench" / kind / f"{stem}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


# ---------------------------------------------------------------------------
class Requests:
    """What the client saw of each request, on ``time.monotonic``."""

    def __init__(self):
        self.lock = threading.Condition()
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: List[float] = []
        self.first: List[float] = []         # the entry stage resolved
        self.entry: List[int] = []
        self.idle: List[bool] = []
        self.tokens: List[int] = []
        self.error: Dict[int, str] = {}
        self.kept: Dict[int, list] = {}      # idx -> [(endpoint, prompt, logits)]
        self.open = 0

    def add(self, due: float, entry: int, idle: bool) -> int:
        with self.lock:
            self.due.append(due)
            self.sent.append(math.nan)
            self.done.append(math.nan)
            self.first.append(math.nan)
            self.entry.append(entry)
            self.idle.append(idle)
            self.tokens.append(0)
            self.open += 1
            return len(self.due) - 1

    def finish(self, idx: int, t: float, error: Optional[str] = None):
        with self.lock:
            self.done[idx] = t
            if error is not None:
                self.error[idx] = error
            self.open -= 1
            self.lock.notify_all()

    def wait(self, deadline: float) -> bool:
        with self.lock:
            while self.open > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self.lock.wait(left)
            return True

    def arrays(self) -> dict:
        with self.lock:
            return {k: np.asarray(getattr(self, k)) for k in
                    ("due", "sent", "done", "first", "entry", "idle",
                     "tokens")}


class Run:
    """Everything a per-layer reader may read about one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---------------------------------------------------------------------------
def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     f"TPU; the benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX finds {len(devs)}")
    return devs


def configure_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _compile_counter():
    import jax
    count = [0]

    def listener(event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            count[0] += 1
    jax.monitoring.register_event_listener(listener)
    return count


class FullCollections:
    """Python's full (generation 2) garbage collections: each one stops
    every thread of the process, the generator's too."""

    def __init__(self):
        self.pauses: List[float] = []
        self._start = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._start = time.monotonic()
        elif self._start is not None:
            self.pauses.append(time.monotonic() - self._start)
            self._start = None

    def close(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        return {"count": len(self.pauses),
                "max_ms": 1e3 * max(self.pauses, default=0.0)}


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
def run_cell(bench: Bench, cell: str, seed: int, seconds: float,
             trace: bool, started: float, require_tpu: bool = True,
             rate_scale: float = 1.0, control: bool = False,
             keep_trace: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result dict (the printed line, with
    ``checks`` last).  ``rate_scale`` and ``control`` serve the knee sweep
    and the limit calibration; a benchmark run leaves them alone."""
    import jax
    from repro.core.pool import PoolConfig
    from repro.serving import ModelEndpoint, ServingEngine, WeightStore
    from repro.telemetry import Tracer

    w = bench.workload(cell)
    devs = _devices(w["chips"], require_tpu)
    compiles = _compile_counter()
    cfile = bench.config_file(w["config"])
    arch = bench.arch(w["config"])
    cfg = arch.build(cfile)
    sizes = arch.sizes_of(cfile)
    mix = bench.mix(w["traffic"])
    limits = bench.limits(cell)
    names = traffic.endpoints(mix)
    nxt = traffic.successors(mix)
    B, S = mix["endpoint_batch"], mix["prompt_len"]
    ann = jax.profiler.TraceAnnotation if trace else (
        lambda name: contextlib.nullcontext())

    # -- set-up ------------------------------------------------------------
    tmp = tempfile.TemporaryDirectory(prefix="bench-weights-")
    store = WeightStore(tmp.name)
    for i, name in enumerate(names):
        params = arch.make_params(cfg, seed, i)
        store.publish(name, params)
        del params
    engine = ServingEngine(tracer=Tracer(capacity=1 << 16) if trace else None)
    pool = PoolConfig(max_instances=mix["max_instances"],
                      keep_alive=mix["keep_alive_s"])
    eps = {}
    for name in names:
        eps[name] = ModelEndpoint(name, cfg, store, batch_size=B, seq_len=S)
        engine.deploy(eps[name], pool_config=pool, batch_size=B,
                      batch_max_wait=mix.get("batch_max_wait_s", 0.01))
    for chain in mix.get("chains", []):
        engine.chain(chain)
    warm = traffic.rng(seed, 4).integers(0, cfg.vocab_size, (len(names), S),
                                         dtype=np.int32)
    for i, name in enumerate(names):
        engine.submit_request(name, warm[i]).result(timeout=1200)
    if mix.get("idle_before_window"):
        time.sleep(mix["keep_alive_s"] + 0.5)
    # Set-up leaves some hundreds of thousands of long-lived objects; a full
    # collection that walks them all stalls every thread for ~100 ms.
    # Frozen, they stay out of the window's collections.
    gc.collect()
    gc.freeze()

    reqs = Requests()
    n_timings = {n: len(ep.timings) for n, ep in eps.items()}
    fill0 = {n: _fill(b) for n, b in engine.batchers.items()}
    compiles0 = compiles[0]
    if trace:
        engine.tracer.clear()

    # -- the window ---------------------------------------------------------
    if mix["loop"] == "open":
        sched = traffic.open_schedule(mix, seed, seconds, cfg.vocab_size,
                                      rate_scale)
        prompts_of = lambda idx: sched.prompts[idx]  # noqa: E731
        sample = set(traffic.check_sample(mix, seed, len(sched)).tolist())
    else:
        pool_prompts = traffic.closed_prompts(mix, seed, cfg.vocab_size)
        prompts_of = lambda idx: pool_prompts[idx % len(pool_prompts)]  # noqa: E731
        sample = set(traffic.check_sample(
            mix, seed, mix["check_from_first"]).tolist())

    collections = FullCollections()
    t0 = time.monotonic()
    t_end = t0 + seconds
    setup_s = t0 - started

    def submit(idx: int, name: str, prompt: np.ndarray):
        with ann("bench.submit"):
            fut = engine.submit_request(name, prompt)
        fut.add_done_callback(lambda f: on_done(idx, name, prompt, f))

    def on_done(idx: int, name: str, prompt, fut):
        t = time.monotonic()
        try:
            row = np.asarray(fut.result(), np.float32).reshape(-1)
        except Exception as exc:                       # noqa: BLE001
            reqs.finish(idx, t, f"{type(exc).__name__}: {exc}")
            return
        if math.isnan(reqs.first[idx]):
            reqs.first[idx] = t
        if idx in sample:
            reqs.kept.setdefault(idx, []).append((name, prompt, row))
        reqs.tokens[idx] += len(prompt)
        if name in nxt:
            nxt_prompt = np.append(prompt[1:], np.int32(row.argmax()))
            submit(idx, nxt[name], nxt_prompt)
            return
        reqs.finish(idx, t)
        if mix["loop"] == "closed" and t < t_end:
            send_closed(reqs.entry[idx])

    def send_closed(client: int):
        now = time.monotonic()
        idx = reqs.add(now - t0, client, False)
        reqs.sent[idx] = now
        submit(idx, names[0], prompts_of(idx))

    tracer_thread = None
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        span_s = mix.get("trace_seconds") or seconds
        offset = max(0.0, (seconds - span_s) / 2)
        mark = {}

        def tracing():
            time.sleep(max(0.0, t0 + offset - time.monotonic()))
            with trace_mod.capture(trace_dir):
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                    mark["t"] = time.monotonic()
                    time.sleep(span_s)
                    mark["end"] = time.monotonic()
        tracer_thread = threading.Thread(target=tracing, name="bench-trace")
        tracer_thread.start()

    if mix["loop"] == "open":
        for i in range(len(sched)):
            idx = reqs.add(float(sched.due[i]), int(sched.entry[i]),
                           bool(sched.after_idle[i]))
            due = t0 + sched.due[i]
            with ann("bench.wait_due"):
                time.sleep(max(0.0, due - time.monotonic()))
            reqs.sent[idx] = time.monotonic()
            submit(idx, names[sched.entry[i]], prompts_of(idx))
    else:
        for c in range(mix["clients"]):
            send_closed(c)
    with ann("bench.wait_window"):
        time.sleep(max(0.0, t_end - time.monotonic()))
    all_done = reqs.wait(t_end + RESULT_GRACE_S)
    if tracer_thread is not None:
        tracer_thread.join()
    compiles_in_window = compiles[0] - compiles0
    full_gc = collections.close()

    # -- after the window: memory, counters, then free the program ---------
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs[:w["chips"]])
    timings = {n: ep.timings[n_timings[n]:] for n, ep in eps.items()}
    fills = {n: _fill(b, fill0[n]) for n, b in engine.batchers.items()}
    spans, freshens = [], []
    if trace:
        engine.tracer.sweep_expired()
        spans = [s for s in engine.tracer.spans() if s.start >= t0]
        freshens = [f for f in engine.tracer.freshen_spans(include_pending=True)
                    if f.start >= t0]
    engine.close()
    del engine, eps, store
    gc.unfreeze()
    gc.collect()
    for arr in jax.live_arrays():
        arr.delete()
    tmp.cleanup()
    r = reqs.arrays()
    lat_ms = (r["done"] - (t0 + r["due"])) * 1e3
    first_ms = (r["first"] - (t0 + r["due"])) * 1e3
    ok = ~np.isnan(r["done"]) & np.array(
        [i not in reqs.error for i in range(len(r["done"]))], bool)
    attempted = len(r["due"])
    failed = int(attempted - ok.sum())

    # -- the trace ------------------------------------------------------------
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    summary = None
    if trace:
        events = trace_mod.load_events(trace_dir)
        win = next((s, d) for n, s, d in events["host"]
                   if n == trace_mod.WINDOW) if events["host"] else None
        if win is None or not events["devices"]:
            log(f"trace planes: {events['planes']}")
            raise RuntimeError("the trace holds no device ops or no window")
        shift = win[0] - mark["t"] * 1e9     # monotonic -> trace clock, ns
        flight = [(s * 1e9 + shift, e * 1e9 + shift) for s, e in
                  zip(r["sent"], r["done"]) if not math.isnan(e)]
        summary = trace_mod.reduce(events, "jit_score", flight)
        if keep_trace:
            Path(keep_trace).write_text(json.dumps(
                trace_mod.window_only(events)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]

    # -- correctness ----------------------------------------------------------
    kept = [k for k in sorted(reqs.kept) if ok[k]]
    missing = [k for k in sorted(sample) if k < attempted and not ok[k]]
    by_ep: Dict[str, list] = {}
    for k in kept:
        for name, prompt, row in reqs.kept[k]:
            by_ep.setdefault(name, []).append((prompt, row))
    # The control puts the float8 reference in the program's place: its
    # logits for the same prompts go through the same comparison and the
    # same verdict as the served ones.
    err, ctl = 0.0, 0.0
    for i, name in enumerate(names):
        if name not in by_ep:
            continue
        params = arch.make_params(cfg, seed, i)
        toks = np.stack([p for p, _ in by_ep[name]])
        served = np.stack([row for _, row in by_ep[name]])
        ref = arch.reference_logits(sizes, params, toks)
        err = max(err, rel_err(served, ref))
        if control:
            low = arch.reference_logits(sizes, params, toks, quant="fp8")
            ctl = max(ctl, rel_err(low, ref))
        del params
    compared = sum(len(v) for v in by_ep.values())
    checks = {CHECK: {"value": err, "limit": limits[CHECK]}}

    def verdict(value: float) -> bool:
        return bool(failed == 0 and not missing and compared > 0
                    and value <= limits[CHECK])
    correct = verdict(err)

    # -- metrics --------------------------------------------------------------
    run = Run(cell=cell, mix=mix, cfg=cfg, sizes=sizes, seconds=seconds,
              t0=t0, requests=r, ok=ok, lat_ms=lat_ms, first_ms=first_ms,
              timings=timings,
              fills=fills, batch=B, spans=spans, freshens=freshens,
              trace=summary, step=arch.score_step(sizes, B, S),
              row_step=arch.score_step(sizes, 1, S),
              params=arch.param_count(sizes),
              peak=peaks.peaks(devs[0].device_kind) if trace else None,
              trace_mark=(mark["t"], mark["end"]) if trace else None)
    metrics = {}
    for m in bench.metrics(cell, per_layer=trace):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = bench.reader(m["name"], per_layer=trace)(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["top_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    lag = (r["sent"] - (t0 + r["due"])) * 1e3
    result["setup_s"] = setup_s
    result["compiles_in_window"] = compiles_in_window
    result["generator_lag_ms"] = {"p50": float(np.nanmedian(lag)),
                                  "max": float(np.nanmax(lag))}
    result["full_gc"] = full_gc
    result["compared_rows"] = compared
    result["params"] = run.params
    result["finished_late"] = int((r["done"] > t_end).sum())
    result["after_idle"] = int(r["idle"].sum())
    if r["idle"].any():
        result["after_idle_first_ms"] = {
            names[e]: first_ms[r["idle"] & (r["entry"] == e)].tolist()
            for e in np.unique(r["entry"][r["idle"]])}
    if control:
        result["control"] = {"correct": verdict(ctl), CHECK: ctl}
    result["checks"] = checks
    log(f"cell {cell} seed {seed}: {attempted} attempted, {failed} failed, "
        f"{compared} rows compared, compiles in window {compiles_in_window}, "
        f"generator lag p50 {result['generator_lag_ms']['p50']:.3f} ms max "
        f"{result['generator_lag_ms']['max']:.3f} ms, full collections "
        f"{full_gc['count']} max {full_gc['max_ms']:.1f} ms, "
        f"all done {all_done}")
    if reqs.error:
        log(f"first error: {next(iter(reqs.error.values()))}")
    if missing:
        log(f"sampled requests not served: {len(missing)}")
    if control:
        log(f"control {CHECK} = {ctl!r} limit {limits[CHECK]!r} correct "
            f"{result['control']['correct']}")
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    return result


def _fill(batcher, base=(0, 0.0)):
    """(batches, rows) the batcher formed since ``base``."""
    s = batcher.metrics.snapshot().get(f"batcher.{batcher.name}.batch.fill")
    count, total = (s["count"], s["sum"]) if s else (0, 0.0)
    return count - base[0], total - base[1]


def rel_err(served: np.ndarray, ref: np.ndarray) -> float:
    """The worst row's relative error: ||served - ref|| / ||ref||."""
    served = np.asarray(served, np.float32).reshape(ref.shape)
    num = np.linalg.norm(served - ref, axis=-1)
    den = np.linalg.norm(ref, axis=-1)
    return float(np.max(num / den))


# ---------------------------------------------------------------------------
def main(argv=None, started: Optional[float] = None) -> int:
    started = time.monotonic() if started is None else started
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = Bench()
    configure_compile_cache(bench.root)
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), started)
    except NoChip as exc:
        log(f"bench: {exc}")
        return 3
    print(json.dumps(result), flush=True)
    return 0
