"""The reduction from a profiler trace to device numbers.

``capture`` records a window with the JAX profiler (host Python calls not
traced).  ``load_events`` reads the ``.xplane.pb`` it wrote into plain
lists: per device, its ops and its XLA modules; on the host, the
harness's own ``bench.*`` annotations.  ``reduce`` turns those into:

- ``window_s``: the traced window, the ``bench.traced_window`` annotation;
- ``busy_s``: the union of the device's op intervals inside it, averaged
  over the devices;
- ``module_s`` / ``module_count``: summed device time and number of runs
  of the XLA modules whose name starts with a given prefix;
- ``top_ops``: the ten device ops that took most time in all;
- ``idle_gaps``: the ten longest gaps between device ops, each named by
  the innermost ``bench.*`` annotation open at its middle and by whether a
  request was in flight then (``in_flight``) or not (``no_request``).

Times in the event lists are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.traced_window"
NAME_CHARS = 160          # an op's name is its HLO text: keep its head
Event = Tuple[str, float, float]             # name, start_ns, duration_ns


@contextlib.contextmanager
def capture(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1         # the harness's annotations, no more
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_events(log_dir: str) -> dict:
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host, names = [], [], []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        names.append((plane.name, sorted(lines)))
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            devices.append({
                "plane": plane.name,
                "ops": [(e.name[:NAME_CHARS], e.start_ns, e.duration_ns)
                        for e in lines["XLA Ops"].events],
                "modules": [(e.name, e.start_ns, e.duration_ns)
                            for e in lines["XLA Modules"].events]
                if "XLA Modules" in lines else []})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    return {"devices": devices, "host": host, "planes": names}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: float, hi: float):
    for name, s, d in events:
        e = s + d
        if e > lo and s < hi:
            yield name, max(s, lo), min(e, hi)


def _label(t: float, host: Sequence[Event],
           in_flight: Sequence[Tuple[float, float]]) -> str:
    open_ = [(d, n) for n, s, d in host
             if n != WINDOW and s <= t <= s + d]
    name = min(open_)[1] if open_ else "bench.unannotated"
    busy = any(s <= t <= e for s, e in in_flight)
    return f"{name}:{'in_flight' if busy else 'no_request'}"


def reduce(events: dict, module_prefix: Optional[str] = None,
           in_flight: Sequence[Tuple[float, float]] = ()) -> dict:
    """Device numbers of the traced window (see the module docstring).
    ``in_flight`` lists (start_ns, end_ns) of requests on the trace clock."""
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if not windows or not events["devices"]:
        raise ValueError("the trace holds no traced window or no device")
    lo, hi = windows[0]
    busy, gaps, per_op = [], [], {}
    mod_s, mod_n = 0.0, 0
    for dev in events["devices"]:
        spans = []
        for name, s, e in _clip(dev["ops"], lo, hi):
            spans.append((s, e))
            per_op[name] = per_op.get(name, 0.0) + (e - s)
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
        if module_prefix:
            for name, s, e in _clip(dev["modules"], lo, hi):
                if name.startswith(module_prefix):
                    mod_s += e - s
                    mod_n += 1
    gaps.sort(reverse=True)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "module_s": mod_s * ns,
        "module_count": mod_n,
        "top_ops": [[n, t * ns] for n, t in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_label(mid, events["host"], in_flight), g * ns]
                      for g, mid in gaps[:10]],
    }


def window_only(events: dict, max_ops: int = 4000) -> dict:
    """The start of the traced window, cut after the first ``max_ops``
    device ops of the first device: small enough to keep as a recorded
    sample, and still a whole trace that ``reduce`` reads."""
    lo, hi = next((s, s + d) for n, s, d in events["host"] if n == WINDOW)
    ops = sorted((ev for ev in events["devices"][0]["ops"]
                  if ev[1] + ev[2] > lo and ev[1] < hi),
                 key=lambda ev: ev[1])[:max_ops]
    if ops:
        hi = min(hi, max(s + d for _, s, d in ops))

    def keep(evs):
        return [ev for ev in evs if ev[1] + ev[2] > lo and ev[1] < hi]

    dev = events["devices"][0]
    host = [ev for ev in keep(events["host"]) if ev[0] != WINDOW]
    return {"devices": [{"plane": dev["plane"], "ops": keep(dev["ops"]),
                         "modules": keep(dev["modules"])}],
            "host": [(WINDOW, lo, hi - lo)] + host,
            "planes": events.get("planes", [])}
