"""Shared helpers of the harness tests: a tiny benchmark root and a run of
one of its cells on the CPU, the harness's look for a chip skipped."""
from __future__ import annotations

import time

from bench import harness, peaks
from bench import trace as trace_mod

SEED = 2**33 + 5


def run(root, cell, trace=False, seconds=1.5, **kw):
    return harness.run_cell(harness.Bench(root), cell, SEED, seconds, trace,
                            time.monotonic(), require_tpu=False, **kw)


def fake_device(monkeypatch):
    """The CPU's trace has no device plane: give it one, an op and a
    ``jit_score`` module after each ``bench.submit`` the harness annotated,
    and give the CPU the v5e's peaks, so that a traced run goes through
    every reader on the CPU."""
    real = trace_mod.load_events

    def load(log_dir):
        ev = real(log_dir)
        ops = [("fusion", s + d, 1e6) for n, s, d in ev["host"]
               if n == "bench.submit"]
        ev["devices"] = [{"plane": "/device:TPU:0", "ops": ops,
                          "modules": [("jit_score", s, d)
                                      for _, s, d in ops]}]
        return ev
    monkeypatch.setattr(trace_mod, "load_events", load)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
