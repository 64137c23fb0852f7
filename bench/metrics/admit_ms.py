"""admit_ms.<cell>: the platform's admission of one invocation, from the
program's tracer spans: route + queue + acquire phases (``core/scheduler.py``
and ``core/pool.py``), mean per invocation span.  Only spans that began
before the profiler started count: the profiler's own host work slows the
serving threads while it records, and at a load near capacity the queue
phase would then measure that backlog instead of admission."""

PHASES = ("route", "queue", "acquire")


def read(run):
    before = run.trace_mark[0] if run.trace_mark else float("inf")
    spans = [s for s in run.spans if s.start < before]
    if not spans:
        return None
    total = sum(sum(s.phase_seconds().get(p, 0.0) for p in PHASES)
                for s in spans)
    return 1e3 * total / len(spans)
