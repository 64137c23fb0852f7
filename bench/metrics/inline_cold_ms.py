"""inline_cold_ms.<cell>: the freshen plan's work done inline, on the
request's critical path (``ModelEndpoint.build_plan``: weights, compile,
warm-up; ``timings`` weights + compile + warmup), mean per invocation in
the window.  A freshened or warm invocation adds about nothing."""


def read(run):
    vals = [t["weights"] + t["compile"] + t["warmup"]
            for ts in run.timings.values() for t in ts]
    return 1e3 * sum(vals) / len(vals) if vals else None
