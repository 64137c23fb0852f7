"""The program's freshen-plan entry spans (``repro.telemetry`` ``PlanSpan``,
one per execution of a plan entry, by a freshen thread or inline) begun
since the window opened: the same cut as ``Run.spans``, whose invocations
include those due in the window that finished after it.  ``Run.spans``
holds the invocation spans and their tracer holds the plan spans; a
program that records none gives an empty list."""


def of_entry(run, entry: str) -> list:
    if not run.spans:
        return []
    plans = getattr(run.spans[0].tracer, "plan_spans", None)
    if plans is None:
        return []
    return [p for p in plans() if p.entry == entry and p.start >= run.t0]


def children(spans, name: str) -> list:
    """The children called ``name`` of ``spans`` (a plan span is published
    once all of its children have closed)."""
    return [c for p in spans for c in p.children if c.name == name]
