"""End-to-end telemetry for the serving fabric.

``Tracer`` produces per-invocation span trees (route/batch_wait/queue/
acquire/boot_process/boot_init/warm_to/fr_wait/run/release phases),
freshen-lifecycle spans linked to the arrivals they anchored, and a span
per freshen-plan entry execution (the weights entry split into ``read``
and ``upload``), each host activity also a ``fabric.*`` profiler
annotation; ``MetricsRegistry`` holds
typed counters/gauges/histograms behind the components' existing
``stats()`` views; ``export_chrome`` writes traces loadable in
chrome://tracing / Perfetto.  Everything is zero-overhead when disabled
(``NULL_TRACER``).  See docs/architecture.md "Observability".
"""
from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry)
from repro.telemetry.tracer import (NULL_SPAN, NULL_TRACER, PHASES,
                                    FreshenSpan, InvocationSpan,
                                    PhaseSpan, PlanSpan, Tracer,
                                    current_plan, current_span)
from repro.telemetry.export import chrome_trace_events

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Tracer", "InvocationSpan", "FreshenSpan", "PhaseSpan", "PlanSpan",
    "NULL_TRACER", "NULL_SPAN", "PHASES", "current_plan", "current_span",
    "chrome_trace_events",
]
