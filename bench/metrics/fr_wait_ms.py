"""fr_wait_ms.<cell>: time an invocation spent blocked in FrWait on a
plan entry that a freshen was still running (``core/freshen.py``
``FreshenState.fr_wait``: the ``fr_wait`` phases of the invocation
spans), summed, per invocation span in the window, so that it compares
with ``inline_cold_ms``.  A program without the phase gives no reading."""

from repro.telemetry.tracer import PHASES


def read(run):
    if "fr_wait" not in PHASES or not run.spans:
        return None
    total = sum(ph.duration for s in run.spans for ph in s.phases
                if ph.name == "fr_wait")
    return 1e3 * total / len(run.spans)
