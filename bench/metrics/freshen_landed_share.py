"""freshen_landed_share.<cell>: of the freshens the scheduler dispatched in
the window (``core/prediction.py`` predictor, ``_dispatch_freshen``), the
share that an arrival used (tracer ``FreshenSpan`` outcome ``landed``);
the rest expired or were still pending.  Nothing dispatched: no reading."""


def read(run):
    sent = [f for f in run.freshens if f.outcome != "gated"]
    if not sent:
        return None
    return 100.0 * sum(f.outcome == "landed" for f in sent) / len(sent)
