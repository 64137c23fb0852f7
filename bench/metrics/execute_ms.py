"""execute_ms.<cell>: the model forward of one invocation through the
``Executor``, ending in ``block_until_ready`` (``ModelEndpoint.timings``
``execute``), mean over the window's invocations."""


def read(run):
    vals = [t["execute"] for ts in run.timings.values() for t in ts]
    return 1e3 * sum(vals) / len(vals) if vals else None
