"""batch_wait_ms.<cell>: a row's wait in the endpoint batcher, from its
``submit_request`` to its batch's dispatch (``serving/batching.py``
``EndpointBatcher``: the ``batch_wait`` phase of the batch's invocation
span, its per-row ``waits``), mean over the rows.  As for ``admit_ms``,
only spans that began before the profiler started count: its host work
would back the queue up."""


def read(run):
    before = run.trace_mark[0] if run.trace_mark else float("inf")
    waits = [w for s in run.spans if s.start < before
             for ph in s.phases if ph.name == "batch_wait"
             for w in ph.attrs.get("waits", ())]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
