"""batch_fill.<cell>: rows per batch the endpoint batchers formed in the
window, as a share of the compiled batch (``serving/batching.py``
``EndpointBatcher``, its ``batch.fill`` histogram).  The rest of each
batch is padding the device computes for nothing."""


def read(run):
    batches = sum(c for c, _ in run.fills.values())
    rows = sum(t for _, t in run.fills.values())
    if not batches:
        return None
    return 100.0 * rows / batches / run.batch
