"""weights_read_ms.<cell>: making one endpoint's weights readable on the
host: mapping its checkpoint file (populated up front) and viewing each
leaf in its dtype (``checkpoint/io.py`` ``read_flat``: the ``read`` child
of each ``weights`` plan-entry span), mean over the window's weight loads,
by freshen threads and inline alike."""

from bench import plan_spans


def read(run):
    reads = plan_spans.children(plan_spans.of_entry(run, "weights"), "read")
    if not reads:
        return None
    return 1e3 * sum(c.duration for c in reads) / len(reads)
