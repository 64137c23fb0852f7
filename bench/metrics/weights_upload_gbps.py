"""weights_upload_gbps.<cell>: the rate at which weight loads put the
leaves on the device (``checkpoint/io.py`` ``place_flat``: the
``upload`` child of each ``weights`` plan-entry span, from the first
leaf's transfer, out of the views of the mapped checkpoint file, until
every leaf is on the device): the uploads' summed ``bytes`` over their
summed seconds, in 1e9 bytes per second."""

from bench import plan_spans


def read(run):
    ups = plan_spans.children(plan_spans.of_entry(run, "weights"), "upload")
    seconds = sum(c.duration for c in ups)
    if not ups or seconds <= 0:
        return None
    return sum(c.attrs.get("bytes", 0) for c in ups) / seconds / 1e9
