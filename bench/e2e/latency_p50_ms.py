"""latency_p50_ms: the median, over every request due in the window, of
the time from when it was due to when its last stage resolved."""

import numpy as np


def read(run):
    lat = run.lat_ms[run.ok]
    return float(np.percentile(lat, 50)) if len(lat) else None
