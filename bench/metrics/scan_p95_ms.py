"""95th percentile of request latency, in ms, over every request due in
the window: from the time it was due to the end of the step that
answered it (host clock)."""

import numpy as np


def read(ctx):
    lat = ctx["latency_s"]
    return float(np.percentile(lat, 95) * 1e3) if lat.shape[0] else None
