"""Median request latency, in ms, over every request due in the window
(host clock): the steadier statistic beside ``scan_p95_ms``."""

import numpy as np


def read(ctx):
    lat = ctx["latency_s"]
    return float(np.percentile(lat, 50) * 1e3) if lat.shape[0] else None
