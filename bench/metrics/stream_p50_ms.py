"""Median latency, due time to reply, of every request due in the window.

A request that failed or got no reply is a miss: it ranks above every
answered one and, where the percentile lands on it, reads as the wait
limit (the window plus the wait past its close).
"""

import numpy as np


def read(run):
    lat = run["lat_ms"]
    return min(float(np.percentile(lat, 50, method="inverted_cdf")),
               run["miss_ms"])
