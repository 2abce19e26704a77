"""99th percentile of how late the load generator sent: actual send
time minus due time (the bench's own host clock, in its own process)."""

import numpy as np


def read(run):
    return float(np.percentile(run["late_ms"], 99, method="inverted_cdf"))
