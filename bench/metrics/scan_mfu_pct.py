"""The whole scan step's share of the chip's peak: the least time for the
window's rows (``bench.work``: bytes over HBM bandwidth or lookups over
the int8 peak, whichever is larger) over the window's length."""

from bench import work


def read(run):
    least = work.least_seconds(run["cfg"], run["rows"] / run["chips"],
                               run["device_kind"])
    return 100.0 * least / run["window_s"]
