"""Rows returned to the host in the window, over the window's length."""


def read(run):
    return run["rows"] / run["window_s"]
