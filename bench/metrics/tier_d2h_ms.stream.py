"""Mean device-to-host leg of the serving tier per batch over the window:
the ``tier.d2h`` span (``np.asarray`` of the output and its slice: the
wait for the device and the copy back), from the program's span log cut
to the spans that start in the window. Nothing where the program keeps
no span log or the log lost part of the window."""

from bench.clock import T_START


def read(run):
    from repro import obs
    if not hasattr(obs, "span_log"):
        return None
    t0 = T_START + run["setup_s"]
    log = obs.span_log()
    if not log.holds(t0):
        return None
    secs = log.durations("tier.d2h", t0, t0 + run["window_s"])
    return float(secs.mean()) * 1e3 if secs.size else None
