"""Mean host-to-device time of the artifact's call over the window: the
``engine.h2d`` span (``jnp.asarray`` of the codes: the host's layout and
the start of the copy), from the program's span log cut to the spans
that start in the window. Nothing where the program keeps no span log or
the log lost part of the window."""

from bench.clock import T_START


def read(run):
    from repro import obs
    if not hasattr(obs, "span_log"):
        return None
    t0 = T_START + run["setup_s"]
    log = obs.span_log()
    if not log.holds(t0):
        return None
    secs = log.durations("engine.h2d", t0, t0 + run["window_s"])
    return float(secs.mean()) * 1e3 if secs.size else None
