"""Mean self time of the serving tier's batch span over the window: each
``tier.batch`` span (flush decision to the batcher resuming with the
results) less its ``tier.h2d``, ``tier.compute`` and ``tier.d2h``
children: concatenation and padding, the hand-off to the executor and
back, slicing.  From the program's span log cut to the spans that start
in the window; nothing where the program keeps no span log or the log
lost part of the window."""

from bench.clock import T_START


def read(run):
    from repro import obs
    if not hasattr(obs, "span_log"):
        return None
    t0 = T_START + run["setup_s"]
    log = obs.span_log()
    if not log.holds(t0):
        return None
    secs = log.self_seconds("tier.batch", t0, t0 + run["window_s"])
    return float(secs.mean()) * 1e3 if secs.size else None
