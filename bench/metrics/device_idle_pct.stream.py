"""Device idle share of the traced window in the stream cells
(``bench.trace.idle_pct``)."""

from bench.trace import idle_pct as read  # noqa: F401
