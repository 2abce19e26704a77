"""Unified observability for the serving stack (metrics + span tracing).

One dependency-free substrate shared by the whole pipeline — the
micro-batching tier (``repro.serve``), the compile-once engine
(``repro.engine``) and the truth-table compiler (``repro.compile``) all
record into the process-default :class:`Registry`, so a single
``obs.registry().snapshot()`` (or ``render_prometheus()``) answers both
"where did this request's latency go?" (queue-wait / assembly / device
histograms) and "which compile pass got slower?" (per-pass timing
counters).  Beside it, one process span log (``obs.span_log()``) keeps
every layer's timed intervals, with request and batch ids, on the
``perf_counter`` clock, mirrored into the profiler's trace by
``obs.span``.  See docs/observability.md for the metric table and the
span table.
"""

from repro.obs.metrics import (DEFAULT_TIME_BUCKETS, Counter, Family, Gauge,
                               Histogram, Registry, REGISTRY, registry)
from repro.obs.report import PeriodicReporter, summary_line
from repro.obs.trace import (CURRENT_SPAN, REQUEST_ID, SpanLog, new_id, span,
                             span_log)

__all__ = ["CURRENT_SPAN", "Counter", "DEFAULT_TIME_BUCKETS", "Family",
           "Gauge", "Histogram", "PeriodicReporter", "REGISTRY",
           "REQUEST_ID", "Registry", "SpanLog", "new_id", "registry", "span",
           "span_log", "summary_line"]
