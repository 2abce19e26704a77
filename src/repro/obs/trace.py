"""The process span log: finished spans kept in memory, on two clocks.

Every layer of the serving stack records the intervals it spends into
one bounded, preallocated ring, :func:`span_log`.  A record is a name,
a start and an end (``time.perf_counter`` seconds), the record's span
id, the id of the span that caused it (its parent), and the request and
batch it belongs to (``-1`` where there is none).  A reader takes the
records back as columns, optionally cut to the spans that start in
``[t0, t1)``, and can tell whether the ring still holds every record
that started in that window (:meth:`SpanLog.holds`).

Two ways to write:

* ``with obs.span(name):`` times a synchronous section.  It also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so the section
  shows in the profiler's host plane on the device trace's clock.
* ``with obs.span(name, profile=False):`` times a section that crosses
  an ``await``, into the log only: a profiler annotation opened on the
  event-loop thread would interleave with other coroutines' ones.  An
  interval known only after the fact goes in with
  ``span_log().record(...)``.

Both set :data:`CURRENT_SPAN` for their block, so a span opened inside
another (in the same thread, or the same asyncio task) takes it as its
parent; :data:`REQUEST_ID` carries a request's id across the layers it
passes through.  Both are ``contextvars``: each asyncio task and each
thread sees its own.  ``docs/observability.md`` lists the spans the
stack records.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import json
import threading
import time

import numpy as np
from jax import profiler as _profiler

# holds a whole 51 s window of the benchmark's event stream (some 65 000
# requests and 22 000 batches, ~400 000 records) with room to spare
CAPACITY = 1 << 20

# finished records wait in a deque (an atomic append, no lock) and move
# into the ring this many at a time: a lock and a one-row numpy store per
# record would cost more than the rest of a span
_FLUSH = 1024

_RECORD = np.dtype([("name", np.int32), ("start", np.float64),
                    ("end", np.float64), ("span", np.int64),
                    ("parent", np.int64), ("request", np.int64),
                    ("batch", np.int64)])

# the id of the request being served, and of the innermost open span, in
# this task or thread (-1: none)
REQUEST_ID: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_request_id", default=-1)
CURRENT_SPAN: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_current_span", default=-1)

_ids = itertools.count(1)


def new_id() -> int:
    """A process-unique id for a span, a request or a batch."""
    return next(_ids)


class SpanLog:
    """A ring of the last ``capacity`` finished spans.

    ``record`` appends the row to a pending deque; every ``_FLUSH`` rows,
    and before any read, the pending rows move in bulk, under a lock,
    into a preallocated structured array.  Once the ring is full it
    overwrites the oldest rows and counts them in ``dropped``.
    """

    def __init__(self, capacity: int = CAPACITY) -> None:
        if capacity <= 0:
            raise ValueError("span log capacity must be positive")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._buf = np.zeros(capacity, _RECORD)
        self._names: list[str] = []
        self._codes: dict[str, int] = {}
        self._pending: collections.deque[tuple] = collections.deque()
        self._n = 0                        # records moved into the ring
        self._lost = -np.inf               # latest start overwritten

    def record(self, name: str, start: float, end: float, *,
               span_id: int | None = None, parent: int = -1,
               request: int = -1, batch: int = -1) -> int:
        """Write one finished span; returns its id (new if not given)."""
        if span_id is None:
            span_id = new_id()
        code = self._codes.get(name)
        if code is None:
            code = self._intern(name)
        self._pending.append((code, start, end, span_id, parent, request,
                              batch))
        if len(self._pending) >= _FLUSH:
            self._flush()
        return span_id

    def _intern(self, name: str) -> int:
        with self._lock:
            code = self._codes.get(name)
            if code is None:
                code = len(self._names)
                self._names.append(name)
                self._codes[name] = code
            return code

    def _flush(self) -> None:
        """Move the pending records into the ring, oldest first."""
        with self._lock:
            pending = self._pending
            rows = [pending.popleft() for _ in range(len(pending))]
            if not rows:
                return
            rows = np.array(rows, _RECORD)
            for lo in range(0, len(rows), self.capacity):
                chunk = rows[lo:lo + self.capacity]
                slots = (self._n + np.arange(len(chunk))) % self.capacity
                empty = max(0, self.capacity - self._n)
                if len(chunk) > empty:
                    self._lost = max(self._lost, float(
                        self._buf["start"][slots[empty:]].max()))
                self._buf[slots] = chunk
                self._n += len(chunk)

    @property
    def dropped(self) -> int:
        """Records overwritten since the log was made."""
        self._flush()
        return max(0, self._n - self.capacity)

    @property
    def oldest_start(self) -> float | None:
        """The earliest start the ring still holds (None when empty)."""
        self._flush()
        with self._lock:
            n = min(self._n, self.capacity)
            return float(self._buf["start"][:n].min()) if n else None

    def holds(self, t0: float) -> bool:
        """Whether every span that started at or after ``t0`` is still
        in the ring (none of them was overwritten)."""
        self._flush()
        return bool(self._lost < t0)

    def _rows(self) -> tuple[np.ndarray, list[str]]:
        """The held records, oldest first, and the name table."""
        self._flush()
        with self._lock:
            i = self._n % self.capacity
            if self._n <= self.capacity:
                rows = self._buf[:self._n].copy()
            else:
                rows = np.concatenate([self._buf[i:], self._buf[:i]])
            return rows, list(self._names)

    @staticmethod
    def _cut(rows: np.ndarray, t0, t1) -> np.ndarray:
        keep = np.ones(len(rows), bool)
        if t0 is not None:
            keep &= rows["start"] >= t0
        if t1 is not None:
            keep &= rows["start"] < t1
        return keep

    def columns(self, t0: float | None = None,
                t1: float | None = None) -> dict[str, np.ndarray]:
        """The held spans that start in ``[t0, t1)`` as columns, in the
        order they finished: ``name`` (str), ``start``, ``end``,
        ``span``, ``parent``, ``request``, ``batch``."""
        rows, names = self._rows()
        rows = rows[self._cut(rows, t0, t1)]
        cols = {k: rows[k] for k in _RECORD.names}
        cols["name"] = np.asarray(names, dtype=object)[rows["name"]]
        return cols

    def durations(self, name: str, t0: float | None = None,
                  t1: float | None = None) -> np.ndarray:
        """Seconds of every ``name`` span that starts in ``[t0, t1)``."""
        rows, names = self._rows()
        if name not in names:
            return np.zeros(0)
        rows = rows[(rows["name"] == names.index(name))
                    & self._cut(rows, t0, t1)]
        return rows["end"] - rows["start"]

    def self_seconds(self, name: str, t0: float | None = None,
                     t1: float | None = None) -> np.ndarray:
        """Self time of every ``name`` span that starts in ``[t0, t1)``:
        its duration less the durations of its direct children."""
        rows, names = self._rows()
        if name not in names:
            return np.zeros(0)
        sel = rows[(rows["name"] == names.index(name))
                   & self._cut(rows, t0, t1)]
        own = sel["end"] - sel["start"]
        if not len(sel):
            return own
        order = np.argsort(sel["span"])
        ids = sel["span"][order]
        pos = np.minimum(np.searchsorted(ids, rows["parent"]), len(ids) - 1)
        child = ids[pos] == rows["parent"]
        covered = np.zeros(len(sel))
        np.add.at(covered, order[pos[child]],
                  rows["end"][child] - rows["start"][child])
        return own - covered

    def dump_json(self, path: str) -> str:
        """Write the held spans as JSON columns (``--spans-json``)."""
        cols = self.columns()
        payload = {"clock": "time.perf_counter", "capacity": self.capacity,
                   "dropped": self.dropped,
                   "columns": {k: v.tolist() for k, v in cols.items()}}
        with open(path, "w") as f:
            json.dump(payload, f)
            f.write("\n")
        return path


# the process span log: every layer of the stack records here (tests that
# need isolation construct their own SpanLog)
SPAN_LOG = SpanLog()


def span_log() -> SpanLog:
    """The process-default :class:`SpanLog`."""
    return SPAN_LOG


class span:
    """Time a ``with`` block into the process span log.

    ``parent`` and ``request`` default to :data:`CURRENT_SPAN` and
    :data:`REQUEST_ID`; ``start`` backdates the span to an earlier
    ``perf_counter`` reading.  With ``profile`` the block is also a
    ``jax.profiler.TraceAnnotation`` of the same name; pass
    ``profile=False`` for a block that crosses an ``await``.  After the
    block, ``start``, ``end`` and ``span_id`` hold what was recorded.
    """

    __slots__ = ("name", "parent", "request", "batch", "start", "end",
                 "span_id", "_token", "_annotation")

    def __init__(self, name: str, *, profile: bool = True,
                 parent: int | None = None, request: int | None = None,
                 batch: int = -1, start: float | None = None) -> None:
        self.name, self.parent, self.request = name, parent, request
        self.batch, self.start, self.end = batch, start, None
        self._annotation = (_profiler.TraceAnnotation(name) if profile
                            else None)

    def __enter__(self) -> "span":
        self.span_id = new_id()
        if self.parent is None:
            self.parent = CURRENT_SPAN.get()
        if self.request is None:
            self.request = REQUEST_ID.get()
        self._token = CURRENT_SPAN.set(self.span_id)
        if self._annotation is not None:
            self._annotation.__enter__()
        if self.start is None:
            self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        CURRENT_SPAN.reset(self._token)
        SPAN_LOG.record(self.name, self.start, self.end,
                        span_id=self.span_id, parent=self.parent,
                        request=self.request, batch=self.batch)
