"""Device-sharded async micro-batching serving tier over ``CompiledLUTNet``.

The paper's deployment regime is extreme-throughput inference: per-request
work is a few thousand table lookups, so the host-side request loop — not
the kernel — is where a serving stack squanders the hardware.  This module
is the request-side half of the deployment story that
``repro.engine.compile_network`` started:

* **micro-batching** — incoming requests (each a ragged ``(rows, n_in)``
  code batch) are coalesced into ``block_b``-bucketed batches and flushed
  either when ``max_batch_rows`` rows have accumulated or when the oldest
  request has waited ``flush_deadline_s`` (size-or-deadline flush);
* **device sharding** — with more than one device the padded batch is laid
  out with ``jax.sharding`` on the batch axis (``NamedSharding`` over a
  1-D ``"data"`` mesh) and the engine's forward runs under ``shard_map``:
  the tiny table slabs are replicated, the batch is split, every device
  executes the same fused kernel on its shard (embarrassingly parallel);
  with one device the tier degrades gracefully to a plain engine call;
* **backpressure** — the queue is bounded at ``max_queue_rows`` queued
  rows; a request that would overflow it is rejected immediately with
  :class:`TierOverloaded` instead of growing an unbounded backlog;
* **per-request timeouts** — a request that has not been *launched* into a
  batch within ``request_timeout_s`` is dropped with
  :class:`RequestTimeout` (a request whose batch is already computing
  always gets its result);
* **compile-once steady state** — ``start()`` warms every batch bucket, so
  a steady-state serving loop performs **zero jit re-traces and zero
  compiler runs** (``stats()["retraces_after_warmup"]`` /
  ``["compiler_runs_after_warmup"]`` — asserted by tests/test_serve.py and
  gated by the bench's ``serving_tier`` section).

Example (single process, default device set)::

    import asyncio
    import numpy as np
    from repro import engine, serve

    net = engine.compile_network(layers, optimize_level=3, in_features=12)

    async def main():
        async with serve.ServingTier(net) as tier:
            out = await tier.infer(np.zeros((3, net.n_in), np.int32))
            print(out.shape, tier.stats()["batches"])

    asyncio.run(main())

Outputs are bit-exact with calling the ``CompiledLUTNet`` directly on the
same rows — coalescing, padding and sharding are pure layout.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import itertools
import math
import time

import numpy as np

import jax

from repro import engine as rengine
from repro import obs

# each tier instance gets its own label value so per-tier stats stay
# separable in the shared process registry (stats() reads them back)
_TIER_IDS = itertools.count()


class _TierMetrics:
    """This tier's labeled children in the process metrics registry.

    One instance per ServingTier: counters mirror the legacy ``stats()``
    fields, the stage histograms time each request's legs (queue wait /
    batch assembly / device time — see docs/observability.md), and the
    two ``*_after_warmup`` gauges carry the compile-once contract into
    the snapshot.
    """

    def __init__(self, tier_id: str) -> None:
        reg = obs.registry()
        t = {"tier": tier_id}

        def ctr(name, help_):
            return reg.counter(name, help_, labels=("tier",)).labels(**t)

        def hist(name, help_):
            return reg.histogram(name, help_, labels=("tier",)).labels(**t)

        def gauge(name, help_):
            return reg.gauge(name, help_, labels=("tier",)).labels(**t)

        self.requests = ctr("serve_requests_total",
                            "requests accepted by the serving tier")
        self.rows = ctr("serve_rows_total", "request rows accepted")
        self.batches = ctr("serve_batches_total", "coalesced batches run")
        self.padded_rows = ctr("serve_padded_rows_total",
                               "kernel rows launched incl. bucket padding")
        self.rejected = ctr("serve_rejected_total",
                            "requests rejected by backpressure")
        self.timed_out = ctr("serve_timed_out_total",
                             "requests expired before launch")
        self.expired_rows = ctr("serve_expired_rows_total",
                                "rows dropped by request timeouts")
        self.flush = reg.counter(
            "serve_flush_total", "batch flushes by cause",
            labels=("tier", "cause"))
        self.flush_by_cause = {
            cause: self.flush.labels(tier=tier_id, cause=cause)
            for cause in ("size", "deadline", "drain")}
        self.queue_wait = hist(
            "serve_queue_wait_seconds",
            "enqueue -> flush decision (queue wait)")
        self.assembly = hist(
            "serve_assembly_seconds",
            "flush -> device dispatch (batch concat + executor hand-off)")
        self.device = hist(
            "serve_device_seconds",
            "device dispatch -> completion (padded batch forward)")
        self.latency = hist(
            "serve_request_latency_seconds",
            "enqueue -> completion (whole request span)")
        self.queued_rows = gauge("serve_queued_rows",
                                 "rows currently queued")
        self.retraces = gauge(
            "serve_retraces_after_warmup",
            "jit traces added after warmup (compile-once: must stay 0)")
        self.compiler_runs = gauge(
            "serve_compiler_runs_after_warmup",
            "compiler runs after warmup (compile-once: must stay 0)")


class TierError(Exception):
    """Base class for serving-tier request failures."""


class TierOverloaded(TierError):
    """The bounded request queue is full — the request was rejected."""


class TierClosed(TierError):
    """The tier is stopped (or stopping) and accepts no new requests."""


class RequestTimeout(TierError):
    """The request expired before its batch was launched."""


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """Knobs of the micro-batching serving tier.

    * ``max_batch_rows`` — flush a batch once this many rows are queued
      (None: the artifact's ``block_b``).  A single request larger than
      this forms its own batch.
    * ``flush_deadline_s`` — flush a non-empty partial batch once its
      oldest request has waited this long (the latency bound under light
      load).
    * ``max_queue_rows`` — bounded-queue backpressure: a request that
      would push the queued-row count past this is rejected with
      :class:`TierOverloaded`.
    * ``request_timeout_s`` — per-request launch deadline; ``None``
      disables timeouts.
    * ``devices`` — devices for data-parallel batch sharding (None: all
      of ``jax.devices()``).  One device means no sharding machinery at
      all.
    * ``warmup`` — trace every batch bucket in ``start()`` so steady
      state is re-trace free.
    """

    max_batch_rows: int | None = None
    flush_deadline_s: float = 0.005
    max_queue_rows: int = 4096
    request_timeout_s: float | None = None
    devices: tuple | None = None
    warmup: bool = True


@dataclasses.dataclass
class _Request:
    codes: np.ndarray            # (rows, n_in) int32
    future: asyncio.Future       # resolves to (rows, n_out) np.ndarray
    enqueue_t: float             # loop clock, for the flush deadline
    deadline_t: float | None     # absolute launch deadline (None: never)
    enqueue_perf: float          # perf_counter, for the span log
    request_id: int
    parent_span: int             # the caller's open span (-1: none)


class ServingTier:
    """Async micro-batching front-end over one :class:`CompiledLUTNet`.

    Drive it from an event loop: ``await tier.start()`` (or ``async with
    ServingTier(net) as tier``), then any number of concurrent
    ``await tier.infer(codes)`` calls, then ``await tier.stop()``.
    ``infer`` accepts ``(rows, n_in)`` or a single ``(n_in,)`` row and
    returns the matching ``(rows, n_out)`` / ``(n_out,)`` int32 output,
    bit-exact with ``net(codes)``.
    """

    def __init__(self, net, config: TierConfig | None = None):
        cfg = config or TierConfig()
        self._net = net
        self._cfg = cfg
        # the artifact's ExecutionPlan is the source of truth for the batch
        # tile (an autotuned artifact may have picked a non-default
        # block_b); net.block_b is the fallback for plan-less stand-ins
        block_b = getattr(getattr(net, "plan", None), "block_b", None) \
            or net.block_b
        self._max_batch = cfg.max_batch_rows or block_b
        if self._max_batch <= 0:
            raise ValueError("max_batch_rows must be positive")
        devices = tuple(cfg.devices) if cfg.devices else tuple(jax.devices())
        self._devices = devices
        # batches are padded to a multiple of this unit: block_b keeps the
        # engine on its one-trace-per-bucket contract, len(devices) keeps
        # the shard_map batch axis evenly divisible
        self._bucket_unit = math.lcm(block_b, len(devices))
        # where a padded batch is put: split on the batch axis over a 1-D
        # "data" mesh, or with one device uncommitted on the default
        # device, as the engine itself puts host codes (so a direct call
        # of the artifact shares the tier's trace)
        if len(devices) == 1:
            self._mesh, self._placement = None, None
        else:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            self._mesh = Mesh(np.asarray(devices), ("data",))
            self._placement = NamedSharding(self._mesh, P("data"))
        self._forward, self._sharded_jit = self._make_forward()
        self._pending: collections.deque[_Request] = collections.deque()
        self._queued_rows = 0
        self._wake = asyncio.Event()
        self._stopping = False
        self._task: asyncio.Task | None = None
        self._started = False
        # observability: every counter the old flat stats() dict carried
        # now lives in the process metrics registry (labeled per tier);
        # stats() reads them back so its keys are unchanged
        self._metrics = _TierMetrics(str(next(_TIER_IDS)))
        self._traces0 = 0
        self._compiler_runs0 = 0

    # -- forward construction ----------------------------------------------

    def _make_forward(self):
        """(forward(padded) -> jax.Array, sharded jit fn or None); the
        padded batch is already on ``self._placement``."""
        net = self._net
        if self._mesh is None:
            return net, None
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh
        # the slabs are replicated onto every device of the mesh once, here,
        # and passed as arguments: captured as constants they would stay
        # on the device they were built on.  Only the batch axis of the
        # codes is split.
        fn, arrays = net.forward_parts()
        arrays = jax.device_put(arrays, NamedSharding(mesh, P()))
        fwd = jax.jit(jax.shard_map(
            lambda codes, slabs: fn(codes, *slabs), mesh=mesh,
            in_specs=(P("data"), P()), out_specs=P("data"),
            check_vma=False))
        return (lambda padded: fwd(padded, arrays)), fwd

    def _bucket(self, rows: int) -> int:
        return -(-rows // self._bucket_unit) * self._bucket_unit

    def _run_batch(self, batch: np.ndarray, batch_id: int = -1,
                   parent: int = -1):
        """Pad to the bucket, put it on the device(s), run the (possibly
        sharded) forward, copy the result back and slice it.

        The three host calls are spans under ``parent`` with
        ``batch_id``: ``tier.h2d`` (the put, which starts the copy),
        ``tier.compute`` (the forward's dispatch) and ``tier.d2h`` (the
        wait for the device and the copy back).  Nothing waits between
        them, so the copy in, the dispatch and the kernel overlap as
        before.  Returns ``(out, padded_rows, t_dispatch, t_done)`` — the
        two timestamps bracket the device leg of every request in the
        batch.
        """
        rows = batch.shape[0]
        padded_rows = self._bucket(rows)
        if padded_rows != rows:
            batch = np.concatenate(
                [batch, np.zeros((padded_rows - rows, batch.shape[1]),
                                 dtype=batch.dtype)], axis=0)
        with obs.span("tier.h2d", parent=parent, batch=batch_id) as h2d:
            x = jax.device_put(batch, self._placement)
        with obs.span("tier.compute", parent=parent, batch=batch_id):
            out = self._forward(x)
        with obs.span("tier.d2h", parent=parent, batch=batch_id) as d2h:
            out = np.asarray(out)[:rows]
        return out, padded_rows, h2d.start, d2h.end

    def _trace_count(self) -> int:
        # the tier hands the artifact device batches only
        n = self._net.jit_cache_size(host=False)
        if self._sharded_jit is not None:
            n += self._sharded_jit._cache_size()
        return n

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> "ServingTier":
        """Warm the batch buckets and start the batcher task."""
        if self._started:
            raise TierError("tier already started")
        self._started = True
        if self._cfg.warmup:
            loop = asyncio.get_running_loop()
            for rows in range(self._bucket_unit,
                              self._bucket(self._max_batch) + 1,
                              self._bucket_unit):
                zeros = np.zeros((rows, self._net.n_in), dtype=np.int32)
                await loop.run_in_executor(
                    None, lambda z=zeros: jax.block_until_ready(
                        self._run_batch(z)[0]))
        self._traces0 = self._trace_count()
        self._compiler_runs0 = rengine.compile_runs()
        self._task = asyncio.create_task(self._batcher())
        return self

    async def stop(self) -> None:
        """Drain queued requests into final batches, then shut down.

        Safe on an empty queue (returns as soon as the batcher notices);
        requests submitted after ``stop`` raise :class:`TierClosed`.
        """
        if not self._started or self._stopping:
            return
        self._stopping = True
        self._wake.set()
        if self._task is not None:
            await self._task

    async def __aenter__(self) -> "ServingTier":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # -- request path -------------------------------------------------------

    async def infer(self, codes) -> np.ndarray:
        """Submit one request; resolves when its batch has been served.

        ``codes`` is ``(rows, n_in)`` (or one ``(n_in,)`` row) of int
        codes.  Raises :class:`TierOverloaded` when the bounded queue is
        full, :class:`RequestTimeout` when the request expires before
        launch, :class:`TierClosed` when the tier is stopped, and
        ``ValueError`` on a shape mismatch.

        >>> import asyncio, numpy as np
        >>> from repro import engine, serve
        >>> rng = np.random.default_rng(0)
        >>> idx = np.stack([np.sort(rng.choice(6, 2, replace=False))
        ...                 for _ in range(4)]).astype(np.int32)
        >>> tbl = rng.integers(0, 4, (4, 16), dtype=np.int32)
        >>> net = engine.compile_network([(idx, tbl, 2)], in_features=6,
        ...                              block_b=4)
        >>> async def main():
        ...     async with serve.ServingTier(net) as tier:
        ...         codes = rng.integers(0, 4, (3, 6), dtype=np.int32)
        ...         out = await tier.infer(codes)
        ...         return codes, out, tier.stats()
        >>> codes, out, stats = asyncio.run(main())
        >>> bool((out == np.asarray(net(codes))).all())    # bit-exact
        True
        >>> stats["retraces_after_warmup"]                 # compile-once
        0
        """
        arr = np.asarray(codes, dtype=np.int32)
        single = arr.ndim == 1
        if single:
            arr = arr[None, :]
        if arr.ndim != 2 or arr.shape[1] != self._net.n_in:
            raise ValueError(
                f"expected (rows, {self._net.n_in}) codes, got "
                f"{np.asarray(codes).shape}")
        if self._stopping or not self._started:
            raise TierClosed("serving tier is not accepting requests")
        rows = arr.shape[0]
        if rows == 0:
            return arr.reshape(0, self._net.n_out)
        if self._queued_rows + rows > self._cfg.max_queue_rows:
            self._metrics.rejected.inc()
            raise TierOverloaded(
                f"queue holds {self._queued_rows} rows; request of {rows} "
                f"would exceed max_queue_rows={self._cfg.max_queue_rows}")
        loop = asyncio.get_running_loop()
        now = loop.time()
        deadline = (None if self._cfg.request_timeout_s is None
                    else now + self._cfg.request_timeout_s)
        request_id = obs.REQUEST_ID.get()
        if request_id < 0:
            request_id = obs.new_id()
        req = _Request(arr, loop.create_future(), now, deadline,
                       time.perf_counter(), request_id,
                       obs.CURRENT_SPAN.get())
        self._pending.append(req)
        self._queued_rows += rows
        self._metrics.requests.inc()
        self._metrics.rows.inc(rows)
        self._wake.set()
        out = await req.future
        return out[0] if single else out

    # -- batcher ------------------------------------------------------------

    def _expire_overdue(self, now: float) -> None:
        while self._pending:
            req = self._pending[0]
            if req.deadline_t is None or now < req.deadline_t:
                break
            self._pending.popleft()
            self._queued_rows -= req.codes.shape[0]
            self._metrics.timed_out.inc()
            self._metrics.expired_rows.inc(req.codes.shape[0])
            if not req.future.done():
                req.future.set_exception(RequestTimeout(
                    f"request waited past request_timeout_s="
                    f"{self._cfg.request_timeout_s}"))

    def _take_batch(self) -> list[_Request]:
        taken, rows = [], 0
        while self._pending:
            nxt = self._pending[0].codes.shape[0]
            if taken and rows + nxt > self._max_batch:
                break
            taken.append(self._pending.popleft())
            rows += nxt
            self._queued_rows -= nxt
            if rows >= self._max_batch:
                break
        return taken

    async def _batcher(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            while not self._pending and not self._stopping:
                self._wake.clear()
                await self._wake.wait()
            now = loop.time()
            self._expire_overdue(now)
            if not self._pending:
                if self._stopping:
                    break
                continue
            # size-or-deadline coalescing window, bounded by the oldest
            # request's timeout so an expiring request is noticed in time
            cause = "drain" if self._stopping else None
            while not self._stopping:
                if self._queued_rows >= self._max_batch:
                    cause = "size"
                    break
                oldest = self._pending[0]
                flush_at = oldest.enqueue_t + self._cfg.flush_deadline_s
                if oldest.deadline_t is not None:
                    flush_at = min(flush_at, oldest.deadline_t)
                remaining = flush_at - loop.time()
                if remaining <= 0:
                    cause = "deadline"
                    break
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), remaining)
                except asyncio.TimeoutError:
                    pass
            self._expire_overdue(loop.time())
            batch = self._take_batch()
            if not batch:
                continue
            cause = cause or "drain"
            batch_id = obs.new_id()
            # the flush decision ends the queue wait; the batch span runs
            # until the batcher resumes with the results
            with obs.span("tier.batch", profile=False, parent=-1,
                          request=-1, batch=batch_id) as span:
                codes = (batch[0].codes if len(batch) == 1 else
                         np.concatenate([r.codes for r in batch], axis=0))
                try:
                    out, padded_rows, t_dispatch, t_done = (
                        await loop.run_in_executor(
                            None, self._run_batch, codes, batch_id,
                            span.span_id))
                except Exception as exc:           # pragma: no cover
                    for req in batch:
                        if not req.future.done():
                            req.future.set_exception(TierError(
                                f"batch execution failed: {exc!r}"))
                    continue
            t_flush = span.start
            self._metrics.batches.inc()
            self._metrics.padded_rows.inc(padded_rows)
            self._metrics.flush_by_cause[cause].inc()
            off = 0
            log = obs.span_log()
            for req in batch:
                n = req.codes.shape[0]
                if not req.future.done():
                    req.future.set_result(out[off:off + n])
                off += n
                # the batch's shared timestamps give every request's legs
                log.record("tier.queue", req.enqueue_perf, t_flush,
                           parent=req.parent_span, request=req.request_id,
                           batch=batch_id)
                self._metrics.queue_wait.observe(t_flush - req.enqueue_perf)
                self._metrics.assembly.observe(t_dispatch - t_flush)
                self._metrics.device.observe(t_done - t_dispatch)
                self._metrics.latency.observe(t_done - req.enqueue_perf)
        # post-drain: anything that slipped in after the final drain pass
        while self._pending:
            req = self._pending.popleft()
            self._queued_rows -= req.codes.shape[0]
            if not req.future.done():
                req.future.set_exception(TierClosed("tier stopped"))

    # -- observability ------------------------------------------------------

    def stats(self) -> dict:
        """Steady-state serving counters (see the bench's ``serving_tier``
        section for the latency/QPS view built on top of these).

        ``batch_occupancy`` is served rows / padded batch capacity — the
        fraction of kernel work doing real requests rather than bucket
        padding.  ``retraces_after_warmup`` / ``compiler_runs_after_warmup``
        are the compile-once serving contract and must stay exactly 0 in
        steady state.  The same counters live in the process metrics
        registry (``repro.obs``, labeled per tier); this dict is the
        backward-compatible flat view of this tier's slice of it.
        """
        m = self._metrics
        n_rows = int(m.rows.value)
        n_batches = int(m.batches.value)
        n_padded = int(m.padded_rows.value)
        served_rows = n_rows - int(m.expired_rows.value) - self._queued_rows
        retraces = self._trace_count() - self._traces0
        compiler_runs = rengine.compile_runs() - self._compiler_runs0
        # mirror the point-in-time quantities into the registry so a
        # snapshot taken after the run carries the compile-once contract
        m.queued_rows.set(self._queued_rows)
        m.retraces.set(retraces)
        m.compiler_runs.set(compiler_runs)
        return {
            "requests": int(m.requests.value),
            "rows": n_rows,
            "batches": n_batches,
            "padded_rows": n_padded,
            "batch_occupancy": served_rows / n_padded if n_padded else 0.0,
            "mean_batch_rows": (served_rows / n_batches
                                if n_batches else 0.0),
            "flush_causes": {cause: int(c.value)
                             for cause, c in m.flush_by_cause.items()},
            "rejected": int(m.rejected.value),
            "timed_out": int(m.timed_out.value),
            "queued_rows": self._queued_rows,
            "n_devices": len(self._devices),
            # the device the batches ran on, as JAX reports it
            "platform": self._devices[0].platform,
            "device_kind": self._devices[0].device_kind,
            "sharded": self._sharded_jit is not None,
            "bucket_unit": self._bucket_unit,
            "max_batch_rows": self._max_batch,
            "retraces_after_warmup": retraces,
            "compiler_runs_after_warmup": compiler_runs,
        }

    def latency_breakdown(self) -> dict:
        """Per-stage latency summary from this tier's span histograms.

        ``{stage: {count, mean_ms, p50_ms, p99_ms}}`` for the three span
        legs (``queue_wait``, ``assembly``, ``device``) plus the whole
        request (``total``) — the "where did the latency go" view that
        ``loadgen.LoadReport.breakdown`` and the bench's ``serving_tier``
        section surface.  Percentiles are bucket-interpolated estimates;
        a stage with no observations reports zeros.
        """
        m = self._metrics
        out = {}
        for stage, h in (("queue_wait", m.queue_wait),
                         ("assembly", m.assembly),
                         ("device", m.device),
                         ("total", m.latency)):
            n = h.count
            out[stage] = {
                "count": n,
                "mean_ms": h.mean() * 1e3 if n else 0.0,
                "p50_ms": h.quantile(0.5) * 1e3 if n else 0.0,
                "p99_ms": h.quantile(0.99) * 1e3 if n else 0.0,
            }
        return out


async def serve_once(net, requests, config: TierConfig | None = None
                     ) -> list[np.ndarray]:
    """Convenience: start a tier, serve ``requests`` concurrently, stop.

    ``requests`` is an iterable of ``(rows, n_in)`` arrays; returns the
    outputs in order.  This is the one-shot shape used by the bench and
    the docs examples::

        outs = asyncio.run(serve.serve_once(net, [r0, r1, r2]))
    """
    async with ServingTier(net, config) as tier:
        return list(await asyncio.gather(
            *[tier.infer(r) for r in requests]))


def run_requests(net, requests, config: TierConfig | None = None
                 ) -> list[np.ndarray]:
    """Blocking wrapper over :func:`serve_once` for sync callers/tests."""
    return asyncio.run(serve_once(net, requests, config))
