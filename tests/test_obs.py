"""Observability substrate contract: ``repro.obs`` + its stack wiring.

* **primitives** — counter monotonicity, histogram bucketing at the
  edges (``le`` semantics), strict edge validation, interpolated
  quantiles, labeled-family child reuse, idempotent-but-strict
  registration;
* **atomic snapshot** — a snapshot taken while other threads increment
  never shows a histogram whose ``count`` disagrees with its bucket
  counts;
* **exposition** — a golden Prometheus text rendering and a JSON dump;
* **span log** — durations, parent ids and self time of nested spans,
  the ring's bound (``dropped``, oldest start, ``holds``), the profiler
  annotation ``obs.span`` opens, one request id from the HTTP ingress
  through the tier's queue to its batch;
* **stack wiring** — the serving tier feeds the stage histograms and its
  ``latency_breakdown()``/``LoadReport.breakdown`` stay JSON-safe on
  empty and tiny runs (the loadgen 0/1/2-request edges);
* **hot-path overhead** — serving a request costs a bounded handful of
  metric operations and span records (regression-tested so an exporter
  can never creep into the request path).
"""

import asyncio
import io
import json
import math
import sys
import threading
import time

import numpy as np
import pytest

from repro import engine, obs, serve

# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def test_counter_monotonic():
    c = obs.Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)


def test_gauge_set_and_inc():
    g = obs.Gauge()
    g.set(4)
    g.inc(-1.5)
    assert g.value == 2.5


def test_histogram_bucketing_at_the_edges():
    """``le`` semantics: a value equal to an edge lands in that edge's
    bucket (inclusive upper bound), one past it in the next."""
    h = obs.Histogram(edges=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.0000001, 2.0, 5.0, 5.0000001, 100.0):
        h.observe(v)
    snap = h._snapshot()
    assert snap["counts"] == [2, 2, 1, 2]       # le=1, le=2, le=5, +Inf
    assert snap["count"] == 7
    assert snap["sum"] == pytest.approx(sum(
        (0.5, 1.0, 1.0000001, 2.0, 5.0, 5.0000001, 100.0)))


def test_histogram_edge_validation():
    with pytest.raises(ValueError, match="strictly increase"):
        obs.Histogram(edges=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError, match="at least one"):
        obs.Histogram(edges=())


def test_histogram_quantile_interpolation():
    h = obs.Histogram(edges=(1.0, 2.0, 4.0))
    for _ in range(10):
        h.observe(1.5)                           # all in the (1, 2] bucket
    # rank q*10 inside a uniform bucket: linear interpolation over (1, 2]
    assert h.quantile(0.5) == pytest.approx(1.5)
    assert h.quantile(1.0) == pytest.approx(2.0)
    assert h.quantile(0.0) == pytest.approx(1.0)
    h.observe(100.0)                             # +Inf bucket
    assert h.quantile(1.0) == 4.0                # clamps to largest edge
    assert math.isnan(obs.Histogram(edges=(1.0,)).quantile(0.5))
    assert math.isnan(obs.Histogram(edges=(1.0,)).mean())
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)


def test_labeled_family_reuses_children():
    reg = obs.Registry()
    fam = reg.counter("hits_total", "hits", labels=("kind",))
    a1 = fam.labels(kind="a")
    a2 = fam.labels(kind="a")
    b = fam.labels(kind="b")
    assert a1 is a2 and a1 is not b
    a1.inc(3)
    b.inc()
    # same name + same shape -> the same Family object back
    assert reg.counter("hits_total", labels=("kind",)) is fam
    with pytest.raises(ValueError, match="expected labels"):
        fam.labels(nope="x")
    snap = reg.snapshot()["hits_total"]
    assert snap["series"] == [{"labels": {"kind": "a"}, "value": 3.0},
                              {"labels": {"kind": "b"}, "value": 1.0}]


def test_registry_rejects_conflicting_reregistration():
    reg = obs.Registry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labels=("tier",))
    reg.histogram("h_seconds", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="bucket edges"):
        reg.histogram("h_seconds", buckets=(1.0, 3.0))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")
    with pytest.raises(ValueError, match="invalid label name"):
        reg.counter("ok_total", labels=("bad-label",))
    assert reg.get("x_total") is not None
    assert reg.get("never_registered") is None


# ---------------------------------------------------------------------------
# snapshot atomicity + exposition
# ---------------------------------------------------------------------------


def test_snapshot_consistent_under_concurrent_increment():
    """Histogram ``count`` must always equal the sum of its bucket counts
    in a snapshot, no matter how hard other threads are observing."""
    reg = obs.Registry()
    h = reg.histogram("h_seconds", buckets=(0.5, 1.5))
    c = reg.counter("c_total")
    stop = threading.Event()

    def mutate():
        while not stop.is_set():
            h.observe(1.0)
            c.inc()

    threads = [threading.Thread(target=mutate) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        deadline = time.perf_counter() + 0.5
        snaps = 0
        while time.perf_counter() < deadline:
            s = reg.snapshot()["h_seconds"]["series"][0]
            assert sum(s["counts"]) == s["count"]
            snaps += 1
        assert snaps > 10
    finally:
        stop.set()
        for t in threads:
            t.join()
    s = reg.snapshot()["h_seconds"]["series"][0]
    assert s["count"] > 0 and sum(s["counts"]) == s["count"]


def test_prometheus_text_golden():
    reg = obs.Registry()
    reg.gauge("depth", "queue depth").set(2.5)
    reg.counter("hits_total", "hits by kind",
                labels=("kind",)).labels(kind="a").inc()
    reg.counter("jobs_total", "jobs processed").inc(3)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert reg.render_prometheus() == (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 2.5\n"
        "# HELP hits_total hits by kind\n"
        "# TYPE hits_total counter\n"
        'hits_total{kind="a"} 1\n'
        "# HELP jobs_total jobs processed\n"
        "# TYPE jobs_total counter\n"
        "jobs_total 3\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 5.55\n"
        "lat_seconds_count 3\n")


def test_dump_json_roundtrip(tmp_path):
    reg = obs.Registry()
    reg.counter("n_total", "n").inc(7)
    path = str(tmp_path / "m.json")
    assert reg.dump_json(path) == path
    with open(path) as f:
        snap = json.load(f)
    assert snap["n_total"]["series"][0]["value"] == 7.0


def test_span_marks_and_durations():
    """Records written after the fact read back as columns, cut to the
    spans that start in ``[t0, t1)``, with their durations."""
    log = obs.SpanLog(capacity=16)
    q = log.record("tier.queue", 10.0, 10.5, request=7, batch=3)
    b = log.record("tier.batch", 10.5, 11.0, batch=3)
    log.record("tier.h2d", 10.6, 10.7, parent=b, batch=3)
    log.record("tier.queue", 12.0, 12.25, request=8, batch=4)
    cols = log.columns()
    assert list(cols["name"]) == ["tier.queue", "tier.batch", "tier.h2d",
                                  "tier.queue"]
    assert list(cols["span"][:2]) == [q, b] and q != b
    assert list(cols["request"]) == [7, -1, -1, 8]
    assert list(cols["batch"]) == [3, 3, 3, 4]
    assert list(cols["parent"]) == [-1, -1, b, -1]
    np.testing.assert_allclose(log.durations("tier.queue"), [0.5, 0.25])
    np.testing.assert_allclose(log.durations("tier.queue", 11.0), [0.25])
    np.testing.assert_allclose(log.durations("tier.queue", 9.0, 12.0),
                               [0.5])
    assert list(log.columns(10.5, 10.6)["name"]) == ["tier.batch"]
    assert log.durations("never.recorded").size == 0
    assert log.self_seconds("never.recorded").size == 0


def test_nested_spans_parent_ids_and_self_time():
    """``obs.span`` blocks nest by ``CURRENT_SPAN``: the inner span's
    parent is the outer one, and the outer span's self time is its
    duration less its direct children's (not its grandchildren's)."""
    t0 = time.perf_counter()
    token = obs.REQUEST_ID.set(41)
    try:
        with obs.span("test.outer", profile=False) as outer:
            time.sleep(0.01)
            with obs.span("test.child") as child:
                with obs.span("test.grandchild") as grand:
                    time.sleep(0.02)
            with obs.span("test.child") as child2:
                time.sleep(0.01)
    finally:
        obs.REQUEST_ID.reset(token)
    assert obs.CURRENT_SPAN.get() == -1
    log = obs.span_log()
    cols = log.columns(t0)
    by_id = {int(s): i for i, s in enumerate(cols["span"])}
    assert cols["parent"][by_id[child.span_id]] == outer.span_id
    assert cols["parent"][by_id[child2.span_id]] == outer.span_id
    assert cols["parent"][by_id[grand.span_id]] == child.span_id
    assert cols["parent"][by_id[outer.span_id]] == -1
    assert set(cols["request"][list(by_id.values())]) == {41}
    whole = outer.end - outer.start
    kids = (child.end - child.start) + (child2.end - child2.start)
    (own,) = log.self_seconds("test.outer", t0)
    assert own == pytest.approx(whole - kids)
    assert 0.009 <= own < whole - 0.03
    (leaf,) = log.self_seconds("test.grandchild", t0)
    assert leaf == pytest.approx(grand.end - grand.start)


def test_span_log_ring_bound_and_oldest_start():
    """A full ring overwrites its oldest records, counts them in
    ``dropped``, and no longer holds a window that began before the
    oldest start it kept."""
    log = obs.SpanLog(capacity=4)
    assert log.oldest_start is None and log.dropped == 0
    assert log.holds(0.0)
    for i in range(10):
        log.record("x", float(i), i + 0.5)
    assert log.dropped == 6
    assert log.oldest_start == 6.0
    np.testing.assert_allclose(log.columns()["start"], [6, 7, 8, 9])
    # the record that started at 5.0 was overwritten: a window from 5.0
    # on is no longer whole, one from 5.5 on is
    assert log.holds(5.5) and not log.holds(5.0)
    with pytest.raises(ValueError):
        obs.SpanLog(capacity=0)


def test_span_log_concurrent_writers_lose_nothing():
    """Threads record into one log at once (the event loop and the
    tier's executor do): every record lands once, under its own name,
    and a ring too small for them all counts exactly what it dropped."""
    n_threads, per = 16, 3000
    logs = (obs.SpanLog(capacity=1 << 17), obs.SpanLog(capacity=10000))

    def work(k):
        for i in range(per):
            for log in logs:
                log.record(f"w{k % 3}", float(i), i + 1.0, request=k,
                           batch=i)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    whole, small = logs
    cols = whole.columns()
    assert whole.dropped == 0 and len(cols["span"]) == n_threads * per
    assert len(set(cols["span"].tolist())) == n_threads * per
    for k in range(n_threads):
        mine = cols["request"] == k
        assert sorted(cols["batch"][mine].tolist()) == list(range(per))
        assert set(cols["name"][mine]) == {f"w{k % 3}"}
    assert small.dropped == n_threads * per - 10000
    assert len(small.columns()["span"]) == 10000


def test_span_opens_a_trace_annotation_of_its_name(monkeypatch):
    """``obs.span`` mirrors its block into the profiler (a
    ``jax.profiler.TraceAnnotation`` of the same name, open while the
    block runs); ``profile=False`` opens none."""
    import jax.profiler
    events = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with obs.span("test.annotated"):
        events.append(("body", None))
    with obs.span("test.log_only", profile=False):
        pass
    assert events == [("enter", "test.annotated"), ("body", None),
                      ("exit", "test.annotated")]


def test_span_log_dump_json(tmp_path):
    t0 = time.perf_counter()
    with obs.span("test.dumped", profile=False):
        pass
    path = obs.span_log().dump_json(str(tmp_path / "spans.json"))
    with open(path) as f:
        dump = json.load(f)
    assert dump["clock"] == "time.perf_counter"
    assert dump["capacity"] == obs.span_log().capacity
    cols = dump["columns"]
    i = cols["name"].index("test.dumped")
    assert cols["start"][i] >= t0 and cols["end"][i] >= cols["start"][i]


def test_summary_line_and_periodic_reporter():
    reg = obs.Registry()
    line = obs.summary_line(reg)
    assert line.startswith("[obs] requests=0")
    stream = io.StringIO()
    rep = obs.PeriodicReporter(interval_s=0.02, reg=reg, stream=stream)
    with rep:
        time.sleep(0.1)
    assert "[obs] requests=0" in stream.getvalue()
    after = stream.getvalue()
    time.sleep(0.05)
    assert stream.getvalue() == after, "reporter printed after stop()"
    # a non-positive interval never starts the thread
    off = obs.PeriodicReporter(interval_s=0, reg=reg, stream=stream)
    with off:
        assert off._thread is None


# ---------------------------------------------------------------------------
# stack wiring
# ---------------------------------------------------------------------------


def _tiny_net(seed=7):
    rng = np.random.default_rng(seed)
    layers = []
    for a, b in zip((10, 16), (16, 6)):
        idx = np.stack([np.sort(rng.choice(a, 2, replace=False))
                        for _ in range(b)]).astype(np.int32)
        tab = rng.integers(0, 4, (b, 2 ** 4), dtype=np.int32)
        layers.append((idx, tab, 2))
    return engine.compile_network(layers, optimize_level=2, in_features=10,
                                  block_b=4)


@pytest.fixture(scope="module")
def net():
    return _tiny_net()


def test_tier_feeds_stage_histograms_and_breakdown(net):
    async def main():
        async with serve.ServingTier(net) as tier:
            t0 = time.perf_counter()
            await asyncio.gather(*[
                tier.infer(np.zeros((2, net.n_in), np.int32))
                for _ in range(5)])
            return tier.latency_breakdown(), t0

    breakdown, t0 = asyncio.run(main())
    assert set(breakdown) == {"queue_wait", "assembly", "device", "total"}
    for stage, leg in breakdown.items():
        assert leg["count"] == 5, stage
        assert leg["mean_ms"] >= 0.0
        assert leg["p50_ms"] <= leg["p99_ms"]
    # the log kept one queue span per request, each joined by its batch
    # id to a batch span whose three device legs are its children and
    # lie inside it, after the queue wait
    cols = obs.span_log().columns(t0)
    name = cols["name"]
    queue = np.flatnonzero(name == "tier.queue")
    assert len(queue) == 5
    assert len(set(cols["request"][queue])) == 5
    for q in queue:
        (b,) = np.flatnonzero((name == "tier.batch")
                              & (cols["batch"] == cols["batch"][q]))
        assert cols["end"][q] == cols["start"][b]
        legs = np.flatnonzero(cols["parent"] == cols["span"][b])
        assert sorted(name[legs]) == ["tier.compute", "tier.d2h",
                                      "tier.h2d"]
        assert (cols["start"][legs] >= cols["start"][b]).all()
        assert (cols["end"][legs] <= cols["end"][b]).all()
    # breakdown is JSON-strict (no NaN): the --metrics-json contract
    json.dumps(breakdown, allow_nan=False)


def test_request_id_from_ingress_through_tier_to_batch(net):
    """One HTTP request's id is on its ``ingress.request``, its
    ``ingress.decode`` and ``ingress.infer`` children and its
    ``tier.queue`` span, whose batch id names the ``tier.batch`` that
    served it; the decode and infer spans lie inside the request's."""
    t0 = time.perf_counter()
    with serve.BackgroundIngress(net) as ing:
        for rows in (1, 3):
            asyncio.run(serve.http_infer(
                "127.0.0.1", ing.port, np.ones((rows, net.n_in), np.int32)))
    cols = obs.span_log().columns(t0)
    name = cols["name"]
    reqs = np.flatnonzero(name == "ingress.request")
    assert len(reqs) == 2
    for r in reqs:
        rid, sid = cols["request"][r], cols["span"][r]
        mine = cols["request"] == rid
        kids = np.flatnonzero(mine & (cols["parent"] == sid))
        assert sorted(name[kids]) == ["ingress.decode", "ingress.infer"]
        assert (cols["start"][kids] >= cols["start"][r]).all()
        assert (cols["end"][kids] <= cols["end"][r]).all()
        (infer,) = kids[name[kids] == "ingress.infer"]
        (q,) = np.flatnonzero(mine & (name == "tier.queue"))
        assert cols["parent"][q] == cols["span"][infer]
        (b,) = np.flatnonzero((name == "tier.batch")
                              & (cols["batch"] == cols["batch"][q]))
        assert cols["batch"][q] >= 0 and cols["request"][b] == -1


def test_tier_metrics_in_process_registry(net):
    before = _tier_series_count()
    serve.run_requests(net, [np.zeros((3, net.n_in), np.int32)])
    assert _tier_series_count() == before + 1
    snap = obs.registry().snapshot()
    for name in ("serve_requests_total", "serve_queue_wait_seconds",
                 "serve_assembly_seconds", "serve_device_seconds",
                 "serve_request_latency_seconds", "serve_flush_total",
                 "serve_retraces_after_warmup"):
        assert name in snap, name


def _tier_series_count() -> int:
    fam = obs.registry().get("serve_requests_total")
    return len(fam._series()) if fam is not None else 0


def test_loadgen_edge_counts(net):
    """0-, 1- and 2-request runs must produce a well-formed LoadReport
    (np.percentile raises on an empty sample without the guard)."""
    rep0 = serve.run_closed_loop(net, n_clients=1, n_per_client=0, bw=2)
    assert rep0.n_requests == 0 and rep0.rows == 0
    assert math.isnan(rep0.p50_ms) and math.isnan(rep0.mean_ms)
    assert rep0.qps == 0.0
    d = rep0.as_dict()
    assert d["n_requests"] == 0
    json.dumps(d["breakdown"], allow_nan=False)

    rep1 = serve.run_closed_loop(net, n_clients=1, n_per_client=1, bw=2)
    assert rep1.n_requests == 1
    assert rep1.p50_ms == pytest.approx(rep1.p99_ms)
    assert rep1.p50_ms > 0.0 and rep1.qps > 0.0
    assert rep1.breakdown["total"]["count"] == 1

    rep2 = serve.run_closed_loop(net, n_clients=2, n_per_client=1, bw=2)
    assert rep2.n_requests == 2
    assert rep2.p50_ms <= rep2.p90_ms <= rep2.p99_ms
    assert rep2.as_dict()["n_requests"] == 2


def test_engine_counters_record_compiles_and_memo():
    reg = obs.registry()

    def total(name):
        m = reg.get(name)
        if m is None:
            return 0.0
        if isinstance(m, obs.Family):
            return sum(c.value for _, c in m._series())
        return m.value

    runs0 = total("engine_compiler_runs_total")
    builds0 = total("engine_builds_total")
    slab0 = reg.get("engine_slab_build_seconds").count
    _tiny_net(seed=8)
    assert total("engine_compiler_runs_total") == runs0 + 1
    assert total("engine_builds_total") == builds0 + 1
    assert reg.get("engine_slab_build_seconds").count == slab0 + 1

    hits0, misses0 = total("engine_memo_hits_total"), total(
        "engine_memo_misses_total")
    rng = np.random.default_rng(3)
    idx = np.stack([np.sort(rng.choice(6, 2, replace=False))
                    for _ in range(4)]).astype(np.int32)
    tab = rng.integers(0, 4, (4, 2 ** 4), dtype=np.int32)
    triples = [(idx, tab, 2)]
    engine.cache_clear()
    from repro.kernels.ops import FUSED_VMEM_BUDGET_BYTES
    kwargs = dict(optimize_level=1, in_features=6, fused=True,
                  use_pallas=True, block_b=8,
                  vmem_budget_bytes=FUSED_VMEM_BUDGET_BYTES)
    engine.cached_compile(triples, **kwargs)
    engine.cached_compile(triples, **kwargs)
    assert total("engine_memo_misses_total") == misses0 + 1
    assert total("engine_memo_hits_total") == hits0 + 1


def test_compile_pass_timings_in_registry(net):
    # the module fixture compiled at level 2, so the pipeline has run at
    # least once in this process and its passes are in the registry
    snap = obs.registry().snapshot()
    runs = {tuple(s["labels"].values()): s["value"]
            for s in snap["compile_pass_runs_total"]["series"]}
    secs = {tuple(s["labels"].values()): s["value"]
            for s in snap["compile_pass_seconds_total"]["series"]}
    assert ("reachability",) in runs
    for key, n in runs.items():
        assert n >= 1
        assert secs[key] >= 0.0
    assert snap["compile_optimize_seconds"]["series"][0]["count"] >= 1
    assert any(s["value"] >= 1
               for s in snap["compile_optimize_runs_total"]["series"])


# ---------------------------------------------------------------------------
# hot-path overhead regression
# ---------------------------------------------------------------------------


def test_request_path_metric_overhead_is_bounded(net, monkeypatch):
    """Serving a request costs a bounded handful of metric ops: 2 counter
    incs at submit, 4 histogram observes at completion, ~3 counter incs
    amortized per batch.  A metrics/tracing change that adds per-request
    rendering, snapshotting or extra metric traffic trips this budget.
    The span log gets at most 4 records a request and 7 a batch."""
    ops = {"n": 0}
    records = []

    def counted(orig):
        def wrapper(self, *a, **kw):
            ops["n"] += 1
            return orig(self, *a, **kw)
        return wrapper

    monkeypatch.setattr(obs.Counter, "inc", counted(obs.Counter.inc))
    monkeypatch.setattr(obs.Gauge, "inc", counted(obs.Gauge.inc))
    monkeypatch.setattr(obs.Gauge, "set", counted(obs.Gauge.set))
    monkeypatch.setattr(obs.Histogram, "observe",
                        counted(obs.Histogram.observe))
    record = obs.SpanLog.record

    def recorded(self, name, *a, **kw):
        records.append(name)
        return record(self, name, *a, **kw)

    monkeypatch.setattr(obs.SpanLog, "record", recorded)

    n_requests = 24
    reqs = [np.full((2, net.n_in), i % 4, np.int32)
            for i in range(n_requests)]
    serve.run_requests(net, reqs)
    # 2 (submit) + 4 (observe) per request, <= 3 per batch (batches <=
    # requests), plus a constant few for lifecycle — 10/request is the
    # regression ceiling, ~2-3x the typical coalesced cost
    assert ops["n"] <= 10 * n_requests, (
        f"{ops['n']} metric ops for {n_requests} requests — the request "
        "path grew metric work beyond the counter-increment budget")
    # warm-up runs one bucket: a batch's worth of records more
    n_batches = records.count("tier.batch")
    assert 1 <= n_batches <= n_requests
    assert len(records) <= 4 * n_requests + 7 * (n_batches + 1), (
        f"{len(records)} span records for {n_requests} requests in "
        f"{n_batches} batches: {sorted(set(records))}")
