"""HTTP ingress contract: ``repro.serve.HttpIngress`` + open-loop load.

The ingress is pure plumbing between a socket and ``ServingTier.infer``,
so the contracts mirror the tier's plus the wire-level ones:

* **transport correctness** — JSON and raw-int8 responses through a real
  localhost socket are bit-exact with calling the artifact directly, and
  steady state adds zero traces and zero compiler runs;
* **typed error mapping** — 400/404/405/429/503 each carry the JSON
  ``{"error", "detail"}`` body docs/ingress.md tables, and the client
  (``serve.http_infer``) raises the matching typed exception;
* **per-tenant quota** — deterministic token-bucket math with an
  injected clock, and over-quota 429s accounted identically by the
  ``LoadReport`` outcomes and the ``ingress_rejected_total`` metric;
* **open-loop generator** — seeded Poisson schedule is reproducible;
  under capacity every request completes, past capacity the bounded
  queue sheds with 503s instead of queueing unboundedly;
* **CLI end to end** (subprocess) — ``serve --lut --http 0 --smoke``
  verifies bit-exact over HTTP and exits zero; the serve-forever mode
  drains on SIGTERM and still dumps its ``--metrics-json`` snapshot.
"""

import asyncio
import http.client
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro import engine, obs, serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


@pytest.fixture(scope="module")
def net():
    """Tiny compiled artifact (no compiler pass: cheap, still jitted)."""
    rng = np.random.default_rng(7)
    idx = np.stack([np.sort(rng.choice(12, 3, replace=False))
                    for _ in range(8)]).astype(np.int32)
    tbl = rng.integers(0, 4, (8, 2 ** 6), dtype=np.int32)
    return engine.compile_network([(idx, tbl, 2)], in_features=12,
                                  block_b=8)


def _codes(net, rows, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 4, (rows, net.n_in), dtype=np.int32)


def _counter(snap, name, **labels):
    for s in snap.get(name, {}).get("series", []):
        if s["labels"] == labels:
            return s["value"]
    return 0.0


def _request(port, method, path, body=None, headers=None):
    """One blocking HTTP request against the background ingress."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# deterministic building blocks
# ---------------------------------------------------------------------------

def test_token_bucket_injected_clock():
    """Quota math is exact under an injected monotonic clock."""
    b = serve.TokenBucket(rate=10.0, burst=5.0, now=0.0)
    assert b.try_take(5, now=0.0)            # full burst available
    assert not b.try_take(1, now=0.0)        # empty
    assert not b.try_take(2, now=0.1)        # refilled only 1 token
    assert b.try_take(1, now=0.1)
    assert b.try_take(5, now=100.0)          # refill caps at burst ...
    assert b.tokens == 0.0                   # ... not 1000 tokens
    assert not b.try_take(1, now=99.0)       # clock never runs backwards
    with pytest.raises(ValueError, match="positive"):
        serve.TokenBucket(rate=0.0, burst=5.0)


def test_quota_config_burst_defaults_to_rate():
    assert serve.QuotaConfig(rate_rows_per_s=250.0).burst == 250.0
    assert serve.QuotaConfig(rate_rows_per_s=250.0, burst_rows=7.0).burst \
        == 7.0


def test_poisson_arrivals_seeded_schedule():
    a = serve.poisson_arrivals(200.0, 500, seed=3)
    b = serve.poisson_arrivals(200.0, 500, seed=3)
    np.testing.assert_array_equal(a, b)      # reproducible
    assert a.shape == (500,)
    assert np.all(np.diff(a) >= 0)           # cumulative times
    # mean inter-arrival ~ 1/rate (loose: 500 samples)
    assert 0.5 / 200.0 < float(a[-1] / 500) < 2.0 / 200.0
    assert not np.array_equal(a, serve.poisson_arrivals(200.0, 500, seed=4))
    with pytest.raises(ValueError, match="positive"):
        serve.poisson_arrivals(0.0, 4)


# ---------------------------------------------------------------------------
# HTTP transport: bit-exact + typed errors over a real socket
# ---------------------------------------------------------------------------

def test_http_json_and_raw_bit_exact(net):
    with serve.BackgroundIngress(net) as ing:
        codes = _codes(net, 5, seed=1)
        want = np.asarray(net(codes))
        raw = asyncio.run(serve.http_infer("127.0.0.1", ing.port, codes))
        as_json = asyncio.run(serve.http_infer("127.0.0.1", ing.port,
                                               codes, raw=False))
        np.testing.assert_array_equal(raw, want)
        np.testing.assert_array_equal(as_json, want)
        # one flat row is promoted to (1, n_in)
        status, _, body = _request(
            ing.port, "POST", "/v1/infer",
            body=json.dumps({"codes": codes[0].tolist()}),
            headers={"content-type": "application/json"})
        assert status == 200
        np.testing.assert_array_equal(
            np.asarray(json.loads(body)["outputs"]), want[:1])
        stats = ing.stats()
    assert stats["retraces_after_warmup"] == 0
    assert stats["compiler_runs_after_warmup"] == 0


def test_http_error_mappings(net):
    with serve.BackgroundIngress(net) as ing:
        port = ing.port
        for method, path, body, hdrs, status, err in [
            ("GET", "/nope", None, {}, 404, "not_found"),
            ("GET", "/v1/infer", None, {}, 405, "method_not_allowed"),
            ("POST", "/healthz", None, {}, 405, "method_not_allowed"),
            ("POST", "/v1/infer", b"{not json",
             {"content-type": "application/json"}, 400, "bad_request"),
            ("POST", "/v1/infer", json.dumps({"codes": [[1, 2, 3]]}),
             {"content-type": "application/json"}, 400, "bad_request"),
            ("POST", "/v1/infer", b"\x01" * (net.n_in + 1),
             {"content-type": "application/octet-stream"}, 400,
             "bad_request"),
        ]:
            got, _, body_out = _request(port, method, path, body, hdrs)
            assert got == status, (method, path, body_out)
            assert json.loads(body_out)["error"] == err

        status, _, body = _request(port, "GET", "/healthz")
        health = json.loads(body)
        assert status == 200 and health["status"] == "ok"
        assert health["retraces_after_warmup"] == 0
        assert health["compiler_runs_after_warmup"] == 0

        status, headers, body = _request(port, "GET", "/metrics")
        assert status == 200
        assert headers["content-type"].startswith("text/plain")
        text = body.decode()
        assert "# TYPE ingress_requests_total counter" in text
        assert 'ingress_requests_total{route="/healthz",status="200"}' \
            in text


# ---------------------------------------------------------------------------
# per-tenant quota: 429 accounting matches the LoadReport exactly
# ---------------------------------------------------------------------------

def test_quota_rejections_match_load_report(net):
    """6 burst tokens at 2 rows/request admit exactly 3 requests; every
    other request 429s, and the ``ingress_rejected_total{reason=quota}``
    delta equals the LoadReport's ``rejected_quota`` outcome."""
    cfg = serve.IngressConfig(
        quota=serve.QuotaConfig(rate_rows_per_s=0.5, burst_rows=6.0))
    before = obs.registry().snapshot()
    with serve.BackgroundIngress(net, config=cfg) as ing:
        rep = serve.run_open_loop(
            url=ing.url, offered_rps=500.0, n_requests=10,
            rows_min=2, rows_max=2, seed=11, tenant="alice",
            verify_net=net)
    after = obs.registry().snapshot()

    assert rep.outcomes["ok"] == 3                     # 6 tokens / 2 rows
    assert rep.outcomes["rejected_quota"] == 7
    assert rep.rejected == 7 and rep.timed_out == 0
    assert rep.rejection_rate == pytest.approx(0.7)
    assert sum(rep.outcomes.values()) == rep.n_requests == 10
    delta = (_counter(after, "ingress_rejected_total", reason="quota")
             - _counter(before, "ingress_rejected_total", reason="quota"))
    assert delta == rep.outcomes["rejected_quota"]


def test_quota_isolates_tenants(net):
    """One tenant exhausting its bucket must not affect another's."""
    cfg = serve.IngressConfig(
        quota=serve.QuotaConfig(rate_rows_per_s=0.5, burst_rows=4.0))

    async def main(port):
        codes = _codes(net, 4, seed=2)
        await serve.http_infer("127.0.0.1", port, codes, tenant="noisy")
        with pytest.raises(serve.QuotaExceeded):
            await serve.http_infer("127.0.0.1", port, codes, tenant="noisy")
        return await serve.http_infer("127.0.0.1", port, codes,
                                      tenant="quiet")

    with serve.BackgroundIngress(net, config=cfg) as ing:
        out = asyncio.run(main(ing.port))
    np.testing.assert_array_equal(out, np.asarray(net(_codes(net, 4,
                                                             seed=2))))


# ---------------------------------------------------------------------------
# open-loop generator: determinism under capacity, shedding past it
# ---------------------------------------------------------------------------

def test_open_loop_in_process_all_ok_and_deterministic(net):
    kw = dict(offered_rps=300.0, n_requests=12, rows_max=4, seed=5)
    a = serve.run_open_loop(net, **kw)       # check_outputs verifies
    b = serve.run_open_loop(net, **kw)       # bit-exact vs net(codes)
    assert a.outcomes == b.outcomes == {"ok": 12}
    assert a.rejection_rate == 0.0
    assert a.n_clients == 0                  # the open-loop marker
    assert a.rows == b.rows                  # same seeded request sizes
    assert a.stats["retraces_after_warmup"] == 0
    assert a.stats["compiler_runs_after_warmup"] == 0


class _SlowNet:
    """Fixed per-batch cost so overload is deterministic in tests."""

    def __init__(self, inner, delay_s=0.02):
        self._inner, self._delay = inner, delay_s
        self.n_in, self.n_out = inner.n_in, inner.n_out
        self.block_b = inner.block_b

    def jit_cache_size(self, host=True):
        return self._inner.jit_cache_size(host)

    def __call__(self, codes):
        time.sleep(self._delay)
        return self._inner(codes)


def test_open_loop_overload_sheds_not_queues(net):
    """Past capacity the bounded queue must reject (503), keep some
    goodput, and keep the outcome accounting consistent."""
    cfg = serve.TierConfig(max_batch_rows=8, flush_deadline_s=0.002,
                           max_queue_rows=8)
    rep = serve.run_open_loop(_SlowNet(net), config=cfg,
                              offered_rps=1000.0, n_requests=30,
                              rows_min=2, rows_max=4, seed=0,
                              check_outputs=False)
    assert rep.outcomes["ok"] >= 1
    assert rep.outcomes.get("rejected_overload", 0) > 0
    assert rep.rejected == (rep.outcomes.get("rejected_overload", 0)
                            + rep.outcomes.get("rejected_quota", 0)
                            + rep.outcomes.get("closed", 0))
    assert rep.goodput_rps < rep.offered_rps
    assert rep.rejection_rate == pytest.approx(
        1.0 - rep.outcomes["ok"] / rep.n_requests)


def test_open_loop_url_mode_needs_sizing():
    with pytest.raises(ValueError, match="exactly one"):
        serve.run_open_loop()
    with pytest.raises(ValueError, match="verify_net= or n_in="):
        serve.run_open_loop(url="http://127.0.0.1:1")


# ---------------------------------------------------------------------------
# CLI end to end (subprocess): --http --smoke, and SIGTERM drain
# ---------------------------------------------------------------------------

def _subprocess_env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=SRC + os.pathsep
                + os.environ.get("PYTHONPATH", ""))


@pytest.fixture(scope="module")
def artifact(net, tmp_path_factory):
    """The tiny artifact saved to disk so subprocesses skip model A."""
    path = str(tmp_path_factory.mktemp("ingress") / "tiny.npz")
    net.save(path)
    return path


def test_cli_http_smoke_end_to_end(net, artifact, tmp_path):
    """``serve --lut --http 0 --smoke``: open-loop load through a live
    localhost ingress, every response verified bit-exact, compile-once
    counters zero, LoadReport, metrics snapshot and span log dumped."""
    report = str(tmp_path / "r.json")
    metrics = str(tmp_path / "m.json")
    spans = str(tmp_path / "s.json")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--lut",
         "--artifact", artifact, "--http", "0", "--smoke",
         "--report-every-s", "0", "--report-json", report,
         "--metrics-json", metrics, "--spans-json", spans],
        env=_subprocess_env(), capture_output=True, text=True,
        timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "http ingress listening on http://127.0.0.1:" in proc.stdout
    assert "responses verified bit-exact over HTTP" in proc.stdout
    assert "retraces=0" in proc.stdout
    assert "compiler_runs=0" in proc.stdout
    with open(report) as fh:
        rep = json.load(fh)
    assert rep["n_clients"] == 0                       # open loop
    assert sum(rep["outcomes"].values()) == rep["n_requests"] == 16
    with open(metrics) as fh:
        snap = json.load(fh)
    assert any(s["labels"].get("route") == "/v1/infer"
               for s in snap["ingress_requests_total"]["series"])
    assert all(s["count"] > 0
               for s in snap["ingress_infer_seconds"]["series"])
    with open(spans) as fh:
        names = json.load(fh)["columns"]["name"]
    assert names.count("ingress.request") >= 16
    assert names.count("tier.queue") >= 16


def test_cli_http_sigterm_drains_and_dumps_metrics(net, artifact, tmp_path):
    """Serve-forever mode: answer requests, then SIGTERM -> graceful
    drain, exit 0, and the ``--metrics-json`` snapshot still lands."""
    metrics = str(tmp_path / "m.json")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--lut",
         "--artifact", artifact, "--http", "0",
         "--report-every-s", "0", "--metrics-json", metrics],
        env=_subprocess_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, cwd=REPO)
    try:
        port, head = None, []
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            head.append(line)
            if "listening on http://127.0.0.1:" in line:
                port = int(line.split("http://127.0.0.1:")[1].split()[0])
                break
        assert port is not None, "".join(head) + proc.stderr.read()

        codes = _codes(net, 3, seed=9)
        out = asyncio.run(serve.http_infer("127.0.0.1", port, codes))
        np.testing.assert_array_equal(out, np.asarray(net(codes)))

        proc.send_signal(signal.SIGTERM)
        stdout, stderr = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:                        # pragma: no cover
            proc.kill()
            proc.communicate()
    full = "".join(head) + stdout
    assert proc.returncode == 0, full + stderr[-2000:]
    assert "draining" in full
    assert f"metrics snapshot -> {metrics}" in full
    with open(metrics) as fh:
        snap = json.load(fh)
    assert any(s["labels"].get("route") == "/v1/infer"
               and s["labels"].get("status") == "200"
               for s in snap["ingress_requests_total"]["series"])
    for name in ("serve_retraces_after_warmup",
                 "serve_compiler_runs_after_warmup"):
        assert all(s["value"] == 0 for s in snap[name]["series"]), name
