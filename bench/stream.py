"""Open-loop event stream through the HTTP ingress and the serving tier.

The server is the program's own ``BackgroundIngress`` (HTTP ingress in
front of the micro-batching ``ServingTier``) in this process, which
holds the chips.  The client is ``bench.stream_client`` in a process of
its own; it sends the requests that a kind's ``schedule`` lays out (see
``bench/kinds/``).  Every request due in the window is timed from its
due time to its reply; a request that fails or gets no reply enters
the percentiles as a miss and makes the run incorrect.  Every answered
request is compared with the reference.
"""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import time

import numpy as np

from bench import program, traffic
from bench.clock import T_START

WAIT_S = 60.0          # how long past the window's close a reply may come
# the client's connections, at most: far above the few that the offered
# rate keeps busy, and at 8 rows per request half the tier's 4096-row
# queue, so that the burst after a stall of the host is not refused
MAX_CONNECTIONS = 256
WINDOW_STREAMS = (1, 2)    # the seed's streams of the window's schedule, codes
WARMUP_STREAMS = (5, 6)    # and of the one second of warm-up requests

# the program's histograms the per-layer metrics read (sum and count)
SPANS = ("ingress_decode_seconds", "serve_queue_wait_seconds",
         "serve_device_seconds")


def requests(schedule, mix: dict, cfg: dict, seconds: float, seed: int,
             streams: tuple = WINDOW_STREAMS) -> dict:
    """Due times, row counts and input codes (concatenated in request
    order) of every request ``schedule`` lays out in ``seconds``."""
    sched = schedule(mix, seconds, seed, streams[0])
    sched["codes"] = traffic.codes(sched["rows"].sum(), cfg["in_features"],
                                   cfg["bw"], seed, streams[1])
    return sched


def _spans() -> dict:
    from repro import obs
    snap = obs.registry().snapshot()
    out = {}
    for name in SPANS:
        series = snap.get(name, {}).get("series", [])
        out[name] = (sum(s["sum"] for s in series),
                     sum(s["count"] for s in series))
    return out


def start_client(port: int, n_out: int, mix: dict, warm: dict,
                 win: dict) -> subprocess.Popen:
    """The load generator, handed the warm-up's and the window's
    requests; it opens enough keep-alive connections for the rate at
    20 ms per request, and more, up to ``MAX_CONNECTIONS``, whenever all
    of them are busy."""
    buf = io.BytesIO()
    np.savez(buf, **{f"warm_{k}": v for k, v in warm.items()},
             **{f"win_{k}": v for k, v in win.items()})
    payload = buf.getvalue()
    params = {"host": "127.0.0.1", "port": port, "n_out": n_out,
              "wait_s": WAIT_S, "payload_bytes": len(payload),
              "connections": max(8, int(mix["rate_rps"] * 0.02)),
              "max_connections": MAX_CONNECTIONS}
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "bench.stream_client"], cwd=program.ROOT,
        env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    proc.stdin.write((json.dumps(params) + "\n").encode() + payload)
    proc.stdin.flush()
    return proc


def finish_client(proc: subprocess.Popen) -> dict:
    data = proc.stdout.read()
    if proc.wait() != 0:
        raise RuntimeError(f"load generator exited with {proc.returncode}")
    with np.load(io.BytesIO(data)) as z:
        return {k: z[k] for k in z.files}


def serve(ctx, mix: dict):
    """The program's ingress and tier over the cell's chips, started."""
    import jax
    from repro import serve as rserve

    net = program.compile_net(ctx.cfg, ctx.tables, mix["block_b"])
    print(f"[bench] set-up: {net.layout} artifact compiled at "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    tier_cfg = rserve.TierConfig(
        flush_deadline_s=mix["flush_deadline_ms"] * 1e-3,
        max_queue_rows=mix["max_queue_rows"],
        devices=tuple(jax.devices()[:ctx.chips]))
    ing = rserve.BackgroundIngress(net, tier_cfg,
                                   rserve.IngressConfig(port=0)).start()
    print(f"[bench] set-up: ingress and tier warm at "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    return net, ing


def run(ctx, schedule) -> dict:
    """One run of an open-loop stream whose arrivals ``schedule`` lays
    out: ``schedule(mix, seconds, seed, stream) -> {"at", "rows"}``."""
    import jax

    mix, cfg, seed = ctx.mix, ctx.cfg, ctx.seed
    win = requests(schedule, mix, cfg, ctx.seconds, seed)
    warm = requests(schedule, mix, cfg, 1.0, seed, WARMUP_STREAMS)
    net, ing = serve(ctx, mix)
    try:
        proc = start_client(ing.port, net.n_out, mix, warm, win)
        try:
            ready = proc.stdout.readline().decode().split()
            if not ready or ready[0] != "ready":
                raise RuntimeError(f"load generator did not get ready: "
                                   f"{ready}")
            spans0 = _spans()
            with ctx.window():
                proc.stdin.write(b"go\n")
                proc.stdin.flush()
                res = finish_client(proc)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        spans1 = _spans()
        stats = ing.stats()
        ctx.layout = net.layout
        ctx.memory_peak(jax.devices()[:ctx.chips])
    finally:
        ing.stop()
    del net, ing
    print(f"[bench] warm-up: {ready[1]} of {ready[2]} requests ok; since start: "
          f"{stats['batches']} batches, mean {stats['mean_batch_rows']:.2f} "
          f"rows, flushes {stats['flush_causes']}", file=sys.stderr)
    status, counts = np.unique(res["status"], return_counts=True)
    print(f"[bench] window: replies by HTTP status (0: none) "
          f"{dict(zip(status.tolist(), counts.tolist()))}", file=sys.stderr)

    ok = res["status"] == 200
    lat = np.where(ok, res["lat_s"], np.inf) * 1e3
    return {
        "attempted": int(len(ok)), "failed": int((~ok).sum()),
        "record": {
            "lat_ms": lat, "late_ms": res["late_s"] * 1e3,
            "miss_ms": (ctx.seconds + WAIT_S) * 1e3,
            "spans": {k: (spans1[k][0] - spans0[k][0],
                          spans1[k][1] - spans0[k][1]) for k in SPANS},
        },
        "compare": [(win["codes"][np.repeat(ok, win["rows"])], res["out"])],
        "retraces": stats["retraces_after_warmup"],
        "compiler_runs": stats["compiler_runs_after_warmup"],
    }
