"""Open-loop HTTP load generator: the client side of a stream cell.

Runs in a process of its own, started by ``bench.stream``, and imports
NumPy and the standard library only: it never shares the server's
interpreter lock and never touches the chip.  It speaks the raw int8
``POST /v1/infer`` protocol of the system's HTTP ingress over keep-alive
connections, opening another connection whenever every open one is
busy, up to ``max_connections``; beyond that a request waits for a free
connection, and its latency, timed from its due time, counts the wait.
The bound lies far above what the offered rate needs, and keeps a burst
after a stall of the host within what the server admits.

Protocol with the parent, on standard input and output:

1. the parent writes one JSON line of parameters and then an ``.npz`` of
   ``payload_bytes`` bytes: due times (``at``), row counts (``rows``)
   and input codes (``codes``) of the warm-up's requests (``warm_``)
   and of the window's (``win_``);
2. the client opens its connections, sends the warm-up requests at
   their due times, and writes ``ready <answered> <sent>``;
3. the parent writes ``go``; the client sends every request of the
   window at its due time, waits for the replies, and writes one
   ``.npz`` with, per request, its due-to-reply latency, its lateness
   (actual send minus due), its HTTP status (0: no reply) and the
   output codes of the requests that were answered.
"""

from __future__ import annotations

import asyncio
import gc
import io
import json
import sys
import time

import numpy as np

REQUEST_HEAD = ("POST /v1/infer HTTP/1.1\r\nhost: {host}:{port}\r\n"
                "content-type: application/octet-stream\r\n"
                "content-length: {n}\r\n\r\n")


class Pool:
    """Keep-alive connections; a busy pool grows, up to ``limit``."""

    def __init__(self, host: str, port: int, limit: int):
        self.host, self.port = host, port
        self.idle: list = []
        self.slots = asyncio.Semaphore(limit)

    async def open(self, n: int) -> None:
        for _ in range(n):
            self.idle.append(await asyncio.open_connection(self.host,
                                                           self.port))

    async def post(self, body: bytes) -> tuple[int, bytes]:
        async with self.slots:
            return await self._post(body)

    async def _post(self, body: bytes) -> tuple[int, bytes]:
        conn = self.idle.pop() if self.idle else \
            await asyncio.open_connection(self.host, self.port)
        reader, writer = conn
        head = REQUEST_HEAD.format(host=self.host, port=self.port,
                                   n=len(body))
        writer.write(head.encode("latin-1") + body)
        status_line = await reader.readline()
        parts = status_line.split()
        if len(parts) < 2:
            raise ConnectionError(f"no HTTP status line: {status_line!r}")
        length, close = 0, False
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, value = line.decode("latin-1").partition(":")
            key = key.strip().lower()
            if key == "content-length":
                length = int(value.strip())
            elif key == "connection":
                close = value.strip().lower() == "close"
        payload = await reader.readexactly(length) if length else b""
        if close:
            writer.close()
        else:
            self.idle.append(conn)
        return int(parts[1]), payload

    def close(self) -> None:
        for _, writer in self.idle:
            writer.close()
        self.idle.clear()


async def offer(pool: Pool, at: np.ndarray, bodies: list, n_out: int,
                wait_s: float) -> dict:
    """Send request i at ``t0 + at[i]``; time it from then to its reply."""
    loop = asyncio.get_running_loop()
    n = len(at)
    lat = np.full(n, np.nan)
    late = np.zeros(n)
    status = np.zeros(n, np.int32)
    outs: list = [None] * n

    async def one(i: int, due: float) -> None:
        late[i] = loop.time() - due
        try:
            code, payload = await pool.post(bodies[i])
        except (ConnectionError, OSError, asyncio.IncompleteReadError):
            return
        status[i] = code
        if code == 200:
            lat[i] = loop.time() - due
            outs[i] = payload

    tasks = []
    t0 = loop.time() + 0.01
    for i in range(n):
        due = t0 + at[i]
        while True:
            ahead = due - loop.time()
            if ahead <= 0:
                break
            # the selector sleeps in whole milliseconds: sleep short of
            # the due time, then yield to the loop until it comes
            await asyncio.sleep(ahead - 0.0015 if ahead > 0.002 else 0)
        tasks.append(asyncio.ensure_future(one(i, due)))
    close_t = t0 + (at[-1] if n else 0.0)
    remaining = max(0.0, close_t + wait_s - loop.time())
    done, pending = await asyncio.wait(tasks, timeout=remaining)
    for t in pending:
        t.cancel()
    for t in done:
        t.result()
    ok = [o for o in outs if o is not None]
    out = (np.frombuffer(b"".join(ok), np.int8).reshape(-1, n_out)
           if ok else np.zeros((0, n_out), np.int8))
    return {"lat_s": lat, "late_s": late, "status": status, "out": out}


def bodies_of(rows: np.ndarray, codes: np.ndarray) -> list:
    ends = np.cumsum(rows)
    return [codes[e - r:e].tobytes() for r, e in zip(rows, ends)]


def main() -> None:
    p = json.loads(sys.stdin.buffer.readline())
    with np.load(io.BytesIO(sys.stdin.buffer.read(p["payload_bytes"]))) as z:
        req = {k: z[k] for k in z.files}
    asyncio.run(offer_all(p, req))


async def offer_all(p: dict, req: dict) -> None:
    loop = asyncio.get_running_loop()
    bodies = {w: bodies_of(req[f"{w}_rows"], req[f"{w}_codes"])
              for w in ("warm", "win")}
    pool = Pool(p["host"], p["port"], p["max_connections"])
    await pool.open(p["connections"])
    res = await offer(pool, req["warm_at"], bodies["warm"], p["n_out"],
                      p["wait_s"])
    sys.stdout.write(f"ready {int((res['status'] == 200).sum())} "
                     f"{len(bodies['warm'])}\n")
    sys.stdout.flush()
    go = await loop.run_in_executor(None, sys.stdin.buffer.readline)
    if go.strip() != b"go":
        raise SystemExit(f"expected 'go', got {go!r}")
    # a collection of the generator's own objects would stall its
    # schedule; the window's garbage is small enough to keep
    gc.collect()
    gc.freeze()
    gc.disable()
    t_start = time.perf_counter()
    res = await offer(pool, req["win_at"], bodies["win"], p["n_out"],
                      p["wait_s"])
    res["window_s"] = np.float64(time.perf_counter() - t_start)
    gc.enable()
    pool.close()
    buf = io.BytesIO()
    np.savez(buf, **res)
    sys.stdout.buffer.write(buf.getvalue())
    sys.stdout.flush()


if __name__ == "__main__":
    main()
