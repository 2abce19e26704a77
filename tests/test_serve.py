"""Micro-batching serving-tier contract: ``repro.serve.ServingTier``.

The tier is pure request plumbing over a ``CompiledLUTNet``, so the
contracts are:

* **coalescing correctness** — concurrent ragged requests, coalesced into
  shared batches, return outputs bit-exact with calling the artifact
  directly on each request's rows;
* **flush policy** — size flush under load, deadline flush under light
  load, drain flush at shutdown (empty-queue shutdown returns promptly);
* **backpressure / timeouts** — a full bounded queue rejects instead of
  queueing unboundedly; a request not launched within its timeout fails
  with ``RequestTimeout``;
* **compile-once steady state** — after ``start()``'s warmup a serving
  loop adds zero jit traces and zero compiler runs;
* **device sharding** — with a forced multi-device CPU the batch axis is
  sharded over all devices and stays bit-exact (subprocess: the device
  count is fixed at jax import time).
"""

import asyncio
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro import engine, serve

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def _random_stack(widths, fan_ins, bws, seed=0):
    rng = np.random.default_rng(seed)
    layers = []
    for (n_in, n_out), fi, bw in zip(zip(widths[:-1], widths[1:]),
                                     fan_ins, bws):
        fi = min(fi, n_in)
        idx = np.stack([np.sort(rng.choice(n_in, fi, replace=False))
                        for _ in range(n_out)]).astype(np.int32)
        tab = rng.integers(0, 2 ** bw, (n_out, 2 ** (fi * bw)),
                           dtype=np.int32)
        layers.append((idx, tab, bw))
    return layers


@pytest.fixture(scope="module")
def net():
    layers = _random_stack((12, 20, 16, 8), (3, 3, 3), (2, 2, 2), seed=13)
    return engine.compile_network(layers, optimize_level=3, in_features=12,
                                  block_b=8)


def _requests(net, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 4, (int(k), net.n_in), dtype=np.int32)
            for k in sizes]


def test_coalescing_bit_exact_and_zero_retrace(net):
    """Concurrent ragged requests coalesce into fewer batches, outputs are
    bit-exact vs direct ``net(codes)``, and steady state adds no traces."""
    sizes = np.random.default_rng(1).integers(1, 7, 60)
    reqs = _requests(net, sizes, seed=2)

    async def main():
        cfg = serve.TierConfig(max_batch_rows=16, flush_deadline_s=0.002)
        async with serve.ServingTier(net, cfg) as tier:
            outs = await asyncio.gather(*[tier.infer(r) for r in reqs])
            return outs, tier.stats()

    outs, stats = asyncio.run(main())
    for r, o in zip(reqs, outs):
        assert o.dtype == np.int32
        np.testing.assert_array_equal(o, np.asarray(net(r)))
    assert stats["batches"] < stats["requests"], "no coalescing happened"
    assert stats["retraces_after_warmup"] == 0
    assert stats["compiler_runs_after_warmup"] == 0
    assert stats["rows"] == int(sizes.sum())
    assert 0.0 < stats["batch_occupancy"] <= 1.0
    assert stats["flush_causes"]["size"] >= 1


def test_single_row_and_empty_and_validation(net):
    async def main():
        async with serve.ServingTier(net) as tier:
            row = np.zeros((net.n_in,), np.int32)
            single = await tier.infer(row)
            empty = await tier.infer(np.zeros((0, net.n_in), np.int32))
            with pytest.raises(ValueError, match="expected"):
                await tier.infer(np.zeros((2, net.n_in + 1), np.int32))
            return single, empty

    single, empty = asyncio.run(main())
    assert single.shape == (net.n_out,)
    np.testing.assert_array_equal(
        single, np.asarray(net(np.zeros((1, net.n_in), np.int32)))[0])
    assert empty.shape == (0, net.n_out) and empty.dtype == np.int32


def test_deadline_flush_under_light_load(net):
    """A partial batch (3 rows, max 64) must flush on the deadline, not
    wait for the size threshold."""
    req = _requests(net, [3], seed=3)[0]

    async def main():
        cfg = serve.TierConfig(max_batch_rows=64, flush_deadline_s=0.05)
        async with serve.ServingTier(net, cfg) as tier:
            t0 = time.perf_counter()
            out = await tier.infer(req)
            dt = time.perf_counter() - t0
            return out, dt, tier.stats()

    out, dt, stats = asyncio.run(main())
    np.testing.assert_array_equal(out, np.asarray(net(req)))
    assert dt >= 0.04, "flushed before the deadline window"
    assert stats["flush_causes"]["deadline"] == 1
    assert stats["flush_causes"]["size"] == 0


def _slow_net(net, delay_s):
    """Wrap the artifact so every batch takes at least ``delay_s``."""

    class Slow:
        n_in, n_out, block_b = net.n_in, net.n_out, net.block_b

        def __call__(self, codes):
            time.sleep(delay_s)
            return net(codes)

        def jit_cache_size(self, host=True):
            return net.jit_cache_size(host)

    return Slow()


def test_backpressure_rejects_when_queue_full(net):
    """With the batcher stuck in a slow batch, the bounded queue must
    reject the overflowing request immediately."""
    slow = _slow_net(net, 0.2)

    async def main():
        cfg = serve.TierConfig(max_batch_rows=4, flush_deadline_s=0.0,
                               max_queue_rows=8, warmup=False)
        async with serve.ServingTier(slow, cfg) as tier:
            first = asyncio.ensure_future(
                tier.infer(np.zeros((4, net.n_in), np.int32)))
            await asyncio.sleep(0.05)       # batcher now inside the batch
            q1 = asyncio.ensure_future(
                tier.infer(np.zeros((8, net.n_in), np.int32)))
            await asyncio.sleep(0)
            with pytest.raises(serve.TierOverloaded):
                await tier.infer(np.zeros((1, net.n_in), np.int32))
            stats_mid = tier.stats()
            out0, out1 = await first, await q1
            return out0, out1, stats_mid, tier.stats()

    out0, out1, stats_mid, stats = asyncio.run(main())
    assert stats_mid["rejected"] == 1
    assert out0.shape == (4, net.n_out) and out1.shape == (8, net.n_out)
    assert stats["queued_rows"] == 0


def test_request_timeout_before_launch(net):
    """A request stuck behind a long-running batch past its timeout fails
    with RequestTimeout; one already inside a batch still resolves."""
    slow = _slow_net(net, 0.25)

    async def main():
        cfg = serve.TierConfig(max_batch_rows=2, flush_deadline_s=0.0,
                               request_timeout_s=0.1, warmup=False)
        async with serve.ServingTier(slow, cfg) as tier:
            first = asyncio.ensure_future(
                tier.infer(np.zeros((2, net.n_in), np.int32)))
            await asyncio.sleep(0.05)       # first batch is computing
            with pytest.raises(serve.RequestTimeout):
                await tier.infer(np.zeros((1, net.n_in), np.int32))
            out0 = await first
            return out0, tier.stats()

    out0, stats = asyncio.run(main())
    assert out0.shape == (2, net.n_out)
    assert stats["timed_out"] == 1


def test_empty_queue_shutdown_is_prompt(net):
    """stop() on an idle tier returns quickly and later submits raise."""

    async def main():
        tier = serve.ServingTier(net, serve.TierConfig(warmup=False))
        await tier.start()
        t0 = time.perf_counter()
        await tier.stop()
        dt = time.perf_counter() - t0
        with pytest.raises(serve.TierClosed):
            await tier.infer(np.zeros((1, net.n_in), np.int32))
        return dt

    assert asyncio.run(main()) < 1.0


def test_drain_flush_on_shutdown(net):
    """Requests still queued when stop() is called are served (drain
    flush), not dropped."""
    req = _requests(net, [5], seed=4)[0]

    async def main():
        cfg = serve.TierConfig(max_batch_rows=64, flush_deadline_s=5.0)
        tier = await serve.ServingTier(net, cfg).start()
        fut = asyncio.ensure_future(tier.infer(req))
        await asyncio.sleep(0.02)           # queued, deadline far away
        await tier.stop()
        out = await fut
        return out, tier.stats()

    out, stats = asyncio.run(main())
    np.testing.assert_array_equal(out, np.asarray(net(req)))
    assert stats["flush_causes"]["drain"] == 1


def test_double_start_rejected_and_serve_once_helper(net):
    reqs = _requests(net, [2, 3, 1], seed=5)
    outs = serve.run_requests(net, reqs)
    for r, o in zip(reqs, outs):
        np.testing.assert_array_equal(o, np.asarray(net(r)))

    async def main():
        tier = await serve.ServingTier(net).start()
        with pytest.raises(serve.TierError, match="already started"):
            await tier.start()
        await tier.stop()

    asyncio.run(main())


def test_oversized_request_forms_its_own_batch(net):
    """A request larger than max_batch_rows is served whole (its own
    batch) rather than split or rejected."""
    req = _requests(net, [20], seed=6)[0]

    async def main():
        cfg = serve.TierConfig(max_batch_rows=8, flush_deadline_s=0.001)
        async with serve.ServingTier(net, cfg) as tier:
            out = await tier.infer(req)
            return out, tier.stats()

    out, stats = asyncio.run(main())
    np.testing.assert_array_equal(out, np.asarray(net(req)))
    assert stats["batches"] == 1 and stats["rows"] == 20


@pytest.mark.parametrize("n_dev", [4])
def test_multi_device_sharded_serving(n_dev):
    """Data-parallel fan-out over a forced multi-device CPU: the batch
    axis is sharded with jax.sharding, outputs stay bit-exact and the
    steady state stays re-trace free.  Runs in a subprocess because the
    CPU device count is fixed at jax import time."""
    prog = textwrap.dedent(f"""
        import asyncio, numpy as np, jax
        from repro import engine, serve

        assert len(jax.devices()) == {n_dev}
        rng = np.random.default_rng(0)
        layers = []
        for a, b in zip((12, 20, 16), (20, 16, 8)):
            idx = np.stack([np.sort(rng.choice(a, 3, replace=False))
                            for _ in range(b)]).astype(np.int32)
            tab = rng.integers(0, 4, (b, 2 ** 6), dtype=np.int32)
            layers.append((idx, tab, 2))
        net = engine.compile_network(layers, optimize_level=3,
                                     in_features=12, block_b=8)
        reqs = [rng.integers(0, 4, (int(k), 12), dtype=np.int32)
                for k in rng.integers(1, 7, 30)]

        async def main():
            cfg = serve.TierConfig(max_batch_rows=32,
                                   flush_deadline_s=0.002)
            async with serve.ServingTier(net, cfg) as tier:
                st0 = tier.stats()
                assert st0["n_devices"] == {n_dev} and st0["sharded"]
                assert st0["bucket_unit"] % {n_dev} == 0
                outs = await asyncio.gather(*[tier.infer(r) for r in reqs])
                return outs, tier.stats()

        outs, stats = asyncio.run(main())
        for r, o in zip(reqs, outs):
            np.testing.assert_array_equal(o, np.asarray(net(r)))
        assert stats["retraces_after_warmup"] == 0
        assert stats["compiler_runs_after_warmup"] == 0
        assert stats["batches"] < stats["requests"]
        print("SHARDED_OK", stats["batches"])
    """)
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count={n_dev}"),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", prog], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED_OK" in proc.stdout
