"""Closed-loop scan: one caller streams pre-made batches through
``CompiledLUTNet.__call__`` back to back (a kind hands them over, see
``bench/kinds/``).

Host-to-device and device-to-host transfers are inside the window,
since a scan's users pay them.  A sample of the window's calls, drawn
from the seed, is kept for the comparison with the reference.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import program
from bench.clock import T_START

SAMPLED_CALLS = 4


def run(ctx, batches: list) -> dict:
    """One run over ``batches``, cycled for the window's length."""
    import jax
    from repro import engine

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    net = program.compile_net(cfg, ctx.tables, mix["block_b"])
    print(f"[bench] set-up: {net.layout} artifact compiled at "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    for b in batches:                       # compile and warm every batch
        np.asarray(net(b))
    traces0, runs0 = net.jit_cache_size(), engine.compile_runs()
    print(f"[bench] set-up: every batch served once at "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    rng = np.random.default_rng([seed, 7])
    kept: list = []
    calls = 0
    with ctx.window() as win:
        while True:
            out = np.asarray(net(batches[calls % len(batches)]))
            # reservoir sample of the calls, drawn from the seed
            j = int(rng.integers(0, calls + 1))
            if len(kept) < SAMPLED_CALLS:
                kept.append((calls, out))
            elif j < SAMPLED_CALLS:
                kept[j] = (calls, out)
            calls += 1
            if time.perf_counter() - win.t0 >= ctx.seconds:
                break
    rows = calls * mix["rows_per_call"]
    ctx.layout = net.layout
    ctx.memory_peak(jax.devices()[:ctx.chips])
    retraces = net.jit_cache_size() - traces0
    compiler_runs = engine.compile_runs() - runs0
    del net
    kept.sort(key=lambda k: k[0])
    return {
        "attempted": calls, "failed": 0,
        "record": {"rows": rows, "calls": calls},
        "compare": [(batches[i % len(batches)], out) for i, out in kept],
        "retraces": retraces, "compiler_runs": compiler_runs,
    }
