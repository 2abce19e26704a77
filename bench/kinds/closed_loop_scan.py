"""Closed-loop scan: one caller sends its next batch as soon as the
previous one has come back (``bench/scan.py``).  Parameters:
``rows_per_call``, ``distinct_batches`` and the artifact's ``block_b``.
"""

from __future__ import annotations

import numpy as np

from bench import scan, traffic


def batches(mix: dict, n_in: int, bw: int, seed: int) -> list:
    """The pre-made batches a scan cycles through, int32 codes."""
    rng = traffic.rng(seed, 3)
    return [rng.integers(0, 2 ** bw, (mix["rows_per_call"], n_in),
                         dtype=np.int32)
            for _ in range(mix["distinct_batches"])]


def inputs(cfg: dict, mix: dict, seed: int, seconds: float) -> list:
    return batches(mix, cfg["in_features"], cfg["bw"], seed)


def run(ctx) -> dict:
    return scan.run(ctx, inputs(ctx.cfg, ctx.mix, ctx.seed, ctx.seconds))
