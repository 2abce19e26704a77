"""Open-loop HTTP at Poisson arrival times.

Independent clients post raw int8 codes to ``POST /v1/infer``, whether
or not earlier requests have finished (``bench/stream.py``).
Parameters: ``rate_rps``, ``rows_min``, ``rows_max``, and the tier's
``block_b``, ``flush_deadline_ms`` and ``max_queue_rows``.
"""

from __future__ import annotations

import numpy as np

from bench import stream, traffic


def schedule(mix: dict, seconds: float, seed: int, stream_id: int) -> dict:
    """Arrival offsets (s) and row counts of every request due in a
    window of ``seconds``.

    ``n = round(rate * seconds)`` requests, due in ``(0, seconds]``.  The
    gaps are the ``n`` quantiles of an exponential distribution with mean
    ``seconds / n``, so their sum is the window; row counts cycle evenly
    through ``rows_min..rows_max``.  The seed only permutes both.
    """
    n = max(1, int(round(mix["rate_rps"] * seconds)))
    rng = traffic.rng(seed, stream_id)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    sizes = np.arange(n) % (mix["rows_max"] - mix["rows_min"] + 1) \
        + mix["rows_min"]
    at = np.cumsum(rng.permutation(gaps))
    return {"at": at, "rows": rng.permutation(sizes).astype(np.int64)}


def inputs(cfg: dict, mix: dict, seed: int, seconds: float) -> list:
    return [stream.requests(schedule, mix, cfg, seconds, seed)["codes"]]


def run(ctx) -> dict:
    return stream.run(ctx, schedule)
