"""The least work a LUT network's rows need, from published shapes only.

Whatever implements the network, a row has to bring its input codes in
and take its output codes out, bit-packed, and look up one table entry
per served neuron.  The least time for ``rows`` rows on a chip is the
larger of those bytes over the chip's HBM bandwidth and those lookups
over its int8 peak (``bench/peaks.json``, keyed by ``device_kind``).
A measured time can only be longer, so a share of this bound cannot
pass 100%.
"""

from __future__ import annotations

import json
import math
import os

from bench.models.logicnet_ref import layer_shapes

HERE = os.path.dirname(os.path.abspath(__file__))


def per_row(cfg: dict) -> dict:
    """Bytes and table lookups one row of ``cfg``'s served stack needs."""
    layers = layer_shapes(cfg)
    first, last = layers[0], layers[-1]
    bytes_in = math.ceil(first["n_in"] * first["bw_in"] / 8)
    bytes_out = math.ceil(last["n_out"] * last["bw_out"] / 8)
    return {"bytes": bytes_in + bytes_out,
            "ops": sum(lay["n_out"] for lay in layers)}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def least_seconds(cfg: dict, rows: int, device_kind: str) -> float:
    """The least time ``rows`` rows can take on one chip of this kind."""
    w, p = per_row(cfg), peaks(device_kind)
    return max(rows * w["bytes"] / p["hbm_bytes_per_s"],
               rows * w["ops"] / p["int8_ops_per_s"])
