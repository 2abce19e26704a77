"""Weights and plain reference of a LogicNets sparse stack, in NumPy.

A LogicNet neuron j of a sparse layer reads ``fan_in`` input codes
``c_k`` of its layer's input quantizer, and computes

    v_k  = c_k * step_in                       (dequantize)
    pre  = sum_k v_k * w[j, k] + b[j]          (sparse linear)
    y    = (pre - mean[j]) * gamma[j] / sqrt(var[j] + 1e-5) + beta[j]
                                               (batch norm, eval mode)
    out  = round(clip(y, 0, max_val) / step_out)   (QuantReLU code)

with ``step = max_val / (2**bw - 1)``, as the thesis (Part II, the
SparseLinear module with its implicit input quantizer and batch norm)
defines it.  The served system evaluates this function through truth
tables; this module evaluates it directly, layer after layer, in a
stated floating-point type.  It imports nothing of the system under
test and takes nothing from it: the weights come from :func:`make_weights`
and the seed alone.
"""

from __future__ import annotations

import numpy as np

BN_EPS = 1e-5


def step(bw: int, max_val: float) -> float:
    return max_val / (2 ** bw - 1)


def layer_shapes(cfg: dict) -> list[dict]:
    """The sparse layers of ``cfg``: input width, neurons, fan-in and the
    code widths in and out.  The last sparse layer's output codes are the
    input quantizer of the dense final layer (``bw``), or the network's
    output quantizer (``bw_fc``) where the final layer is sparse too."""
    widths = [cfg["in_features"], *cfg["hidden"]]
    sparse = list(zip(widths[:-1], widths[1:]))
    fan_ins = [cfg["fan_in"]] * len(sparse)
    if not cfg["final_dense"]:
        sparse.append((widths[-1], cfg["n_classes"]))
        fan_ins.append(cfg.get("fan_in_fc") or cfg["fan_in"])
    out = []
    for i, ((n_in, n_out), fi) in enumerate(zip(sparse, fan_ins)):
        last = i == len(sparse) - 1
        bw_out = cfg["bw_fc"] if last and not cfg["final_dense"] \
            else cfg["bw"]
        out.append({"n_in": n_in, "n_out": n_out, "fan_in": min(fi, n_in),
                    "bw_in": cfg["bw"], "bw_out": bw_out})
    return out


def make_weights(cfg: dict, seed: int) -> list[dict]:
    """Seeded float32 weights of every sparse layer of ``cfg``.

    Each neuron draws ``fan_in`` distinct inputs uniformly (the a-priori
    random expander), weights from N(0, 1/fan_in), and batch-norm
    statistics near those of a freshly calibrated layer.  The same seed
    gives the same weights on every machine.
    """
    rng = np.random.default_rng(seed)
    layers = []
    for s in layer_shapes(cfg):
        n_in, n_out, fi = s["n_in"], s["n_out"], s["fan_in"]
        idx = np.sort(np.argsort(rng.random((n_out, n_in)), axis=1)[:, :fi],
                      axis=1).astype(np.int32)
        f32 = np.float32
        layers.append({
            **s,
            "idx": idx,
            "w": (rng.standard_normal((n_out, fi)) / np.sqrt(fi)).astype(f32),
            "b": (0.1 * rng.standard_normal(n_out)).astype(f32),
            "gamma": rng.uniform(0.8, 1.2, n_out).astype(f32),
            "beta": (0.2 * rng.standard_normal(n_out)).astype(f32),
            "mean": (0.3 * rng.standard_normal(n_out)).astype(f32),
            "var": rng.uniform(0.5, 1.5, n_out).astype(f32),
        })
    return layers


def layer_forward(layer: dict, codes: np.ndarray, max_val: float,
                  dtype=np.float32) -> np.ndarray:
    """(rows, n_in) input codes -> (rows, n_out) int32 output codes,
    with every operation of the neuron rounded to ``dtype``."""
    cast = lambda a: np.asarray(a, np.float32).astype(dtype)
    v = codes.astype(dtype) * cast(step(layer["bw_in"], max_val))
    x = v[:, layer["idx"]]                                   # (rows, O, fi)
    pre = x[:, :, 0] * cast(layer["w"][:, 0])
    for k in range(1, x.shape[2]):
        pre = pre + x[:, :, k] * cast(layer["w"][:, k])
    pre = pre + cast(layer["b"])
    scale = cast(layer["gamma"]) / np.sqrt(cast(layer["var"])
                                           + cast(BN_EPS)).astype(dtype)
    y = (pre - cast(layer["mean"])) * scale + cast(layer["beta"])
    y = np.clip(y, cast(0.0), cast(max_val))
    return np.round(y / cast(step(layer["bw_out"], max_val))) \
        .astype(np.int32)


def forward(weights: list[dict], codes: np.ndarray, max_val: float,
            dtype=np.float32, block_rows: int = 4096) -> np.ndarray:
    """The whole sparse stack on ``codes``, in blocks of rows."""
    outs = []
    for start in range(0, codes.shape[0], block_rows):
        h = np.asarray(codes[start:start + block_rows], np.int32)
        for layer in weights:
            h = layer_forward(layer, h, max_val, dtype)
        outs.append(h)
    if not outs:
        return np.zeros((0, weights[-1]["n_out"]), np.int32)
    return np.concatenate(outs)
