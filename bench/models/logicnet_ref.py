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

Skip connections (thesis §7).  A configuration may hold ``skips``, a
list of ``[s, d]`` pairs in the program's convention.  ``s`` names an
activation: 0 is the network's input codes, ``k >= 1`` the output codes
of sparse layer ``k - 1``.  ``d`` is the sparse layer whose input it
joins.  The input bus of layer ``d`` is the previous layer's output (the
network's input for ``d = 0``), followed by each ``s`` paired with that
``d``, in the order the list gives.  A skip carries its source's codes
unchanged: every sparse layer's input quantizer is ``(bw, max_val)``,
the quantizer those codes were made in.  A skip's width is its
activation's own, ``in_features`` for ``s = 0`` and ``hidden[s - 1]``
otherwise.

A configuration names only what this module reads (:data:`READ_KEYS`)
or describes (:data:`DESCRIPTIVE_KEYS`); :func:`make_weights` refuses
any other key, so that no mechanism a configuration names is dropped in
silence.
"""

from __future__ import annotations

import numpy as np

BN_EPS = 1e-5

READ_KEYS = ("in_features", "n_classes", "hidden", "fan_in", "bw",
             "final_dense", "fan_in_fc", "bw_fc", "max_val", "skips",
             "optimize_level", "model_seed", "limits")
DESCRIPTIVE_KEYS = ("name", "architecture", "source", "served", "assumed",
                    "precision")


def step(bw: int, max_val: float) -> float:
    return max_val / (2 ** bw - 1)


def check_config(cfg: dict) -> None:
    """Refuse a configuration this module would not run as it states:
    another architecture, another precision than float32, or a key it
    neither reads nor counts as description."""
    if cfg.get("architecture") != "logicnet":
        raise ValueError(f"architecture {cfg.get('architecture')!r}: the "
                         f"reference runs 'logicnet' only")
    if cfg.get("precision") != "float32":
        raise ValueError(f"precision {cfg.get('precision')!r}: the "
                         f"reference computes in 'float32' only")
    unknown = sorted(set(cfg) - set(READ_KEYS) - set(DESCRIPTIVE_KEYS))
    if unknown:
        raise ValueError(f"configuration keys {unknown} are not read by "
                         f"the benchmark")


def layer_shapes(cfg: dict) -> list[dict]:
    """The sparse layers of ``cfg``: input bus width, neurons, fan-in, the
    code widths in and out, and the activations the bus is built from
    (``sources``; see the module docstring).  The last sparse layer's
    output codes are the input quantizer of the dense final layer
    (``bw``), or the network's output quantizer (``bw_fc``) where the
    final layer is sparse too."""
    n_outs = list(cfg["hidden"])
    fan_ins = [cfg["fan_in"]] * len(n_outs)
    if not cfg["final_dense"]:
        n_outs.append(cfg["n_classes"])
        fan_ins.append(cfg.get("fan_in_fc") or cfg["fan_in"])
    widths = [cfg["in_features"], *n_outs]        # of activation 0, 1, ...
    sources = [[d] for d in range(len(n_outs))]
    for pair in cfg.get("skips", ()):
        s, d = pair
        if not 0 <= s < d:
            raise ValueError(f"skip {pair}: its activation must come before "
                             f"the layer's own input (0 <= s < d)")
        if d >= len(n_outs):
            raise ValueError(f"skip {pair}: layer {d} is not in the served "
                             f"sparse stack of {len(n_outs)} layers")
        if s in sources[d][1:]:
            raise ValueError(f"skip {pair} is listed twice")
        sources[d].append(s)
    out = []
    for d, (n_out, fi) in enumerate(zip(n_outs, fan_ins)):
        n_in = sum(widths[s] for s in sources[d])
        last = d == len(n_outs) - 1
        bw_out = cfg["bw_fc"] if last and not cfg["final_dense"] \
            else cfg["bw"]
        out.append({"n_in": n_in, "n_out": n_out, "fan_in": min(fi, n_in),
                    "bw_in": cfg["bw"], "bw_out": bw_out,
                    "sources": sources[d]})
    return out


def make_weights(cfg: dict, seed: int) -> list[dict]:
    """Seeded float32 weights of every sparse layer of ``cfg``.

    Each neuron draws ``fan_in`` distinct inputs uniformly (the a-priori
    random expander) over its whole input bus, weights from N(0, 1/fan_in),
    and batch-norm statistics near those of a freshly calibrated layer.
    The same seed gives the same weights on every machine.  ``cfg`` is
    checked first (:func:`check_config`).
    """
    check_config(cfg)
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
    """The whole sparse stack on ``codes``, in blocks of rows.  Within a
    block, an activation is kept only until the last layer that reads
    it."""
    last_read = {s: d for d, layer in enumerate(weights)
                 for s in layer["sources"]}
    outs = []
    for start in range(0, codes.shape[0], block_rows):
        acts = {0: np.asarray(codes[start:start + block_rows], np.int32)}
        for d, layer in enumerate(weights):
            bus = np.concatenate([acts[s] for s in layer["sources"]], axis=1)
            for s in layer["sources"]:
                if last_read[s] == d:
                    del acts[s]
            acts[d + 1] = layer_forward(layer, bus, max_val, dtype)
        outs.append(acts[len(weights)])
    if not outs:
        return np.zeros((0, weights[-1]["n_out"]), np.int32)
    return np.concatenate(outs)
