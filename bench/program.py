"""The system under test, as the benchmark builds and calls it.

Everything here goes through the program's own entry points: its
LogicNet model structure and truth-table generation
(``repro.core.logicnet``), and its compiler and serving artifact
(``repro.engine.compile_network``).  The benchmark hands it weights that
it made from the configuration's ``model_seed``
(``bench.models.logicnet_ref.make_weights``); the program makes the
tables and everything after them.
"""

from __future__ import annotations

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Import the checkout's ``repro`` package, and nothing installed."""
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "__init__.py")):
        raise SystemExit(f"the system under test is missing: no "
                         f"{os.path.join('src', 'repro')} in {ROOT}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro
    return repro


def logicnet_cfg(cfg: dict):
    from repro.core.logicnet import LogicNetCfg
    return LogicNetCfg(
        cfg["in_features"], cfg["n_classes"], hidden=tuple(cfg["hidden"]),
        fan_in=cfg["fan_in"], bw=cfg["bw"], final_dense=cfg["final_dense"],
        fan_in_fc=cfg.get("fan_in_fc"), bw_fc=cfg["bw_fc"],
        max_val=cfg["max_val"],
        skips=tuple(tuple(p) for p in cfg.get("skips", ())))


def program_model(weights: list[dict]) -> list[dict]:
    """The bench's weights in the program's layer structure (dense
    masked weight matrices over each layer's whole input bus, skips
    included, batch-norm parameters and running stats)."""
    model = []
    for lay in weights:
        n_in, n_out = lay["n_in"], lay["n_out"]
        cols = np.repeat(np.arange(n_out), lay["idx"].shape[1])
        rows = lay["idx"].reshape(-1)
        w = np.zeros((n_in, n_out), np.float32)
        w[rows, cols] = lay["w"].reshape(-1)
        mask = np.zeros((n_in, n_out), np.float32)
        mask[rows, cols] = 1.0
        model.append({
            "params": {"w": w, "b": lay["b"],
                       "bn": {"scale": lay["gamma"], "bias": lay["beta"]}},
            "mask": mask,
            "bn_state": {"mean": lay["mean"], "var": lay["var"]},
        })
    return model


def make_tables(cfg: dict, weights: list[dict]):
    """The program's truth tables of the sparse stack, generated on the
    host's CPU backend so that every machine serves the same tables."""
    import jax
    from repro.core import logicnet as LN

    with jax.default_device(jax.devices("cpu")[0]):
        return LN.generate_tables(logicnet_cfg(cfg), program_model(weights))


def compile_net(cfg: dict, tables, block_b: int):
    """The serving artifact, compiled as ``serve --lut`` compiles it."""
    from repro import engine
    return engine.compile_network(
        tables, optimize_level=cfg["optimize_level"],
        in_features=cfg["in_features"], block_b=block_b)
