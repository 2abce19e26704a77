"""Traffic mixes and the kinds of load that offer them.

A mix is a JSON file under ``bench/traffic/``, found by the cell's
``traffic`` name.  Its ``kind`` names a module of its own,
``bench/kinds/<kind>.py``, which reads the mix's parameters and
provides

* ``run(ctx)`` -- offer the load to the system under test for one run;
* ``inputs(cfg, mix, seed, seconds)`` -- the input codes a run of that
  length compares, for the control (``bench/control.py``).

A new arrival process or load pattern is a new kind file and a mix that
names it; the kinds share the HTTP driver (``bench/stream.py``) and the
scan loop (``bench/scan.py``).  Every seed gets the same amount of work:
a kind draws only order and code values from the seed.  This module
uses NumPy only, so the load generator's process never imports JAX.
"""

from __future__ import annotations

import importlib
import json
import os
import re

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_KIND = re.compile(r"[a-z_][a-z0-9_]*")


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as fh:
        mix = json.load(fh)
    kind_ = mix.get("kind", "")
    if not (_KIND.fullmatch(kind_) and os.path.isfile(
            os.path.join(HERE, "kinds", f"{kind_}.py"))):
        raise ValueError(f"traffic {name}: no kind {kind_!r} in bench/kinds/")
    return mix


def kind(mix: dict):
    """The module that offers this mix's kind of load."""
    return importlib.import_module(f"bench.kinds.{mix['kind']}")


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def codes(rows: int, n_in: int, bw: int, seed: int, stream: int,
          dtype=np.int8) -> np.ndarray:
    """``rows`` rows of ``n_in`` uniform ``bw``-bit input codes."""
    return rng(seed, stream).integers(0, 2 ** bw, (int(rows), n_in),
                                      dtype=dtype)
