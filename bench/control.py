"""The control of a cell's comparison: the reference in bfloat16.

    python -m bench.control --workload jsc-a.stream --seeds 1,2,3 --seconds 10

The configurations state float32 for the neuron's arithmetic.  The
control puts the plain reference in the program's place, computed in
the nearest type below, bfloat16, and reads the number a run compares
(the share of output codes that differ from the float32 reference) on
the same inputs a run of the cell compares: every request of a stream
window, or the batches of a scan.  One JSON line per seed.  The limit
in each configuration's file lies between what sound runs of the
program read and what this control reads; this tool is not part of a
run.
"""

from __future__ import annotations

import argparse
import json

import ml_dtypes
import numpy as np

from bench import traffic
from bench.models import logicnet_ref
from bench.run import find_cell, load_spec


def reading(cfg: dict, inputs: list, dtype) -> float:
    weights = logicnet_ref.make_weights(cfg, cfg["model_seed"])
    wrong = total = 0
    for codes in inputs:
        want = logicnet_ref.forward(weights, codes, cfg["max_val"])
        got = logicnet_ref.forward(weights, codes, cfg["max_val"], dtype)
        wrong += int((got != want).sum())
        total += want.size
    return wrong / total


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    _, cfg, mix = find_cell(load_spec(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        inputs = traffic.kind(mix).inputs(cfg, mix, seed, args.seconds)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "rows": int(sum(len(c) for c in inputs)),
            "mismatch_share_bf16": reading(cfg, inputs, ml_dtypes.bfloat16),
        }), flush=True)


if __name__ == "__main__":
    main()
