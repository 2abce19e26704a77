"""Skip connections in the benchmark's own model of a LogicNet (thesis §7):
the reference's bus, its weights, the configuration check, and what the
benchmark hands the program (CPU only, no chip)."""

import copy
import hashlib
import json
import os
import sys
import traceback

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import program, run, work  # noqa: E402
from bench.models import logicnet_ref  # noqa: E402

# a tiny skip stack: input and first hidden layer into the sparse head,
# whose bus is [h1 8 | x 8 | h0 8]; even widths, as the program sizes a
# skip from the wrong activation where widths differ
SKIP = {"name": "tiny-skip", "architecture": "logicnet", "in_features": 8,
        "n_classes": 4, "hidden": [8, 8], "fan_in": 2, "bw": 2,
        "final_dense": False, "fan_in_fc": 2, "bw_fc": 2, "max_val": 2.0,
        "skips": [[0, 2], [1, 2]], "precision": "float32",
        "optimize_level": 3, "model_seed": 0,
        "limits": {"mismatch_share": 1e-4}}
FIELDS = ("idx", "w", "b", "gamma", "beta", "mean", "var")


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as fh:
        return json.load(fh)


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _codes(cfg, rows, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2 ** cfg["bw"], (rows, cfg["in_features"]),
                        dtype=np.int32)


# -- (a) today's configurations read as they did before skips ---------------

@pytest.mark.parametrize("name, weights_sha, forward_sha", [
    ("jsc-a",
     "2326cc5c142c945796261365b5dbed278828ac312f61d462a9fd01da23c44172",
     "b06f920e2d07284dc7b96102c4952a663666db0574c270e448520ade9df70e72"),
    ("mnist-512x3",
     "7d06c4591249edd75f0286b6f6d17ea0652eca54940d9a7b9804e6b613ef156f",
     "6685ffe710f1030e60b57266c98ee1ae088249f15e50e7cdf93bc779fa5c88c2"),
])
def test_configurations_without_skips_are_bit_identical(name, weights_sha,
                                                        forward_sha):
    """Digests taken from the reference before it read ``skips``."""
    cfg = _cfg(name)
    w = logicnet_ref.make_weights(cfg, cfg["model_seed"])
    assert _digest([lay[k] for lay in w for k in FIELDS]) == weights_sha
    out = logicnet_ref.forward(w, _codes(cfg, 256, 20261019), cfg["max_val"])
    assert _digest([out]) == forward_sha


# -- (b) the reference agrees with the program's float skip path -------------

def test_skip_stack_matches_the_program_float_path():
    program.import_program()
    import jax.numpy as jnp
    from repro.core import logicnet as LN
    from repro.core.quantize import codes as quantize_codes

    w = logicnet_ref.make_weights(SKIP, 7)
    assert [lay["n_in"] for lay in w] == [8, 8, 24]
    cfg = program.logicnet_cfg(SKIP)
    x = _codes(SKIP, 256, 2 ** 33 + 1)
    step = np.float32(logicnet_ref.step(SKIP["bw"], SKIP["max_val"]))
    h, _ = LN.forward(cfg, program.program_model(w),
                      jnp.asarray(x.astype(np.float32) * step), train=False)
    got = np.asarray(quantize_codes(cfg.out_quant, h))
    want = logicnet_ref.forward(w, x, SKIP["max_val"], block_rows=100)
    assert want.shape == (256, 4)
    np.testing.assert_array_equal(got, want)


# -- (c) the bus order: previous output, then the skips as listed ------------

def _identity_neurons(layer, positions):
    """Neuron j passes on the code at bus position ``positions[j]``."""
    n_out, fi = layer["n_out"], layer["fan_in"]
    idx = np.stack([positions, (positions + 1) % layer["n_in"]], axis=1)
    f32 = np.float32
    one = np.sqrt(f32(1.0) + f32(logicnet_ref.BN_EPS))
    return dict(layer, idx=idx.astype(np.int32),
                w=np.tile(np.array([1.0] + [0.0] * (fi - 1), f32), (n_out, 1)),
                b=np.zeros(n_out, f32), gamma=np.full(n_out, one, f32),
                beta=np.zeros(n_out, f32), mean=np.zeros(n_out, f32),
                var=np.ones(n_out, f32))


def test_bus_is_previous_output_then_skips_in_listed_order():
    """Layer 2 of a stack of uneven widths reads [h1 5 | h0 3 | x 4]; its
    neurons read only the skipped activations' positions and pass them
    on, so a bus in any other order reads other columns."""
    cfg = dict(SKIP, in_features=4, hidden=[3, 5, 6], final_dense=True,
               skips=[[1, 2], [0, 2]])
    shapes = logicnet_ref.layer_shapes(cfg)
    assert [s["sources"] for s in shapes] == [[0], [1], [2, 1, 0]]
    assert [s["n_in"] for s in shapes] == [4, 3, 12]
    w = logicnet_ref.make_weights(cfg, 3)
    h0_pos, x_pos = np.arange(5, 8), np.arange(8, 12)
    w[2] = _identity_neurons(w[2], np.concatenate([h0_pos, x_pos[:3]]))
    x = _codes(cfg, 50, 11)
    h0 = logicnet_ref.layer_forward(w[0], x, cfg["max_val"])
    out = logicnet_ref.forward(w, x, cfg["max_val"], block_rows=16)
    assert out.any()
    np.testing.assert_array_equal(out[:, :3], h0)
    np.testing.assert_array_equal(out[:, 3:], x[:, :3])


# -- (d) what the reference refuses -----------------------------------------

@pytest.mark.parametrize("change, match", [
    ({"skips": [[2, 2]]}, "0 <= s < d"),
    ({"skips": [[2, 1]]}, "0 <= s < d"),
    ({"skips": [[0, 2]], "final_dense": True}, "not in the served"),
    ({"skips": [[0, 2], [1, 2], [0, 2]]}, "listed twice"),
    ({"residual": True}, "not read"),
    ({"architecture": "llama"}, "architecture"),
    ({"precision": "bfloat16"}, "precision"),
])
def test_refused_configurations(change, match):
    cfg = {**copy.deepcopy(SKIP), **change}
    with pytest.raises(ValueError, match=match):
        logicnet_ref.make_weights(cfg, 0)


@pytest.mark.parametrize("name", ["jsc-a", "mnist-512x3"])
def test_benchmark_configurations_pass_the_check(name):
    logicnet_ref.check_config(_cfg(name))


# -- (e) the program is asked for the skips the configuration states ---------

def test_program_is_handed_the_skips(monkeypatch):
    program.import_program()
    from repro.core import logicnet as LN
    seen = []
    monkeypatch.setattr(LN, "generate_tables",
                        lambda cfg, model: seen.append(cfg.skips))
    program.make_tables(SKIP, logicnet_ref.make_weights(SKIP, 0))
    assert seen == [((0, 2), (1, 2))]


def _run_skip():
    import jax
    program.import_program()
    cell = {"name": "tiny-skip.scan", "chips": 1}
    mix = {"kind": "closed_loop_scan", "rows_per_call": 64,
           "distinct_batches": 1, "block_b": 128}
    return run.run_cell(cell, SKIP, mix, 5, 0.5, False, jax.devices())


def _forward(weights, codes, max_val, drop_skips):
    """The reference's codes of the stack; with ``drop_skips`` every
    skip segment of every bus is code 0, as a program that serves the
    stack without its skips would read it."""
    acts = [np.asarray(codes, np.int32)]
    for layer in weights:
        prev, *skips = layer["sources"]
        bus = [acts[prev]] + [np.zeros_like(acts[s]) if drop_skips
                              else acts[s] for s in skips]
        acts.append(logicnet_ref.layer_forward(
            layer, np.concatenate(bus, axis=1), max_val))
    return acts[-1]


def test_a_skip_stack_is_not_served_as_a_plain_stack():
    """A skip configuration is never served as a plain stack: either the
    program's table generation refuses it, or the run serves it and
    agrees with the skip-aware reference."""
    try:
        res = _run_skip()
    except NotImplementedError as exc:
        frames = traceback.extract_tb(exc.__traceback__)
        assert any(f.name == "generate_tables" and os.sep + "repro" + os.sep
                   in f.filename for f in frames), frames
        return
    assert res["correct"] is True
    assert res["checks"]["codes_compared"]["value"] >= 1
    assert res["checks"]["mismatch_share"]["value"] == 0


class _StandIn:
    """A served artifact that answers with the reference's codes."""

    layout = "stand-in"

    def __init__(self, cfg, weights, drop_skips):
        self.cfg, self.weights, self.drop_skips = cfg, weights, drop_skips

    def __call__(self, codes):
        return _forward(self.weights, np.asarray(codes),
                        self.cfg["max_val"], self.drop_skips)

    def jit_cache_size(self):
        return 0


@pytest.mark.parametrize("drop_skips, correct", [(True, False),
                                                 (False, True)])
def test_the_harness_fails_a_stack_served_without_its_skips(
        drop_skips, correct, monkeypatch):
    """The program's layers from the weights to the served codes, its
    table generation (``program.make_tables``) and its compiled artifact
    (``program.compile_net``), are replaced by a stand-in that answers
    with the reference's codes; with ``drop_skips`` each skip segment of
    each bus is code 0.  The scan kind's loop, its sample and the
    comparison are the harness's own: it reads a stack served without
    its skips as incorrect, and one served with them as correct."""
    monkeypatch.setattr(program, "make_tables",
                        lambda cfg, weights: weights)
    monkeypatch.setattr(program, "compile_net",
                        lambda cfg, weights, block_b:
                        _StandIn(cfg, weights, drop_skips))
    res = _run_skip()
    mismatch = res["checks"]["mismatch_share"]
    assert res["correct"] is correct
    assert res["checks"]["codes_compared"]["value"] >= 1
    if drop_skips:
        assert mismatch["value"] > mismatch["limit"]
    else:
        assert mismatch["value"] == 0


def test_dropped_skips_change_far_more_codes_than_the_limit():
    """At the published widths of the next configuration, mnist-512x3
    with ``skips`` [[0, 2], [1, 2]]: zeroing the skip segments of the
    last sparse layer's 1808-wide bus changes far more than the limit's
    share of the output codes, so the limit catches a dropped skip."""
    cfg = dict(_cfg("mnist-512x3"), skips=[[0, 2], [1, 2]])
    w = logicnet_ref.make_weights(cfg, cfg["model_seed"])
    x = _codes(cfg, 256, 1)
    want = logicnet_ref.forward(w, x, cfg["max_val"])
    np.testing.assert_array_equal(_forward(w, x, cfg["max_val"], False), want)
    share = float((_forward(w, x, cfg["max_val"], True) != want).mean())
    limit = cfg["limits"]["mismatch_share"]
    print(f"dropped skips change {share} of the codes "
          f"({share / limit:.0f} x the limit {limit})")
    assert share > 100 * limit


# -- (f) skips cost no lookups and move no bytes ------------------------------

def test_skips_leave_the_work_per_row_unchanged():
    cfg = dict(_cfg("mnist-512x3"), skips=[[0, 2], [1, 2]])
    assert [s["n_in"] for s in logicnet_ref.layer_shapes(cfg)] == \
        [784, 512, 512 + 784 + 512]
    assert work.per_row(cfg) == work.per_row(_cfg("mnist-512x3")) == \
        {"bytes": 324, "ops": 1536}
