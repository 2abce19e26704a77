"""Serving-artifact contract: ``repro.engine.CompiledLUTNet``.

Three contracts under test:

* **bit-exactness** — the artifact matches ``network_table_forward`` (the
  reference semantics) across the mixed, uniform and per-layer-fallback
  layouts, including packed-int8 boundary codes {0, 255};
* **round-trip** — ``save``/``load`` reproduces the live artifact's
  outputs exactly (slabs, plan and stats all survive the ``.npz``);
* **compile-once** — a steady-state serving loop performs zero jit
  re-traces and zero compiler re-runs after warmup, and the legacy flag
  API (``ops.lut_network``) memoizes instead of silently recompiling.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.table_infer import network_table_forward
from repro.core.truth_table import LayerTruthTable
from repro.kernels.ops import lut_network


def _random_stack(widths, fan_ins, bws, seed=0):
    rng = np.random.default_rng(seed)
    layers = []
    for (n_in, n_out), fi, bw in zip(zip(widths[:-1], widths[1:]),
                                     fan_ins, bws):
        fi = min(fi, n_in)
        idx = np.stack([np.sort(rng.choice(n_in, fi, replace=False))
                        for _ in range(n_out)]).astype(np.int32)
        tab = rng.integers(0, 2 ** bw, (n_out, 2 ** (fi * bw)),
                           dtype=np.int32)
        layers.append((idx, tab, bw))
    return layers


def _tables(layers):
    return [LayerTruthTable(tab, idx, bw, bw) for idx, tab, bw in layers]


def _codes(n_in, bw, batch, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** bw, (batch, n_in), dtype=np.int32))


STACK = ((12, 20, 16, 8), (3, 3, 3), (2, 2, 2))


@pytest.mark.parametrize("kwargs,layout", [
    ({}, "uniform"),
    ({"optimize_level": 2}, "mixed"),
    ({"optimize_level": 3}, "mixed"),
    ({"vmem_budget_bytes": 64}, "per_layer"),
    ({"fused": False}, "per_layer"),
    ({"use_pallas": False}, "reference"),
])
def test_artifact_bit_exact_across_layouts(kwargs, layout):
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=13)
    codes = _codes(widths[0], bws[0], 27, seed=1)
    want = np.asarray(network_table_forward(_tables(layers), codes))

    eng = engine.compile_network(layers, in_features=widths[0], **kwargs)
    assert eng.layout == layout
    assert eng.n_in == widths[0] and eng.n_out == widths[-1]
    np.testing.assert_array_equal(np.asarray(eng(codes)), want)
    # plan records the actual decision, stats only when the compiler ran
    assert (eng.plan.reason == "fused") == (layout in ("uniform", "mixed"))
    assert (eng.stats is not None) == ("optimize_level" in kwargs)
    assert eng.vmem_breakdown()["layout"] == layout


def test_batch_edges_and_input_validation():
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=13)
    eng = engine.compile_network(layers, in_features=widths[0], block_b=8)
    for zeros in (jnp.zeros, np.zeros):          # device and host batches
        empty = eng(zeros((0, widths[0]), np.int32))
        assert empty.shape == (0, widths[-1]) and empty.dtype == jnp.int32
        with pytest.raises(ValueError, match="expected"):
            eng(zeros((4, widths[0] + 1), np.int32))
    # ragged batches (pad-and-slice) match the unpadded reference
    codes = _codes(widths[0], bws[0], 11, seed=2)
    want = np.asarray(network_table_forward(_tables(layers), codes))
    np.testing.assert_array_equal(np.asarray(eng(codes)), want)
    # numpy input is accepted
    np.testing.assert_array_equal(np.asarray(eng(np.asarray(codes))), want)


def _calls():
    from repro import obs
    fam = obs.registry().get("engine_calls_total")
    return {i: fam.labels(input=i).value for i in ("host", "device")}


@pytest.mark.parametrize("batch", [1, 7, 9])     # block_b 8: -1, +1
@pytest.mark.parametrize("kwargs,layout", [
    ({}, "uniform"),
    ({"optimize_level": 3}, "mixed"),
    ({"fused": False}, "per_layer"),
    ({"use_pallas": False}, "reference"),
])
def test_host_and_device_batches_agree(kwargs, layout, batch):
    """A host batch (put flat, through the host-batch entry) and the same
    batch already on the device give identical codes, bit-exact with the
    reference, for ragged batches; each call is counted by where its
    batch came from, and once both paths are warm, other batches of the
    same ``block_b`` bucket add no trace."""
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=19)
    eng = engine.compile_network(layers, in_features=widths[0], block_b=8,
                                 **kwargs)
    assert eng.layout == layout
    padded = -(-batch // 8) * 8
    host = np.random.default_rng(batch).integers(
        0, 2 ** bws[0], (padded, widths[0]), dtype=np.int32)
    want = np.asarray(network_table_forward(_tables(layers), host))

    calls0 = _calls()
    got_host = np.asarray(eng(host[:batch]))
    got_device = np.asarray(eng(jnp.asarray(host[:batch])))
    np.testing.assert_array_equal(got_host, want[:batch])
    np.testing.assert_array_equal(got_device, want[:batch])
    calls = _calls()
    assert calls["host"] == calls0["host"] + 1
    assert calls["device"] == calls0["device"] + 1

    traces0 = eng.jit_cache_size()
    for n in (padded - 7, batch, padded):          # the same bucket
        np.testing.assert_array_equal(np.asarray(eng(host[:n])), want[:n])
        np.testing.assert_array_equal(
            np.asarray(eng(jnp.asarray(host[:n]))), want[:n])
    assert eng.jit_cache_size() == traces0, "a warm bucket re-traced"


@pytest.mark.parametrize("form", ["non_contiguous", "fortran", "int8",
                                  "int64", "list"])
def test_host_batch_forms(form):
    """Host batches that are not C-contiguous int32 are served as their
    int32 codes: strided views, column-major arrays, narrower and wider
    integer types, and nested lists."""
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=37)
    eng = engine.compile_network(layers, optimize_level=3,
                                 in_features=widths[0], block_b=8)
    wide = np.random.default_rng(5).integers(
        0, 2 ** bws[0], (11, 2 * widths[0]), dtype=np.int32)
    codes = np.ascontiguousarray(wide[:, ::2])
    want = np.asarray(network_table_forward(_tables(layers), codes))
    batch = {"non_contiguous": wide[:, ::2],
             "fortran": np.asfortranarray(codes),
             "int8": codes.astype(np.int8),
             "int64": codes.astype(np.int64),
             "list": codes.tolist()}[form]
    np.testing.assert_array_equal(np.asarray(eng(batch)), want)


@pytest.mark.parametrize("kwargs,layout", [
    ({"optimize_level": 3}, "mixed"),
    ({}, "uniform"),
    ({"vmem_budget_bytes": 64}, "per_layer"),
])
def test_save_load_round_trip_across_layouts(tmp_path, kwargs, layout):
    """Acceptance: save -> load preserves outputs exactly vs both the live
    artifact and the ``network_table_forward`` reference."""
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=21)
    codes = _codes(widths[0], bws[0], 33, seed=3)
    want = np.asarray(network_table_forward(_tables(layers), codes))

    eng = engine.compile_network(layers, in_features=widths[0], **kwargs)
    assert eng.layout == layout
    live = np.asarray(eng(codes))
    np.testing.assert_array_equal(live, want)

    path = os.path.join(tmp_path, "net.npz")
    assert eng.save(path) == path
    eng2 = engine.load(path)
    assert eng2.layout == eng.layout
    assert (eng2.n_in, eng2.n_out, eng2.block_b) == (
        eng.n_in, eng.n_out, eng.block_b)
    assert eng2.plan == eng.plan
    np.testing.assert_array_equal(np.asarray(eng2(codes)), live)
    np.testing.assert_array_equal(np.asarray(eng2(codes)), want)
    if eng.stats is not None:
        assert eng2.stats.as_dict() == eng.stats.as_dict()
    assert eng2.vmem_breakdown() == eng.vmem_breakdown()


def test_round_trip_packed_int8_boundary_codes(tmp_path):
    """Packed-int8 tables with boundary codes 0/255 must survive the uint8
    view through the npz and back (mixed and uniform layouts)."""
    layers = _random_stack((8, 10, 6), (2, 2), (2, 2), seed=9)
    idx, tab, bw = layers[-1]
    layers[-1] = (idx, (tab % 2) * 255, bw)      # outputs exactly {0, 255}
    codes = _codes(8, 2, 19, seed=4)
    want = np.asarray(network_table_forward(_tables(layers), codes))
    assert set(np.unique(want)) <= {0, 255}

    for kwargs, layout in (({"optimize_level": 3}, "mixed"), ({}, "uniform")):
        eng = engine.compile_network(layers, in_features=8, **kwargs)
        assert eng.layout == layout and eng.slabs.packed
        tables = (eng.slabs.table_slabs if layout == "mixed"
                  else (eng.slabs.table_slab,))
        assert all(t.dtype == jnp.int8 for t in tables)
        path = os.path.join(tmp_path, f"{layout}.npz")
        eng.save(path)
        eng2 = engine.load(path)
        assert eng2.slabs.packed
        np.testing.assert_array_equal(np.asarray(eng2(codes)), want)


def _wide_output_stack():
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=17)
    idx, tab, bw = layers[-1]
    layers[-1] = (idx, tab + 254, bw)           # codes 254..257
    return (layers, _tables(layers), widths[0],
            _codes(widths[0], bws[0], 21, seed=6))


def _wide_input_stack():
    """9-bit input codes into 2-bit outputs: every table code fits a byte,
    but odd inputs past 256 have no bf16 value."""
    rng = np.random.default_rng(17)
    idx0 = np.arange(8, dtype=np.int32)[:, None]
    tab0 = rng.integers(0, 4, (8, 512), dtype=np.int32)
    idx1 = np.stack([np.sort(rng.choice(8, 2, replace=False))
                     for _ in range(4)]).astype(np.int32)
    tab1 = rng.integers(0, 4, (4, 16), dtype=np.int32)
    layers = [(idx0, tab0, 9), (idx1, tab1, 2)]
    tables = [LayerTruthTable(tab0, idx0, 9, 2),
              LayerTruthTable(tab1, idx1, 2, 2)]
    return layers, tables, 8, _codes(8, 9, 21, seed=6)


@pytest.mark.parametrize("kwargs", [{}, {"optimize_level": 3}])
@pytest.mark.parametrize("stack", [_wide_output_stack, _wide_input_stack])
def test_codes_past_exact_gather_range_refused_and_served(kwargs, stack):
    """Codes of 256 and above, out of a table or into one, are past what
    the fused kernels' bf16 one-hot gathers carry exactly: the plan
    refuses both fused layouts, and the per-layer kernel serves the stack
    bit-exact instead."""
    layers, tables, n_in, codes = stack()
    want = np.asarray(network_table_forward(tables, codes))
    assert max(int(want.max()), int(codes.max())) >= 256

    eng = engine.compile_network(layers, in_features=n_in, **kwargs)
    assert eng.layout == "per_layer"
    assert eng.plan.reason == "codes_exceed_f32_exact_range"
    np.testing.assert_array_equal(np.asarray(eng(codes)), want)


@pytest.mark.parametrize("kwargs,layout", [
    ({}, "uniform"), ({"optimize_level": 3}, "mixed")])
def test_load_refuses_table_slabs_before_format_4(tmp_path, kwargs, layout):
    """An artifact whose fused table slab still has the flat 2-D layout
    (formats 1-3) is refused at load: no TPU kernel can read it."""
    from repro.checkpoint.ckpt import load_arrays, save_arrays

    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=3)
    eng = engine.compile_network(layers, in_features=widths[0], **kwargs)
    assert eng.layout == layout
    path = os.path.join(tmp_path, f"{layout}.npz")
    eng.save(path)
    arrays, meta = load_arrays(path)
    tabs = [k for k in arrays if k.startswith("table_slab")]
    flat = np.concatenate([arrays.pop(k).reshape(-1) for k in tabs])
    arrays["table_slab"] = (flat[None, :] if layout == "mixed"
                            else flat.reshape(eng.slabs.table_slab.shape[0],
                                              -1))
    meta["format"] = 3
    save_arrays(path, arrays, meta)
    with pytest.raises(ValueError, match="before format 4"):
        engine.load(path)


def test_round_trip_per_layer_fallback_over_budget(tmp_path):
    """The over-VMEM-budget artifact serializes its per-layer triples and
    still serves bit-exactly after a reload."""
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=31)
    eng = engine.compile_network(layers, in_features=widths[0],
                                 vmem_budget_bytes=64)
    assert eng.layout == "per_layer"
    assert eng.plan.reason == "slab_exceeds_vmem_budget"
    codes = _codes(widths[0], bws[0], 14, seed=5)
    want = np.asarray(network_table_forward(_tables(layers), codes))
    path = os.path.join(tmp_path, "fallback.npz")
    eng.save(path)
    eng2 = engine.load(path)
    assert eng2.layout == "per_layer" and eng2.plan == eng.plan
    np.testing.assert_array_equal(np.asarray(eng2(codes)), want)


def _gather_counts():
    from repro import obs
    fam = obs.registry().get("engine_per_layer_gather_total")
    return {g: fam.labels(gather=g).value for g in ("two_level", "compare")}


def test_per_layer_gather_follows_table_codes(tmp_path):
    """One table code at 256 is past the exact bf16 range: that layer, and
    only that one, keeps the integer compare loop.  The choice is counted
    once per layer at build (and again at load), and the artifact serves
    bit-exactly either way."""
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=23)
    idx, tab, bw = layers[-1]
    tab = tab.copy()
    tab[0, 0] = 256
    layers[-1] = (idx, tab, bw)
    codes = _codes(widths[0], bws[0], 19, seed=8)
    want = np.asarray(network_table_forward(_tables(layers), codes))

    before = _gather_counts()
    eng = engine.compile_network(layers, in_features=widths[0], fused=False)
    assert eng.layout == "per_layer"
    assert eng.two_level == (True, True, False)
    after = _gather_counts()
    assert after["two_level"] == before["two_level"] + 2
    assert after["compare"] == before["compare"] + 1
    np.testing.assert_array_equal(np.asarray(eng(codes)), want)

    path = os.path.join(tmp_path, "per_layer.npz")
    eng.save(path)
    eng2 = engine.load(path)
    assert eng2.two_level == eng.two_level
    assert _gather_counts()["compare"] == after["compare"] + 1
    np.testing.assert_array_equal(np.asarray(eng2(codes)), want)


def test_mnist_shaped_stack_takes_two_level_gather_per_layer():
    """An MNIST Table 7.1 shape (784 -> 512 x 3, fan-in 6, 2-bit codes,
    4096-entry tables) is over the fused kernels' VMEM bound; every one of
    its per-layer kernels takes the two-level gather, bit-exactly."""
    widths, fan_ins, bws = (784, 512, 512, 512), (6, 6, 6), (2, 2, 2)
    layers = _random_stack(widths, fan_ins, bws, seed=5)
    codes = _codes(widths[0], bws[0], 9, seed=3)
    before = _gather_counts()
    eng = engine.compile_network(layers, in_features=widths[0])
    assert eng.layout == "per_layer"
    assert eng.plan.reason == "kernel_exceeds_vmem_limit"
    assert eng.two_level == (True, True, True)
    after = _gather_counts()
    assert after["two_level"] == before["two_level"] + 3
    assert after["compare"] == before["compare"]
    want = np.asarray(network_table_forward(_tables(layers), codes))
    np.testing.assert_array_equal(np.asarray(eng(codes)), want)


def test_load_rejects_foreign_npz(tmp_path):
    from repro.checkpoint import save_arrays

    path = os.path.join(tmp_path, "other.npz")
    save_arrays(path, {"x": np.zeros(3)}, {"kind": "something_else"})
    with pytest.raises(ValueError, match="not a repro.engine"):
        engine.load(path)
    # a plain np.savez file (no manifest) must fail with the friendly
    # ValueError too, not an opaque KeyError from deep inside the loader
    plain = os.path.join(tmp_path, "plain.npz")
    np.savez(plain, x=np.zeros(3))
    with pytest.raises(ValueError, match="manifest"):
        engine.load(plain)


def test_default_in_features_ignores_hidden_layer_indices():
    """Regression: the inferred input-bus width must come from the FIRST
    layer's indices only — a hidden layer wider than the input bus used
    to inflate n_in and reject valid codes."""
    widths, fan_ins, bws = (4, 10, 3), (2, 2), (2, 2)
    layers = _random_stack(widths, fan_ins, bws, seed=41)
    codes = _codes(4, 2, 5, seed=9)
    want = np.asarray(network_table_forward(_tables(layers), codes))
    for kwargs in ({}, {"optimize_level": 3}):
        eng = engine.compile_network(layers, **kwargs)   # no in_features
        assert eng.n_in == 4
        np.testing.assert_array_equal(np.asarray(eng(codes)), want)


def test_compile_network_accepts_optimize_result():
    """An already-computed OptimizeResult is reused, not recompiled."""
    from repro import compile as rcompile

    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=17)
    res = rcompile.optimize(rcompile.tables_from_triples(layers), 3,
                            in_features=widths[0])
    runs0 = engine.compile_runs()
    eng = engine.compile_network(res)
    assert engine.compile_runs() == runs0      # no compiler run
    assert eng.layout == "mixed" and eng.stats is res.stats
    assert eng.n_in == widths[0]
    codes = _codes(widths[0], bws[0], 9, seed=6)
    want = np.asarray(network_table_forward(_tables(layers), codes))
    np.testing.assert_array_equal(np.asarray(eng(codes)), want)
    with pytest.raises(ValueError, match="OptimizeResult"):
        engine.compile_network(res, optimize_level=3)


def test_serving_loop_zero_retrace_zero_recompile():
    """Acceptance: after warmup, a steady-state serving loop with ragged
    batch sizes adds no jit traces and never re-runs the compiler."""
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=23)
    eng = engine.compile_network(layers, optimize_level=3,
                                 in_features=widths[0], block_b=32)
    assert eng.layout == "mixed"
    want_full = np.asarray(network_table_forward(
        _tables(layers), _codes(widths[0], bws[0], 32, seed=7)))
    np.testing.assert_array_equal(
        np.asarray(eng(_codes(widths[0], bws[0], 32, seed=7))), want_full)

    traces0 = eng.jit_cache_size()
    runs0 = engine.compile_runs()
    for batch in (32, 1, 17, 32, 9, 25, 32):   # one block_b bucket
        codes = _codes(widths[0], bws[0], batch, seed=7)
        out = np.asarray(eng(codes))
        np.testing.assert_array_equal(out, want_full[:batch])
    assert eng.jit_cache_size() == traces0, "serving loop re-traced"
    assert engine.compile_runs() == runs0, "serving loop re-ran the compiler"


def test_legacy_flag_api_memoizes():
    """Regression (the `_cache_size` pattern): ops.lut_network with
    optimize_level= used to re-run the compiler and rebuild slabs on every
    call; the engine memo must absorb repeated calls entirely."""
    widths, fan_ins, bws = STACK
    layers = _random_stack(widths, fan_ins, bws, seed=29)
    codes = _codes(widths[0], bws[0], 21, seed=8)
    want = np.asarray(network_table_forward(_tables(layers), codes))

    got = np.asarray(lut_network(codes, layers, optimize_level=3))
    np.testing.assert_array_equal(got, want)
    size0 = engine.cache_size()
    runs0 = engine.compile_runs()
    for _ in range(4):
        got = np.asarray(lut_network(codes, layers, optimize_level=3))
    np.testing.assert_array_equal(got, want)
    assert engine.cache_size() == size0, "legacy calls grew the memo"
    assert engine.compile_runs() == runs0, "legacy calls re-ran the compiler"
    # distinct flag combinations are distinct artifacts ...
    lut_network(codes, layers, optimize_level=2)
    assert engine.cache_size() == size0 + 1
    # ... and cache_clear forces a fresh compile
    engine.cache_clear()
    assert engine.cache_size() == 0
    got = np.asarray(lut_network(codes, layers, optimize_level=3))
    np.testing.assert_array_equal(got, want)
    assert engine.compile_runs() == runs0 + 2


def test_cache_bounded_fifo_eviction_order(monkeypatch):
    """The legacy-API memo is FIFO-bounded: filling past _CACHE_MAX
    evicts the *oldest insertion* (not least-recently-used — a re-hit
    does not refresh an entry's position)."""
    from repro.engine import engine as engmod

    engine.cache_clear()
    monkeypatch.setattr(engmod, "_CACHE_MAX", 3)
    stacks = [_random_stack((8, 6, 4), (2, 2), (2, 2), seed=100 + i)
              for i in range(4)]

    def compiled(stack):
        return engine.cached_compile(
            stack, optimize_level=None, in_features=8, fused=True,
            use_pallas=True, block_b=8,
            vmem_budget_bytes=8 * 2 ** 20)

    a, b, c = (compiled(s) for s in stacks[:3])
    assert engine.cache_size() == 3
    assert compiled(stacks[0]) is a, "expected a memo hit"
    # inserting a 4th evicts the oldest insertion: stack 0, even though
    # it was just re-hit (FIFO, not LRU)
    d = compiled(stacks[3])
    assert engine.cache_size() == 3
    assert compiled(stacks[1]) is b and compiled(stacks[2]) is c
    a2 = compiled(stacks[0])
    assert a2 is not a, "evicted entry must recompile"
    # that reinsertion evicted the new oldest entry (stack 1); the
    # younger entries survived
    assert engine.cache_size() == 3
    assert compiled(stacks[2]) is c and compiled(stacks[3]) is d
    engine.cache_clear()
    assert engine.cache_size() == 0


def test_cache_clear_after_in_place_edit():
    """The documented immutability contract: an in-place table edit is
    served stale until ``engine.cache_clear()`` forces a fresh compile."""
    engine.cache_clear()
    layers = _random_stack((8, 6, 4), (2, 2), (2, 2), seed=77)
    codes = _codes(8, 2, 9, seed=9)
    stale = np.asarray(lut_network(codes, layers))
    np.testing.assert_array_equal(
        stale, np.asarray(network_table_forward(_tables(layers), codes)))

    idx0, tab0, bw0 = layers[0]
    tab0 += 1
    tab0 %= 2 ** bw0                     # in-place edit, same array id
    np.testing.assert_array_equal(
        np.asarray(lut_network(codes, layers)), stale)   # stale hit

    engine.cache_clear()
    fresh = np.asarray(lut_network(codes, layers))
    np.testing.assert_array_equal(
        fresh, np.asarray(network_table_forward(_tables(layers), codes)))
    assert not np.array_equal(fresh, stale), (
        "the edit was chosen to change outputs; stale and fresh must "
        "differ for this regression test to mean anything")


def test_generated_model_round_trip(tmp_path):
    """End-to-end on real generated tables (fpga4hep model C shape): the
    engine artifact equals the float-path verification codes, survives a
    round-trip, and reports the compiler's stats."""
    import jax

    from repro.configs import fpga4hep
    from repro.core import logicnet as LN
    from repro.core.quantize import codes as qcodes

    cfg = fpga4hep.model_c()
    model = LN.init(cfg, jax.random.PRNGKey(0))
    tables = LN.generate_tables(cfg, model)
    x = jax.random.uniform(jax.random.PRNGKey(1), (40, cfg.in_features),
                           minval=-1, maxval=3)
    eng = engine.compile_network(tables, optimize_level=3,
                                 in_features=cfg.in_features)
    in_codes = qcodes(cfg.layer_cfgs()[0].in_quant, x)
    want = np.asarray(network_table_forward(tables, in_codes))
    np.testing.assert_array_equal(np.asarray(eng(in_codes)), want)
    assert eng.stats.table_bytes_after < eng.stats.table_bytes_before

    path = os.path.join(tmp_path, "model_c.npz")
    eng.save(path)
    eng2 = engine.load(path)
    np.testing.assert_array_equal(np.asarray(eng2(in_codes)), want)
    assert eng2.stats.table_bytes_after == eng.stats.table_bytes_after
