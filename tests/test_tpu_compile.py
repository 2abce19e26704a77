"""The LUT kernels compile for a described TPU v5e (nothing runs).

The TPU compiler is installed with JAX, and compiles for a chip that is
described rather than attached: these tests hand it the kernels of the
served path at their real shapes and fail on whatever Mosaic would refuse
on the chip, such as a lane-splitting reshape, an unsupported primitive,
or more VMEM than a kernel may hold.  Interpret mode, which every other
kernel test uses, sees none of that.

The topology is described inside a module fixture, only once a test of
this file runs: only one process at a time may load the TPU library, so
doing it at import would break a multi-worker test run.
"""

import functools
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import compile as C
from repro.configs import fpga4hep, mnist
from repro.core import logicnet as LN
from repro.kernels.lut_lookup import lut_lookup_pallas
from repro.kernels.lut_network import (MixedNetworkSlabs, NetworkSlabs,
                                       build_mixed_network_slabs,
                                       build_network_slabs,
                                       lut_network_mixed_pallas,
                                       lut_network_pallas)
from repro.kernels.plan import fused_plan


@pytest.fixture(scope="module")
def topo():
    # the compiler would otherwise write its logs outside the checkout
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from jax.experimental import topologies

    def restore():
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir

    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield described
    restore()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model_a():
    """Generated fpga4hep model A: raw tables and its level-3 compile."""
    cfg = fpga4hep.model_a()
    model = LN.init(cfg, jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (256, cfg.in_features),
                           minval=-1, maxval=3)
    _, model = LN.forward(cfg, model, x, train=True)
    tables = LN.generate_tables(cfg, model)
    return cfg, tables, C.optimize(tables, level=3,
                                   in_features=cfg.in_features)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, args, sharding):
    """Lower and compile ``fn`` for the described chip; return its HLO."""
    return jax.jit(fn).lower(*_shapes(args, sharding)).compile().as_text()


@pytest.mark.parametrize("block_b", [16, 128])
def test_mixed_kernel_compiles(one_chip, model_a, block_b):
    cfg, _, res = model_a
    s = build_mixed_network_slabs(res.mixed_tables)
    codes = np.zeros((2 * block_b, cfg.in_features), np.int32)

    def fn(c, idx, shift, width, tabs):
        return lut_network_mixed_pallas(
            c, MixedNetworkSlabs(idx, shift, width, tabs, s.meta, s.out_perm,
                                 s.packed), block_b=block_b)
    hlo = _compile(fn, (codes, s.idx_slab, s.shift_slab, s.width_slab,
                        s.table_slabs), one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("block_b", [16, 128])
def test_uniform_kernel_compiles(one_chip, model_a, block_b):
    cfg, tables, _ = model_a
    s = build_network_slabs([(t.indices, t.table, t.bw_in) for t in tables])
    codes = np.zeros((2 * block_b, cfg.in_features), np.int32)

    def fn(c, idx, tab):
        return lut_network_pallas(c, NetworkSlabs(idx, tab, s.meta, s.packed),
                                  block_b=block_b)
    assert "tpu_custom_call" in _compile(
        fn, (codes, s.idx_slab, s.table_slab), one_chip)


@pytest.mark.parametrize("block_b", [16, 128])
def test_per_layer_kernel_compiles(one_chip, model_a, block_b):
    cfg, tables, _ = model_a
    t = tables[1]
    codes = np.zeros((2 * block_b, t.indices.max() + 1), np.int32)
    fn = functools.partial(lut_lookup_pallas, bw_in=t.bw_in,
                           block_b=block_b)
    assert "tpu_custom_call" in _compile(
        fn, (codes, np.asarray(t.indices, np.int32),
             np.asarray(t.table, np.int32)), one_chip)


def _mnist_stack(hidden, bw, fan_in, seed=0):
    rng = np.random.default_rng(seed)
    widths = (mnist.IN_FEATURES, *hidden)
    return [(np.stack([np.sort(rng.choice(n_in, fan_in, replace=False))
                       for _ in range(n_out)]).astype(np.int32),
             rng.integers(0, 2 ** bw, (n_out, 2 ** (fan_in * bw)),
                          dtype=np.int32), bw)
            for n_in, n_out in zip(widths[:-1], widths[1:])]


def test_mnist_stacks_refused_by_fused_plan_and_per_layer_compiles(one_chip):
    """No MNIST Table 7.1 stack fits the fused kernel's VMEM bound, so the
    plan refuses each one with a reason.  The per-layer kernels they take
    instead compile: the two-level gather at the mnist-512x3 shapes
    (784 -> 512 -> 512, 4096 entries, block_b 128) through the engine's
    jitted forward, and the compare loop at the largest stack's widest
    layer."""
    from repro.engine.engine import _per_layer_forward

    plans = {hidden: fused_plan(_mnist_stack(hidden, bw, fi))
             for hidden, bw, fi in mnist.TABLE_7_1}
    assert all(p.reason == "kernel_exceeds_vmem_limit" and not p.fused
               for p in plans.values())
    codes = np.zeros((256, mnist.IN_FEATURES), np.int32)

    stack = _mnist_stack((512, 512), 2, 6)
    assert all(t.shape[1] == 4096 for _, t, _ in stack)
    fn = functools.partial(_per_layer_forward, bws=(2, 2),
                           two_level=(True, True), block_b=128,
                           interpret=False)
    hlo = _compile(fn, (codes, tuple((i, t) for i, t, _ in stack)),
                   one_chip)
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2

    hidden, bw, fi = max(mnist.TABLE_7_1,
                         key=lambda r: plans[r[0]].slab_bytes)
    idx, tab, _ = _mnist_stack(hidden[:1], bw, fi)[0]
    fn = functools.partial(lut_lookup_pallas, bw_in=bw, block_b=128)
    assert "tpu_custom_call" in _compile(fn, (codes, idx, tab), one_chip)


def test_sharded_tier_forward_compiles_for_four_chips(topo, model_a,
                                                      monkeypatch):
    """The serving tier's four-chip program: the batch split over a 1-D
    mesh, the slabs replicated as arguments, no collective needed."""
    from repro import engine

    monkeypatch.setattr(engine.engine, "interpret_mode", lambda: False)
    cfg, tables, _ = model_a
    net = engine.compile_network(tables, optimize_level=3,
                                 in_features=cfg.in_features, block_b=16)
    fn, arrays = net.forward_parts()
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    fwd = jax.jit(jax.shard_map(
        lambda codes, slabs: fn(codes, *slabs), mesh=mesh,
        in_specs=(P("data"), P()), out_specs=P("data"), check_vma=False))
    codes = jax.ShapeDtypeStruct((64, cfg.in_features), np.int32,
                                 sharding=NamedSharding(mesh, P("data")))
    hlo = fwd.lower(codes, _shapes(arrays, NamedSharding(mesh, P()))
                    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo and "all-reduce" not in hlo


def _mnist_512x3():
    stack = _mnist_stack((512, 512, 512), 2, 6)
    return stack, mnist.IN_FEATURES, {}


def _model_a_level3(model_a):
    cfg, tables, _ = model_a
    return tables, cfg.in_features, {"optimize_level": 3}


@pytest.mark.parametrize("case,rows,layout,kernel", [
    ("mnist-512x3", 8192, "per_layer", "_per_layer_forward"),
    ("model-a-level3", 65536, "mixed", "_mixed_forward"),
])
def test_host_batch_entry_takes_codes_flat(
        one_chip, model_a, monkeypatch, case, rows, layout, kernel):
    """A host batch at the scan shapes crosses in the host's byte order:
    the entry's codes parameter is 1-D (a narrow int32 matrix would be
    column-major, ``{0,1}``, and make the put transpose on the host), and
    the kernel keeps the name of the layout's jitted forward, which the
    benchmark's roofline readers look for."""
    from repro import engine

    monkeypatch.setattr(engine.engine, "interpret_mode", lambda: False)
    layers, n_in, kwargs = (_mnist_512x3() if case == "mnist-512x3"
                            else _model_a_level3(model_a))
    net = engine.compile_network(layers, in_features=n_in, block_b=128,
                                 **kwargs)
    assert net.layout == layout
    static, arrays = net._parts()
    flat = jax.ShapeDtypeStruct((rows * n_in,), np.int32, sharding=one_chip)
    hlo = engine.engine._host_forward.lower(
        flat, _shapes(arrays, one_chip), layout=layout,
        static=tuple(sorted(static.items())), n_in=n_in).compile().as_text()
    params = re.search(r"entry_computation_layout=\{\((.*?)\)->", hlo)[1]
    assert params.startswith(f"s32[{rows * n_in}]{{0:")
    calls = re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    assert calls and set(calls) == {kernel}
