"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile natively; on any other backend
they run through the Pallas interpreter, which executes the kernel body
in Python for correctness validation — tests sweep shapes and dtypes
against the ref.py oracles either way.  ``interpret_mode`` makes that
choice for the whole repository.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lut_lookup import DEFAULT_BLOCK_B, lut_lookup_pallas
from repro.kernels.masked_matmul import masked_matmul_pallas
# the fused-path costing lives in repro.kernels.plan since the
# ExecutionPlan refactor; re-exported here so long-standing importers
# (`from repro.kernels.ops import fused_plan`) keep working
from repro.kernels.plan import (FUSED_VMEM_BUDGET_BYTES,  # noqa: F401
                                FusedPlan, fused_plan)


def interpret_mode() -> bool:
    """Whether Pallas kernels run through the interpreter.

    The one place the repository decides how its kernels execute: native
    Mosaic kernels when JAX's default backend is a TPU, the Pallas
    interpreter on any other backend (the CPU test suite).  The engine,
    the autotuner and the benchmark all ask here.  Code that must run on
    the chip checks ``jax.devices()[0].platform`` itself and fails
    instead of accepting the interpreter.
    """
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit,
                   static_argnames=("bw_in", "use_pallas", "block_b"))
def lut_lookup(codes: jax.Array, indices: jax.Array, table: jax.Array,
               bw_in: int, use_pallas: bool = True,
               block_b: int = DEFAULT_BLOCK_B) -> jax.Array:
    """LogicNets LUT-layer inference: (B, I) codes -> (B, O) codes.

    Jit'd with a shape/static-arg cache: repeated calls on the same layer
    shapes reuse the traced kernel — which is why ``lut_network``'s
    per-layer fallback must route through this wrapper rather than calling
    ``lut_lookup_pallas`` directly (the bare call re-traces every layer on
    every invocation).

    The table is a tracer here, so its codes cannot pick the gather: this
    wrapper keeps the integer compare loop, exact for any int32 table.
    The engine's per-layer artifacts decide from their host tables
    (``engine.per_layer_gathers``) and take the two-level MXU gather
    where it is exact.
    """
    if not use_pallas:
        return ref.lut_lookup_ref(codes, indices, table, bw_in)
    return lut_lookup_pallas(codes, indices, table, bw_in, block_b=block_b,
                             interpret=interpret_mode())


def lut_network(codes: jax.Array, layers, *, fused: bool = True,
                use_pallas: bool = True, block_b: int = DEFAULT_BLOCK_B,
                vmem_budget_bytes: int = FUSED_VMEM_BUDGET_BYTES,
                optimize_level: int | None = None) -> jax.Array:
    """Whole sparse-stack LUT inference: (B, I0) codes -> (B, O_last) codes.

    ``layers`` is a sequence of ``(indices, table, bw_in)`` triples, one per
    sparse layer (exactly ``LayerTruthTable``'s fields).  With ``fused``
    the stack runs as a single ``pallas_call`` (activations never leave
    VMEM) when the concatenated slabs fit ``vmem_budget_bytes``; otherwise
    — and always when ``fused=False`` — it falls back to one
    ``lut_lookup`` call per layer.  Both paths are bit-exact with the
    ``table_infer.network_table_forward`` reference semantics.

    ``optimize_level`` (0-3) runs the truth-table compiler
    (``repro.compile``) over the stack first: smaller slabs mean stacks
    that used to overflow ``vmem_budget_bytes`` can take the fused path,
    and the output stays bit-identical on every reachable input.  The
    fused path then consumes the compiler's *mixed-width* lowering
    (``CNet.to_mixed_tables``) directly — per-(neuron, element) shift
    slabs and exact ``2^(sum of input widths)``-entry tables, so
    dead-input pruning and level-3 re-encoding bank their full table-byte
    savings as VMEM instead of being padded back to each bus's widest
    feature.

    This is now a thin compatibility wrapper over the serving engine
    (``repro.engine.compile_network``): the compile/cost/build/jit
    decision runs once and is memoized keyed on the layer arrays'
    *identity* plus the flags, so repeated calls with the same layers —
    the legacy serving-loop pattern — reuse the cached artifact instead
    of silently recompiling every call.  New code should hold the
    ``CompiledLUTNet`` directly (and ``save``/``load`` it for
    deployment); callers that mutate a table array in place must call
    ``repro.engine.cache_clear()`` to avoid stale results.

    Example::

        import numpy as np
        from repro.kernels.ops import lut_network
        rng = np.random.default_rng(0)
        idx = np.stack([np.sort(rng.choice(6, 2, replace=False))
                        for _ in range(4)]).astype(np.int32)
        tab = rng.integers(0, 4, (4, 16), dtype=np.int32)
        codes = rng.integers(0, 4, (3, 6), dtype=np.int32)
        out = lut_network(codes, [(idx, tab, 2)], fused=True)
        assert out.shape == (3, 4)
    """
    from repro import engine
    eng = engine.cached_compile(layers, optimize_level=optimize_level,
                                in_features=int(codes.shape[-1]),
                                fused=fused, use_pallas=use_pallas,
                                block_b=block_b,
                                vmem_budget_bytes=vmem_budget_bytes)
    return eng(codes)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def masked_matmul(x: jax.Array, w: jax.Array, mask: jax.Array,
                  b: jax.Array | None = None,
                  use_pallas: bool = True) -> jax.Array:
    """y = x @ (w * mask) + b."""
    if not use_pallas:
        return ref.masked_matmul_ref(x, w, mask, b)
    return masked_matmul_pallas(x, w, mask, b, interpret=interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "use_pallas"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int | None = None,
                    use_pallas: bool = True) -> jax.Array:
    """Blocked attention; GQA via Hq % Hkv == 0."""
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=interpret_mode())
