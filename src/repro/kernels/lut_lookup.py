"""Pallas TPU kernel: LogicNets LUT-layer inference (the HBB gather).

TPU adaptation of the paper's core mechanism (DESIGN.md §2): on an FPGA a
neuron *is* a configured K-LUT; on a TPU the layer's truth tables live as a
tensor in VMEM and inference is "pack input codes -> gather output codes".

Scattered gathers are slow on TPU (no hardware gather across lanes), so both
gathers are expressed as **one-hot contractions on the MXU**:

  * fan-in gather:  sel[o,k,i] = (indices[o,k] == i); g = sel · codes
  * table gather, two-level (``_table_gather_two_level``): the packed
    entry index splits into a low half, gathered by one batched matmul
    against its one-hot, and a high half, a sqrt(E)-way select.  This
    module's per-layer kernel and both fused kernels (``lut_network``)
    share it; it is exact for codes below ``EXACT_CODE_LIMIT``.
  * table gather, compare: out[b,o] += Σ_e (entry[b,o] == e+off) *
    table[o,e+off], streamed over E in chunks so the compare tensor stays
    inside VMEM.  Integer and exact for any int32 table, at the cost of
    one compare-select per entry; the per-layer kernel keeps it only for
    a layer whose table codes or input codes fall outside the exact bf16
    range (``two_level_exact``, decided from the table on the host).

``lut_lookup_pallas`` takes the gather from the table's layout: a 3-D
(O, n_hi, n_lo) table (``two_level_table``) takes the two-level gather on
a (neuron tiles × batch tiles) grid, batch inner, so each neuron tile's
table is fetched once per layer; a 2-D (O, E) table takes the compare on a
(batch tiles × neuron tiles) grid.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The batch tile every kernel (and the engine) defaults to.  This is the
# single source of truth — ops.py, the plan machinery and the serving tier
# all consume it (re-exported as ``repro.kernels.DEFAULT_BLOCK_B``), so an
# ``ExecutionPlan``'s ``block_b`` is the only other place the tile lives.
DEFAULT_BLOCK_B = 128

# Codes (and table outputs) the one-hot MXU contractions carry exactly: the
# operands are bf16, whose 8-bit significand holds every integer in
# [0, 256].  LogicNets codes are at most 8 bits, so every fused plan admits
# only codes below this; wider codes take the integer per-layer kernel.
EXACT_CODE_LIMIT = 256

# elements of the per-layer kernel's compare tensor per grid step
_COMPARE_ELEMS = 1 << 20

# VMEM bytes the two-level gather's per-step values may take (the f32
# (block_o, n_hi, block_b) partials and the bf16 low one-hot, the fan-in
# gather's product): this caps the per-layer kernel's batch tile
_TWO_LEVEL_STEP_BYTES = 12 << 20


def pack_fan_in_entries(codes: jax.Array, idx: jax.Array,
                        bw_in: int, *, wide: bool = False) -> jax.Array:
    """(bb, I) codes + (bo, FI) indices -> (bo, bb) packed table entries.

    Fan-in gather as a one-hot contraction (MXU), then shift-pack each
    neuron's gathered codes into its table index.  Shared by this
    per-layer kernel and the fused whole-network kernel (lut_network).
    """
    fan_in = idx.shape[1]
    g = gather_fan_in_codes(codes, idx, wide=wide)        # (bo, FI, bb)
    shifts = bw_in * jax.lax.broadcasted_iota(jnp.int32, (fan_in, 1), 0)[:, 0]
    return jnp.sum(g << shifts[None, :, None], axis=1)    # (bo, bb)


def gather_fan_in_codes(codes: jax.Array, idx: jax.Array, *,
                        wide: bool = False) -> jax.Array:
    """(bb, I) codes + (bo, FI) indices -> (bo, FI, bb) gathered codes.

    The fan-in gather as a one-hot MXU contraction — the shared first half
    of both packing conventions (uniform shift and per-element shifts).
    By default the operands are bf16: one MXU pass, exact for every code
    below ``EXACT_CODE_LIMIT`` (the fused plans admit no wider code).
    ``wide`` (the per-layer kernel, which serves any int32 table) runs the
    contraction in f32 at HIGHEST precision instead: exact below 2^24.
    """
    bb, n_in = codes.shape
    bo, fan_in = idx.shape
    dt, prec = ((jnp.float32, jax.lax.Precision.HIGHEST) if wide
                else (jnp.bfloat16, None))
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (n_in, 1), 0)[:, 0]
    sel = (idx[:, :, None] == iota_i[None, None, :]).astype(jnp.float32)
    # (bo*FI, I) x (bb, I) -> (bo*FI, bb), contracting I on both sides
    g = jax.lax.dot_general(
        sel.reshape(bo * fan_in, n_in).astype(dt), codes.astype(dt),
        (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32)
    return g.reshape(bo, fan_in, bb).astype(jnp.int32)


def pack_fan_in_entries_mixed(codes: jax.Array, idx: jax.Array,
                              shifts: jax.Array,
                              widths: jax.Array) -> jax.Array:
    """Mixed-width packing: per-(neuron, element) shifts instead of the
    uniform ``bw_in * k`` ladder.

    ``shifts``/``widths`` are (bo, FI) int32: element k of neuron j lands
    at bits [shifts[j,k], shifts[j,k] + widths[j,k]) of its table entry.
    A width of 0 marks a padded element (neurons below the layer's max
    fan-in) — the mask zeroes its contribution entirely, which is what
    lets the fused mixed-width kernel keep exact ``2^(sum widths)``-entry
    tables with no padding rows.  Real elements always carry codes below
    ``2^width`` (the producing layer's contract), so the mask is a no-op
    for them.
    """
    g = gather_fan_in_codes(codes, idx)                    # (bo, FI, bb)
    g = g & ((1 << widths) - 1)[:, :, None]
    return jnp.sum(g << shifts[:, :, None], axis=1)        # (bo, bb)


def _gather_split(n_entries: int) -> tuple[int, int]:
    """(n_hi, n_lo) of the two-level gather over ``n_entries`` (a power of
    two): entry ``e`` sits at ``[e >> lo_bits, e & (n_lo - 1)]``."""
    lo_bits = (n_entries.bit_length() - 1) // 2
    return n_entries >> lo_bits, 1 << lo_bits


def _table_gather_two_level(entry: jax.Array,
                            table: jax.Array) -> jax.Array:
    """Gather table[o, entry[o, b]] for all (o, b): (bo, bb) -> (bb, bo).

    ``table`` is (bo, n_hi, n_lo) int32 (or bf16), each neuron's table
    already in the host-side (n_hi, n_lo) layout (``_gather_split``).
    Instead of an elementwise compare/select over all table entries, the
    gather splits the packed entry index into low/high halves: the low
    half is gathered with one *batched matmul* against its one-hot (MXU
    work), which collapses the entry axis from E to sqrt(E); the high half
    then costs only an O(B*O*sqrt(E)) elementwise select.  Exact — bf16
    one-hot contractions are exact on codes below ``EXACT_CODE_LIMIT`` —
    at matmul throughput instead of compare/select throughput.  Shared by
    the per-layer kernel here and the uniform and mixed-width fused
    kernels (the entry packing is what differs between them); only a
    per-layer table outside that exact range still takes the compare
    loop (``_kernel``).
    """
    bo, n_hi, n_lo = table.shape
    bb = entry.shape[1]
    lo = entry & (n_lo - 1)
    hi = entry >> (n_lo.bit_length() - 1)

    # batch stays on lanes throughout: both one-hots broadcast the entry
    # halves along sublanes, so no in-kernel relayout is needed
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (bo, n_lo, bb), 1)
    oh_lo = (lo[:, None, :] == lo_iota).astype(jnp.bfloat16)  # (bo,n_lo,bb)
    # (bo, n_hi, n_lo) x (bo, n_lo, bb) -> (bo, n_hi, bb), batched over bo
    part = jax.lax.dot_general(
        table.astype(jnp.bfloat16), oh_lo, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (bo, n_hi, bb), 1)
    out = jnp.sum(jnp.where(hi[:, None, :] == hi_iota, part, 0.0),
                  axis=1)                                    # (bo, bb)
    return out.astype(jnp.int32).T                           # (bb, bo)


def two_level_exact(table, bw_in: int) -> bool:
    """Whether the two-level gather serves this layer exactly: every code
    of the concrete (host) ``table`` lies in [0, ``EXACT_CODE_LIMIT``) and
    every input code, of ``bw_in`` bits, lies below it too."""
    t = np.asarray(table)
    return (1 << int(bw_in) <= EXACT_CODE_LIMIT
            and int(np.min(t, initial=0)) >= 0
            and int(np.max(t, initial=0)) < EXACT_CODE_LIMIT)


def two_level_table(table: jax.Array) -> jax.Array:
    """(O, E) int32 table -> the (O, n_hi, n_lo) bf16 layout the two-level
    gather contracts.  Only for a table ``two_level_exact`` admits: bf16
    holds its codes exactly.  Mosaic cannot split the lane axis inside a
    kernel, so this reshape runs ahead of the ``pallas_call``."""
    n_out, n_entries = table.shape
    return table.reshape(n_out, *_gather_split(n_entries)).astype(
        jnp.bfloat16)


def _two_level_kernel(codes_ref, idx_ref, table_ref, out_ref, *, bw_in: int):
    entry = pack_fan_in_entries(codes_ref[...], idx_ref[...], bw_in)
    out_ref[...] = _table_gather_two_level(entry, table_ref[...])


def _kernel(codes_ref, idx_ref, table_ref, out_ref, *, bw_in: int,
            e_chunk: int):
    codes = codes_ref[...]                      # (bb, I) int32
    idx = idx_ref[...]                          # (bo, FI) int32
    bb = codes.shape[0]
    bo, n_entries = table_ref.shape             # (bo, E) int32

    entry = pack_fan_in_entries(codes, idx, bw_in, wide=True)   # (bo, bb)

    # --- table gather, streamed over entry chunks -------------------------
    n_chunks = n_entries // e_chunk

    def body(c, acc):
        off = pl.multiple_of(c * e_chunk, e_chunk)
        tchunk = table_ref[:, pl.ds(off, e_chunk)]          # (bo, ec)
        eids = off + jax.lax.broadcasted_iota(jnp.int32, (1, e_chunk), 1)
        hit = (entry[:, :, None] == eids[None, :, :])     # (bo, bb, ec)
        return acc + jnp.sum(jnp.where(hit, tchunk[:, None, :], 0), axis=2)

    acc = jnp.zeros((bo, bb), jnp.int32)
    acc = jax.lax.fori_loop(0, n_chunks, body, acc)
    out_ref[...] = acc.T                                   # (bb, bo)


def lut_lookup_pallas(codes: jax.Array, indices: jax.Array, table: jax.Array,
                      bw_in: int, *, block_b: int = DEFAULT_BLOCK_B,
                      block_o: int = 128,
                      e_chunk: int = 512,
                      interpret: bool = False) -> jax.Array:
    """(batch, I) codes -> (batch, O) codes through per-neuron truth tables.

    A 3-D ``table`` (``two_level_table``) takes the two-level gather; a
    2-D (O, E) int32 table takes the compare loop, streamed in
    ``e_chunk``-entry chunks.
    """
    batch, n_in = codes.shape
    n_out, fan_in = indices.shape
    if batch == 0:
        # a zero-size grid (min(block_b, 0) == 0) is ill-formed; the empty
        # result needs no kernel at all
        return jnp.zeros((0, n_out), dtype=jnp.int32)
    block_o = min(block_o, n_out)
    if table.ndim == 3:
        return _lookup_two_level(codes, indices, table, bw_in,
                                 block_b=block_b, block_o=block_o,
                                 interpret=interpret)
    n_entries = table.shape[1]
    e_chunk = min(e_chunk, n_entries)
    # the streamed compare holds a (block_o, block_b, e_chunk) tensor: cap
    # the batch tile so it stays near 4 MiB of the chip's scoped VMEM
    cap = max(8, _COMPARE_ELEMS // (block_o * e_chunk) // 8 * 8)
    block_b = min(block_b, batch, cap)
    # Both are powers of two (entries = 2^(fan_in*bw_in)), so chunks tile
    # the table exactly — required for the streamed compare to be sound.
    assert n_entries % e_chunk == 0, (n_entries, e_chunk)
    grid = (pl.cdiv(batch, block_b), pl.cdiv(n_out, block_o))

    return pl.pallas_call(
        functools.partial(_kernel, bw_in=bw_in, e_chunk=e_chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n_in), lambda b, o: (b, 0)),
            pl.BlockSpec((block_o, fan_in), lambda b, o: (o, 0)),
            pl.BlockSpec((block_o, n_entries), lambda b, o: (o, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda b, o: (b, o)),
        out_shape=jax.ShapeDtypeStruct((batch, n_out), jnp.int32),
        interpret=interpret,
    )(codes, indices, table)


def _lookup_two_level(codes, indices, table, bw_in, *, block_b, block_o,
                      interpret):
    batch, n_in = codes.shape
    n_out, fan_in = indices.shape
    _, n_hi, n_lo = table.shape
    # per batch row, a step holds the f32 partials and their select
    # (2 x n_hi), the bf16 low one-hot and the fan-in gather's f32 product
    # (with its int32 copy) for each of its block_o neurons
    row_bytes = block_o * (8 * n_hi + 2 * n_lo + 8 * fan_in)
    cap = max(8, _TWO_LEVEL_STEP_BYTES // row_bytes // 8 * 8)
    block_b = min(block_b, batch, cap)
    # neuron tiles outer, batch inner: the table block's index stays put
    # across the inner loop, so the pipeline fetches each tile's table once
    grid = (pl.cdiv(n_out, block_o), pl.cdiv(batch, block_b))

    return pl.pallas_call(
        functools.partial(_two_level_kernel, bw_in=bw_in),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n_in), lambda o, b: (b, 0)),
            pl.BlockSpec((block_o, fan_in), lambda o, b: (o, 0)),
            pl.BlockSpec((block_o, n_hi, n_lo), lambda o, b: (o, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda o, b: (b, o)),
        out_shape=jax.ShapeDtypeStruct((batch, n_out), jnp.int32),
        interpret=interpret,
    )(codes, indices, table)
