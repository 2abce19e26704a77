"""Pallas TPU kernel: fused whole-network LogicNets LUT inference.

The paper's deployment claim is that an extreme-throughput LogicNet *is* a
pipeline of LUTs: on the FPGA every layer's truth tables live in fabric and
an activation never leaves the chip between layers — that "no off-chip
round-trip" discipline is what buys sub-microsecond whole-network latency.
The per-layer ``lut_lookup`` kernel violates the analogy on TPU: each layer
is its own ``pallas_call``, so int32 activation codes bounce through HBM
between every pair of layers.

This kernel is the TPU transliteration of the FPGA pipeline.  All layers'
truth tables and fan-in indices are concatenated into two VMEM-resident
slabs (padded to the widest layer, with *static* per-layer shape metadata
compiled into the kernel), the grid runs over batch tiles only, and the
activation codes stay in registers/VMEM from network input to network
output — one ``pallas_call`` for the whole sparse stack, exactly as the
fabric holds the whole net.

Layout — layers are *row-stacked*, not padded to a (L, O_max, ...) box:

  * ``idx_slab``   (sum_l O_l, FI_max) int32 — layer l's fan-in indices in
    rows ``[row_off_l, row_off_l + O_l)``; padding is zero and never read
    (per-layer static slices, offsets compiled into the kernel).
  * ``table_slab`` (sum_l O_l, n_hi, n_lo) int32, or int8 when every
    layer's output codes fit a byte (``bw_out <= 8``) — each neuron's
    E_max-entry row laid out on the host as the (n_hi, n_lo) matrix the
    two-level gather contracts (``E_max = n_hi * n_lo``), so the kernel
    only ever slices whole neurons off the leading axis.  Mosaic cannot
    split the lane axis of an in-kernel vector, which is why the split is
    done here and not in the kernel.  Packed tables are widened in-kernel
    with a mask, quartering the VMEM footprint so deeper stacks stay under
    the budget that ``ops.lut_network`` enforces.

Row-stacking means a narrow layer costs only its own rows — heterogeneous
stacks (and stacks shrunk by ``repro.compile``'s dead-neuron elimination)
get proportionally smaller slabs, where the old box layout paid
``L * O_max`` rows regardless.

Per layer the fan-in gather and the table gather are the per-layer
kernel's own: the one-hot MXU contraction and the *two-level one-hot
gather* (``lut_lookup._table_gather_two_level``), whose bulk is a batched
matmul.  What the fused kernels add is the saved HBM round trips.

Mixed-width layout (``MixedNetworkSlabs`` / ``lut_network_mixed_pallas``)
— the compiler-exact variant of the same engine.  ``repro.compile``'s
dead-input pruning and level-3 re-encoding leave each neuron with its own
per-element input widths and a compact ``2^(sum of widths)``-entry table;
the uniform layout above would pad all of that back to the layer's widest
feature and largest entry count.  The mixed slabs don't:

  * ``idx_slab`` / ``shift_slab`` / ``width_slab`` (sum_l O_l, FI_max)
    int32 — per-(neuron, element) fan-in indices, packed-entry bit
    offsets, and element widths (0 marks fan-in padding), generalizing the
    uniform ``bw_in * k`` shift ladder.
  * ``table_slabs`` — one (rows, n_hi, n_lo) int32 | int8 slab per
    distinct entry count in the stack, each row one neuron's table of
    exactly ``2^(sum of its input widths)`` entries in the two-level
    gather's (n_hi, n_lo) layout; a neuron's row is static, so the packed
    slabs cost byte-for-byte what the netlist's ``table_bytes()``
    accounting proves.

Within a layer neurons are grouped by entry count (equal-size tables are
consecutive rows of one slab, a ``(group, n_hi, n_lo)`` block for the
same batched two-level gather); the group sort permutes the layer's
output bus, which the builder folds into the *next* layer's indices —
only the final layer's permutation survives, undone in-kernel by one
static column shuffle.

Both slab dataclasses split cleanly into *arrays* (the slabs) and
*static, hashable metadata* (``meta`` / ``out_perm`` / ``packed``) —
a deliberate contract the serving engine (``repro.engine``) relies on
twice: its jitted forwards close over the metadata only and take the
slab arrays as arguments (so equal-shaped artifacts share one trace),
and ``CompiledLUTNet.save``/``load`` serialize an artifact as exactly
those arrays plus a JSON record of the metadata, reconstructing the
slabs here without re-running either builder.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lut_lookup import (DEFAULT_BLOCK_B, EXACT_CODE_LIMIT,
                                      _gather_split,
                                      _table_gather_two_level,
                                      pack_fan_in_entries,
                                      pack_fan_in_entries_mixed)


class LayerMeta(NamedTuple):
    """Static per-layer shape metadata (compiled into the kernel)."""

    n_out: int
    fan_in: int
    n_entries: int
    bw_in: int


@dataclasses.dataclass(frozen=True)
class NetworkSlabs:
    """A whole sparse stack packed for single-kernel execution.

    Arrays + static metadata only (see module docstring): constructing
    one from deserialized arrays — or from tracers inside a jitted
    wrapper — is supported and is how ``repro.engine`` serves and
    round-trips artifacts without rebuilding slabs.
    """

    idx_slab: jax.Array      # (sum_l O_l, FI_max) int32
    table_slab: jax.Array    # (sum_l O_l, n_hi, n_lo) int32 | int8 (packed)
    meta: tuple[LayerMeta, ...]
    packed: bool

    @property
    def n_layers(self) -> int:
        return len(self.meta)

    @property
    def n_out(self) -> int:
        return self.meta[-1].n_out

    def vmem_bytes(self) -> int:
        return (self.idx_slab.size * self.idx_slab.dtype.itemsize
                + self.table_slab.size * self.table_slab.dtype.itemsize)

    def vmem_breakdown(self) -> dict:
        """Per-slab VMEM bytes (bench / fused-fallback diagnostics)."""
        idx = self.idx_slab.size * self.idx_slab.dtype.itemsize
        tab = self.table_slab.size * self.table_slab.dtype.itemsize
        return {"idx_slab_bytes": idx, "table_slab_bytes": tab,
                "total_bytes": idx + tab, "packed_int8": self.packed}


def estimate_slab_bytes(layers: Sequence[tuple],
                        pack: bool | None = None) -> tuple[int, bool, bool]:
    """Projected fused-slab footprint, int8-pack and f32-exact eligibility.

    Computed from shapes plus one pass of min/max over the tables (no
    copies) — lets ``ops.lut_network`` decide *before* paying for slab
    construction it would discard on the per-layer fallback path.  Returns
    ``(bytes, pack, f32_exact)``; ``f32_exact`` is False when any output
    code is outside [0, ``EXACT_CODE_LIMIT``), or any input code may be
    (``bw_in`` over 8), where the kernel's bf16 one-hot gathers would
    round.  ``pack`` follows
    ``build_network_slabs``: None auto-packs
    when every code fits a byte; an explicit value costs that choice
    instead (the plan machinery uses this to price pack on/off variants).
    """
    o_sum = sum(np.asarray(t).shape[0] for _, t, _ in layers)
    fi_max = max(np.asarray(i).shape[1] for i, _, _ in layers)
    e_max = max(np.asarray(t).shape[1] for _, t, _ in layers)
    lo_hi = [(int(np.min(t, initial=0)), int(np.max(t, initial=0)))
             for _, t, _ in layers]
    byte_ok = all(lo >= 0 and hi < 256 for lo, hi in lo_hi)
    f32_exact = (all(lo >= 0 and hi < EXACT_CODE_LIMIT for lo, hi in lo_hi)
                 and _inputs_exact(bw for _, _, bw in layers))
    use_pack = _resolve_pack(byte_ok, pack)
    table_itemsize = 1 if use_pack else 4
    return (o_sum * fi_max * 4
            + o_sum * e_max * table_itemsize), use_pack, f32_exact


def _tile_bytes(shape: tuple[int, ...], itemsize: int) -> int:
    """VMEM bytes of an array laid out in TPU tiles: the minor dim rounds
    up to 128 lanes, the second-minor to a tile's sublanes (8 for 32-bit,
    16 for bf16, 32 for int8)."""
    *lead, rows, cols = shape
    sub = 32 // itemsize
    return (math.prod(lead) * -(-rows // sub) * sub * -(-cols // 128) * 128
            * itemsize)


def _group_step_bytes(g: int, fan_in: int, n_in: int, n_hi: int, n_lo: int,
                      block_b: int) -> int:
    """VMEM the values of one neuron group's step occupy when all are live:
    the fan-in one-hot (f32 and its bf16 copy) and its product, the
    widened table (int32 and bf16), the low one-hot and the two
    (g, n_hi, block_b) f32 partials of the two-level gather."""
    return (_tile_bytes((g * fan_in, n_in), 4)
            + _tile_bytes((g * fan_in, n_in), 2)
            + 2 * _tile_bytes((g * fan_in, block_b), 4)
            + _tile_bytes((g, n_hi, n_lo), 4)
            + _tile_bytes((g, n_hi, n_lo), 2)
            + _tile_bytes((g, n_lo, block_b), 2)
            + 2 * _tile_bytes((g, n_hi, block_b), 4))


def kernel_vmem_bytes(layers, block_b: int, pack: bool) -> int:
    """Conservative VMEM bound for one fused kernel at batch tile
    ``block_b``, from shapes alone (no slab is built).

    Counts the double-buffered, tile-padded slab and code blocks plus the
    largest neuron group's step with all its values live at once.  The
    slab bytes that ``estimate_slab_bytes`` reports are what the tables
    cost; this is what the kernel needs around them.  Mosaic may schedule
    tighter than this bound, but a stack within it compiles in seconds:
    the kernel unrolls one batched matmul per neuron, so stacks of
    thousands of wide neurons also take minutes to compile.  ``layers``
    is the uniform triple list or a ``MixedLayerTables`` sequence.
    """
    layers = list(layers)
    table_item = 1 if pack else 4
    mixed = hasattr(layers[0], "entry_bits")
    steps = []                      # (g, fan_in, n_in, n_hi, n_lo)
    slabs = 0
    n_in = n_in0 = int(np.max(np.asarray(
        layers[0].indices if mixed else layers[0][0]), initial=0)) + 1
    if mixed:
        for L in layers:
            fi = L.indices.shape[1]
            bits, counts = np.unique(np.asarray(L.entry_bits),
                                     return_counts=True)
            for b, g in zip(bits, counts):
                n_hi, n_lo = _gather_split(1 << int(b))
                steps.append((int(g), fi, n_in, n_hi, n_lo))
                slabs += _tile_bytes((int(g), n_hi, n_lo), table_item)
            n_in = L.indices.shape[0]
        o_sum = sum(L.indices.shape[0] for L in layers)
        fi_max = max(L.indices.shape[1] for L in layers)
        slabs += 3 * _tile_bytes((o_sum, fi_max), 4)
    else:
        e_max = max(np.asarray(t).shape[1] for _, t, _ in layers)
        n_hi_max, n_lo = _gather_split(e_max)
        for idx, tab, _ in layers:
            o, n_e = np.asarray(tab).shape
            steps.append((o, np.asarray(idx).shape[1], n_in,
                          -(-n_e // n_lo), n_lo))
            n_in = o
        o_sum = sum(s[0] for s in steps)
        fi_max = max(s[1] for s in steps)
        slabs += (_tile_bytes((o_sum, fi_max), 4)
                  + _tile_bytes((o_sum, n_hi_max, n_lo), table_item))
    io = _tile_bytes((block_b, n_in0), 4) + _tile_bytes((block_b, n_in), 4)
    return 2 * (slabs + io) + max(_group_step_bytes(*s, block_b)
                                  for s in steps)


def _resolve_pack(byte_ok: bool, pack: bool | None) -> bool:
    """One pack policy for both slab builders: None auto-packs when every
    code fits an unsigned byte; an explicit True outside that range must
    raise — the int8 store would silently wrap codes >= 256 (uint8 cast).
    """
    if pack is None:
        return byte_ok
    if pack and not byte_ok:
        raise ValueError(
            "pack=True stores table codes as unsigned bytes; these tables "
            "hold codes outside [0, 256) — use pack=None (auto) or "
            "pack=False")
    return pack


def build_network_slabs(layers: Sequence[tuple], *,
                        pack: bool | None = None) -> NetworkSlabs:
    """Pack per-layer ``(indices, table, bw_in)`` triples into fused slabs.

    ``pack=None`` (auto) stores the table slab as int8 whenever every
    layer's output codes fit an unsigned byte — true for any LogicNets
    topology with ``bw_out <= 8``.  Host-side (numpy): tables come straight
    from ``LayerTruthTable`` generation.
    """
    if not layers:
        raise ValueError("fused network needs at least one layer")
    metas = []
    idx_np, tab_np = [], []
    for indices, table, bw_in in layers:
        idx = np.asarray(indices, dtype=np.int32)
        tab = np.asarray(table, dtype=np.int32)
        m = LayerMeta(tab.shape[0], idx.shape[1], tab.shape[1], int(bw_in))
        if m.n_entries != 1 << (m.fan_in * m.bw_in):
            raise ValueError(
                f"table has {m.n_entries} entries; fan_in={m.fan_in} at "
                f"bw_in={m.bw_in} requires 2^{m.fan_in * m.bw_in}")
        metas.append(m)
        idx_np.append(idx)
        tab_np.append(tab)
    o_sum = sum(m.n_out for m in metas)
    fi_max = max(m.fan_in for m in metas)
    e_max = max(m.n_entries for m in metas)

    idx_slab = np.zeros((o_sum, fi_max), dtype=np.int32)
    in_range = all(int(t.max(initial=0)) < 256 and int(t.min(initial=0)) >= 0
                   for t in tab_np)
    pack = _resolve_pack(in_range, pack)
    _check_exact(in_range and _inputs_exact(m.bw_in for m in metas))
    tab_dtype = np.int8 if pack else np.int32
    table_slab = np.zeros((o_sum, e_max), dtype=tab_dtype)
    row = 0
    for idx, tab, m in zip(idx_np, tab_np, metas):
        idx_slab[row:row + m.n_out, :m.fan_in] = idx
        table_slab[row:row + m.n_out, :m.n_entries] = (
            tab.astype(np.uint8).view(np.int8) if pack else tab)
        row += m.n_out
    table_slab = table_slab.reshape(o_sum, *_gather_split(e_max))
    return NetworkSlabs(jnp.asarray(idx_slab), jnp.asarray(table_slab),
                        tuple(metas), bool(pack))


def _inputs_exact(widths) -> bool:
    """Whether every input code, of at most these bit widths, stays below
    ``EXACT_CODE_LIMIT`` through the fan-in one-hot gather."""
    return all(1 << int(w) <= EXACT_CODE_LIMIT for w in widths)


def _check_exact(in_range: bool) -> None:
    if not in_range:
        raise ValueError(
            "fused kernels gather codes through bf16 one-hot contractions, "
            f"exact only for input and output codes in [0, "
            f"{EXACT_CODE_LIMIT}) — use the per-layer path (fused=False) "
            "for wider codes")


def _kernel(codes_ref, idx_ref, table_ref, out_ref, *,
            meta: tuple[LayerMeta, ...], packed: bool):
    h = codes_ref[...]                                       # (bb, I0)
    n_lo = table_ref.shape[2]
    # Static unroll: each layer reads its (unpadded) row-slice of the slabs
    # and hands its output codes straight to the next layer — no HBM in
    # between.  Row offsets are compile-time constants.
    row = 0
    for m in meta:
        idx = idx_ref[row:row + m.n_out, :m.fan_in]
        # a layer narrower than E_max needs only its leading hi rows
        table = table_ref[row:row + m.n_out, :-(-m.n_entries // n_lo)]
        if packed:
            table = table.astype(jnp.int32) & 0xFF
        entry = pack_fan_in_entries(h, idx, m.bw_in)         # (bo, bb)
        h = _table_gather_two_level(entry, table)
        row += m.n_out
    out_ref[...] = h


def lut_network_pallas(codes: jax.Array, slabs: NetworkSlabs, *,
                       block_b: int = DEFAULT_BLOCK_B,
                       interpret: bool = False) -> jax.Array:
    """Whole sparse stack in one kernel: (batch, I0) -> (batch, O_last)."""
    batch, n_in = codes.shape
    if batch == 0:
        # a zero-size grid (min(block_b, 0) == 0) is ill-formed; the empty
        # result needs no kernel at all
        return jnp.zeros((0, slabs.n_out), dtype=jnp.int32)
    o_sum, fi_max = slabs.idx_slab.shape
    block_b = min(block_b, batch)
    grid = (pl.cdiv(batch, block_b),)

    return pl.pallas_call(
        functools.partial(_kernel, meta=slabs.meta, packed=slabs.packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n_in), lambda b: (b, 0)),
            pl.BlockSpec((o_sum, fi_max), lambda b: (0, 0)),
            pl.BlockSpec(slabs.table_slab.shape, lambda b: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, slabs.n_out), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, slabs.n_out), jnp.int32),
        interpret=interpret,
    )(codes, slabs.idx_slab, slabs.table_slab)


# ---------------------------------------------------------------------------
# Mixed-width fused path: compiler-exact slabs (no padding to the widest
# feature / largest entry count) — see the module docstring's second half.
# ---------------------------------------------------------------------------


class MixedGroupMeta(NamedTuple):
    """One equal-entry-count neuron group inside a layer (static).

    ``offs`` (static, per neuron of the group) holds each neuron's row in
    the table slab of its entry count when row-dedup shared storage
    across neurons — identical tables (CSE'd neurons replicated for
    consumers in different layers, duplicated output heads, constant
    neurons) point at one copy.  None = contiguous layout: the group's
    tables are the next ``n_out`` rows of that slab.
    """

    n_out: int
    entry_bits: int
    offs: tuple[int, ...] | None = None


class MixedLayerMeta(NamedTuple):
    """Static per-layer shape metadata for the mixed-width kernel."""

    n_out: int
    fan_in: int
    groups: tuple[MixedGroupMeta, ...]


@dataclasses.dataclass(frozen=True)
class MixedNetworkSlabs:
    """A sparse stack packed at its exact compiled table footprint.

    ``out_perm`` is the static gather that undoes the final layer's
    group-sort: ``result[:, j] == kernel_bus[:, out_perm[j]]`` (None when
    the sort was the identity).  Intermediate layers need no fixup — the
    builder rewrote each layer's fan-in indices against its producer's
    permuted bus.
    """

    idx_slab: jax.Array      # (sum_l O_l, FI_max) int32
    shift_slab: jax.Array    # (sum_l O_l, FI_max) int32
    width_slab: jax.Array    # (sum_l O_l, FI_max) int32
    # one (rows, n_hi, n_lo) int32 | int8 slab per entry count, ascending
    # (``table_classes(meta)``)
    table_slabs: tuple[jax.Array, ...]
    meta: tuple[MixedLayerMeta, ...]
    out_perm: tuple[int, ...] | None
    packed: bool
    # table entries elided by build-time row dedup (identical tables
    # share one stored copy); 0 when no duplicates existed or dedup was
    # off — the slab arrays are then byte-identical to the legacy layout
    dedup_entries_saved: int = 0

    @property
    def n_layers(self) -> int:
        return len(self.meta)

    @property
    def n_out(self) -> int:
        return self.meta[-1].n_out

    def vmem_bytes(self) -> int:
        return sum(s.size * s.dtype.itemsize
                   for s in (self.idx_slab, self.shift_slab,
                             self.width_slab, *self.table_slabs))

    def vmem_breakdown(self) -> dict:
        """Per-slab VMEM bytes (bench / fused-fallback diagnostics).

        ``table_slab_bytes`` is the headline: with ``packed_int8`` it
        equals the netlist's exact per-neuron ``table_bytes()`` accounting
        for codes <= 8 bits — the fused path banks byte-for-byte what the
        compiler proved.
        """
        idx = self.idx_slab.size * self.idx_slab.dtype.itemsize
        sh = self.shift_slab.size * self.shift_slab.dtype.itemsize
        wd = self.width_slab.size * self.width_slab.dtype.itemsize
        tab = sum(t.size * t.dtype.itemsize for t in self.table_slabs)
        return {"idx_slab_bytes": idx, "shift_slab_bytes": sh,
                "width_slab_bytes": wd, "table_slab_bytes": tab,
                "total_bytes": idx + sh + wd + tab,
                "packed_int8": self.packed, "layout": "mixed"}


def _mixed_lo_hi(layers) -> tuple[int, int]:
    lo = min((int(t.min()) for L in layers for t in L.tables if t.size),
             default=0)
    hi = max((int(t.max()) for L in layers for t in L.tables if t.size),
             default=0)
    return lo, hi


def _mixed_inputs_exact(layers) -> bool:
    return _inputs_exact(np.max(L.elem_widths, initial=0) for L in layers)


def estimate_mixed_slab_bytes(layers,
                              pack: bool | None = None
                              ) -> tuple[int, bool, bool]:
    """Projected mixed-slab footprint, int8-pack and f32-exact eligibility.

    ``layers`` is a ``MixedLayerTables`` sequence (``repro.compile``'s
    ``CNet.to_mixed_tables`` lowering).  The table slab costs exactly the
    stack's total table entries (1 or 4 bytes each); the metadata adds
    three (sum O, FI_max) int32 slabs (indices, shifts, widths).  Same
    contract as ``estimate_slab_bytes``: lets the plan machinery decide
    before any slab is built, with ``pack`` forcing the on/off choice
    (None auto-packs when every code fits a byte).  The estimate is a
    pre-dedup *upper bound*: ``build_mixed_network_slabs``'s row dedup
    can only shrink the table slab below it (by
    ``dedup_entries_saved`` entries), never exceed it.
    """
    o_sum = sum(L.indices.shape[0] for L in layers)
    fi_max = max(L.indices.shape[1] for L in layers)
    entries = sum(L.n_entries for L in layers)
    lo, hi = _mixed_lo_hi(layers)
    use_pack = _resolve_pack(lo >= 0 and hi < 256, pack)
    f32_exact = lo >= 0 and hi < EXACT_CODE_LIMIT and _mixed_inputs_exact(
        layers)
    return (3 * o_sum * fi_max * 4
            + entries * (1 if use_pack else 4)), use_pack, f32_exact


def build_mixed_network_slabs(layers, *, pack: bool | None = None,
                              dedup: bool = True) -> MixedNetworkSlabs:
    """Pack ``MixedLayerTables`` into compiler-exact fused slabs.

    Host-side (numpy).  Within each layer, neurons are stably sorted by
    entry count so equal-size tables form contiguous groups (one batched
    two-level gather each); the sort permutes the layer's output bus, so
    the next layer's fan-in indices are rewritten against the permuted
    order and only the final layer's permutation is kept (``out_perm``)
    for the kernel to undo.  ``pack`` follows ``build_network_slabs``:
    None auto-packs to int8 when every code fits a byte, True validates
    the byte range and raises instead of wrapping.

    ``dedup=True`` content-dedups identical table rows across the whole
    slab: neurons with byte-identical tables (same entry count, same
    codes) share one stored copy, with each group's per-neuron slab
    rows recorded in ``MixedGroupMeta.offs`` for the kernel's static
    reconstruction.  This catches what netlist-level CSE cannot merge —
    same-function neurons wired to *different* input indices, and
    replicated final-layer heads — on top of compiler-merged neurons
    whose consumers span layers.  When no duplicate exists the layout
    (and the serialized artifact) is byte-identical to ``dedup=False``.
    """
    layers = list(layers)
    if not layers:
        raise ValueError("fused network needs at least one layer")
    lo, hi = _mixed_lo_hi(layers)
    pack = _resolve_pack(lo >= 0 and hi < 256, pack)
    _check_exact(lo >= 0 and hi < EXACT_CODE_LIMIT
                 and _mixed_inputs_exact(layers))

    fi_max = max(L.indices.shape[1] for L in layers)
    layer_meta_rows = []           # (o, fi, group boundaries, slab rows)
    idx_rows, shift_rows, width_rows = [], [], []
    class_rows: dict[int, list[np.ndarray]] = {}   # entry bits -> tables
    seen: dict[tuple[int, bytes], int] = {}   # table content -> slab row
    entries_total = 0
    any_dup = False
    inv_prev: np.ndarray | None = None   # prev bus: old feature -> new pos
    for L in layers:
        o = L.indices.shape[0]
        fi = L.indices.shape[1]
        idx = np.asarray(L.indices, dtype=np.int32)
        if inv_prev is not None:
            idx = inv_prev[idx].astype(np.int32)
        eb = np.asarray(L.entry_bits, dtype=np.int64)
        order = np.argsort(eb, kind="stable")
        idx = idx[order]
        shifts = np.asarray(L.shifts, dtype=np.int32)[order]
        widths = np.asarray(L.elem_widths, dtype=np.int32)[order]
        eb = eb[order]
        bounds = []
        start = 0
        for j in range(1, o + 1):
            if j == o or eb[j] != eb[start]:
                bounds.append((start, j, int(eb[start])))
                start = j
        offs = []
        for j, src in enumerate(order):
            t = np.asarray(L.tables[src], dtype=np.int32)
            if t.shape[0] != 1 << int(eb[j]):
                raise ValueError(
                    f"neuron table has {t.shape[0]} entries; its element "
                    f"widths sum to {int(eb[j])} bits and require "
                    f"2^{int(eb[j])}")
            entries_total += t.shape[0]
            rows = class_rows.setdefault(int(eb[j]), [])
            off = seen.get((t.shape[0], t.tobytes())) if dedup else None
            if off is None:
                off = len(rows)
                if dedup:
                    seen[(t.shape[0], t.tobytes())] = off
                rows.append(t)
            else:
                any_dup = True
            offs.append(off)
        pad = np.zeros((o, fi_max - fi), dtype=np.int32)
        idx_rows.append(np.concatenate([idx, pad], axis=1))
        shift_rows.append(np.concatenate([shifts, pad], axis=1))
        width_rows.append(np.concatenate([widths, pad], axis=1))
        layer_meta_rows.append((o, fi, bounds, offs))
        inv_prev = np.argsort(order)
    # offs only materialize when a duplicate actually exists, so a
    # dup-free build stays byte-identical (slabs, meta, artifact) to the
    # legacy contiguous layout
    metas = tuple(
        MixedLayerMeta(o, fi, tuple(
            MixedGroupMeta(e - s, ebits,
                           tuple(offs[s:e]) if any_dup else None)
            for s, e, ebits in bounds))
        for o, fi, bounds, offs in layer_meta_rows)
    table_slabs = []
    for ebits in sorted(class_rows):
        t = np.stack(class_rows[ebits]).reshape(
            -1, *_gather_split(1 << ebits))
        table_slabs.append(jnp.asarray(
            t.astype(np.uint8).view(np.int8) if pack else t))
    out_perm = (None if np.array_equal(inv_prev, np.arange(len(inv_prev)))
                else tuple(int(p) for p in inv_prev))
    stored = sum(int(t.size) for t in table_slabs)
    return MixedNetworkSlabs(
        jnp.asarray(np.concatenate(idx_rows)),
        jnp.asarray(np.concatenate(shift_rows)),
        jnp.asarray(np.concatenate(width_rows)),
        tuple(table_slabs), metas, out_perm, bool(pack),
        dedup_entries_saved=entries_total - stored)


def table_classes(meta: tuple[MixedLayerMeta, ...]) -> tuple[int, ...]:
    """Entry bits of each table slab, in ``table_slabs`` order."""
    return tuple(sorted({g.entry_bits for m in meta for g in m.groups}))


def _mixed_kernel(codes_ref, idx_ref, shift_ref, width_ref, *refs,
                  meta: tuple[MixedLayerMeta, ...],
                  packed: bool, out_perm: tuple[int, ...] | None):
    table_refs = dict(zip(table_classes(meta), refs[:-1]))
    out_ref = refs[-1]
    next_row = dict.fromkeys(table_refs, 0)
    h = codes_ref[...]                                       # (bb, I0)
    # Static unroll over layers and, within a layer, over equal-entry-count
    # neuron groups: each group reads its exact rows of the slabs (all
    # compile-time constants) and runs the same batched two-level gather as
    # the uniform kernel — activation codes never leave VMEM.
    row = 0
    for m in meta:
        parts = []
        for g in m.groups:
            idx = idx_ref[row:row + g.n_out, :m.fan_in]
            sh = shift_ref[row:row + g.n_out, :m.fan_in]
            wd = width_ref[row:row + g.n_out, :m.fan_in]
            table_ref = table_refs[g.entry_bits]
            if g.offs is None:
                r = next_row[g.entry_bits]
                table = table_ref[r:r + g.n_out]
                next_row[g.entry_bits] = r + g.n_out
            else:
                # row-dedup layout: per-neuron static rows.  Consecutive
                # rows (the common case — dedup leaves most runs
                # contiguous) are merged into single slices so the
                # unrolled program stays near the contiguous size.
                blocks = []
                i = 0
                while i < len(g.offs):
                    j = i
                    while (j + 1 < len(g.offs)
                           and g.offs[j + 1] == g.offs[j] + 1):
                        j += 1
                    blocks.append(table_ref[g.offs[i]:g.offs[j] + 1])
                    i = j + 1
                table = (blocks[0] if len(blocks) == 1
                         else jnp.concatenate(blocks, axis=0))
            if packed:
                table = table.astype(jnp.int32) & 0xFF
            entry = pack_fan_in_entries_mixed(h, idx, sh, wd)
            parts.append(_table_gather_two_level(entry, table))
            row += g.n_out
        h = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if out_perm is not None:
        # undo the final layer's group-sort: a static column shuffle
        # (compile-time slice per output) — Pallas kernels cannot capture
        # array constants, and a dynamic gather would be the slow path on
        # TPU anyway
        h = jnp.concatenate([h[:, p:p + 1] for p in out_perm], axis=1)
    out_ref[...] = h


def lut_network_mixed_pallas(codes: jax.Array, slabs: MixedNetworkSlabs, *,
                             block_b: int = DEFAULT_BLOCK_B,
                             interpret: bool = False) -> jax.Array:
    """Whole sparse stack, compiler-exact slabs: (batch, I0) -> (batch, O)."""
    batch, n_in = codes.shape
    if batch == 0:
        # same empty-batch edge as lut_network_pallas: no kernel to launch
        return jnp.zeros((0, slabs.n_out), dtype=jnp.int32)
    o_sum, fi_max = slabs.idx_slab.shape
    block_b = min(block_b, batch)
    grid = (pl.cdiv(batch, block_b),)

    return pl.pallas_call(
        functools.partial(_mixed_kernel, meta=slabs.meta,
                          packed=slabs.packed, out_perm=slabs.out_perm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n_in), lambda b: (b, 0)),
            pl.BlockSpec((o_sum, fi_max), lambda b: (0, 0)),
            pl.BlockSpec((o_sum, fi_max), lambda b: (0, 0)),
            pl.BlockSpec((o_sum, fi_max), lambda b: (0, 0)),
            *[pl.BlockSpec(t.shape, lambda b: (0, 0, 0))
              for t in slabs.table_slabs],
        ],
        out_specs=pl.BlockSpec((block_b, slabs.n_out), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, slabs.n_out), jnp.int32),
        interpret=interpret,
    )(codes, slabs.idx_slab, slabs.shift_slab, slabs.width_slab,
      *slabs.table_slabs)
