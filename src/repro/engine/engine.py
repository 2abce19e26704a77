"""Compile-once serving artifact: ``compile_network(...) -> CompiledLUTNet``.

The paper's whole point is extreme-throughput inference — a LogicNet is a
pipeline of LUTs serving one input per clock — yet the legacy keyword-flag
API (``fused=`` / ``optimize_level=`` on four different entry points)
re-ran the truth-table compiler, rebuilt the VMEM slabs host-side and
re-traced the Pallas kernel on *every* call.  This module is the
ahead-of-time half of the deployment story:

    from repro import engine
    net = engine.compile_network(tables, optimize_level=3,
                                 in_features=cfg.in_features)
    out = net(codes)              # jitted, zero re-trace, zero re-compile
    net.plan                      # the ExecutionPlan that chose the layout
    net.stats                     # CompileStats from the one optimize run
    net.vmem_breakdown()          # per-slab VMEM bytes
    net.save("model_a.npz")       # deployment skips the compiler entirely
    net2 = engine.load("model_a.npz")   # exact same slabs, bit-exact

``compile_network`` runs ``repro.compile.optimize`` ONCE, costs both slab
layouts through ``kernels.ops.fused_plan``, builds the chosen slabs
(mixed-width, uniform, or the per-layer fallback) ONCE, and serves every
subsequent call through a shared jitted forward.

Batch-shape robustness: the forward functions are jitted with *static*
``block_b`` and every call pads its batch up to the next ``block_b``
multiple (sliced back afterwards), so a serving loop with ragged batch
sizes hits one trace per ``block_b`` bucket instead of one per distinct
batch size.  The jitted forwards take the slab arrays as *arguments*
(static metadata only is closed over), so two artifacts with the same
shapes — e.g. a live artifact and its ``save``/``load`` round-trip —
share a single trace.

Serialization rides the checkpoint manifest machinery
(``checkpoint.ckpt.save_arrays`` / ``load_arrays``): one ``.npz`` holding
the slab arrays plus a JSON metadata record (layout, static per-layer
shape metadata, the ExecutionPlan — variant, source and autotune timing
table — and the CompileStats of the build).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint.ckpt import load_arrays, save_arrays
from repro.compile.pipeline import CompileStats, OptimizeResult
from repro.engine.autotune import ExecutionPlan, autotune_network
from repro.kernels import ref
from repro.kernels.lut_lookup import (DEFAULT_BLOCK_B, lut_lookup_pallas,
                                      two_level_exact, two_level_table)
from repro.kernels.lut_network import (LayerMeta, MixedGroupMeta,
                                       MixedLayerMeta, MixedNetworkSlabs,
                                       NetworkSlabs,
                                       build_mixed_network_slabs,
                                       build_network_slabs,
                                       lut_network_mixed_pallas,
                                       lut_network_pallas, table_classes)
from repro.kernels.ops import interpret_mode
from repro.kernels.plan import (FUSED_VMEM_BUDGET_BYTES, FusedPlan,
                                fused_plan)

# format 2 (ExecutionPlan refactor): meta["plan"] is the full ExecutionPlan
# record (variant + autotune timing table); format-1 artifacts carried the
# bare FusedPlan and load() synthesizes their default plan.
# format 3 (slab row-dedup): mixed layer_meta groups may carry a third
# element — the per-neuron table rows of shared tables — plus
# meta["dedup_entries_saved"]
# format 4 (chip table layout): table slabs are 3-D (rows, n_hi, n_lo);
# the mixed layout stores one slab per entry count ("table_slab_b<bits>")
FORMAT_VERSION = 4
ARTIFACT_KIND = "repro.engine.CompiledLUTNet"

# process-wide count of optimize() runs issued by this module; serving
# tests and the bench's `serving` section assert it stays flat after
# warmup ("zero compiler re-runs")
_compile_runs = 0

# registry-backed build metrics (docs/observability.md): what the engine
# decided (layout), what it cost (compiler runs, slab build time), and
# how the legacy-flag memo behaves (hits/misses)
_M_COMPILER_RUNS = obs.registry().counter(
    "engine_compiler_runs_total",
    "truth-table compiler invocations issued by the engine")
_M_BUILDS = obs.registry().counter(
    "engine_builds_total", "CompiledLUTNet builds by chosen layout",
    labels=("layout",))
_M_PER_LAYER_GATHER = obs.registry().counter(
    "engine_per_layer_gather_total",
    "layers of per-layer artifacts by the table gather their kernel takes",
    labels=("gather",))
_M_CALLS = obs.registry().counter(
    "engine_calls_total",
    "CompiledLUTNet calls by where the batch came from: host (put flat) "
    "or device (served as it is)",
    labels=("input",))
_M_SLAB_BUILD = obs.registry().histogram(
    "engine_slab_build_seconds",
    "host-side slab construction time per compile_network build")
_M_MEMO_HITS = obs.registry().counter(
    "engine_memo_hits_total",
    "cached_compile hits (legacy flag calls served from the memo)")
_M_MEMO_MISSES = obs.registry().counter(
    "engine_memo_misses_total",
    "cached_compile misses (legacy flag calls that built an artifact)")
_M_LOADS = obs.registry().counter(
    "engine_artifact_loads_total",
    "CompiledLUTNet artifacts rebuilt from disk via engine.load")


def compile_runs() -> int:
    """How many times this module has invoked the truth-table compiler."""
    return _compile_runs


# ---------------------------------------------------------------------------
# Shared jitted forwards — one per layout, keyed on (shapes, static meta).
#
# The slab arrays are jit *arguments*, not closure constants: every artifact
# with the same shapes and static metadata (including a save/load round-trip
# of the same model) reuses one trace, and a fresh artifact for a new model
# costs exactly one trace per block_b bucket.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("meta", "packed", "block_b",
                                             "interpret"))
def _uniform_forward(codes, idx_slab, table_slab, *, meta, packed, block_b,
                     interpret):
    slabs = NetworkSlabs(idx_slab, table_slab, meta, packed)
    return lut_network_pallas(codes, slabs, block_b=block_b,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("meta", "out_perm", "packed",
                                             "block_b", "interpret"))
def _mixed_forward(codes, idx_slab, shift_slab, width_slab, table_slabs, *,
                   meta, out_perm, packed, block_b, interpret):
    slabs = MixedNetworkSlabs(idx_slab, shift_slab, width_slab, table_slabs,
                              meta, out_perm, packed)
    return lut_network_mixed_pallas(codes, slabs, block_b=block_b,
                                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bws", "two_level", "block_b",
                                             "interpret"))
def _per_layer_forward(codes, idx_tabs, *, bws, two_level, block_b,
                       interpret):
    for (idx, tab), bw, two in zip(idx_tabs, bws, two_level):
        if two:
            tab = two_level_table(tab)
        codes = lut_lookup_pallas(codes, idx, tab, bw, block_b=block_b,
                                  interpret=interpret)
    return codes


@functools.partial(jax.jit, static_argnames=("bws",))
def _reference_forward(codes, idx_tabs, *, bws):
    for (idx, tab), bw in zip(idx_tabs, bws):
        codes = ref.lut_lookup_ref(codes, idx, tab, bw)
    return codes


_FORWARDS = {"uniform": _uniform_forward, "mixed": _mixed_forward,
             "per_layer": _per_layer_forward, "reference": _reference_forward}


# ---------------------------------------------------------------------------
# The host-batch entry.  A TPU lays a narrow int32 matrix out column-major
# by default (``s32[8192,784]{0,1}``), so putting a host batch as it is
# makes PJRT transpose it on the host and the forward copy it back to
# row-major on the device.  A 1-D array's device layout is the host's byte
# order: the batch crosses flat and is unflattened on the device.  The
# result keeps its default layout: pinned row-major, a result narrower
# than 128 lanes is padded to them and fetched slower (jsc-a's 64-wide
# codes, on a v5e).  The layout's forward stays a jitted function of its
# own inside the entry, so its kernels keep their names.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("layout", "static", "n_in"))
def _host_forward(flat, arrays, *, layout, static, n_in):
    return _FORWARDS[layout](flat.reshape(-1, n_in), *arrays, **dict(static))


@dataclasses.dataclass(frozen=True)
class CompiledLUTNet:
    """An ahead-of-time compiled LUT network, ready to serve.

    ``layout`` is the execution path ``compile_network`` chose:

    * ``"mixed"``   — the fused mixed-width kernel over compiler-exact
      slabs (what ``optimize_level=`` + ``fused=True`` executes);
    * ``"uniform"`` — the fused kernel over row-stacked uniform slabs;
    * ``"per_layer"`` — one ``lut_lookup`` Pallas call per layer (the
      over-VMEM-budget / non-f32-exact fallback, still one jitted chain);
    * ``"reference"`` — the plain-jnp per-layer oracle (``use_pallas=False``
      compatibility; jitted but kernel-free).

    Exactly one of ``slabs`` / ``layers`` is populated.  ``two_level``
    (per-layer layout only) says, per layer, whether its kernel takes the
    two-level MXU gather or the integer compare loop, as
    ``per_layer_gathers`` decided from the tables at build time.  ``plan`` is the
    :class:`~repro.engine.autotune.ExecutionPlan` that made the decision
    — heuristic, autotuned or synthesized from a pre-autotune artifact;
    its compat properties (``plan.reason``, ``plan.slab_bytes``, ...)
    keep the old bare-``FusedPlan`` surface working, and ``layout`` /
    ``block_b`` here always mirror ``plan.layout`` / ``plan.block_b``.
    ``stats`` is the ``CompileStats`` of the single
    ``repro.compile.optimize`` run (None when the build skipped the
    compiler).  The artifact is bit-exact with
    ``table_infer.network_table_forward`` on the stack it was built from.
    """

    layout: str
    n_in: int
    n_out: int
    block_b: int
    plan: ExecutionPlan
    stats: CompileStats | None
    slabs: NetworkSlabs | MixedNetworkSlabs | None = None
    layers: tuple[tuple[jax.Array, jax.Array, int], ...] | None = None
    two_level: tuple[bool, ...] | None = None

    def __call__(self, codes) -> jax.Array:
        """(batch, n_in) int codes -> (batch, n_out) int32 codes.

        Ragged batches are padded up to the next ``block_b`` multiple and
        sliced back, so any batch in (0, block_b] reuses one trace — a
        steady-state serving loop performs zero re-traces after warmup.

        A batch already on the device (a ``jax.Array``, as the serving
        tier hands over) goes straight to the layout's forward.  A host
        batch is put flat, in its own byte order, and the host-batch
        entry unflattens it on the device (see ``_host_forward``).

        Two spans time its host work: ``engine.h2d``, the put of the
        codes, and ``engine.dispatch``, until the forward call returns.
        Nothing waits for the device: the caller keeps async dispatch.
        """
        host = not isinstance(codes, jax.Array)
        _M_CALLS.labels(input="host" if host else "device").inc()
        with obs.span("engine.h2d"):
            if host:
                codes = np.ascontiguousarray(codes, dtype=np.int32)
            elif codes.dtype != jnp.int32:
                codes = jnp.asarray(codes, dtype=jnp.int32)
            if codes.ndim != 2 or codes.shape[1] != self.n_in:
                raise ValueError(
                    f"expected (batch, {self.n_in}) codes, got {codes.shape}")
            batch = codes.shape[0]
            if batch == 0:
                return jnp.zeros((0, self.n_out), dtype=jnp.int32)
            if host:
                codes = jax.device_put(codes.reshape(-1))
        with obs.span("engine.dispatch"):
            pad = -batch % self.block_b
            if pad:
                codes = jnp.pad(codes, (0, pad * self.n_in) if host
                                else ((0, pad), (0, 0)))
            static, arrays = self._parts()
            if host:
                out = _host_forward(codes, arrays, layout=self.layout,
                                    static=tuple(sorted(static.items())),
                                    n_in=self.n_in)
            else:
                out = _FORWARDS[self.layout](codes, *arrays, **static)
            return out[:batch] if pad else out

    def _parts(self):
        """``(static, arrays)``: the static keywords of the layout's
        jitted forward in ``_FORWARDS``, and the slabs it takes."""
        if self.layout == "mixed":
            s = self.slabs
            arrays = (s.idx_slab, s.shift_slab, s.width_slab, s.table_slabs)
            static = {"meta": s.meta, "out_perm": s.out_perm,
                      "packed": s.packed}
        elif self.layout == "uniform":
            s = self.slabs
            arrays = (s.idx_slab, s.table_slab)
            static = {"meta": s.meta, "packed": s.packed}
        else:
            arrays = (tuple((idx, tab) for idx, tab, _ in self.layers),)
            static = {"bws": tuple(bw for _, _, bw in self.layers)}
        if self.layout == "per_layer":
            static["two_level"] = self.two_level
        if self.layout != "reference":
            static.update(block_b=self.block_b, interpret=interpret_mode())
        return static, arrays

    def forward_parts(self):
        """``(fn, arrays)`` such that ``fn(codes, *arrays)`` is this
        artifact's jitted forward on a padded batch.

        ``fn`` binds the static half (layout metadata, ``block_b`` and
        the kernel backend); ``arrays`` are the slabs.  A caller that
        places the slabs itself — the sharded serving tier replicates
        them over its mesh — passes them as arguments instead of letting
        a trace capture them as constants on one device.
        """
        static, arrays = self._parts()
        return functools.partial(_FORWARDS[self.layout], **static), arrays

    def jit_cache_size(self, host: bool = True) -> int:
        """Trace count of this artifact's (shared) jitted forward and,
        unless ``host`` is False, of the host-batch entry.

        Both are process-wide, so treat this as a monotonic counter: a
        steady-state serving loop must not grow it (the bench's
        ``retraces_after_warmup`` and the regression tests take
        before/after deltas).  A caller that only hands over device
        batches, as the serving tier does, passes ``host=False``, so
        that host calls elsewhere in the process are not counted as its
        own.
        """
        n = _FORWARDS[self.layout]._cache_size()
        if host:
            n += _host_forward._cache_size()
        return n

    def vmem_breakdown(self) -> dict:
        """Per-slab VMEM bytes of the chosen layout (serving diagnostics)."""
        if self.slabs is not None:
            return {**self.slabs.vmem_breakdown(), "layout": self.layout}
        idx = sum(i.size * i.dtype.itemsize for i, _, _ in self.layers)
        tab = sum(t.size * t.dtype.itemsize for _, t, _ in self.layers)
        return {"idx_slab_bytes": idx, "table_slab_bytes": tab,
                "total_bytes": idx + tab, "packed_int8": False,
                "layout": self.layout}

    # -- serialization ------------------------------------------------------

    def save(self, path: str) -> str:
        """Write the artifact as one ``.npz`` (checkpoint manifest format).

        Everything needed to serve — slab arrays, static shape metadata,
        the plan and the compile stats — round-trips; ``engine.load`` on a
        fresh process rebuilds bit-exact slabs without touching the
        compiler (a model A artifact at level 3 loads straight into its
        exact table slab).
        """
        meta: dict = {
            "kind": ARTIFACT_KIND, "format": FORMAT_VERSION,
            "layout": self.layout, "n_in": self.n_in, "n_out": self.n_out,
            "block_b": self.block_b,
            "plan": self.plan.as_dict(),
            "stats": None if self.stats is None else self.stats.as_dict(),
        }
        arrays: dict[str, np.ndarray] = {}
        if self.layout == "mixed":
            s = self.slabs
            arrays = {"idx_slab": s.idx_slab, "shift_slab": s.shift_slab,
                      "width_slab": s.width_slab}
            for bits, t in zip(table_classes(s.meta), s.table_slabs):
                arrays[f"table_slab_b{bits}"] = t
            meta["packed"] = s.packed
            meta["out_perm"] = (None if s.out_perm is None
                                else list(s.out_perm))
            meta["layer_meta"] = [
                {"n_out": m.n_out, "fan_in": m.fan_in,
                 # 2-element groups = contiguous layout; a third element
                 # carries the row-dedup slab rows (format 3)
                 "groups": [[g.n_out, g.entry_bits] if g.offs is None
                            else [g.n_out, g.entry_bits, list(g.offs)]
                            for g in m.groups]}
                for m in s.meta]
            meta["dedup_entries_saved"] = int(s.dedup_entries_saved)
        elif self.layout == "uniform":
            s = self.slabs
            arrays = {"idx_slab": s.idx_slab, "table_slab": s.table_slab}
            meta["packed"] = s.packed
            meta["layer_meta"] = [list(m) for m in s.meta]
        else:
            meta["bws"] = [int(bw) for _, _, bw in self.layers]
            for li, (idx, tab, _) in enumerate(self.layers):
                arrays[f"idx_{li}"] = idx
                arrays[f"table_{li}"] = tab
        return save_arrays(path, arrays, meta)


def load(path: str) -> CompiledLUTNet:
    """Rebuild a ``CompiledLUTNet`` from ``CompiledLUTNet.save`` output.

    No compiler run, no slab build: the saved slabs are handed to the
    shared jitted forwards as-is, so a deployment process pays one jit
    trace per batch bucket and nothing else.
    """
    arrays, meta = load_arrays(path)
    if meta.get("kind") != ARTIFACT_KIND:
        raise ValueError(
            f"{path} is not a {ARTIFACT_KIND} artifact "
            f"(kind={meta.get('kind')!r})")
    if meta.get("format", 0) > FORMAT_VERSION:
        raise ValueError(
            f"{path} has artifact format {meta['format']}; this build "
            f"reads <= {FORMAT_VERSION}")
    pd = meta["plan"]
    if "variant" in pd:
        plan = ExecutionPlan.from_dict(pd)
    else:
        # format-1 artifact: the record is a bare FusedPlan — synthesize
        # the default plan so the loaded artifact speaks the new surface
        # (zero search, zero compiler runs, bit-exact slabs as always)
        plan = ExecutionPlan.from_fused(
            FusedPlan.from_dict(pd), meta["layout"], int(meta["block_b"]),
            source="synthesized")
    stats = (None if meta["stats"] is None
             else CompileStats.from_dict(meta["stats"]))
    layout = meta["layout"]
    if (layout == "mixed" and "table_slab" in arrays) or (
            layout == "uniform" and arrays["table_slab"].ndim != 3):
        raise ValueError(
            f"{path} holds {layout} table slabs in the layout before "
            f"format 4, which no TPU kernel can read; compile it again")
    slabs = None
    layers = None
    two_level = None
    if layout == "mixed":
        lm = tuple(
            MixedLayerMeta(m["n_out"], m["fan_in"],
                           tuple(MixedGroupMeta(
                               int(g[0]), int(g[1]),
                               tuple(int(o) for o in g[2])
                               if len(g) > 2 else None)
                                 for g in m["groups"]))
            for m in meta["layer_meta"])
        out_perm = (None if meta["out_perm"] is None
                    else tuple(int(p) for p in meta["out_perm"]))
        slabs = MixedNetworkSlabs(
            jnp.asarray(arrays["idx_slab"]), jnp.asarray(arrays["shift_slab"]),
            jnp.asarray(arrays["width_slab"]),
            tuple(jnp.asarray(arrays[f"table_slab_b{bits}"])
                  for bits in table_classes(lm)),
            lm, out_perm, bool(meta["packed"]),
            dedup_entries_saved=int(meta.get("dedup_entries_saved", 0)))
    elif layout == "uniform":
        lm = tuple(LayerMeta(*(int(v) for v in m))
                   for m in meta["layer_meta"])
        slabs = NetworkSlabs(jnp.asarray(arrays["idx_slab"]),
                             jnp.asarray(arrays["table_slab"]),
                             lm, bool(meta["packed"]))
    else:
        triples = [(arrays[f"idx_{li}"], arrays[f"table_{li}"], int(bw))
                   for li, bw in enumerate(meta["bws"])]
        layers = tuple((jnp.asarray(i), jnp.asarray(t), bw)
                       for i, t, bw in triples)
        if layout == "per_layer":
            two_level = per_layer_gathers(triples)
    _M_LOADS.inc()
    return CompiledLUTNet(layout=layout, n_in=int(meta["n_in"]),
                          n_out=int(meta["n_out"]),
                          block_b=int(meta["block_b"]), plan=plan,
                          stats=stats, slabs=slabs, layers=layers,
                          two_level=two_level)


# ---------------------------------------------------------------------------
# compile_network: the one place the compile/cost/build/jit decision lives
# ---------------------------------------------------------------------------


def per_layer_gathers(triples) -> tuple[bool, ...]:
    """Per layer of a per-layer artifact, whether its kernel takes the
    two-level MXU gather (``two_level_exact``: every table code and input
    code within the exact bf16 range) or keeps the integer compare loop.

    Decided once, from the host tables, and counted once per layer in
    ``engine_per_layer_gather_total``.
    """
    two_level = tuple(two_level_exact(t, bw) for _, t, bw in triples)
    for two in two_level:
        _M_PER_LAYER_GATHER.labels(
            gather="two_level" if two else "compare").inc()
    return two_level


def _as_triples(layers) -> list[tuple[np.ndarray, np.ndarray, int]]:
    out = []
    for lay in layers:
        if hasattr(lay, "indices") and hasattr(lay, "table"):
            out.append((lay.indices, lay.table, int(lay.bw_in)))
        else:
            idx, tab, bw = lay
            out.append((idx, tab, int(bw)))
    if not out:
        raise ValueError("compile_network needs at least one layer")
    return out


def compile_network(layers, *, optimize_level: int | None = None,
                    in_features: int | None = None, fused: bool = True,
                    use_pallas: bool = True, block_b: int = DEFAULT_BLOCK_B,
                    vmem_budget_bytes: int = FUSED_VMEM_BUDGET_BYTES,
                    autotune: bool = False, autotune_codes=None,
                    autotune_block_bs=None) -> CompiledLUTNet:
    """Compile a sparse LUT stack into a serving artifact, once.

    ``layers`` is a ``LayerTruthTable`` list, a sequence of
    ``(indices, table, bw_in)`` triples, or an already-computed
    ``repro.compile.OptimizeResult`` (the compiler is then skipped and its
    lowerings reused — ``optimize_level`` must be None in that case).

    The decision ladder is exactly the one the legacy flags used to
    re-evaluate per call, now evaluated once:

    1. ``optimize_level`` set -> run ``compile.optimize`` (ONE run);
       cost the compiler's mixed-width lowering via ``fused_plan`` and
       take the fused mixed path when it fits the VMEM budget;
    2. otherwise cost the uniform layout; take the fused uniform path
       when eligible;
    3. otherwise fall back to the jitted per-layer chain (``use_pallas=
       False`` pins the plain-jnp reference chain instead).

    ``autotune=True`` replaces the static ladder with measurement: every
    eligible :class:`~repro.kernels.plan.PlanVariant` (layout x block_b x
    pack) is built and its jitted forward timed on the actual backend
    (see ``repro.engine.autotune``), and the artifact carries the winner
    plus the full timing table — ``save``/``load`` replay it with zero
    search.  ``autotune_codes`` supplies the representative batch
    (None: seeded synthetic codes); ``autotune_block_bs`` overrides the
    ``block_b`` sweep (the requested ``block_b`` always joins it, so the
    heuristic default stays among the candidates).  ``autotune`` is
    ignored when the caller pinned the path with ``fused=False`` or
    ``use_pallas=False`` — there is nothing left to search.

    ``in_features`` is the served input bus width (``codes.shape[-1]``);
    defaults to the widest first-layer index + 1.
    """
    global _compile_runs
    res: OptimizeResult | None = None
    if isinstance(layers, OptimizeResult):
        if optimize_level is not None:
            raise ValueError(
                "layers is already an OptimizeResult; optimize_level must "
                "be None (the compiler does not run again)")
        res = layers
    else:
        triples = _as_triples(layers)
        if in_features is None:
            # the input bus width: only the FIRST layer's indices address
            # it (later layers address their producer's bus)
            in_features = int(np.max(np.asarray(triples[0][0]))) + 1
        if optimize_level is not None:
            from repro.compile import optimize, tables_from_triples
            res = optimize(tables_from_triples(triples), optimize_level,
                           in_features=in_features)
            _compile_runs += 1
            _M_COMPILER_RUNS.inc()
    stats = res.stats if res is not None else None

    if autotune and use_pallas and fused:
        mixed = res.mixed_tables if res is not None else None
        if res is not None:
            triples = [(tt.indices, tt.table, tt.bw_in)
                       for tt in res.tables]
            if in_features is None:
                in_features = res.cnet.in_features
        # search cost is observed by autotune's own histogram
        # (engine_autotune_seconds), not the slab-build one
        plan, built = autotune_network(
            triples, mixed, in_features=in_features, block_b=block_b,
            vmem_budget_bytes=vmem_budget_bytes, codes=autotune_codes,
            block_bs=autotune_block_bs)
        _M_BUILDS.labels(layout=plan.layout).inc()
        if plan.layout in ("mixed", "uniform"):
            return CompiledLUTNet(layout=plan.layout, n_in=in_features,
                                  n_out=built.n_out, block_b=plan.block_b,
                                  plan=plan, stats=stats, slabs=built)
        n_out = int(np.asarray(triples[-1][1]).shape[0])
        return CompiledLUTNet(layout="per_layer", n_in=in_features,
                              n_out=n_out, block_b=plan.block_b, plan=plan,
                              stats=stats, layers=built,
                              two_level=per_layer_gathers(triples))

    if res is not None and use_pallas and fused:
        mixed = res.mixed_tables
        cost = fused_plan(mixed, vmem_budget_bytes, block_b=block_b)
        if cost.fused:
            t0 = time.perf_counter()
            slabs = build_mixed_network_slabs(mixed, pack=cost.pack)
            _M_SLAB_BUILD.observe(time.perf_counter() - t0)
            _M_BUILDS.labels(layout="mixed").inc()
            return CompiledLUTNet(
                layout="mixed",
                n_in=res.cnet.in_features if in_features is None
                else in_features,
                n_out=slabs.n_out, block_b=block_b,
                plan=ExecutionPlan.from_fused(cost, "mixed", block_b),
                stats=stats, slabs=slabs)
    if res is not None:
        # the padded uniform lowering is only materialized once the mixed
        # fused path has been ruled out (same fall-through as the legacy
        # ops.lut_network); the optimized first layer may have pruned its
        # widest input feature, so the bus width comes from the IR, not
        # from the surviving indices
        triples = [(tt.indices, tt.table, tt.bw_in) for tt in res.tables]
        if in_features is None:
            in_features = res.cnet.in_features
    n_out = int(np.asarray(triples[-1][1]).shape[0])

    cost = fused_plan(triples, vmem_budget_bytes, block_b=block_b)
    if not use_pallas or not fused:
        cost = dataclasses.replace(cost, fused=False,
                                   reason="fused_disabled")
    if use_pallas and cost.fused:
        t0 = time.perf_counter()
        slabs = build_network_slabs(triples, pack=cost.pack)
        _M_SLAB_BUILD.observe(time.perf_counter() - t0)
        _M_BUILDS.labels(layout="uniform").inc()
        return CompiledLUTNet(
            layout="uniform", n_in=in_features, n_out=slabs.n_out,
            block_b=block_b,
            plan=ExecutionPlan.from_fused(cost, "uniform", block_b),
            stats=stats, slabs=slabs)
    t0 = time.perf_counter()
    jl = tuple((jnp.asarray(np.asarray(i, dtype=np.int32)),
                jnp.asarray(np.asarray(t, dtype=np.int32)), int(b))
               for i, t, b in triples)
    _M_SLAB_BUILD.observe(time.perf_counter() - t0)
    layout = "per_layer" if use_pallas else "reference"
    _M_BUILDS.labels(layout=layout).inc()
    return CompiledLUTNet(
        layout=layout, n_in=in_features, n_out=n_out, block_b=block_b,
        plan=ExecutionPlan.from_fused(cost, layout, block_b),
        stats=stats, layers=jl,
        two_level=per_layer_gathers(triples) if use_pallas else None)


# ---------------------------------------------------------------------------
# Identity-keyed memo for the legacy flag API (ops.lut_network)
# ---------------------------------------------------------------------------

# key -> (layers kept alive so ids stay unique, CompiledLUTNet); insertion-
# ordered dict gives FIFO eviction
_cache: dict[tuple, tuple[list, CompiledLUTNet]] = {}
_CACHE_MAX = 16


def cached_compile(layers, *, optimize_level: int | None,
                   in_features: int, fused: bool, use_pallas: bool,
                   block_b: int, vmem_budget_bytes: int) -> CompiledLUTNet:
    """Memoized ``compile_network`` keyed by *layer identity* + flags.

    The escape hatch that keeps the legacy per-call API cheap: a caller
    looping over ``ops.lut_network(codes, layers, optimize_level=...)``
    with the same layer arrays hits the cached ``OptimizeResult`` + built
    slabs instead of silently recompiling every call.  Keys use ``id()``
    of the index/table arrays (cheap; no hashing of megabyte tables) and
    each entry pins its arrays, so a live id can never be recycled into a
    collision.  The flip side: arrays handed to ``lut_network`` must be
    treated as immutable — an in-place table edit will serve stale results
    until ``cache_clear()``.  FIFO-bounded to ``_CACHE_MAX`` entries.
    """
    layers = list(layers)
    triples = _as_triples(layers)
    key = (tuple((id(i), id(t), b) for i, t, b in triples),
           optimize_level, in_features, fused, use_pallas, block_b,
           vmem_budget_bytes)
    hit = _cache.get(key)
    if hit is not None:
        _M_MEMO_HITS.inc()
        return hit[1]
    _M_MEMO_MISSES.inc()
    eng = compile_network(triples, optimize_level=optimize_level,
                          in_features=in_features, fused=fused,
                          use_pallas=use_pallas, block_b=block_b,
                          vmem_budget_bytes=vmem_budget_bytes)
    while len(_cache) >= _CACHE_MAX:
        _cache.pop(next(iter(_cache)))
    _cache[key] = (layers, eng)
    return eng


def cache_size() -> int:
    """Number of memoized legacy-API artifacts (regression tests)."""
    return len(_cache)


def cache_clear() -> None:
    """Drop all memoized artifacts (tests / after in-place table edits)."""
    _cache.clear()
