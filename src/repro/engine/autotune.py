"""Compile-time variant autotuner: measure once, persist, replay.

The heuristic ladder in ``compile_network`` picks an execution strategy
from a static byte estimate; LogicNets and *Rethinking Arithmetic* both
observe the winning implementation of a boolean-function network is
workload- and backend-dependent.  This module closes that gap the way the
ROADMAP's layout-autotuner item asked for: enumerate the
:class:`~repro.kernels.plan.PlanVariant` space (layout x block_b x pack),
build each eligible variant's slabs through the existing builders, time
its *jitted* forward on the actual backend over a representative batch
(warmup + median-of-k), and record the winner in an
:class:`ExecutionPlan` that rides in the artifact — deployment replays
the measured choice with zero search and zero extra traces.

The timing protocol is deliberately boring: a seeded synthetic batch (or
a caller-supplied one) shaped like serving traffic, ``AUTOTUNE_WARMUP``
untimed calls to absorb the jit trace, then ``AUTOTUNE_REPS`` timed
passes of ``AUTOTUNE_ITERS`` calls each, keeping the median.  Timings go
through the same process-wide jitted forwards serving uses
(``engine._FORWARDS``), so what is measured is what will run.

Search cost and coverage are observable: ``engine_autotune_seconds``
(histogram, one observation per search) and
``engine_autotune_variants_total`` (counter, labeled by layout).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.lut_lookup import DEFAULT_BLOCK_B, two_level_exact
from repro.kernels.ops import interpret_mode
from repro.kernels.lut_network import (build_mixed_network_slabs,
                                       build_network_slabs)
from repro.kernels.plan import (DEFAULT_BLOCK_BS, FUSED_VMEM_BUDGET_BYTES,
                                FusedPlan, PlanVariant, default_variant,
                                enumerate_variants)

# warmup absorbs the jit trace; each rep times ITERS back-to-back calls
# and the median rep survives (robust to a stray scheduler hiccup without
# needing many samples — interpret-mode calls are milliseconds each)
AUTOTUNE_WARMUP = 1
AUTOTUNE_ITERS = 2
AUTOTUNE_REPS = 3

_M_AUTOTUNE_SECONDS = obs.registry().histogram(
    "engine_autotune_seconds",
    "wall-clock seconds per compile-time variant search")
_M_AUTOTUNE_VARIANTS = obs.registry().counter(
    "engine_autotune_variants_total",
    "plan variants built and timed by the autotuner", labels=("layout",))


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The execution strategy a ``CompiledLUTNet`` runs — and why.

    Supersedes the bare ``layout: str`` + ``FusedPlan`` pair: ``variant``
    pins layout, ``block_b`` and pack together with the byte costing, and
    the compat properties below keep every ``net.plan.reason``-style
    caller working unchanged.

    * ``source`` — ``"heuristic"`` (the static ladder chose), ``"autotune"``
      (measured), or ``"synthesized"`` (reconstructed while loading a
      pre-autotune format-1 artifact);
    * ``timings_us`` — variant key -> median microseconds per forward on
      the autotune batch (empty unless autotuned).  Persisted in the
      artifact so deployment can audit the search without re-running it;
    * ``batch`` — rows in the batch those timings were taken over;
    * ``default_key`` — the heuristic default's variant key, always
      present in ``timings_us`` after a search (the bench's collapse-only
      gate compares the winner against it).
    """

    variant: PlanVariant
    source: str = "heuristic"
    timings_us: dict = dataclasses.field(default_factory=dict)
    batch: int = 0
    default_key: str | None = None

    # -- compat shim: the old FusedPlan/layout surface ----------------------

    @property
    def layout(self) -> str:
        return self.variant.layout

    @property
    def block_b(self) -> int:
        return self.variant.block_b

    @property
    def pack(self) -> bool:
        return self.variant.pack

    @property
    def fused(self) -> bool:
        return self.variant.cost.fused

    @property
    def reason(self) -> str:
        return self.variant.cost.reason

    @property
    def slab_bytes(self) -> int:
        return self.variant.cost.slab_bytes

    @property
    def vmem_budget_bytes(self) -> int:
        return self.variant.cost.vmem_budget_bytes

    @property
    def f32_exact(self) -> bool:
        return self.variant.cost.f32_exact

    # -- (de)serialization --------------------------------------------------

    def as_dict(self) -> dict:
        return {"variant": self.variant.as_dict(), "source": self.source,
                "timings_us": dict(self.timings_us), "batch": self.batch,
                "default_key": self.default_key}

    @classmethod
    def from_dict(cls, d: dict) -> "ExecutionPlan":
        return cls(variant=PlanVariant.from_dict(d["variant"]),
                   source=str(d["source"]),
                   timings_us=dict(d.get("timings_us") or {}),
                   batch=int(d.get("batch") or 0),
                   default_key=d.get("default_key"))

    @classmethod
    def from_fused(cls, cost: FusedPlan, layout: str, block_b: int, *,
                   source: str = "heuristic") -> "ExecutionPlan":
        """Wrap a bare heuristic costing (or a format-1 artifact's
        deserialized ``FusedPlan``) into a plan with no timing table."""
        pack = cost.pack if layout in ("mixed", "uniform") else False
        return cls(variant=PlanVariant(layout, int(block_b), pack, cost),
                   source=source)


def _synthetic_codes(in_features: int, bw: int, batch: int,
                     seed: int = 0) -> np.ndarray:
    """Seeded stand-in for serving traffic: uniform codes over the first
    layer's input alphabet (every LUT entry reachable)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << bw, (batch, in_features), dtype=np.int32)


def _time_forward(fn, *, warmup: int, iters: int, reps: int) -> float:
    """Median microseconds per call of the zero-arg ``fn`` (device-synced)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / iters * 1e6)
    samples.sort()
    return samples[len(samples) // 2]


def autotune_network(uniform_triples, mixed_tables=None, *,
                     in_features: int,
                     block_b: int = DEFAULT_BLOCK_B,
                     vmem_budget_bytes: int = FUSED_VMEM_BUDGET_BYTES,
                     codes=None, block_bs=None, seed: int = 0,
                     warmup: int = AUTOTUNE_WARMUP,
                     iters: int = AUTOTUNE_ITERS,
                     reps: int = AUTOTUNE_REPS):
    """Time every eligible variant and return the measured winner.

    ``uniform_triples`` is the ``(indices, table, bw_in)`` triple list,
    ``mixed_tables`` the compiler's ``MixedLayerTables`` lowering when one
    exists.  ``codes`` supplies the representative batch (None: a seeded
    synthetic batch of ``max(block_bs)`` rows).  ``block_b`` is the
    caller's requested tile — it joins the sweep so the heuristic default
    variant is always among the timed candidates.

    Returns ``(plan, built)``: the :class:`ExecutionPlan` (``source=
    "autotune"``, full timing table) and the winner's already-built
    payload — ``NetworkSlabs`` / ``MixedNetworkSlabs`` for fused layouts,
    the jnp ``(idx, table, bw)`` tuple for per-layer — so
    ``compile_network`` never builds the winning slabs twice.
    """
    from repro.engine import engine as _eng   # lazy: engine imports us

    t_start = time.perf_counter()
    uniform_triples = list(uniform_triples)
    sweep = tuple(sorted({int(b) for b in (block_bs or DEFAULT_BLOCK_BS)}
                         | {int(block_b)}))
    variants = enumerate_variants(uniform_triples, mixed_tables,
                                  block_bs=sweep,
                                  vmem_budget_bytes=vmem_budget_bytes)
    default = default_variant(uniform_triples, mixed_tables,
                              block_b=block_b,
                              vmem_budget_bytes=vmem_budget_bytes)

    if codes is None:
        bw = int(uniform_triples[0][2])
        codes = _synthetic_codes(in_features, bw, max(sweep), seed)
    codes = jnp.asarray(np.asarray(codes, dtype=np.int32))
    batch = int(codes.shape[0])
    interp = interpret_mode()
    # the per-layer variants' gathers, decided as engine.per_layer_gathers
    # does (uncounted: only the artifact's own layers are counted)
    two_level = tuple(two_level_exact(t, b) for _, t, b in uniform_triples)

    # one build per (layout, pack) — slabs are block_b-independent
    built: dict[tuple[str, bool], object] = {}

    def payload(v: PlanVariant):
        k = (v.layout, v.pack)
        if k not in built:
            if v.layout == "mixed":
                built[k] = build_mixed_network_slabs(mixed_tables,
                                                     pack=v.pack)
            elif v.layout == "uniform":
                built[k] = build_network_slabs(uniform_triples, pack=v.pack)
            else:
                built[k] = tuple(
                    (jnp.asarray(np.asarray(i, dtype=np.int32)),
                     jnp.asarray(np.asarray(t, dtype=np.int32)), int(b))
                    for i, t, b in uniform_triples)
        return built[k]

    def forward(v: PlanVariant, p):
        padded = -(-batch // v.block_b) * v.block_b
        x = codes
        if padded != batch:
            x = jnp.concatenate(
                [x, jnp.zeros((padded - batch, in_features), x.dtype)],
                axis=0)
        if v.layout == "mixed":
            return lambda c=x: _eng._mixed_forward(
                c, p.idx_slab, p.shift_slab, p.width_slab, p.table_slabs,
                meta=p.meta, out_perm=p.out_perm, packed=p.packed,
                block_b=v.block_b, interpret=interp)
        if v.layout == "uniform":
            return lambda c=x: _eng._uniform_forward(
                c, p.idx_slab, p.table_slab, meta=p.meta, packed=p.packed,
                block_b=v.block_b, interpret=interp)
        idx_tabs = tuple((i, t) for i, t, _ in p)
        bws = tuple(b for _, _, b in p)
        return lambda c=x: _eng._per_layer_forward(
            c, idx_tabs, bws=bws, two_level=two_level, block_b=v.block_b,
            interpret=interp)

    timings: dict[str, float] = {}
    by_key: dict[str, PlanVariant] = {}
    for v in variants:
        fn = forward(v, payload(v))
        timings[v.key] = _time_forward(fn, warmup=warmup, iters=iters,
                                       reps=reps)
        by_key[v.key] = v
        _M_AUTOTUNE_VARIANTS.labels(layout=v.layout).inc()

    winner = by_key[min(timings, key=timings.get)]
    plan = ExecutionPlan(variant=winner, source="autotune",
                         timings_us=timings, batch=batch,
                         default_key=default.key)
    _M_AUTOTUNE_SECONDS.observe(time.perf_counter() - t_start)
    return plan, payload(winner)
