"""Serving launcher: ``python -m repro.launch.serve``.

Two serving stacks behind one CLI:

* ``--lut`` — the LogicNets deployment path: load (or compile) a
  ``repro.engine.CompiledLUTNet`` and drive it through the
  ``repro.serve`` micro-batching tier under closed-loop load, reporting
  steady-state p50/p99 latency, QPS, batch occupancy and the compile-once
  counters (see docs/serving.md).  This is the CLI face of the bench's
  gated ``serving_tier`` section::

      # compile generated fpga4hep model A at level 3 and serve it
      python -m repro.launch.serve --lut

      # serve a saved artifact (e.g. CI's ENGINE_model_a.npz)
      python -m repro.launch.serve --lut --artifact model_a.npz

      # quick smoke (CI / drift tests)
      python -m repro.launch.serve --lut --smoke

      # put the HTTP ingress in front (0 = ephemeral port) and serve
      # until SIGTERM; add a per-tenant row quota
      python -m repro.launch.serve --lut --http 8080 --tenant-quota 500:1000

      # open-loop (Poisson-arrival) load instead of closed-loop
      python -m repro.launch.serve --lut --open-loop 300

  ``--http`` + ``--smoke`` (or ``--open-loop``) drives open-loop load
  *through* a localhost ingress and verifies responses bit-exact — the
  end-to-end path CI's ingress-smoke step runs (see docs/ingress.md).

* default (no ``--lut``) — the big-model demo: mesh-aware batched LM
  decode, params + caches sharded per parallel/sharding.py, decode step
  jitted with in/out shardings (same core as examples/serve_lm.py).
"""

from __future__ import annotations

import argparse
import json
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np


def _run_lm(args: argparse.Namespace) -> None:
    """Mesh-aware batched LM decode demo (the pre-LUT serving loop)."""
    from repro.configs import get_config, get_smoke_config
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.parallel import sharding as SH
    from repro.parallel.ctx import activation_sharding

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    mesh = make_host_mesh(model=args.model_parallel)
    policy = SH.ShardingPolicy()

    with activation_sharding(mesh, SH.activation_rules(policy)):
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        params_sh = SH.shardings_for_tree(params, mesh, policy)
        params = jax.device_put(params, params_sh)
        cache = M.init_cache(cfg, args.slots, args.cache_len)
        cache_sh = SH.cache_specs(policy, mesh, jax.eval_shape(
            lambda: cache))
        cache = jax.device_put(cache, cache_sh)
        step = jax.jit(S.make_decode_step(cfg),
                       in_shardings=(params_sh, cache_sh, None, None),
                       out_shardings=(None, cache_sh))

        tok = jnp.ones((args.slots, 1), jnp.int32)
        pos = jnp.zeros((args.slots,), jnp.int32)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            logits, cache = step(params, cache, tok, pos)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            pos = pos + 1
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
    print(f"[serve] {cfg.arch_id}: {args.steps} decode steps x "
          f"{args.slots} slots on mesh {dict(mesh.shape)} "
          f"({1e3 * dt / args.steps:.1f} ms/step)")


def _lut_artifact(args: argparse.Namespace):
    """Load ``--artifact`` or compile generated fpga4hep model A.

    Returns ``(net, bw, reference)``: ``reference(codes)`` is the plain
    jnp ``network_table_forward(fused=False)`` over the uncompiled tables
    when this process compiled them, and the artifact itself when it was
    loaded (its tables are not in the file).
    """
    from repro import engine

    if args.artifact:
        net = engine.load(args.artifact)
        print(f"[serve --lut] loaded {args.artifact}: layout={net.layout} "
              f"n_in={net.n_in} n_out={net.n_out} "
              f"table slab {net.vmem_breakdown()['table_slab_bytes']} B "
              f"(compiler runs this process: {engine.compile_runs()})")
        # the artifact does not record its input quantizer width, so the
        # synthetic-code range comes from --input-bw (default 2: valid for
        # every LogicNets config in this repo)
        return net, args.input_bw, net
    from repro.configs import fpga4hep
    from repro.core import logicnet as LN
    from repro.core.table_infer import network_table_forward

    cfg = fpga4hep.model_a()
    model = LN.init(cfg, jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (256, cfg.in_features),
                           minval=-1, maxval=3)
    _, model = LN.forward(cfg, model, x, train=True)
    tables = LN.generate_tables(cfg, model)
    net = engine.compile_network(tables, optimize_level=args.optimize_level,
                                 in_features=cfg.in_features,
                                 block_b=args.block_b,
                                 autotune=args.autotune)
    print(f"[serve --lut] compiled generated fpga4hep model A at level "
          f"{args.optimize_level}: layout={net.layout}, table slab "
          f"{net.vmem_breakdown()['table_slab_bytes']} B")
    if args.autotune:
        plan = net.plan
        us = plan.timings_us
        default_us = us.get(plan.default_key)
        print(f"[serve --lut] autotuned over {len(us)} variants: chose "
              f"{plan.variant.key} ({us[plan.variant.key]:.0f} us/call vs "
              f"heuristic {plan.default_key} at {default_us:.0f} us); "
              f"save the artifact to replay this plan with zero search")
    return net, cfg.bw, lambda codes: network_table_forward(
        tables, codes, fused=False)


def _parse_quota(spec: str | None):
    """``--tenant-quota RATE[:BURST]`` -> QuotaConfig (rows/s) or None."""
    from repro import serve

    if spec is None:
        return None
    rate, _, burst = spec.partition(":")
    return serve.QuotaConfig(rate_rows_per_s=float(rate),
                             burst_rows=float(burst) if burst else None)


def _print_report(rep, st: dict) -> None:
    """The operator-facing LoadReport + tier-counter dump."""
    open_loop = rep.n_clients == 0
    if open_loop:
        print(f"[serve --lut] {rep.n_requests} open-loop requests offered "
              f"at {rep.offered_rps:.0f} rps in {rep.wall_s:.2f}s: "
              f"outcomes={rep.outcomes}, goodput={rep.goodput_rps:.0f} rps, "
              f"rejection_rate={rep.rejection_rate:.2f}")
    else:
        print(f"[serve --lut] {rep.n_requests} requests ({rep.rows} rows) "
              f"from {rep.n_clients} closed-loop clients in {rep.wall_s:.2f}s")
    print(f"[serve --lut] latency p50={rep.p50_ms:.2f}ms "
          f"p90={rep.p90_ms:.2f}ms p99={rep.p99_ms:.2f}ms "
          f"mean={rep.mean_ms:.2f}ms; qps={rep.qps:.0f} "
          f"({rep.rows_per_sec:.0f} rows/s)")
    if st:
        print(f"[serve --lut] {st['batches']} batches, occupancy "
              f"{st['batch_occupancy']:.2f} (mean "
              f"{st['mean_batch_rows']:.1f} rows), "
              f"flushes={st['flush_causes']}, {st['n_devices']} "
              f"{st['platform']} device(s) ({st['device_kind']})"
              f"{' sharded' if st['sharded'] else ''}")
    for stage in ("queue_wait", "assembly", "device"):
        leg = rep.breakdown.get(stage)
        if leg and leg["count"]:
            print(f"[serve --lut] {stage}: mean={leg['mean_ms']:.2f}ms "
                  f"p50={leg['p50_ms']:.2f}ms p99={leg['p99_ms']:.2f}ms")


def _dump_report(args: argparse.Namespace, rep) -> None:
    if args.report_json:
        with open(args.report_json, "w") as fh:
            json.dump(rep.as_dict(), fh, indent=2, default=str)
        print(f"[serve --lut] load report -> {args.report_json}")


def _run_http(args: argparse.Namespace, net, bw, reference,
              tier_cfg) -> dict:
    """HTTP ingress mode: one-shot open-loop smoke, or serve to SIGTERM."""
    from repro import serve

    cfg = serve.IngressConfig(port=args.http, quota=_parse_quota(
        args.tenant_quota))
    ing = serve.BackgroundIngress(net, tier_cfg, cfg).start()
    try:
        print(f"[serve --lut] http ingress listening on {ing.url} "
              f"(POST /v1/infer, GET /healthz, GET /metrics)", flush=True)
        if args.smoke or args.open_loop is not None:
            offered = args.open_loop if args.open_loop is not None else 400.0
            rep = serve.run_open_loop(
                url=ing.url, offered_rps=offered,
                n_requests=args.clients * args.requests_per_client,
                rows_min=args.rows_min, rows_max=args.rows_max, bw=bw,
                seed=args.seed, verify_net=reference, n_in=net.n_in)
            if args.smoke and rep.outcomes.get("ok", 0) != rep.n_requests:
                raise SystemExit(f"smoke requests not all served: "
                                 f"{rep.outcomes}")
            print(f"[serve --lut] {rep.outcomes.get('ok', 0)} responses "
                  f"verified bit-exact over HTTP")
            _print_report(rep, ing.stats())
            _dump_report(args, rep)
        else:
            stop = threading.Event()

            def _drain(signum, frame):
                print(f"[serve --lut] signal {signum}: draining",
                      flush=True)
                stop.set()

            prev = [signal.signal(s, _drain)
                    for s in (signal.SIGTERM, signal.SIGINT)]
            try:
                while not stop.wait(0.5):
                    pass
            finally:
                for s, h in zip((signal.SIGTERM, signal.SIGINT), prev):
                    signal.signal(s, h)
    finally:
        ing.stop()                      # graceful drain
    return ing.stats()


def _run_lut(args: argparse.Namespace) -> None:
    """Load through the micro-batching tier (optionally via HTTP ingress).

    ``--metrics-json`` (and ``--spans-json``) dump in a ``finally`` so an
    overload run killed by SIGTERM still leaves its snapshot (the default
    SIGTERM action is re-pointed at ``SystemExit`` for exactly that
    reason); the HTTP serve-forever mode instead catches SIGTERM for a
    graceful drain.
    """
    from repro import obs, serve

    def _term(signum, frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, _term)
    net, bw, reference = _lut_artifact(args)
    if args.smoke:
        args.clients, args.requests_per_client = 4, 4
    tier_cfg = serve.TierConfig(
        max_batch_rows=args.max_batch_rows,
        flush_deadline_s=args.flush_deadline_ms * 1e-3,
        max_queue_rows=args.max_queue_rows,
        request_timeout_s=(None if args.request_timeout_ms is None
                           else args.request_timeout_ms * 1e-3))
    try:
        with obs.PeriodicReporter(interval_s=args.report_every_s):
            if args.http is not None:
                st = _run_http(args, net, bw, reference, tier_cfg)
            elif args.open_loop is not None:
                rep = serve.run_open_loop(
                    net, config=tier_cfg, offered_rps=args.open_loop,
                    n_requests=args.clients * args.requests_per_client,
                    rows_min=args.rows_min, rows_max=args.rows_max, bw=bw,
                    seed=args.seed)
                st = rep.stats
                _print_report(rep, st)
                _dump_report(args, rep)
            else:
                rep = serve.run_closed_loop(
                    net, config=tier_cfg, n_clients=args.clients,
                    n_per_client=args.requests_per_client,
                    rows_min=args.rows_min, rows_max=args.rows_max, bw=bw,
                    seed=args.seed)
                st = rep.stats
                _print_report(rep, st)
                _dump_report(args, rep)
        print(f"[serve --lut] compile-once contract: "
              f"retraces={st['retraces_after_warmup']} "
              f"compiler_runs={st['compiler_runs_after_warmup']} "
              f"after warmup")
        print("[serve --lut]", obs.summary_line())
    finally:
        if args.metrics_json:
            obs.registry().dump_json(args.metrics_json)
            print(f"[serve --lut] metrics snapshot -> {args.metrics_json}",
                  flush=True)
        if args.spans_json:
            obs.span_log().dump_json(args.spans_json)
            print(f"[serve --lut] span log -> {args.spans_json}", flush=True)
    if st["retraces_after_warmup"] or st["compiler_runs_after_warmup"]:
        raise SystemExit("compile-once contract violated in steady state")


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--lut", action="store_true",
                    help="serve a CompiledLUTNet through the micro-batching "
                    "tier (default: the LM decode demo)")
    # --lut mode
    ap.add_argument("--artifact", default=None, metavar="NPZ",
                    help="saved CompiledLUTNet .npz to serve (default: "
                    "compile generated fpga4hep model A)")
    ap.add_argument("--optimize-level", type=int, default=3,
                    help="truth-table compiler level when compiling")
    ap.add_argument("--block-b", type=int, default=16,
                    help="engine batch bucket (jit block size)")
    ap.add_argument("--autotune", action="store_true",
                    help="when compiling, time every eligible plan variant "
                    "(layout x block_b x pack) and serve the measured "
                    "winner; the tier then buckets on the plan's block_b")
    ap.add_argument("--clients", type=int, default=8,
                    help="closed-loop concurrent clients")
    ap.add_argument("--requests-per-client", type=int, default=16)
    ap.add_argument("--rows-min", type=int, default=1)
    ap.add_argument("--rows-max", type=int, default=8,
                    help="request batch rows are uniform in [min, max]")
    ap.add_argument("--max-batch-rows", type=int, default=None,
                    help="tier size-flush threshold (default: block_b)")
    ap.add_argument("--flush-deadline-ms", type=float, default=2.0,
                    help="tier deadline flush for partial batches")
    ap.add_argument("--max-queue-rows", type=int, default=4096,
                    help="bounded-queue backpressure limit")
    ap.add_argument("--request-timeout-ms", type=float, default=None,
                    help="per-request launch deadline (default: none)")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="put the HTTP ingress in front of the tier on "
                    "this port (0 = ephemeral; the bound port is printed). "
                    "With --smoke/--open-loop: one-shot verified load "
                    "through the ingress; otherwise serve until SIGTERM "
                    "with a graceful drain (see docs/ingress.md)")
    ap.add_argument("--tenant-quota", default=None, metavar="RATE[:BURST]",
                    help="per-tenant token-bucket admission quota in "
                    "rows/s (burst defaults to one second of rate); "
                    "requests over quota get HTTP 429")
    ap.add_argument("--open-loop", type=float, default=None, metavar="RPS",
                    help="use the open-loop Poisson-arrival generator at "
                    "this offered load instead of closed-loop clients "
                    "(total requests stays clients * requests-per-client)")
    ap.add_argument("--report-json", default=None, metavar="PATH",
                    help="dump the LoadReport (latencies, goodput, "
                    "outcome breakdown) as JSON")
    ap.add_argument("--input-bw", type=int, default=2,
                    help="synthetic request code width when serving a "
                    "saved --artifact (codes are uniform in [0, 2**bw); "
                    "compiling instead uses the model's own width)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny load (4 clients x 4 requests) for CI")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the full obs metrics snapshot (tier "
                    "histograms, engine + compiler counters) as JSON on "
                    "exit (see docs/observability.md)")
    ap.add_argument("--spans-json", default=None, metavar="PATH",
                    help="dump the process span log (every layer's timed "
                    "spans, request and batch ids) as JSON on exit (see "
                    "docs/observability.md)")
    ap.add_argument("--report-every-s", type=float, default=5.0,
                    help="periodic one-line stats report interval while "
                    "the load runs (0 disables)")
    # LM mode
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()
    if args.lut:
        _run_lut(args)
    else:
        _run_lm(args)


if __name__ == "__main__":
    main()
