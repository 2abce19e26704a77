"""Find the knee of a stream cell: the highest offered rate it sustains.

    python -m bench.sweep --workload jsc-a.stream --seed 1 --seconds 5 \
        --rates 500,1000,2000

One process serves the cell's configuration through the ingress and
tier once, then offers each rate in turn from a fresh load-generator
process, with the cell's traffic otherwise unchanged.  Per rate it
prints one JSON line: requests offered and answered, answered requests
per second over the offered span, p50 and p99 from due time, the
generator's lateness, and the backlog trend (median latency of the last
tenth of the requests over that of the first tenth).  A rate is
sustained when every request is answered, the trend stays near 1 and
the generator keeps to its schedule.  The cell's rate is then fixed, as
a number, in its traffic file; this tool is not part of a run.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import program, stream, traffic
from bench.models import logicnet_ref
from bench.run import Ctx, chips, find_cell, load_spec, use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell, cfg, mix = find_cell(load_spec(), args.workload)
    program.import_program()
    use_compile_cache()
    chips(cell["chips"])
    weights = logicnet_ref.make_weights(cfg, cfg["model_seed"])
    ctx = Ctx(cell, cfg, mix, args.seed, args.seconds, False,
              program.make_tables(cfg, weights))
    schedule = traffic.kind(mix).schedule
    net, ing = stream.serve(ctx, mix)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            m = dict(mix, rate_rps=rate)
            proc = stream.start_client(
                ing.port, net.n_out, m,
                stream.requests(schedule, m, cfg, 1.0, args.seed,
                                stream.WARMUP_STREAMS),
                stream.requests(schedule, m, cfg, args.seconds, args.seed))
            proc.stdout.readline()
            proc.stdin.write(b"go\n")
            proc.stdin.flush()
            res = stream.finish_client(proc)
            ok = res["status"] == 200
            lat = np.where(ok, res["lat_s"], np.inf) * 1e3
            tenth = max(1, len(lat) // 10)
            print(json.dumps({
                "rate_rps": rate, "offered": int(len(ok)),
                "answered": int(ok.sum()),
                "answered_per_s": float(ok.sum() / args.seconds),
                "p50_ms": float(np.percentile(lat, 50, method="inverted_cdf")),
                "p99_ms": float(np.percentile(lat, 99, method="inverted_cdf")),
                "late_p99_ms": float(np.percentile(res["late_s"] * 1e3, 99)),
                "backlog_trend": float(np.median(lat[-tenth:])
                                       / np.median(lat[:tenth])),
                "window_s": float(res["window_s"]),
            }), flush=True)
    finally:
        ing.stop()
    st = ing.stats()
    print(f"[sweep] {st['batches']} batches, flushes {st['flush_causes']}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
