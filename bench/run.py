"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process, which holds the chips (the stream
cells' load generator is a child process that never imports JAX).  The
run makes the configuration's weights from its ``model_seed`` and its
traffic from ``--seed``, lets the program make and compile its truth
tables, warms every shape it will use, measures for ``--seconds``, and
then compares what the timed path produced with the
plain reference (``bench/models``).  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last the
``checks``: each number compared, with its limit.  Off a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.

A cell is found by name: its configuration is a JSON file of sizes under
``bench/configs/``, its traffic a JSON file of parameters under
``bench/traffic/`` whose ``kind`` names the module that offers it,
``bench/kinds/<kind>.py``, and each metric a reader of its own,
``bench/metrics/<name>.py``.
"""

from __future__ import annotations

from bench.clock import T_START  # first: set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

from bench import program, traffic  # noqa: E402
from bench.models import logicnet_ref  # noqa: E402

ROOT = program.ROOT
HERE = os.path.join(ROOT, "bench")


AT_LEAST = ("codes_compared",)   # checks whose limit is a floor, not a cap


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_cell(spec: dict, name: str) -> tuple[dict, dict, dict]:
    """``(cell, configuration, traffic mix)`` of the cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as fh:
        cfg = json.load(fh)
    return cell, cfg, traffic.load(cell["traffic"])


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    return [m for m in spec["per_layer" if trace else "end_to_end"]
            if cell in m.get("workloads", [cell])]


def read_metric(name: str, run: dict):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


def chips(n: int):
    """The first ``n`` TPU devices, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (default backend "
                     f"{devices[0].platform}); the benchmark runs on the "
                     f"chip only")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices


def use_compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else a fixed path in the
    checkout), with every program in it, however quick to compile, so
    that only a checkout's first run compiles."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Ctx:
    """What a traffic kind's runner needs for one run, and what it
    records."""

    def __init__(self, cell: dict, cfg: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, tables):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.chips = cell["chips"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.tables = tables
        self.setup_s = None
        self.window_s = None
        self.trace_dir = None
        self.layout = None
        self.memory_peak_bytes = 0

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it starts; with
        ``--trace 1`` the profiler records it."""
        import jax
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                self.t0 = time.perf_counter()
                self.setup_s = self.t0 - T_START
                yield self
                self.window_s = time.perf_counter() - self.t0
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    def memory_peak(self, devices) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices]
        self.memory_peak_bytes = int(max(peaks))


def compare(cfg: dict, weights: list, pairs: list) -> dict:
    """Share of served output codes that differ from the reference's,
    over every (input codes, served codes) pair the run kept."""
    wrong = total = 0
    for codes, served in pairs:
        want = logicnet_ref.forward(weights, codes, cfg["max_val"])
        if served.shape != want.shape:
            raise ValueError(f"served {served.shape} codes for "
                             f"{want.shape} expected")
        wrong += int((served.astype(np.int32) != want).sum())
        total += want.size
    return {"wrong": wrong, "total": total}


def run_cell(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
             trace: bool, devices) -> dict:
    """One run of a cell on ``devices``; returns the result line's object.

    The model is the configuration's own (``model_seed``), the same in
    every run; ``seed`` draws the traffic.  Models drawn from ``seed``
    pruned to different slab layouts at level 3, so the seed moved the
    work a scan does by more than two runs of one seed differ."""
    weights = logicnet_ref.make_weights(cfg, cfg["model_seed"])
    tables = program.make_tables(cfg, weights)
    print(f"[bench] set-up: tables made at "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    ctx = Ctx(cell, cfg, mix, seed, seconds, trace, tables)
    out = traffic.kind(mix).run(ctx)
    del tables, ctx.tables
    gc.collect()

    dev = devices[0]
    record = {**out["record"], "setup_s": ctx.setup_s,
              "window_s": ctx.window_s, "cfg": cfg, "mix": mix,
              "device_kind": dev.device_kind, "chips": ctx.chips,
              "trace": None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result_tail = {}
    if trace:
        from bench import trace as btrace
        try:
            path = next(os.path.join(d, f) for d, _, fs in
                        os.walk(ctx.trace_dir) for f in fs
                        if f.endswith(".xplane.pb"))
            red = btrace.reduce(path, ctx.chips)
        finally:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        record["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result_tail["breakdown"] = {"device_ops": red["device_ops"],
                                    "idle_gaps": red["idle_gaps"]}

    spec = load_spec()
    metrics = {}
    for m in cell_metrics(spec, cell["name"], trace):
        value = read_metric(m["name"], record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    print(f"[bench] layout {ctx.layout}; compile-once in the window: "
          f"retraces={out['retraces']} compiler_runs={out['compiler_runs']}",
          file=sys.stderr)
    cmp = compare(cfg, weights, out["compare"])
    checks = {
        "mismatch_share": {"value": cmp["wrong"] / max(cmp["total"], 1),
                           "limit": cfg["limits"]["mismatch_share"]},
        "codes_compared": {"value": cmp["total"], "limit": 1},
        "failed": {"value": out["failed"], "limit": 0},
        "retraces": {"value": out["retraces"], "limit": 0},
        "compiler_runs": {"value": out["compiler_runs"], "limit": 0},
    }
    correct = all(c["value"] >= c["limit"] if name in AT_LEAST
                  else c["value"] <= c["limit"]
                  for name, c in checks.items())
    return {"correct": bool(correct), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **result_tail, "checks": checks}


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        rel = ">=" if name in AT_LEAST else "<="
        print(f"check {name}: {c['value']} (limit {rel} {c['limit']})",
              file=sys.stderr)
    print(f"correct: {str(result['correct']).lower()}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")

    cell, cfg, mix = find_cell(load_spec(), args.workload)
    program.import_program()
    use_compile_cache()
    try:
        devices = chips(cell["chips"])
    except NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr, flush=True)
        return 3
    print(f"[bench] {args.workload} on {len(devices)} x "
          f"{devices[0].device_kind}; seed {args.seed}; window "
          f"{args.seconds} s; trace {args.trace}", file=sys.stderr,
          flush=True)
    result = run_cell(cell, cfg, mix, args.seed, args.seconds,
                      bool(args.trace), devices)
    print_result(result)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
