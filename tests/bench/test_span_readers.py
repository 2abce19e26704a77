"""The per-layer readers of the program's span log, driven on the CPU: a
short stream and a short scan through the harness, then each reader on
the run's own record (its ``setup_s`` and ``window_s`` on ``T_START``'s
clock).  Each reads a positive number in its cells; the tier's three
device legs add up to the device leg that ``serve_device_seconds``
times; a window the log no longer holds whole, or a program without a
span log, reads nothing."""

import importlib.util
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import program, run  # noqa: E402
from bench.clock import T_START  # noqa: E402

TINY = {"name": "tiny", "architecture": "logicnet", "in_features": 8,
        "n_classes": 3, "hidden": [8, 8], "fan_in": 2, "bw": 2,
        "final_dense": True, "bw_fc": 2, "max_val": 2.0,
        "precision": "float32", "optimize_level": 3, "model_seed": 0,
        "limits": {"mismatch_share": 1e-4}}
# a light stream: nearly every batch serves one request, so the legs'
# means per batch and the device leg's mean per request weigh alike
STREAM = {"kind": "open_loop_http", "rate_rps": 25, "rows_min": 1,
          "rows_max": 8, "block_b": 16,
          "flush_deadline_ms": 2.0, "max_queue_rows": 4096}
SCAN = {"kind": "closed_loop_scan", "rows_per_call": 256,
        "distinct_batches": 2, "block_b": 128}
CELLS = {"stream": ({"name": "jsc-a.stream", "chips": 1}, STREAM),
         "scan": ({"name": "jsc-a.scan", "chips": 1}, SCAN)}
READERS = {"stream": ["ingress_self_ms.stream", "tier_handoff_ms.stream",
                      "tier_h2d_ms.stream", "tier_compute_ms.stream",
                      "tier_d2h_ms.stream"],
           "scan": ["engine_h2d_ms.scan", "engine_dispatch_ms.scan"]}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "span_reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "bench", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record(kind, monkeypatch, seconds=2.0):
    """Run the cell through the harness and return the record its
    metrics were read from."""
    import jax
    program.import_program()
    seen = []
    read_metric = run.read_metric

    def keep(name, record):
        seen.append(record)
        return read_metric(name, record)

    monkeypatch.setattr(run, "read_metric", keep)
    cell, mix = CELLS[kind]
    res = run.run_cell(cell, TINY, mix, 2 ** 33 + 5, seconds, False,
                       jax.devices())
    assert res["correct"] is True
    return seen[0]


@pytest.fixture(scope="module")
def stream_record():
    with pytest.MonkeyPatch.context() as mp:
        return _record("stream", mp)


@pytest.fixture(scope="module")
def scan_record():
    with pytest.MonkeyPatch.context() as mp:
        return _record("scan", mp)


@pytest.mark.parametrize("kind,name",
                         [(k, n) for k, names in READERS.items()
                          for n in names])
def test_reader_is_positive_in_its_cell(kind, name, stream_record,
                                        scan_record):
    record = stream_record if kind == "stream" else scan_record
    value = _reader(name)(record)
    assert value is not None and value > 0.0, name


def test_tier_legs_add_up_to_the_device_leg(stream_record):
    """``tier.h2d`` + ``tier.compute`` + ``tier.d2h`` split the interval
    that ``serve_device_seconds`` times, so their means add up to
    ``tier_device_ms.stream`` within 10%."""
    legs = sum(_reader(n)(stream_record) for n in
               ("tier_h2d_ms.stream", "tier_compute_ms.stream",
                "tier_d2h_ms.stream"))
    whole = _reader("tier_device_ms.stream")(stream_record)
    assert legs == pytest.approx(whole, rel=0.10)


def test_window_before_the_oldest_record_reads_nothing(monkeypatch):
    """A log that overwrote records from inside the window no longer
    holds it whole: every reader returns None rather than a mean biased
    to the window's end.  So does a window with no span in it, and a
    program that keeps no span log."""
    program.import_program()
    from repro import obs
    from repro.obs import trace

    small = obs.SpanLog(capacity=8)
    monkeypatch.setattr(trace, "SPAN_LOG", small)
    t0 = time.perf_counter()
    for i in range(20):
        for name in ("tier.h2d", "engine.h2d"):
            with obs.span(name):
                pass
    window = {"setup_s": t0 - T_START,
              "window_s": time.perf_counter() - t0}
    assert small.dropped > 0 and small.oldest_start > t0
    assert not small.holds(t0)
    for name in READERS["stream"] + READERS["scan"]:
        assert _reader(name)(window) is None, name

    later = {"setup_s": time.perf_counter() - T_START, "window_s": 1.0}
    assert small.holds(later["setup_s"] + T_START)
    for name in READERS["stream"] + READERS["scan"]:
        assert _reader(name)(later) is None, name

    monkeypatch.delattr(obs, "span_log")
    for name in READERS["stream"] + READERS["scan"]:
        assert _reader(name)(window) is None, name
