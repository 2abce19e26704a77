"""The benchmark's yardstick: work function, peaks, trace reduction,
traffic generator and ``BENCHMARK.json`` (CPU only, no chip)."""

import gzip
import json
import math
import os
import re
import shutil
import statistics
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import stream, trace, traffic, work  # noqa: E402
from bench.kinds import closed_loop_scan, open_loop_http  # noqa: E402


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as fh:
        return json.load(fh)


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


# -- work function -----------------------------------------------------------

@pytest.mark.parametrize("name, bytes_, ops", [
    # 16 inputs x 3 bits = 6 B in; 64 outputs x 3 bits = 24 B out;
    # one lookup per neuron of 3 x 64
    ("jsc-a", 6 + 24, 3 * 64),
    # 784 x 2 bits = 196 B in; 512 x 2 bits = 128 B out; 3 x 512 lookups
    ("mnist-512x3", 196 + 128, 3 * 512),
])
def test_work_per_row_matches_hand_counts(name, bytes_, ops):
    assert work.per_row(_cfg(name)) == {"bytes": bytes_, "ops": ops}


def test_least_time_is_the_larger_bound():
    cfg = _cfg("mnist-512x3")
    rows = 8192
    by_bytes = rows * 324 / 819e9
    by_ops = rows * 1536 / 393e12
    assert by_bytes > by_ops
    assert work.least_seconds(cfg, rows, "TPU v5 lite") == \
        pytest.approx(by_bytes, rel=1e-12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.least_seconds(_cfg("jsc-a"), 1, "cpu")


# -- trace reduction ---------------------------------------------------------

@pytest.fixture(scope="module")
def mnist_trace(tmp_path_factory):
    """Three 8192-row calls of MNIST 512x3 through the per-layer kernel,
    recorded on a TPU v5e inside a ``bench.window`` annotation."""
    path = tmp_path_factory.mktemp("trace") / "mnist.xplane.pb"
    src = os.path.join(ROOT, "bench", "testdata",
                       "mnist-512x3.scan.xplane.pb.gz")
    with gzip.open(src) as fin, open(path, "wb") as fout:
        shutil.copyfileobj(fin, fout)
    return trace.reduce(str(path), 1)


def test_trace_window_busy_and_kernels(mnist_trace):
    r = mnist_trace
    # the window annotation lasted 452.136 ms; the three device modules
    # 139.655 ms each, nearly all of it the three per-layer kernels
    # (152.619 + 133.186 + 133.022 ms over the three calls)
    assert r["window_s"] == pytest.approx(0.452136, rel=1e-5)
    assert r["busy_s"] == pytest.approx(3 * 0.139655, rel=1e-4)
    assert set(r["kernel_s"]) == {"_per_layer_forward"}
    assert r["kernel_s"]["_per_layer_forward"] == pytest.approx(
        0.152619 + 0.133186 + 0.133022, rel=1e-4)
    assert r["busy_s"] <= r["window_s"]


def test_trace_breakdown_lists(mnist_trace):
    ops, gaps = mnist_trace["device_ops"], mnist_trace["idle_gaps"]
    assert ops[0][0] == "_per_layer_forward (custom-call)"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    assert 1 <= len(gaps) <= 10 and len(ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    # idle time cannot exceed the window minus the busy time
    idle = mnist_trace["window_s"] - mnist_trace["busy_s"]
    assert sum(g[1] for g in gaps) <= idle + 1e-9


@pytest.mark.parametrize("text, want", [
    ('%_mixed_forward.1 = s32[65536,64]{1,0:T(8,128)S(1)} custom-call('
     's32[65536,16]{1,0} %copy), custom_call_target="tpu_custom_call"',
     ("_mixed_forward", "custom-call", True)),
    ('%copy.11 = s32[65536,64]{0,1:T(8,128)} copy(s32[65536,64]{1,0} %x)',
     ("copy", "copy", False)),
    ('%copy-start = (s8[14,16,16]{2,1,0}, u32[]{:S(2)}) copy-start(s8[1] %t)',
     ("copy-start", "copy-start", False)),
])
def test_op_names(text, want):
    assert trace.op_name(text) == want


# -- traffic -----------------------------------------------------------------

STREAM = {"kind": "open_loop_http", "rate_rps": 1000, "rows_min": 1,
          "rows_max": 8, "block_b": 16,
          "flush_deadline_ms": 2.0, "max_queue_rows": 4096}
SIZES = {"in_features": 16, "bw": 3}


def test_stream_traffic_repeats_exactly():
    a, b = (stream.requests(open_loop_http.schedule, STREAM, SIZES, 3.0,
                            2 ** 33 + 1) for _ in range(2))
    for key in ("at", "rows", "codes"):
        np.testing.assert_array_equal(a[key], b[key])
    assert a["codes"].shape == (a["rows"].sum(), 16)
    assert a["codes"].max() < 8
    warm = stream.requests(open_loop_http.schedule, STREAM, SIZES, 3.0,
                           2 ** 33 + 1, stream.WARMUP_STREAMS)
    assert not np.array_equal(warm["codes"][:8], a["codes"][:8])


def test_every_seed_gets_the_same_work():
    a = open_loop_http.schedule(STREAM, 3.0, 5, 1)
    b = open_loop_http.schedule(STREAM, 3.0, 6, 1)
    assert len(a["at"]) == len(b["at"]) == 3000
    assert not np.array_equal(a["rows"], b["rows"])
    np.testing.assert_array_equal(np.sort(a["rows"]), np.sort(b["rows"]))
    gaps = [np.sort(np.diff(s["at"], prepend=0.0)) for s in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-6, atol=1e-12)
    assert 0 < a["at"][0] and a["at"][-1] == pytest.approx(3.0)
    assert np.all(gaps[0] > 0)


def test_scan_batches_repeat_exactly():
    mix = {"kind": "closed_loop_scan", "rows_per_call": 64,
           "distinct_batches": 3, "block_b": 128}
    a = closed_loop_scan.batches(mix, 16, 3, 7)
    b = closed_loop_scan.batches(mix, 16, 3, 7)
    assert len(a) == 3
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], closed_loop_scan.batches(mix, 16, 3, 8)[0])


@pytest.mark.parametrize("kind", ["open_loop_http", "closed_loop_scan"])
def test_the_control_reads_what_a_run_compares(kind):
    """``inputs``, which the control reads, are the codes the run sends."""
    mix = STREAM if kind == "open_loop_http" else {
        "kind": kind, "rows_per_call": 64, "distinct_batches": 2,
        "block_b": 128}
    got = traffic.kind(mix).inputs(SIZES, mix, 11, 2.0)
    want = ([stream.requests(open_loop_http.schedule, mix, SIZES, 2.0,
                             11)["codes"]]
            if kind == "open_loop_http"
            else closed_loop_scan.batches(mix, 16, 3, 11))
    assert len(got) == len(want)
    for x, y in zip(got, want):
        np.testing.assert_array_equal(x, y)


def test_a_kind_is_found_by_its_file(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "kinds").mkdir()
    (tmp_path / "traffic" / "odd.json").write_text('{"kind": "no_such"}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="no kind 'no_such'"):
        traffic.load("odd")
    (tmp_path / "kinds" / "no_such.py").write_text("")
    assert traffic.load("odd") == {"kind": "no_such"}


# -- BENCHMARK.json ----------------------------------------------------------

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def _names(spec):
    yield from (c["name"] for c in spec["configs"])
    for w in spec["workloads"]:
        yield from (w["name"], w["config"], w["traffic"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        yield m["name"]
    for c in spec["configs"]:
        yield from c["reduced"]


def test_names_and_units_use_allowed_characters():
    spec = _spec()
    for name in _names(spec):
        assert NAME.fullmatch(name), name
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([c["why"] for c in spec["configs"]]
                 + [c["source"] for c in spec["configs"]]
                 + [w["why"] for w in spec["workloads"]]
                 + [m["layer"] for m in spec["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_finds_its_files_by_name():
    spec = _spec()
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, configs[w["config"]]["file"]))
        kind = traffic.kind(traffic.load(w["traffic"]))
        assert callable(kind.run) and callable(kind.inputs)
        assert w["chips"] in (1, 4)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py")), m["name"]
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_moves_and_cells_agree():
    spec = _spec()
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"]), cell


def test_run_length_fits_the_check_budget():
    spec = _spec()
    rs = spec["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_bound_rule_uses_statistics_quartiles():
    # the spread a bound is set from: interquartile range over the median,
    # with Python's statistics.quantiles (the rule PERF.md states)
    runs = [10.0, 10.2, 10.1, 9.9, 10.3, 10.0]
    q1, _, q3 = statistics.quantiles(runs, n=4)
    spread = (q3 - q1) / statistics.median(runs)
    assert 0 < spread < 0.05
    assert math.isclose(spread, (10.225 - 9.975) / 10.05)
