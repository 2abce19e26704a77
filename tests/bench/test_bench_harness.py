"""The benchmark's harness driven end to end on the CPU, at a size a test
run can hold: sound runs come out correct, the bfloat16 control and
faults planted in the timed path come out incorrect, and the command
refuses to run off the chip."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import ml_dtypes
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import program, run  # noqa: E402
from bench.models import logicnet_ref  # noqa: E402

TINY = {"name": "tiny", "architecture": "logicnet", "in_features": 8,
        "n_classes": 3, "hidden": [8, 8], "fan_in": 2, "bw": 2,
        "final_dense": True, "bw_fc": 2, "max_val": 2.0,
        "precision": "float32", "optimize_level": 3, "model_seed": 0,
        "limits": {"mismatch_share": 1e-4}}
STREAM = {"kind": "open_loop_http", "rate_rps": 40, "rows_min": 1,
          "rows_max": 8, "block_b": 16,
          "flush_deadline_ms": 2.0, "max_queue_rows": 4096}
SCAN = {"kind": "closed_loop_scan", "rows_per_call": 256,
        "distinct_batches": 2, "block_b": 128}
CELLS = {"scan": ({"name": "jsc-a.scan", "chips": 1}, SCAN),
         "stream": ({"name": "jsc-a.stream", "chips": 1}, STREAM)}


def _run(kind, seed=2 ** 33 + 3, cfg=TINY):
    import jax
    program.import_program()
    cell, mix = CELLS[kind]
    return run.run_cell(cell, cfg, mix, seed, 1.0, False, jax.devices())


def _patch_call(monkeypatch, serve):
    """Put ``serve(net, codes)`` in the place of the artifact's call."""
    program.import_program()
    from repro.engine.engine import CompiledLUTNet
    monkeypatch.setattr(CompiledLUTNet, "__call__",
                        lambda self, codes: serve(self, codes))


@pytest.mark.parametrize("kind", ["scan", "stream"])
def test_sound_run_is_correct(kind):
    res = _run(kind)
    assert res["correct"] is True
    assert res["checks"]["mismatch_share"]["value"] == 0.0
    assert res["checks"]["codes_compared"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("kind", ["scan", "stream"])
def test_an_altered_answer_is_incorrect(kind, monkeypatch):
    """A fault where the answer is produced: one output code of every
    call to the artifact is changed."""
    program.import_program()
    from repro.engine.engine import CompiledLUTNet
    call = CompiledLUTNet.__call__

    def altered(net, codes):
        out = call(net, codes)
        return out.at[0, 0].set((out[0, 0] + 1) % 4)

    _patch_call(monkeypatch, altered)
    res = _run(kind)
    assert res["correct"] is False
    assert res["checks"]["mismatch_share"]["value"] > \
        res["checks"]["mismatch_share"]["limit"]


@pytest.mark.parametrize("kind", ["scan", "stream"])
def test_half_the_batch_left_out_is_incorrect(kind, monkeypatch):
    """The artifact serves the first half of every batch's rows and
    leaves the rest at code 0."""
    program.import_program()
    from repro.engine.engine import CompiledLUTNet
    call = CompiledLUTNet.__call__

    def half(net, codes):
        out = call(net, codes)
        return out.at[(out.shape[0] + 1) // 2:].set(0)

    _patch_call(monkeypatch, half)
    res = _run(kind)
    assert res["correct"] is False
    assert res["checks"]["mismatch_share"]["value"] > \
        res["checks"]["mismatch_share"]["limit"]


@pytest.mark.parametrize("exc", ["TierOverloaded", "RuntimeError"])
def test_refused_requests_are_incorrect(exc, monkeypatch):
    """Every fifth request is refused by the tier (503) or fails inside it
    (500): the answered ones are right, and the run is still incorrect."""
    program.import_program()
    from repro.serve import tier as rtier
    infer = rtier.ServingTier.infer
    error = getattr(rtier, exc, None) or RuntimeError
    seen = []

    async def refusing(self, codes):
        seen.append(1)
        if len(seen) % 5 == 0:
            raise error("refused by the planted fault")
        return await infer(self, codes)

    monkeypatch.setattr(rtier.ServingTier, "infer", refusing)
    res = _run("stream")
    assert res["correct"] is False
    assert res["checks"]["mismatch_share"]["value"] == 0.0
    assert res["failed"] == res["checks"]["failed"]["value"] > 0


def test_leaving_out_the_exchange_between_chips_is_incorrect():
    """The stream through the tier sharded over four (virtual) devices, as
    a four-chip host serves it, with the gather of the shards left out:
    every shard's rows come from device 0."""
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import jax, jax.numpy as jnp
        from bench import program, run
        program.import_program()
        from repro.serve import tier as rtier
        make = rtier.ServingTier._make_forward
        FAULT = sys.argv[1] == "fault"

        def no_exchange(self):
            fwd, jitted = make(self)
            if not FAULT or jitted is None:
                return fwd, jitted
            def forward(padded):
                out = fwd(padded)
                shard = out.shape[0] // len(self._devices)
                return jnp.tile(out[:shard], (len(self._devices), 1))
            return forward, jitted

        rtier.ServingTier._make_forward = no_exchange
        cell = {{"name": "jsc-a.stream", "chips": 4}}
        res = run.run_cell(cell, {TINY!r}, {STREAM!r}, 5, 1.0, False,
                           jax.devices())
        print(res["correct"], res["device"]["count"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    for mode, want in (("sound", "True 4"), ("fault", "False 4")):
        out = subprocess.run([sys.executable, "-c", script, mode], cwd=ROOT,
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == want, out.stderr[-2000:]


@pytest.mark.parametrize("kind", ["scan", "stream"])
def test_the_bfloat16_control_is_incorrect(kind, monkeypatch):
    """The control, the reference computed in bfloat16 in the program's
    place, driven through the harness at jsc-a's published widths, fails
    the limit that sound runs meet."""
    import jax.numpy as jnp
    with open(os.path.join(ROOT, "bench", "configs", "jsc-a.json")) as fh:
        cfg = json.load(fh)
    seed = 2 ** 32 + 9
    weights = logicnet_ref.make_weights(cfg, cfg["model_seed"])

    def control(net, codes):
        return jnp.asarray(logicnet_ref.forward(
            weights, np.asarray(codes), cfg["max_val"], ml_dtypes.bfloat16))

    _patch_call(monkeypatch, control)
    res = _run(kind, seed, cfg)
    assert res["correct"] is False
    assert res["checks"]["failed"]["value"] == 0
    assert res["checks"]["mismatch_share"]["value"] > \
        res["checks"]["mismatch_share"]["limit"]


def test_reference_agrees_with_the_program_tables():
    """Every entry of the program's truth tables of the tiny stack is the
    reference's neuron function (sound runs read 0 at this size)."""
    program.import_program()
    w = logicnet_ref.make_weights(TINY, 11)
    tables = program.make_tables(TINY, w)
    for lay, tab in zip(w, tables):
        np.testing.assert_array_equal(tab.indices, lay["idx"])
        fi, bw = lay["fan_in"], lay["bw_in"]
        e = np.arange(2 ** (fi * bw))
        digits = (e[:, None] >> (bw * np.arange(fi))) & (2 ** bw - 1)
        own = dict(lay, idx=np.tile(np.arange(fi, dtype=np.int32),
                                    (lay["n_out"], 1)))
        np.testing.assert_array_equal(
            logicnet_ref.layer_forward(own, digits, 2.0).T, tab.table)


def _cli(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "jsc-a.stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out):
    lines = out.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_command_refuses_the_cpu():
    out = _cli(ROOT)
    assert out.returncode != 0 and _no_result(out)
    assert "no TPU" in out.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0 and _no_result(out)
