"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData``.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per HLO
operation that ran, named by the operation's HLO text
(``%<instruction> = <shape> <opcode>(...)``).  A Pallas kernel is a
``custom-call`` whose text carries ``custom_call_target="tpu_custom_call"``;
its instruction is named after the jitted function that holds the
``pallas_call`` (the program's kernels set no ``name=``), for example
``_mixed_forward.1`` or ``_per_layer_forward.3``.  Host planes
(``/host:CPU``) hold the host threads' events, on the same clock, among
them the benchmark's own ``bench.window`` annotation, which bounds the
measured window.
"""

from __future__ import annotations

import re

import numpy as np

WINDOW = "bench.window"
_OPCODE = re.compile(r"[})\]] ([a-z][a-z0-9-]*)\(")
_INSTR = re.compile(r"^%([^ ]+) = ")


def op_name(text: str) -> tuple[str, str, bool]:
    """``(instruction without its .N suffix, opcode, is a Pallas kernel)``."""
    m = _INSTR.match(text)
    instr = re.sub(r"\.\d+$", "", m.group(1)) if m else text[:64]
    op = _OPCODE.search(text)
    return instr, op.group(1) if op else "?", "tpu_custom_call" in text


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) intervals; returns the disjoint union, sorted."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [iv[0].copy()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.asarray(out)


def reduce(path: str, n_devices: int) -> dict:
    """Busy time, kernel times, top operations and idle gaps of a trace.

    Only the first ``n_devices`` TPU devices count (the chips the cell
    uses), and only what lies inside the ``bench.window`` annotation.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_events = []
    window = None
    devices = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < n_devices:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    else:
                        host_events.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} annotation in the trace")
    if len(devices) < n_devices:
        raise ValueError(f"{path}: {len(devices)} of {n_devices} devices "
                         f"have an XLA Ops line")
    w0, w1 = window
    busy, ops, kernels, gaps = [], {}, {}, []
    for dev in sorted(devices):
        iv = []
        for s, e, name in devices[dev]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            iv.append((s, e))
            instr, opcode, is_kernel = op_name(name)
            key = f"{instr} ({opcode})"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
            if is_kernel:
                kernels[instr] = kernels.get(instr, 0.0) + (e - s) * 1e-9
        u = _union(np.asarray(iv, dtype=np.float64).reshape(-1, 2))
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        edges = np.concatenate([[w0], u.reshape(-1), [w1]]).reshape(-1, 2)
        gaps += [(float(s), float(e)) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    n = len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "kernel_s": {k: v / n for k, v in kernels.items()},
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[_host_activity(host_events, s, e), (e - s) * 1e-9]
                      for s, e in gaps[:10]],
    }


def idle_pct(run: dict):
    """Share of the traced window in which no operation ran on the device:
    one minus the union of the XLA Ops intervals, averaged over the cell's
    chips; nothing without a trace."""
    t = run["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _host_activity(events: list, s: float, e: float) -> str:
    """What the host was doing in the gap ``[s, e)``: the shortest host
    event that covers at least half of it (the most specific), else the
    one that overlaps it the most."""
    best, most, shortest = "no host event", 0.0, float("inf")
    for hs, he, name in events:
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        if ov >= 0.5 * (e - s):
            if most < 0.5 * (e - s) or he - hs < shortest:
                best, most, shortest = name, ov, he - hs
        elif ov > most:
            best, most = name, ov
    return best
