"""Share of its roofline the fused mixed-width LUT kernel
(``lut_network_mixed_pallas``) reached: the least time for the rows it
processed (``bench.work``) over its summed device time in the trace.
The kernel's custom calls are named after the engine's jitted
``_mixed_forward``."""

from bench import work

KERNEL = "_mixed_forward"


def read(run):
    t = run["trace"]
    if t is None or not t["kernel_s"].get(KERNEL):
        return None
    least = work.least_seconds(run["cfg"], run["rows"] / run["chips"],
                               run["device_kind"])
    return 100.0 * least / t["kernel_s"][KERNEL]
