"""``expert_stream_roofline_share``: the least time the chip could take to
stream the held experts a decode step touches, as a share of the time its
``moe_grouped_matmul`` calls took, per expert layer of a step.

The bytes are the least the algorithm reads: each held expert that took a
token (``paddle_generation_experts_touched_total`` over
``paddle_generation_moe_layer_steps_total``: the window's mean a layer
step) times its published matrices in bfloat16, from the architecture
module's ``grouped_matmul_ops_and_bytes`` with no rows; neither the rows
nor any padding a kernel's tiles hold is counted, so a chip cannot read
over 100. The seconds are the device's: the kernel's calls (an instruction
``moe_grouped_matmul.N``) that ran inside a run of the ``jit_decode``
module, summed over the runs that lie whole in the traced window, over
those runs times the step's expert layers. The counters are the measured
window's and the trace is of the seconds after it, so each side is taken
a layer step and not as a total.

``trace_reduce.load_xplane`` keeps neither an instruction's name nor the
modules' line, so the reader opens the run's ``.xplane.pb`` itself, as
``collective_core_share`` does. A run with no such file, a trace of another
window, one with no device plane (the CPU rehearsal) or without the kernel,
a program without the routing counters or an architecture whose module
does not count the kernel's bytes has nothing to read.
"""

import bisect
import os
import re

from benchmarks import architectures
from benchmarks.harness import lm, peaks, trace_reduce

KERNEL = re.compile(r"^%?moe_grouped_matmul(\.\d+)? = ")
MODULES_LINE = "XLA Modules"
DECODE_MODULE = re.compile(r"^jit_decode\b")


def load(path):
    """(the traced window (start, end) or None, chip 0's kernel calls as
    ``(start_ns, dur_ns)``, the decode module's runs on chip 0 as
    ``(start_ns, end_ns)``) of an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    windows, chips = [], {}
    for plane in ProfileData.from_file(path).planes:
        device = trace_reduce.DEVICE_PLANE.match(plane.name)
        if device:
            calls, runs = chips.setdefault(int(device.group(1)), ([], []))
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    calls += [(int(e.start_ns), int(e.duration_ns))
                              for e in line.events if KERNEL.match(e.name)]
                elif line.name == MODULES_LINE:
                    runs += [(int(e.start_ns),
                              int(e.start_ns) + int(e.duration_ns))
                             for e in line.events
                             if DECODE_MODULE.match(e.name)]
        elif plane.name.startswith("/host:"):
            windows += [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                        for line in plane.lines for e in line.events
                        if e.name == trace_reduce.WINDOW_SPAN]
    window = max(windows, key=lambda se: se[1] - se[0]) if windows else None
    calls, runs = chips[min(chips)] if chips else ([], [])
    return window, sorted(calls), sorted(runs)


def kernel_seconds_a_run(calls, runs, window):
    """Mean seconds of the kernel's calls inside one run of the decode
    module, over the runs that lie whole in the window; None where there
    is no such run or no call in any."""
    whole = [(s, e) for s, e in runs if s >= window[0] and e <= window[1]]
    starts = [s for s, _ in calls]
    total = 0
    for s, e in whole:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        total += sum(dur for _, dur in calls[lo:hi])
    return total / 1e9 / len(whole) if whole and total else None


def read(facts):
    counted = getattr(architectures.load(facts.cfg),
                      "grouped_matmul_ops_and_bytes", None)
    touched = facts.counters.get("paddle_generation_experts_touched_total")
    layer_steps = facts.counters.get(
        "paddle_generation_moe_layer_steps_total")
    steps = facts.counters.get("paddle_generation_decode_steps_total")
    if facts.trace is None or counted is None or not touched or \
            not layer_steps or not steps:
        return None
    try:
        path = trace_reduce.find_xplane(os.path.join(
            lm.CHECKOUT, ".bench_out", facts.cell["name"], "trace"))
    except FileNotFoundError:
        return None
    window, calls, runs = load(path)
    if window is None or abs(
            (window[1] - window[0]) / 1e9 - facts.trace["window_s"]) > 1e-6:
        return None
    a_run = kernel_seconds_a_run(calls, runs, window)
    if a_run is None:
        return None
    least_s = counted(facts.cfg, 0, touched)[1] / layer_steps / \
        peaks.peaks_for(facts.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (a_run / (layer_steps / steps))
