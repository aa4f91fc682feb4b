"""``collective_core_share``: the share of chip 0's busy time that
collectives hold on its core, in whichever form the compiler left them.

``collective_share`` (``trace_reduce.py``) goes by an instruction's opcode.
XLA:TPU issues an asynchronous collective as a pair of *fusions* that it
names ``async-collective-start`` and ``async-collective-done``
(``kind=kCustom``): the issue of the transfer and the wait at its end. By
opcode those are ``fusion``, so a step whose all-reduces were made
asynchronous (PR 38) reads near zero there while the waits are still on
the core. This reader goes by opcode *and* by that name, so it reads the
plain all-reduces of a step compiled without the options and the pairs of
one compiled with them on one scale. What it cannot see in either: a
start that the compiler fused into the product before it (that fusion is
the product's), and a product that runs slower beside a transfer.

``trace_reduce.load_xplane`` keeps no instruction's name, so the reader
opens the run's ``.xplane.pb`` itself (the trace ``run.py`` wrote under
``.bench_out/<cell>/trace``) and takes chip 0's operations and the window
from it. A run with no such file, a trace of another window (a stale
file) or one with no device plane (the CPU rehearsal) has nothing to read.
"""

import os
import re

from benchmarks.harness import lm, trace_reduce

ASYNC_PAIR = re.compile(r"^%?async-collective-(?:start|done)\b")


def is_collective(text):
    """Whether a device event, named by its HLO instruction's text, is a
    collective on the core: by opcode, or one half of an asynchronous
    pair by the name the TPU compiler gives it."""
    return bool(ASYNC_PAIR.match(text)) or \
        trace_reduce.parse_hlo_event(text)[2] == trace_reduce.COLLECTIVE_CAT


def load(path):
    """((window start, window end) or None, chip 0's operation events as
    ``[text, start_ns, dur_ns, category]``) of an ``.xplane.pb``; the
    category is ``collective`` or empty."""
    from jax.profiler import ProfileData
    windows, chips, collective = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        device = trace_reduce.DEVICE_PLANE.match(plane.name)
        if device:
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                events = chips.setdefault(int(device.group(1)), [])
                for e in line.events:
                    name = e.name
                    if name not in collective:
                        collective[name] = trace_reduce.COLLECTIVE_CAT \
                            if is_collective(name) else ""
                    events.append([name, int(e.start_ns),
                                   int(e.duration_ns), collective[name]])
        elif plane.name.startswith("/host:"):
            windows += [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                        for line in plane.lines for e in line.events
                        if e.name == trace_reduce.WINDOW_SPAN]
    window = max(windows, key=lambda se: se[1] - se[0]) if windows else None
    return window, chips[min(chips)] if chips else []


def share(events, window):
    """Self time of the collective events over the union of all events,
    both cut to the window, in percent; None for an idle line."""
    clipped = trace_reduce._clip(events, *window)
    busy = sum(e - s for s, e in trace_reduce._union(
        [(s, e) for _, s, e, _ in clipped]))
    if not busy:
        return None
    held = sum(self_ns for _, cat, self_ns in trace_reduce.self_times(clipped)
               if cat == trace_reduce.COLLECTIVE_CAT)
    return 100.0 * held / busy


def read(facts):
    if facts.trace is None:
        return None
    try:
        path = trace_reduce.find_xplane(os.path.join(
            lm.CHECKOUT, ".bench_out", facts.cell["name"], "trace"))
    except FileNotFoundError:
        return None
    window, events = load(path)
    if window is None or not events or \
            abs((window[1] - window[0]) / 1e9 - facts.trace["window_s"]) > 1e-6:
        return None
    return share(events, window)
