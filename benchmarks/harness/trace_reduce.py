"""Reduces a profiler trace to the numbers the benchmark reports: device
busy and idle time, time per operation, the share of collectives and of
hand-written kernels, and the idle gaps attributed to what the host was
doing. Every PR computes these the same way, from this file.

The reduction works on a plain structure, so that it can be checked against
a small recorded trace (``benchmarks/tests/data``) without a profiler:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[label, start_ns, dur_ns, category],
                                       ...]}]},
                {"name": "/host:CPU",
                 "lines": [{"name": "python",
                            "events": [[name, start_ns, dur_ns, ""], ...]}]}]}

``load_xplane`` makes that structure from the ``.xplane.pb`` the JAX
profiler writes. The traced window is the span named ``bench:window`` that
the harness records on the host around the steady period it traces; all
times are on the profiler's one clock.
"""

import bisect
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench:window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)")
# categories the reduction knows by name; any other is the HLO opcode
MOSAIC, COLLECTIVE_CAT = "mosaic", "collective"
# On the TPU an event of the "XLA Ops" line is named by the whole text of
# its HLO instruction: "%fusion.7 = (f32[8,128]{1,0:T(8,128)}, ...)
# fusion(...), kind=kOutput, calls=%fused_computation.3".
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"\bkind=(k\w+)")
_LABEL_MAX = 120


def parse_hlo_event(text):
    """(instruction, label, category) of a device event named by its HLO
    text. The label names the operation without its number (opcode, fusion
    kind or custom-call target, result shapes without layouts), so that the
    same operation in every layer adds up under one name; the category is
    ``mosaic`` for a Pallas kernel (a ``tpu_custom_call``), ``collective``
    for a collective, else the opcode."""
    if " = " not in text:
        return text, text[:_LABEL_MAX], ""
    head, rest = text.split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    if not m:
        return head.lstrip("%"), text[:_LABEL_MAX], ""
    opcode = m.group(1)
    shapes = _LAYOUT.sub("", rest[:max(0, m.start() - 1)]).strip()
    target, kind = _TARGET.search(rest), _KIND.search(rest)
    detail = target.group(1) if target else kind.group(1) if kind else ""
    label = "%s%s -> %s" % (opcode, "(%s)" % detail if detail else "", shapes)
    if opcode == "custom-call" and detail == "tpu_custom_call":
        category = MOSAIC
    elif COLLECTIVE.match(opcode):
        category = COLLECTIVE_CAT
    else:
        category = opcode
    return head.lstrip("%"), label[:_LABEL_MAX], category
# host events shorter than this say nothing about a gap worth naming
_MIN_HOST_SPAN_NS = 5_000
# a gap shorter than this lies between two operations of one program, and is
# the device's own; only longer ones are set against what the host was doing
_MIN_HOST_GAP_NS = 20_000
BETWEEN_OPS = "device:gaps_under_20us"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def load_xplane(path):
    """The plain structure above from an ``.xplane.pb``: device planes whole,
    host planes without instantaneous and very short events."""
    from jax.profiler import ProfileData
    planes, parsed = [], {}     # an instruction's text repeats every step
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                dur = int(e.duration_ns)
                if device and line.name == OPS_LINE:
                    name = e.name
                    if name not in parsed:
                        parsed[name] = parse_hlo_event(name)[1:]
                    label, cat = parsed[name]
                    events.append([label, int(e.start_ns), dur, cat])
                elif device:
                    events.append([e.name[:_LABEL_MAX], int(e.start_ns), dur,
                                   ""])
                elif dur >= _MIN_HOST_SPAN_NS or e.name.startswith("bench:"):
                    events.append([e.name, int(e.start_ns), dur, ""])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(events, w0, w1):
    """(name, start, end, category) of events cut to the window."""
    out = []
    for name, start, dur, cat in events:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            out.append((name, s, e, cat))
    return out


def self_times(events):
    """Self time of each event on one line: its duration minus what the
    events nested inside it cover. Returns [(name, category, self_ns)].
    Events on a line nest or follow each other; they do not cross."""
    order = sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1])))
    out, stack = [], []     # stack items: [name, cat, end, self_ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, cat, _, self_ns = stack.pop()
            out.append((name, cat, self_ns))
    for name, s, e, cat in order:
        close(s)
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, cat, e, e - s])
    close(float("inf"))
    return out


class _Coverage:
    """Covered length of a set of intervals up to a time, for overlaps."""

    def __init__(self, intervals):
        merged = _union(intervals)
        self.starts = np.asarray([s for s, _ in merged], np.int64)
        self.ends = np.asarray([e for _, e in merged], np.int64)
        self.cum = np.concatenate([[0], np.cumsum(self.ends - self.starts)])
        self.total = int(self.cum[-1])

    def upto(self, t):
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return int(self.cum[i - 1]) + int(
            min(t, self.ends[i - 1]) - self.starts[i - 1])

    def overlap(self, a, b):
        return self.upto(b) - self.upto(a)


def _window(trace):
    spans = [(s, s + d) for plane in trace["planes"]
             if plane["name"].startswith("/host:")
             for line in plane["lines"]
             for name, s, d, _ in line["events"] if name == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no %r span" % WINDOW_SPAN)
    return max(spans, key=lambda se: se[1] - se[0])


# off the chip (the CPU rehearsal in benchmarks/tests) XLA's CPU client runs
# the operations on host threads with this line name; they stand in for a
# device line there so that the whole flow can be walked, never on a TPU
_CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"


def _device_ops(trace, rehearsal=False):
    """{chip index: events of its operation line}"""
    out = {}
    if rehearsal:
        events = [ev for plane in trace["planes"]
                  if plane["name"].startswith("/host:")
                  for line in plane["lines"]
                  if line["name"].startswith(_CPU_CLIENT_LINE)
                  for ev in line["events"] if not ev[0].startswith("end: ")]
        return {0: events} if events else {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if OPS_LINE not in lines:
            raise ValueError("device plane %s has no %r line (has %s)"
                             % (plane["name"], OPS_LINE, sorted(lines)))
        out[int(m.group(1))] = lines[OPS_LINE]
    return out


def _attribute(gaps, host_spans, top):
    """Idle seconds by what the host was doing. A gap of 20 us and more
    goes to the most specific span name (the least total time in the
    window) among those that cover at least half of it, to
    ``host:unattributed`` if there is none; shorter gaps are summed under
    ``device:gaps_under_20us``."""
    by_name = {}
    for name, s, e, _ in host_spans:
        if name != WINDOW_SPAN:
            by_name.setdefault(name, []).append((s, e))
    cover = {n: _Coverage(iv) for n, iv in by_name.items()}
    # most specific first; the first that covers half the gap takes it
    ranked = sorted(cover, key=lambda n: cover[n].total)
    idle = {}
    for a, b in gaps:
        if b - a < _MIN_HOST_GAP_NS:
            owner = BETWEEN_OPS
        else:
            owner = next((name for name in ranked
                          if 2 * cover[name].overlap(a, b) >= b - a),
                         "host:unattributed")
        idle[owner] = idle.get(owner, 0) + (b - a)
    ranked_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return [[n, ns / 1e9] for n, ns in ranked_idle]


def reduce_trace(trace, chips=None, top=10, rehearsal=False):
    """The reduction. ``chips``: how many device planes the run used (the
    lowest-numbered ones); all of them when None."""
    w0, w1 = _window(trace)
    per_chip = _device_ops(trace, rehearsal)
    used = sorted(per_chip)[:chips] if chips else sorted(per_chip)
    if not used:
        raise ValueError("trace holds no device plane")
    busy_ns, first = [], None
    for chip in used:
        events = _clip(per_chip[chip], w0, w1)
        merged = _union([(s, e) for _, s, e, _ in events])
        busy_ns.append(sum(e - s for s, e in merged))
        if first is None:
            first = (events, merged)
    events, merged = first          # the breakdown is of the first chip
    busy0 = busy_ns[0]
    by_name, coll_ns, mosaic_ns = {}, 0, 0
    for name, cat, self_ns in self_times(events):
        by_name[name] = by_name.get(name, 0) + self_ns
        if cat == COLLECTIVE_CAT:
            coll_ns += self_ns
        elif cat == MOSAIC:
            mosaic_ns += self_ns
    edges = [w0] + [t for se in merged for t in se] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host_spans = [ev for plane in trace["planes"]
                  if plane["name"].startswith("/host:")
                  for line in plane["lines"]
                  for ev in _clip(line["events"], w0, w1)]
    window_s = (w1 - w0) / 1e9
    busy_s = float(np.mean(busy_ns)) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_chip": [b / 1e9 for b in busy_ns],
        "idle_share": 1.0 - busy_s / window_s,
        "collective_share": coll_ns / busy0 if busy0 else None,
        "mosaic_share": mosaic_ns / busy0 if busy0 else None,
        "device_ops": [[n, ns / 1e9] for n, ns in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": _attribute(gaps, host_spans, top),
        "n_device_events": len(events),
        "n_gaps": len(gaps),
        "longest_gap_s": max((b - a for a, b in gaps), default=0) / 1e9,
    }
