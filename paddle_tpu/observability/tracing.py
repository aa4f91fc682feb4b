"""Host-side trace spans: on the profiler's clock always, and in a ring
exported as Chrome trace-event JSON when armed.

The span half of the reference Fluid profiler (``paddle/platform/
profiler.h:25-131`` RecordEvent + GenProfileReport). ``span()`` is the one
primitive. It always yields a ``jax.profiler.TraceAnnotation``, so every
span of the program lands in whatever ``jax.profiler`` trace is being
taken (``utils.profiler.profiler(trace_dir=...)``, the benchmark's traced
runs, an XProf capture of a live server) on the same clock as the device's
operations, with no flag; with no profiler session the annotation is a
flag check in C++. When the tracer is armed (config flag ``telemetry`` or
``start()``) the span is also recorded per thread as a complete
("ph":"X") event in a bounded ring, which ``emit_chrome_trace`` writes in
the Chrome trace-event format for Perfetto/chrome://tracing. The ring
reads ``time.perf_counter_ns``; the export carries one
``(perf_counter_ns, time_ns)`` anchor so that the file can be laid beside
the device trace, whose clock is the wall clock's nanoseconds.

Names are ``<layer>:<phase>`` with the layers of PERF.md section 3
(``scheduler:``, ``session:``, ``executor:``); the trainer's and the
stateless serving tier's older camel-case names are kept because tests and
``tools/telemetry_probe.py`` read them.

Nesting is positional, as in chrome://tracing: two "X" events on the same
pid/tid nest iff one's [ts, ts+dur] window contains the other's.
"""

import collections
import contextlib
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["span", "instant", "start", "stop", "active", "clear",
           "events", "emit_chrome_trace", "chrome_trace_doc",
           "MAX_EVENTS"]

MAX_EVENTS = 200_000  # ring-buffer bound for always-on tracing


class _Span:
    """An armed span: the annotation plus the ring event, the ring's
    clock read inside the annotation's so the two agree on duration."""

    __slots__ = ("_tracer", "name", "args", "_annotation", "_t0")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._annotation = TraceAnnotation(name, **args)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._tracer._record(self.name, self._t0, t1, self.args)
        return False


class Tracer:
    def __init__(self):
        self.enabled = False
        self._flag_enabled = False      # mirror of config flag "telemetry"
        self._explicit = 0              # nested start()/stop() holds
        self._events = collections.deque(maxlen=MAX_EVENTS)
        self._lock = threading.Lock()
        self._epoch = time.perf_counter_ns()

    # -- lifecycle -------------------------------------------------------
    def _sync_enabled(self):
        self.enabled = self._flag_enabled or self._explicit > 0

    def start(self, clear=False):
        with self._lock:
            self._explicit += 1
            if clear:
                self._events.clear()
            self._sync_enabled()

    def stop(self):
        with self._lock:
            self._explicit = max(0, self._explicit - 1)
            self._sync_enabled()

    def set_flag(self, on):
        """Config-flag hook (observability package syncs ``telemetry``)."""
        with self._lock:
            self._flag_enabled = bool(on)
            self._sync_enabled()

    def clear(self):
        with self._lock:
            self._events.clear()

    # -- recording -------------------------------------------------------
    def _record(self, name, t0, t1, args):
        """One complete event from two ``perf_counter_ns`` readings."""
        ev = {"ph": "X", "name": name, "cat": "host",
              "ts": (t0 - self._epoch) / 1e3,
              "dur": (t1 - t0) / 1e3,
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)

    def instant(self, name, args=None):
        if not self.enabled:
            return
        ev = {"ph": "i", "name": name, "cat": "host", "s": "t",
              "ts": self.now_us(),
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)

    # -- export ----------------------------------------------------------
    def now_us(self):
        """Current time on the trace clock (same scale as event ts)."""
        return (time.perf_counter_ns() - self._epoch) / 1e3

    def clock_anchor(self):
        """One reading of both clocks, taken together: ``ts_us`` on the
        trace clock is ``perf_counter_ns`` is ``time_ns``. The profiler's
        device trace is stamped in wall-clock nanoseconds, so an event at
        ``ts`` sits at ``time_ns + (ts - ts_us) * 1e3`` there."""
        pc, wall = time.perf_counter_ns(), time.time_ns()
        return {"perf_counter_ns": pc, "time_ns": wall,
                "ts_us": (pc - self._epoch) / 1e3}

    def events(self, ts_from=None, ts_to=None):
        with self._lock:
            evs = list(self._events)
        if ts_from is not None:
            evs = [e for e in evs if e["ts"] >= ts_from]
        if ts_to is not None:
            evs = [e for e in evs if e["ts"] <= ts_to]
        return evs

    def emit_chrome_trace(self, path, ts_from=None, ts_to=None):
        """Write {"traceEvents": [...]} (Perfetto/chrome://tracing);
        optionally windowed to [ts_from, ts_to] on the trace clock. The
        document's ``metadata`` holds the ``clock_anchor``."""
        doc = chrome_trace_doc(self.events(ts_from, ts_to))
        doc["metadata"] = {"clock_anchor": self.clock_anchor()}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def chrome_trace_doc(evs, process_name="paddle_tpu host",
                     thread_names=None):
    """The chrome-trace document wrapper shared by the host-op tracer
    and the request-trace exporter: prepends process/thread "M"
    metadata to already-shaped trace events. ``thread_names`` maps
    tid -> display name (default ``host-<tid>``)."""
    tids = {}
    for ev in evs:
        tids.setdefault(ev.get("tid", 0), ev.get("pid", os.getpid()))
    meta = [{"ph": "M", "name": "process_name", "pid": os.getpid(),
             "tid": 0, "args": {"name": process_name}}]
    for tid, pid in sorted(tids.items(),
                           key=lambda kv: (isinstance(kv[0], str),
                                           kv[0])):
        name = (thread_names or {}).get(tid, "host-%s" % tid)
        meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                     "tid": tid, "args": {"name": name}})
    return {"traceEvents": meta + list(evs), "displayTimeUnit": "ms"}


_TRACER = Tracer()


def span(name, **args):
    """``with span("scheduler:admit", round=7): ...``. Always a
    ``TraceAnnotation`` (it reaches any running ``jax.profiler`` trace and
    costs a flag check otherwise); armed, the ring event as well."""
    if _TRACER.enabled:
        return _Span(_TRACER, name, args)
    return TraceAnnotation(name, **args)


def instant(name, **args):
    _TRACER.instant(name, args or None)


def start(clear=False):
    _TRACER.start(clear=clear)


def stop():
    _TRACER.stop()


def active():
    return _TRACER.enabled


def clear():
    _TRACER.clear()


def events(ts_from=None, ts_to=None):
    return _TRACER.events(ts_from, ts_to)


def now_us():
    return _TRACER.now_us()


def emit_chrome_trace(path, ts_from=None, ts_to=None):
    return _TRACER.emit_chrome_trace(path, ts_from, ts_to)


@contextlib.contextmanager
def trace(path=None, clear_first=True):
    """Bounded capture: start tracing, yield the tracer, optionally write
    the Chrome trace on exit."""
    start(clear=clear_first)
    try:
        yield _TRACER
    finally:
        stop()
        if path is not None:
            emit_chrome_trace(path)
