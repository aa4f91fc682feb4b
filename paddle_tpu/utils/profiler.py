"""Profiler: host-side event spans + device (XLA) trace capture.

Parity with the reference Fluid profiler (``paddle/platform/profiler.h:
25-131``: RecordEvent RAII, Enable/DisableProfiler with a sorted event
table; ``fluid/profiler.py`` cuda_profiler ctx mgr). TPU-native: host
spans aggregate through utils.stat (a registry view since the
observability PR) AND record Chrome-trace events
(``observability/tracing.py``); device-side profiling delegates to
jax.profiler (XLA trace, viewable in TensorBoard/Perfetto) — the analog
of nvprof.

``profiler()`` yields a :class:`ProfileHandle`; after the block exits,
``handle.report()`` returns the host event table (the reference's
DisableProfiler report, which the old implementation silently discarded)
and ``handle.chrome_trace(path)`` writes the host span trace.
"""

import contextlib

from ..observability import tracing as _tracing
from . import stat

__all__ = ["profiler", "ProfileHandle", "RecordEvent", "enable_profiler",
           "disable_profiler", "reset_profiler", "profile_report"]

_events = stat.StatSet("Profiler")
_enabled = [False]


def RecordEvent(name):
    """RAII span. Aggregates into the profiler table when profiling is
    enabled; always a profiler annotation, and a Chrome-trace event as
    well when tracing is armed (telemetry flag or
    profiler()/tracing.start())."""
    if _enabled[0]:
        return _events.span(name)  # includes the annotation and the event
    return _tracing.span(name)


def enable_profiler():
    _enabled[0] = True


def disable_profiler():
    _enabled[0] = False
    return profile_report()


def reset_profiler():
    _events.reset()


def profile_report():
    return _events.report()


class ProfileHandle:
    """Result of a ``with profiler(...) as prof:`` block.

    Inside the block the handle is live (report() shows events so far);
    after the block it carries the final report, the captured host trace
    events, and the device trace directory (if any).
    """

    def __init__(self, trace_dir=None):
        self.trace_dir = trace_dir
        self._report = None
        self._ts0 = _tracing.now_us()
        self._ts1 = None

    def report(self):
        """The sorted host event table (final after the block exits)."""
        return self._report if self._report is not None \
            else profile_report()

    def chrome_trace(self, path):
        """Write the HOST spans captured DURING the profiled block as
        Chrome trace-event JSON (the shared span ring buffer may hold
        older events — e.g. always-on telemetry — which are windowed
        out). The DEVICE trace (if trace_dir was given) is under
        ``trace_dir`` in TensorBoard/Perfetto format."""
        return _tracing.emit_chrome_trace(path, ts_from=self._ts0,
                                          ts_to=self._ts1)


@contextlib.contextmanager
def profiler(trace_dir=None):
    """Profile a region. Host spans always; if trace_dir given, also
    capture a device/XLA trace via jax.profiler (nvprof analog).
    Yields a ProfileHandle usable after the block exits."""
    handle = ProfileHandle(trace_dir=trace_dir)
    enable_profiler()
    _tracing.start()
    tracing_device = False
    if trace_dir is not None:
        try:
            import jax
            jax.profiler.start_trace(trace_dir)
            tracing_device = True
        except Exception:
            pass
    try:
        yield handle
    finally:
        if tracing_device:
            import jax
            jax.profiler.stop_trace()
        _tracing.stop()
        handle._ts1 = _tracing.now_us()
        handle._report = disable_profiler()
