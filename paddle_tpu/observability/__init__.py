"""Unified telemetry: metrics registry + Chrome-trace spans.

One subsystem feeds three consumers:

* ``metrics``  — counters/gauges/histograms with labels; Prometheus text
  (``metrics.REGISTRY.expose_text()``) and JSON
  (``metrics.REGISTRY.dump_json()``) exposition. The legacy
  ``utils.stat.StatSet`` table is a view over this registry.
* ``tracing``  — one span primitive, ``tracing.span(name, **args)``,
  named ``<layer>:<phase>`` (``scheduler:``, ``session:``,
  ``executor:``). Every span is a ``jax.profiler.TraceAnnotation``: it
  reaches any ``jax.profiler`` trace without a flag, on the device
  trace's clock, and costs under a microsecond with no profiler session.
  Armed (``telemetry`` or ``tracing.start()``) it is also a ring event,
  exported as Chrome trace-event JSON
  (``tracing.emit_chrome_trace(path)``) with one
  ``(perf_counter_ns, time_ns)`` anchor. In Perfetto a decode round
  reads, on the ``generation-scheduler`` thread, as a
  ``scheduler:host_turn`` (``scheduler:deliver``, ``scheduler:admit``,
  ``scheduler:first_token``, ``session:step_prepare``,
  ``session:step_dispatch`` inside) followed by a ``session:step_wait``
  for the step launched a turn earlier (``GenerationScheduler``'s
  docstring, "One step ahead" and "The dispatcher's clock").
* instrumentation hooks in ``core.executor`` (compile-cache hits/misses,
  per-key compile wall time + XLA FLOPs/bytes), ``trainer`` (step-latency
  histogram, examples/sec, checkpoint time, periodic structured log), and
  ``reader.staging`` (queue depth, arena gauges).

All hooks are gated by the config flag ``telemetry``
(``config.set_flags(telemetry=True)``); disabled, the per-step cost is a
flag check and an annotation per span. Setting the flag also arms the
span ring buffer, so ``timer()``/``RecordEvent`` call sites across the
codebase record trace events with no further setup.

Recovery events are the exception to the gating: the resilience layer's
counters (``paddle_resilience_*`` from ``resilience/supervisor.py`` —
non-finite/skipped/rolled-back steps, reader retries, watchdog stalls,
preemptions — and ``paddle_checkpoint_*`` from ``io.py`` — fallbacks,
quarantines, verify time) record unconditionally, like the serving
metrics: they fire on rare events, never per step, and an operator
debugging a flapping job needs them present without re-running armed.
"""

from . import flight  # noqa: F401
from . import metrics  # noqa: F401
from . import request_trace  # noqa: F401
from . import tracing  # noqa: F401


def enabled():
    """The ``telemetry`` config-flag state (metric hooks armed?)."""
    from .. import config
    return bool(config.get_flag("telemetry"))


# last-synced (request_tracing, sample_rate, telemetry_port): the hook
# runs on EVERY set_flags (fault arming flips fault_injection
# constantly in chaos tests) — skip the sync work when nothing
# observability-shaped changed
_last_sync = [None]
_http_started = [False]


def _on_flags_changed(flags):
    tracing._TRACER.set_flag(flags.get("telemetry", False))
    state = (bool(flags.get("request_tracing", False)),
             float(flags.get("trace_sample_rate", 1.0) or 0.0),
             int(flags.get("telemetry_port", 0) or 0))
    armed, rate, port = state
    if state != _last_sync[0]:
        _last_sync[0] = state
        request_trace._TRACER.set_flag(armed, sample_rate=rate)
        flight.RECORDER.set_armed(armed)
    # The port sync is NOT deduped through _last_sync: a bind can fail
    # (port taken) and re-issuing the same set_flags must RETRY it,
    # not silently no-op. _sync_port_flag is idempotent when the
    # server is already bound, and the http.server import stays off
    # every process that never sets telemetry_port (only re-entered
    # afterwards to stop the server).
    if port or _http_started[0]:
        from . import http as _http
        _http._sync_port_flag(port)
        _http_started[0] = bool(port)


def _install_config_hook():
    from .. import config
    if _on_flags_changed not in config._on_change:
        config._on_change.append(_on_flags_changed)
    _on_flags_changed(config._flags)


_install_config_hook()
