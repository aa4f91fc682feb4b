"""The compile ledger: every second JAX spends making a program runnable,
booked to a role and a stage where it happens.

One process-wide listener on ``jax.monitoring``'s own events, registered
when the executor is imported. JAX stamps three kinds of span around its
compile-side work, each on the thread that does it:

* ``/jax/core/compile/jaxpr_trace_duration``: a jitted function traced to a
  jaxpr (stage ``trace``),
* ``/jax/core/compile/jaxpr_to_mlir_module_duration``: the jaxpr lowered to
  StableHLO, Mosaic kernels included (stage ``lower``),
* ``/jax/core/compile/backend_compile_duration``: XLA's compile, *or* the
  retrieval of the executable from JAX's persistent cache: the span covers
  the cache key, the read and the deserialization. ``/jax/compilation_cache/
  cache_hits`` fires inside such a span, so a span that saw it is booked as
  stage ``cache_read`` and every other as ``compile``.

The role is the one :func:`attribute` set on the thread: the executor wraps
the first call of each compiled step in it (span ``executor:first_call``),
so those seconds go to ``prefill_128``, ``decode``, ``train``... Whatever
compiles outside any first call (a bare ``jax.jit``, a feed's cast, an op
kernel's eager constant) goes to role ``other``: the ledger's total is the
process's total.

**No second is booked twice.** The spans nest: jits called while a step is
traced (``matmul``, ``tanh``) report a trace of their own inside the step's,
and an eager constant computed at trace time compiles inside it. An event
is therefore booked by its *own* seconds, its span less the spans that
ended inside it on the same thread (events arrive children first, so those
are the newest entries of a per-thread list). What a step's first call
books under ``trace`` sums to the step's own trace span less what compiled
inside it. Traces made by op shape inference at build time
(``core/registry.py::infer_shape``) are booked where they happen, on
``paddle_program_infer_shape_seconds_total``, and left out here.

Counters, always on::

    paddle_compile_seconds_total{role,stage}
    paddle_compile_events_total{role,stage}
"""

import contextlib
import threading

from . import metrics as _metrics

__all__ = ["attribute", "quiet", "install", "OTHER", "STAGES"]

OTHER = "other"
STAGES = ("trace", "lower", "compile", "cache_read")

_SECONDS = _metrics.REGISTRY.counter(
    "paddle_compile_seconds_total",
    "Seconds of JAX compile-side work (jax.monitoring spans, each by its "
    "own seconds: nested spans are not counted twice) by the role of the "
    "step being compiled ('other' outside any Executor first call) and "
    "stage: trace, lower, compile (XLA, after a persistent-cache miss or "
    "with no cache) or cache_read (a retrieval from JAX's persistent "
    "cache)",
    labelnames=("role", "stage"))
_EVENTS = _metrics.REGISTRY.counter(
    "paddle_compile_events_total",
    "jax.monitoring compile-side spans booked in "
    "paddle_compile_seconds_total, by role and stage",
    labelnames=("role", "stage"))

_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# per thread: ``role`` (None outside a first call), ``quiet`` (depth of
# shape inference), ``hit`` (a cache hit seen since the last backend span),
# ``done`` ([(start, seconds)] of the spans not yet found inside another)
_TLS = threading.local()
_DONE_CAP = 4096


def _on_event(event, **_):
    if event == _CACHE_HIT:
        _TLS.hit = True


def _on_span(event, start, end, **_):
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    tls = _TLS
    if stage == "compile":
        if getattr(tls, "hit", False):
            stage = "cache_read"
        tls.hit = False
    elif stage == "trace" and getattr(tls, "quiet", 0):
        return
    done = getattr(tls, "done", None)
    if done is None:
        done = tls.done = []
    seconds = end - start
    own = seconds
    while done and done[-1][0] >= start:
        own -= done.pop()[1]
    if len(done) >= _DONE_CAP:
        del done[:_DONE_CAP // 2]
    done.append((start, seconds))
    role = getattr(tls, "role", None) or OTHER
    _SECONDS.labels(role=role, stage=stage).inc(max(own, 0.0))
    _EVENTS.labels(role=role, stage=stage).inc()


@contextlib.contextmanager
def attribute(role):
    """Book what this thread compiles inside the block to ``role``."""
    prev = getattr(_TLS, "role", None)
    _TLS.role = role
    try:
        yield
    finally:
        _TLS.role = prev


@contextlib.contextmanager
def quiet():
    """Shape inference: its traces are booked by the caller, not here."""
    _TLS.quiet = getattr(_TLS, "quiet", 0) + 1
    try:
        yield
    finally:
        _TLS.quiet -= 1


_installed = []


def install():
    """Register the listener, once a process."""
    if _installed:
        return
    import jax.monitoring as mon
    mon.register_event_listener(_on_event)
    mon.register_event_time_span_listener(_on_span)
    _installed.append(True)
