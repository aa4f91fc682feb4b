"""Which lowering each hand-written kernel took, decided and counted in
one place.

A Pallas kernel here has three ways to run: compiled by Mosaic for the
TPU, in the Pallas interpreter (the only way off-TPU — it keeps the CPU
tests honest about semantics and says nothing about the chip), or not at
all, when a geometry gate hands the call to the XLA reference. All three
give the same numbers, so nothing downstream can tell them apart; the
counter below is how a run says which one it got (``chip_smoke.py``
fails on anything but ``compiled``).
"""

import jax

from ..observability import metrics as _metrics

__all__ = ["interpret_mode", "record", "counts"]

# trace-time only: a call site counts each time it is traced (program
# construction's shape inference, then every compile) — zero
# steady-state cost, no flag reads
_LOWERINGS = _metrics.REGISTRY.counter(
    "paddle_kernel_lowerings_total",
    "Hand-written kernel call sites traced, by kernel and by the path "
    "taken: compiled (Mosaic), interpret (Pallas interpreter, off-TPU "
    "only) or xla (geometry gate fell back to the XLA reference)",
    labelnames=("kernel", "path"))


def interpret_mode():
    """True exactly when there is no TPU backend to compile for."""
    return jax.default_backend() != "tpu"


def record(kernel, interpret=None):
    """Count one traced call of ``kernel``: ``interpret`` False/True for
    the Pallas kernel compiled/interpreted, None for the XLA reference
    taken in its place."""
    path = "xla" if interpret is None else \
        ("interpret" if interpret else "compiled")
    _LOWERINGS.labels(kernel=kernel, path=path).inc()


def counts():
    """``{kernel: {path: count}}`` of everything traced so far in this
    process."""
    out = {}
    for (kernel, path), child in _LOWERINGS.children().items():
        out.setdefault(kernel, {})[path] = int(child.value)
    return out
