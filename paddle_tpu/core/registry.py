"""Op registry and execution context.

Capability parity with the reference OpRegistry/OpInfoMap
(``paddle/framework/op_registry.h:36,148``, ``op_info.h:34``), TPU-first:

* An op is ONE pure JAX function (``compute``). The same function serves as
  the runtime kernel (traced into the block's single XLA computation) and as
  build-time shape inference (run under ``jax.eval_shape``). The reference
  needed a separate InferShape pass plus per-device kernels per op
  (``operator.cc:461-533``); here XLA owns device lowering.
* Gradient ops do not need hand-written kernels: backward.py appends generic
  ``vjp_grad`` ops that reuse the forward compute via ``jax.vjp`` at trace
  time (see backward.py), mirroring GradOpDescMaker
  (``paddle/framework/grad_op_desc_maker.h``) without per-op grad code.
"""

import time

import numpy as np

import jax
import jax.numpy as jnp

from .framework import convert_dtype
from ..observability import compile_ledger as _ledger
from ..observability import metrics as _metrics

# The first of a step's traces: every op is traced once here, when the
# program is built, and again whenever the executor traces its block. The
# compile ledger leaves these traces out: they are booked here.
_INFER_SECONDS = _metrics.REGISTRY.counter(
    "paddle_program_infer_shape_seconds_total",
    "Seconds of op shape inference at program build time: compute() "
    "traced under jax.eval_shape, one call an appended op")
_INFER_OPS = _metrics.REGISTRY.counter(
    "paddle_program_infer_shape_ops_total",
    "Ops whose output shapes were inferred by tracing compute() under "
    "jax.eval_shape at program build time")

# Build-time stand-in for unknown (-1) dimensions during eval_shape.
_DIM_PLACEHOLDER = 8191

_registry = {}


class OpDef:
    def __init__(self, type, compute, infer_shape=None, needs_rng=False,
                 skip_eval_shape=False, stateful=False):
        self.type = type
        self.compute = compute
        self.custom_infer_shape = infer_shape
        self.needs_rng = needs_rng
        # Ops whose compute cannot run abstractly (e.g. host IO).
        self.skip_eval_shape = skip_eval_shape
        self.stateful = stateful


def register_op(type, compute=None, **kwargs):
    """Register an op. Usable as a decorator:  @register_op("relu")"""
    def deco(fn):
        if type in _registry:
            raise ValueError("op %r already registered" % type)
        _registry[type] = OpDef(type, fn, **kwargs)
        return fn
    if compute is not None:
        return deco(compute)
    return deco


def get_op_def(type):
    try:
        return _registry[type]
    except KeyError:
        raise NotImplementedError("no TPU op registered for type %r" % type)


def registered_ops():
    return sorted(_registry)


class ExecContext:
    """What an op's compute sees: bound input values + attrs (+ rng key).

    The analog of the reference ExecutionContext (``operator.h:177``) without
    Scope/DeviceContext — values are JAX arrays (or tracers) bound by the
    executor before the call, so compute is a pure function.
    """

    __slots__ = ("op", "_values", "rng_key", "block", "trace")

    def __init__(self, op, values, rng_key=None, block=None, trace=None):
        self.op = op
        self._values = values  # slot -> list of values (None for missing)
        self.rng_key = rng_key
        self.block = block
        self.trace = trace  # executor _TraceState (None in abstract eval)

    def input(self, slot, default=None):
        vals = self._values.get(slot)
        if not vals:
            return default
        return vals[0]

    def inputs(self, slot):
        return self._values.get(slot) or []

    def has_input(self, slot):
        vals = self._values.get(slot)
        return bool(vals) and vals[0] is not None

    def attr(self, name, default=None):
        return self.op.attrs.get(name, default)

    def output_names(self, slot):
        return self.op.outputs.get(slot, [])


def flat_input_slots(op):
    """Deterministic (slot, index) ordering of an op's inputs (for vjp)."""
    out = []
    for slot in sorted(op.inputs):
        for i in range(len(op.inputs[slot])):
            out.append((slot, i))
    return out


def flat_output_slots(op):
    out = []
    for slot in sorted(op.outputs):
        for i in range(len(op.outputs[slot])):
            out.append((slot, i))
    return out


def normalize_outputs(op, result):
    """compute() returns {slot: value-or-list}; normalize to {slot: list}."""
    norm = {}
    for slot, val in result.items():
        if isinstance(val, (list, tuple)):
            norm[slot] = list(val)
        else:
            norm[slot] = [val]
    return norm


def infer_shape(op, block):
    """Set output var shapes/dtypes by abstract-evaluating compute()."""
    opdef = get_op_def(op.type)
    if opdef.custom_infer_shape is not None:
        opdef.custom_infer_shape(op, block)
        return
    if opdef.skip_eval_shape:
        return

    # Bind abstract inputs from block metadata.
    specs = {}
    for slot, names in op.inputs.items():
        vals = []
        for name in names:
            var = block.var_or_none(name)
            if var is None or var.shape is None:
                return  # cannot infer
            shape = tuple(_DIM_PLACEHOLDER if d in (-1, None) else d
                          for d in var.shape)
            vals.append(jax.ShapeDtypeStruct(shape, convert_dtype(var.dtype)))
        specs[slot] = vals

    def abstract_fn():
        rng = jax.random.PRNGKey(0) if opdef.needs_rng else None
        ctx = ExecContext(op, specs_to_values(), rng_key=rng, block=block)
        result = normalize_outputs(op, opdef.compute(ctx))
        flat = []
        for slot, _ in _out_slots:
            vals = result.get(slot, [])
            flat.append(vals.pop(0) if vals else None)
        # eval_shape needs a pytree of arrays; None is fine (leaf dropped)
        return flat

    # We need real tracers: wrap specs via closure over eval_shape inputs.
    leaf_specs = []
    leaf_index = {}
    for slot, vals in specs.items():
        for i, s in enumerate(vals):
            leaf_index[(slot, i)] = len(leaf_specs)
            leaf_specs.append(s)

    _out_slots = flat_output_slots(op)

    _current_leaves = []

    def specs_to_values():
        values = {}
        for slot, vals in specs.items():
            values[slot] = [_current_leaves[leaf_index[(slot, i)]]
                            for i in range(len(vals))]
        return values

    def wrapped(*leaves):
        _current_leaves[:] = leaves
        return abstract_fn()

    t0 = time.perf_counter()
    try:
        with _ledger.quiet():
            out_structs = jax.eval_shape(wrapped, *leaf_specs)
    except Exception as e:  # surface op name for debuggability
        raise type(e)("shape inference failed for op %r: %s" % (op.type, e)) \
            from e
    finally:
        _INFER_SECONDS.inc(time.perf_counter() - t0)
        _INFER_OPS.inc()

    for (slot, i), struct in zip(_out_slots, out_structs):
        names = op.outputs.get(slot, [])
        if i >= len(names) or struct is None:
            continue
        var = block.var_or_none(names[i])
        if var is None:
            continue
        shape = tuple(-1 if d == _DIM_PLACEHOLDER else d for d in struct.shape)
        var.shape = shape
        var.dtype = convert_dtype(struct.dtype)
