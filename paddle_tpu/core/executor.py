"""Executor: lowers a whole Block to ONE jitted XLA computation.

This is the north-star seam (BASELINE.json): the reference Executor walks a
block and dispatches a C++/CUDA kernel per op
(``paddle/framework/executor.cc:77,116-129``, ``operator.cc:461-533``); here
the block is *traced* — each op's JAX compute runs on tracers — and the whole
program becomes a single ``jax.jit`` computation that XLA fuses and schedules
for the MXU. Persistable state (parameters, optimizer accumulators, RNG key,
metric states) lives in a Scope as device arrays and is threaded through the
jitted function with buffer donation, so parameter updates are in-place in
HBM.

Differences from the reference, by design:
* No per-op device contexts / data transforms: XLA owns layout and fusion.
* Temporaries never materialize in a Scope.
* Gradients: ``vjp_grad`` ops (appended by backward.py) are linked to their
  forward op at trace time through a vjp cache — forward activations are
  shared, nothing is recomputed, and the whole fwd+bwd+update step is still
  one XLA computation.
"""

import itertools
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import compile_cache as _compile_cache
from . import ingest as _ingest
from . import registry
from .framework import (Program, Variable, default_main_program,
                        convert_dtype, RNG_STATE_VAR)
from .scope import global_scope
from ..observability import compile_ledger as _ledger
from ..observability import metrics as _metrics
from ..observability import request_trace as _rtrace
from ..observability import tracing as _tracing

EMPTY_VAR = "@EMPTY@"

__all__ = ["Executor", "EMPTY_VAR"]

# Compile-cache + per-step cost telemetry (hooks gated by the config
# flag "telemetry"; family creation here is one-time and free).
_CACHE_HITS = _metrics.REGISTRY.counter(
    "paddle_executor_cache_hits_total",
    "Executor.run compile-cache hits")
_CACHE_MISSES = _metrics.REGISTRY.counter(
    "paddle_executor_cache_misses_total",
    "Executor.run compile-cache misses (trace + XLA compile)")
_STEP_FLOPS = _metrics.REGISTRY.gauge(
    "paddle_executor_step_flops",
    "XLA cost-analysis FLOPs of the cached step (MFU numerator)",
    labelnames=("key",))
_STEP_BYTES = _metrics.REGISTRY.gauge(
    "paddle_executor_step_bytes",
    "XLA cost-analysis bytes accessed of the cached step "
    "(bandwidth-roofline numerator)",
    labelnames=("key",))
# trace-time only, like paddle_kernel_lowerings_total: counts when a step
# is traced (every compile), never on the steady-state path
_FENCED_UPDATES = _metrics.REGISTRY.counter(
    "paddle_executor_fenced_updates_total",
    "Optimizer update ops traced with their dense gradient behind an "
    "optimization barrier, so that XLA compiles the update apart from "
    "the op that produced the gradient",
    labelnames=("op",))
# read once from the module the executor compiled under a strategy's own
# compiler options (_aot_compile): no other step's text is read
_ASYNC_COLLECTIVES = _metrics.REGISTRY.gauge(
    "paddle_executor_async_collectives",
    "Collectives left in asynchronous form (a start and a done with work "
    "between them) in the step compiled under the strategy's compiler "
    "options, by the step's role; 0 where the compiler merged every one "
    "back into a plain collective",
    labelnames=("role",))
# The four spans of a run on counters, from the spans' own clock readings
# (always on). A compiled step goes by its role, the name of its Program.
_RUNS = _metrics.REGISTRY.counter(
    "paddle_executor_runs_total",
    "Executor.run calls by the role of the compiled step (Program.name)",
    labelnames=("role",))
_FIRST_CALLS = _metrics.REGISTRY.counter(
    "paddle_executor_first_calls_total",
    "Executor.run calls that were the first of a compiled step, by role: "
    "paddle_executor_runs_total less these are the steady runs",
    labelnames=("role",))
_HOST_MS = _metrics.REGISTRY.counter(
    "paddle_executor_host_ms_total",
    "Host milliseconds of Executor.run by role and phase: prepare, call "
    "(the step enqueued), writeback, fetch (the wait for the results) as "
    "the executor: spans of those names. The first run of a compiled "
    "step is booked whole under first_call, never under prepare, call or "
    "writeback: its prepare (the step's function built, state placed), "
    "the span executor:first_call (trace, lowering, compile or cache "
    "read, then the call) and its writeback",
    labelnames=("role", "phase"))
_PHASES = ("prepare", "call", "writeback", "fetch", "first_call")
_METRICS_LOCK = _metrics.REGISTRY._lock     # _CacheEntry.book says why

# every second of compile-side work in the process is booked from here on
_ledger.install()

DEFAULT_ROLE = "program"      # a Program nobody named


def _role_of(program):
    """The role a program's compiled steps go by: its name."""
    return program.name or DEFAULT_ROLE


# Global key_id source: labels must not alias across Executors or
# threads (itertools.count.__next__ is atomic under the GIL).
_KEY_IDS = itertools.count(1)


def _dtype_str(dt):
    return "bfloat16" if dt is jnp.bfloat16 else np.dtype(dt).name


def _ingest_spec(var, arriving_dtype, name, packed=False):
    """The prologue step (name, target_dtype, scale, mean, std) for one
    feed arriving as ``arriving_dtype``, or None when the feed needs no
    on-device work. Normalize attrs fire ONLY for wire-form arrivals:
    an already-widened (host-normalized) feed is the legacy path and
    must stay byte-identical."""
    if var is None:
        return None
    target = convert_dtype(var.dtype)
    wire = getattr(var, "wire_dtype", None)
    try:
        arriving = np.dtype(arriving_dtype)
    except TypeError:
        arriving = arriving_dtype  # bf16 scalar type
    if wire is not None and arriving == np.dtype(wire):
        norm = getattr(var, "ingest", None) or {}
        return (name, _dtype_str(target),
                _ingest.canon_norm(norm.get("scale")),
                _ingest.canon_norm(norm.get("mean")),
                _ingest.canon_norm(norm.get("std")))
    if packed and arriving != np.dtype(target):
        # packed feeds skip the host-side asarray cast, so any residual
        # dtype gap is closed on device instead
        return (name, _dtype_str(target), None, None, None)
    return None


class _CacheEntry:
    """One compile-cache slot: the jitted callable, io signature, and —
    when telemetry AOT-compiled the step, the persistent cache
    deserialized it, or a serving artifact primed it — the
    jax.stages.Compiled executable (avoids the double-compile the jit
    call path would pay after a cost-analysis compile).

    ``skey_parts`` is the in-memory cache key minus its process-local
    head (program uid/version) — the stable half of the persistent
    cache digest (core/compile_cache.py); ``pkey`` memoizes that digest
    once computed.

    ``role`` is the name of the entry's Program, which ``fn`` carries too
    (the HLO module is ``jit_<role>``); two entries of one program keep
    one role and differ by ``key_id``. ``called`` turns true with the
    first call, the one that traces, lowers and compiles (or reads JAX's
    cache): it runs inside the span ``executor:first_call``, with the
    compile ledger booking to ``role``. ``options`` are the compiler
    options the strategy gave the step (``DistStrategy.compiler_options``;
    empty with no strategy): such a step is compiled ahead of time, so
    that its module's text is there to read."""

    __slots__ = ("fn", "read", "written", "needs_rng", "options", "key_id",
                 "aot", "aot_failed", "skey_parts", "pkey", "role", "called",
                 "_meters", "_generation")

    def __init__(self, fn, read, written, needs_rng, key_id, role,
                 options=None):
        self.fn = fn
        self.read = read
        self.written = written
        self.needs_rng = needs_rng
        self.options = options or {}
        self.key_id = key_id
        self.aot = None
        self.aot_failed = False
        self.skey_parts = None
        self.pkey = None
        self.role = role
        self.called = False
        self._generation = None

    def book(self, first, t0, t1, t2, t3, t4):
        """One run on the counters, from the clock readings around its
        spans: prepare ``[t0, t1]``, the call ``[t1, t2]``, writeback
        ``[t2, t3]``, fetch ``[t3, t4]`` (``t4`` None: nothing was fetched
        to the host). The entry's ``first`` run is booked whole, up to
        the fetch, under ``first_call``: what the steady phases hold is
        the steady runs' alone. The children are held, not resolved run
        by run; a registry reset drops them."""
        if self._generation != _metrics.REGISTRY.generation:
            self._generation = _metrics.REGISTRY.generation
            self._meters = (_RUNS.labels(role=self.role),) + tuple(
                _HOST_MS.labels(role=self.role, phase=p) for p in _PHASES)
        if first:
            runs, _, _, _, fetch, first_call = self._meters
            runs.inc()
            first_call.inc((t3 - t0) * 1e3)
            fetch.inc(0.0 if t4 is None else (t4 - t3) * 1e3)
            _FIRST_CALLS.labels(role=self.role).inc()
        else:
            # every run pays this: the five children moved under ONE
            # hold of the registry's lock (what Counter.inc takes once a
            # child), so that a run's bookkeeping stays near a
            # microsecond and a snapshot sees all of a run or none. The
            # clock only goes forward: no amount is negative
            runs, prepare, call, writeback, fetch, _ = self._meters
            with _METRICS_LOCK:
                runs._value += 1.0
                prepare._value += (t1 - t0) * 1e3
                call._value += (t2 - t1) * 1e3
                writeback._value += (t3 - t2) * 1e3
                if t4 is not None:
                    fetch._value += (t4 - t3) * 1e3


def _lookup(env, name, op, block):
    try:
        return env[name]
    except KeyError:
        from .enforce import EnforceNotMet
        reader = op.type if op is not None else "<fetch>"
        var = block.var_or_none(name)
        if var is not None and var.persistable:
            raise EnforceNotMet(
                "persistable variable %r read by %r is not initialized in "
                "scope — run the startup program first" % (name, reader))
        raise EnforceNotMet("%r reads undefined variable %r"
                            % (reader, name)) from None


# Mixed-precision op lists (config flag "amp"). WHITE ops are the MXU
# flop sinks: their float inputs are cast to the amp dtype *inside* the
# op's vjp-wrapped function, so the cast's transpose restores f32 param
# cotangents (master weights fall out of autodiff). BLACK ops are
# numerically sensitive reductions: float inputs are forced to f32.
# Everything else runs in whichever dtype flows in (XLA fuses the
# converts into neighbouring HLO).
AMP_WHITE = frozenset({
    "conv2d", "conv3d", "conv2d_transpose", "conv3d_transpose",
    "mul", "matmul", "bilinear_tensor_product",
    # fused recurrent scans: per-step gate matmuls dominate; the scan
    # carries stay bf16 end-to-end (cast once at the boundary)
    "dynamic_lstm", "dynamic_gru", "attention_gru_decoder",
    "sequence_conv",
    # the bias-free projection of models/moe_lm.py: exact on a float32
    # input, one pass on the bfloat16 one it gets here; and the EVA
    # attention ops (ops/eva_ops.py), whose scores and softmaxes stay
    # float32 inside
    "linear", "eva_summaries", "eva_attention",
    "eva_attention_decode_paged",
})
AMP_BLACK = frozenset({
    "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost",
    "huber_loss", "nce", "cos_sim", "squared_l2_distance",
})


def _amp_cast(op_type, val, amp_dtype):
    dt = getattr(val, "dtype", None)
    if dt is None or not jnp.issubdtype(dt, jnp.floating):
        return val
    if op_type in AMP_WHITE and dt == jnp.float32:
        return val.astype(amp_dtype)
    if op_type in AMP_BLACK and dt != jnp.float32:
        return val.astype(jnp.float32)
    return val


class _TraceState:
    """Per-trace mutable state shared across ops in one block execution."""

    def __init__(self, needs_vjp, nan_guards=None, amp=None, quant=None):
        self.vjp_cache = {}   # id(fwd_op) -> (vjp_fn, flat_out_values)
        self.needs_vjp = needs_vjp
        self.amp = jnp.dtype(amp) if amp else None
        # When not None: the program's _quant_compute tag (serving/quant.py
        # arm/install) — {"vars": {weight_name: axis}, "pallas": bool,
        # "key": hashable}. Forward mul/matmul/conv2d consult
        # ops/quant_ops.maybe_quant_compute for the int8 path.
        self.quant = quant
        # When not None: dict collecting per-op finiteness predicates
        # ("op#i:type:var" -> scalar bool). The reference scans every op's
        # outputs under FLAGS_check_nan_inf (framework/executor.cc:120-128);
        # under jit we can't raise mid-trace, so we emit the predicates into
        # the computation and the host checks them after the step. Covers
        # the main block and static_rnn sub-blocks (AND-reduced over time,
        # see control_flow_ops); while/cond (forward-only generation paths)
        # are checked at their op outputs only.
        self.nan_guards = nan_guards


def _gather_inputs(op, env, block):
    values = {}
    for slot, names in op.inputs.items():
        values[slot] = [None if n == EMPTY_VAR else _lookup(env, n, op, block)
                        for n in names]
    return values


def _write_outputs(op, env, norm_result):
    for slot, names in op.outputs.items():
        vals = norm_result.get(slot, [])
        for i, name in enumerate(names):
            if name == EMPTY_VAR:
                continue
            if i < len(vals) and vals[i] is not None:
                env[name] = vals[i]


def _fence_update_grad(op, values):
    """Put the gradient of an optimizer update behind an optimization
    barrier. An update is told by its slots (``Param`` and ``Grad`` in,
    ``ParamOut`` out), not by a list of names.

    Why: left alone, XLA fuses the update into the epilogue of the matmul
    that makes a weight's gradient, and on the v5e that fusion runs the
    product at 44-60% of the MXU's peak where the product alone reaches
    89-93% (PERF.md, PR 34); behind the barrier the product compiles
    alone and the update as one elementwise pass over the parameter and
    its accumulators. The barrier is the identity on values.

    Left as they were, by what the trace can see: gradients of rank < 2
    (biases, norm gains: they come from reductions, not matmuls); a
    ``Rows`` gradient (a merge and a scatter stand before its update);
    and every gradient of a step whose batch is sharded over chips
    (``data_shards() > 1``): there the all-reduce already stands between
    the product and the update, and a fence after it would only force
    the summed bfloat16 gradient's float32 copy through HBM (measured:
    5 ms of a 300 ms step)."""
    if "ParamOut" not in op.outputs or "Rows" in op.inputs or \
            not {"Param", "Grad"} <= op.inputs.keys():
        return
    grad = values["Grad"][0]
    if getattr(grad, "ndim", 0) < 2:
        return
    from .. import parallel as _parallel
    strategy = _parallel.current_strategy()
    if strategy is not None and strategy.data_shards() > 1:
        return
    values["Grad"] = [jax.lax.optimization_barrier(grad)]
    _FENCED_UPDATES.labels(op=op.type).inc()


def _execute_forward_op(op, env, block, trace):
    opdef = registry.get_op_def(op.type)
    values = _gather_inputs(op, env, block)
    _fence_update_grad(op, values)
    rng_key = None
    if opdef.needs_rng:
        env[RNG_STATE_VAR], rng_key = jax.random.split(env[RNG_STATE_VAR])

    amp = trace.amp

    if id(op) in trace.needs_vjp:
        in_slots = registry.flat_input_slots(op)
        out_slots = registry.flat_output_slots(op)
        flat_vals = [values[slot][i] for slot, i in in_slots]

        def f(*args):
            vals = {slot: list(lst) for slot, lst in values.items()}
            for (slot, i), a in zip(in_slots, args):
                # amp cast INSIDE the vjp: its transpose restores f32
                # cotangents for f32 params (master-weight recipe)
                vals[slot][i] = _amp_cast(op.type, a, amp) if amp else a
            ctx = registry.ExecContext(op, vals, rng_key=rng_key,
                                       block=block, trace=trace)
            result = registry.normalize_outputs(op, opdef.compute(ctx))
            return [result.get(slot, [None] * (i + 1))[i] if
                    i < len(result.get(slot, [])) else None
                    for slot, i in out_slots]

        outs_flat, vjp_fn = jax.vjp(f, *flat_vals)
        trace.vjp_cache[id(op)] = (vjp_fn, outs_flat)
        for (slot, i), val in zip(out_slots, outs_flat):
            names = op.outputs.get(slot, [])
            if i < len(names) and val is not None and names[i] != EMPTY_VAR:
                env[names[i]] = val
    else:
        if trace.quant is not None and op.type in ("mul", "matmul",
                                                   "conv2d"):
            from ..ops import quant_ops as _quant_ops
            result = _quant_ops.maybe_quant_compute(op, values, env, trace)
            if result is not None:
                _write_outputs(op, env,
                               registry.normalize_outputs(op, result))
                return
        if amp and (op.type in AMP_WHITE or op.type in AMP_BLACK):
            values = {slot: [_amp_cast(op.type, v, amp) for v in lst]
                      for slot, lst in values.items()}
        ctx = registry.ExecContext(op, values, rng_key=rng_key,
                                   block=block, trace=trace)
        result = registry.normalize_outputs(op, opdef.compute(ctx))
        _write_outputs(op, env, result)


def _is_float0(x):
    return getattr(x, "dtype", None) == jax.dtypes.float0


def _execute_vjp_grad(op, env, block, trace):
    fwd_op = op.attrs["fwd_op"]
    entry = trace.vjp_cache.get(id(fwd_op))
    if entry is None:
        raise RuntimeError(
            "vjp_grad for op %r executed before its forward op — backward "
            "ops must follow forward ops in the same block" % fwd_op.type)
    vjp_fn, outs_flat = entry
    grad_names = op.inputs.get("OutGrads", [])
    cots = []
    for val, gname in zip(outs_flat, grad_names):
        if val is None:
            cots.append(None)
        elif not jnp.issubdtype(val.dtype, jnp.inexact):
            # int/bool primal outputs (loop counters, conds, ids) take a
            # float0 cotangent per jax.vjp's calling convention
            cots.append(np.zeros(val.shape, dtype=jax.dtypes.float0))
        elif gname == EMPTY_VAR:
            cots.append(jnp.zeros_like(val))
        else:
            g = _lookup(env, gname, op, block)
            cots.append(jnp.asarray(g, dtype=val.dtype).reshape(val.shape))
    in_cots = vjp_fn(cots)
    out_names = op.outputs.get("InGrads", [])
    for cot, gname in zip(in_cots, out_names):
        if gname == EMPTY_VAR or cot is None or _is_float0(cot):
            continue
        env[gname] = cot


def run_block(block, env, trace):
    """Trace every op of ``block`` against ``env`` (name -> traced value).
    Each op is traced under ``jax.named_scope(op.type)`` (the analogue of
    the reference executor's per-op RecordEvent): trace-time only, and an
    XProf/Perfetto view of a step then groups device ops by Program op;
    an op built under ``framework.name_scope`` has its scopes in front."""
    for i, op in enumerate(block.ops):
        scope = op.attrs.get("op_namescope")
        with jax.named_scope("%s/%s" % (scope, op.type) if scope
                             else op.type):
            if op.type == "vjp_grad":
                _execute_vjp_grad(op, env, block, trace)
            else:
                _execute_forward_op(op, env, block, trace)
        if trace.nan_guards is not None:
            for name in op.output_names():
                val = env.get(name)
                if val is not None and \
                        jnp.issubdtype(getattr(val, "dtype", None),
                                       jnp.floating):
                    key = "op#%d:%s:%s" % (i, op.type, name)
                    trace.nan_guards[key] = jnp.isfinite(val).all()


def _block_io(block):
    """Classify persistable reads/writes and rng need for a block."""
    read, written, needs_rng = set(), set(), False
    for op in block.ops:
        if op.type != "vjp_grad":
            if registry.get_op_def(op.type).needs_rng:
                needs_rng = True
        for names in op.inputs.values():
            for n in names:
                if n == EMPTY_VAR:
                    continue
                v = block.var_or_none(n)
                if v is not None and v.persistable and n not in written:
                    read.add(n)
        for names in op.outputs.values():
            for n in names:
                if n == EMPTY_VAR:
                    continue
                v = block.var_or_none(n)
                if v is not None and v.persistable:
                    written.add(n)
    return read, written, needs_rng


class Executor:
    """Runs Programs. Parity surface: ``fluid.Executor(place).run(...)``
    (reference ``python/paddle/v2/fluid/executor.py:71,126``)."""

    def __init__(self, place=None, strategy=None):
        """strategy: a parallel.DistStrategy — shards feeds/state over a
        device mesh; XLA inserts the collectives (replaces the reference's
        pserver/NCCL tier, SURVEY §5.8).

        place: checked, not steered by — ``TPUPlace()`` on a host with
        no TPU raises here instead of training on the CPU; arrays then
        live where JAX puts them (its default device, or the
        strategy's mesh)."""
        if place is not None:
            place.jax_device()
        self.place = place
        self.strategy = strategy
        self._cache = {}
        # Per-instance compile count (incremented only on a cache miss,
        # never on the steady-state hit path). Unlike the telemetry
        # counters this is flag-free: it is the proof surface for
        # closed-shape contracts — serving buckets and generation
        # (batch-bucket, cache-bucket) steps assert "exactly one
        # compile per shape across a multi-request run" against it.
        self._compiles = 0

    def _prepare(self, program, feed, fetch_list, scope, donate_state,
                 count_cache=True):
        """Shared run/lower prep: compile-cache lookup + state assembly.
        Returns (entry, state_rw, state_ro, feed_arrays). ``count_cache``
        is False for non-step callers (lower) so the hit/miss telemetry
        counts executed steps only."""
        if program is None:
            program = default_main_program()
        if not isinstance(program, Program):
            raise TypeError("Executor.run expects a Program, got %r"
                            % (program,))
        feed = {} if feed is None else feed
        fetch_list = fetch_list or []
        scope = scope or global_scope()
        block = program.global_block()

        fetch_names = [v.name if isinstance(v, Variable) else v
                       for v in fetch_list]

        # Normalize feeds. Three shapes of arrival:
        # * PackedBatch — the whole batch is ONE uint8 buffer; the step
        #   unpacks it (static slices + bitcasts) and the buffer is
        #   donated. Per-slot widening goes through the ingest prologue.
        # * wire-form array (dtype == the var's declared wire_dtype) —
        #   kept narrow; cast/normalize compiled into the step.
        # * anything else — legacy: host-side asarray cast to var dtype.
        ingest_specs, packed_sig = [], None
        if isinstance(feed, _ingest.PackedBatch):
            buf = feed.buffer
            if self.strategy is not None and isinstance(buf, np.ndarray):
                # unscattered host buffer under a mesh: replicate (still
                # one transfer per device; semantically the same global
                # batch). Staging normally pre-scatters per shard.
                buf = jax.device_put(buf, self.strategy.replicated())
            for slot in feed.layout:
                if slot.kind != "dense":
                    # sparse triples arrive in their final wire dtypes
                    # (index width ids/offsets, canon values) — no
                    # widen prologue
                    continue
                spec = _ingest_spec(block.var_or_none(slot.name),
                                    slot.dtype, slot.name, packed=True)
                if spec is not None:
                    ingest_specs.append(spec)
            packed_sig = feed.signature()
            feed_arrays = {_ingest.PACKED_FEED: buf}
            feed_sig = (("@packed@",) + packed_sig,)
        else:
            feed_arrays = {}
            feed = _ingest.explode_sparse(feed)
            for name, value in feed.items():
                var = block.var_or_none(name)
                # an array (a device array above all: np.asarray would
                # wait for it and fetch it) says its dtype itself
                spec = _ingest_spec(var, value.dtype
                                    if hasattr(value, "dtype")
                                    else np.asarray(value).dtype, name)
                if spec is not None:
                    ingest_specs.append(spec)
                    arr = jnp.asarray(value)  # stays in wire dtype
                else:
                    dtype = convert_dtype(var.dtype) if var is not None \
                        else None
                    arr = jnp.asarray(value, dtype=dtype)
                feed_arrays[name] = arr
            feed_sig = tuple(sorted((n, tuple(a.shape), str(a.dtype))
                                    for n, a in feed_arrays.items()))
        ingest_specs = tuple(sorted(ingest_specs))

        from .. import config as _config
        check_nan_inf = bool(_config.get_flag("check_nan_inf"))
        nonfinite_guard = bool(_config.get_flag("nonfinite_guard"))
        amp = _config.get_flag("amp")
        flash = bool(_config.get_flag("flash_attention"))
        precision = _config.get_flag("matmul_precision")
        telemetry = bool(_config.get_flag("telemetry"))
        # distributed-embedding flags are trace-time too (layout,
        # a2a route, telemetry callbacks) but are consulted ONLY for
        # programs that registered a DistEmbedding table — the default
        # path pays one getattr, zero flag reads
        emb_tables = getattr(program, "_dist_embeddings", None)
        emb_key = None
        if emb_tables:
            emb_key = (bool(_config.get_flag("embedding_shard_rows")),
                       bool(_config.get_flag("embedding_a2a")),
                       telemetry,
                       _config.get_flag("embedding_wire_dtype"))
        # int8 quantized compute: armed programs carry their tag
        # (serving/quant.py); the default path pays one getattr, zero
        # flag reads
        quant = getattr(program, "_quant_compute", None)
        q_key = quant["key"] if quant else None
        # every trace-time flag must key the compile cache; the ingest
        # prologue (wire widening + packed unpack) is trace-time too
        key = (program._uid, program._version, feed_sig, tuple(fetch_names),
               bool(donate_state),
               self.strategy._uid if self.strategy is not None else None,
               check_nan_inf, amp, flash, precision, nonfinite_guard,
               ingest_specs, emb_key, q_key)
        entry = self._cache.get(key)
        if entry is None:
            self._compiles += 1
            if telemetry and count_cache:
                _CACHE_MISSES.inc()
            *built, options = self._build(
                program, block, feed_sig, fetch_names, donate_state,
                check_nan_inf, amp, nonfinite_guard, ingest_specs,
                packed_sig, quant)
            entry = _CacheEntry(*built, key_id="k%d" % next(_KEY_IDS),
                                role=_role_of(program), options=options)
            # the process-stable half of the persistent-cache digest
            # (key[2:] drops program uid/version, which the program's
            # serialized content replaces)
            entry.skey_parts = key[2:]
            self._cache[key] = entry
        elif telemetry and count_cache:
            _CACHE_HITS.inc()
        if entry.pkey is None and self.strategy is None and \
                _config.get_flag("compile_cache_dir"):
            # once per entry, only with the persistent cache armed:
            # hash the program content + stable key into the on-disk key
            entry.pkey = _compile_cache.entry_digest(program,
                                                     entry.skey_parts)

        state_rw, state_ro = {}, {}
        for n in entry.written:
            if scope.has_var(n):
                state_rw[n] = scope.find_var(n)
        for n in entry.read:
            if n in state_rw:
                continue
            if scope.has_var(n):
                state_ro[n] = scope.find_var(n)
            # else: executor raises at trace time with a clear message
        if entry.needs_rng:
            if not scope.has_var(RNG_STATE_VAR):
                seed = program.random_seed if program.random_seed else 0
                scope.set_var(RNG_STATE_VAR, jax.random.PRNGKey(seed))
            state_rw[RNG_STATE_VAR] = scope.find_var(RNG_STATE_VAR)

        if self.strategy is not None:
            # Scatter feeds over the mesh batch axis; pin state to its
            # PartitionSpec (no-op when already placed). GSPMD propagates
            # shardings through the step and inserts ICI collectives.
            # A packed buffer is already placed (scattered per shard by
            # the staging thread, or replicated above) — leave it be.
            feed_arrays = {n: a if n == _ingest.PACKED_FEED
                           else self.strategy.shard_feed(n, a)
                           for n, a in feed_arrays.items()}
            dist_rows = None
            if emb_key is not None and emb_key[0]:
                dist_rows = {n: info["padded"]
                             for n, info in emb_tables.items()}
            state_rw = {n: self.strategy.shard_state(n, a, dist_rows)
                        for n, a in state_rw.items()}
            state_ro = {n: self.strategy.shard_state(n, a, dist_rows)
                        for n, a in state_ro.items()}
        return entry, state_rw, state_ro, feed_arrays

    def compile_stats(self):
        """Flag-free per-executor compile counters: ``entries`` (live
        compile-cache slots) and ``compiles`` (total trace+compile
        events this executor ever paid, lower() included). A closed
        shape set shows here as a plateau: N distinct
        (program, feed-signature, flags) shapes -> exactly N compiles
        no matter how many steps run — the generation acceptance
        criterion (one compile per (batch-bucket, cache-bucket)) and
        the serving-bucket contract are asserted against this."""
        return {"entries": len(self._cache), "compiles": self._compiles}

    def lower(self, program=None, feed=None, fetch_list=None, scope=None,
              donate_state=True):
        """AOT-lower the EXACT computation ``run`` would execute (same
        donation, amp policy, state threading) without running it.
        Returns the ``jax.stages.Lowered`` — ``.compile()`` then
        ``.cost_analysis()`` / ``.as_text()`` for profiling and
        compile-checks of the true step module."""
        entry, state_rw, state_ro, feed_arrays = self._prepare(
            program, feed, fetch_list, scope, donate_state,
            count_cache=False)
        with _ledger.attribute(entry.role):
            return entry.fn.lower(state_rw, state_ro, feed_arrays)

    def cache_digest(self, program, feed=None, fetch_list=None, scope=None,
                     donate_state=True):
        """The process-stable persistent-cache digest of the EXACT
        computation ``run`` would execute for these arguments (program
        content + feed/fetch signature + trace-time flags + environment
        fingerprint — core/compile_cache.py). The digest is what an AOT
        serving artifact records per bucket, so a loader can prove
        "this serialized executable IS the computation I would compile
        here" before trusting it."""
        entry, _, _, _ = self._prepare(program, feed, fetch_list, scope,
                                       donate_state, count_cache=False)
        if entry.pkey is None:
            entry.pkey = _compile_cache.entry_digest(program,
                                                     entry.skey_parts)
        return entry.pkey

    def prime_aot(self, program, feed, fetch_list, scope, compiled,
                  expect_digest=None, donate_state=True):
        """Install a deserialized ``jax.stages.Compiled`` as the AOT
        executable for the cache entry these arguments resolve to —
        the serving cold-start path: deserialize, don't compile.

        When ``expect_digest`` is given it must equal this entry's
        :meth:`cache_digest` (raises ValueError otherwise) — version
        skew, flag drift, or a different topology therefore can't
        install an executable that computes something else; callers
        catch and fall back to the compile path. If the executable
        turns out aval-incompatible anyway, ``run``'s existing AOT
        fallback degrades to the jitted path at first call."""
        entry, _, _, _ = self._prepare(program, feed, fetch_list, scope,
                                       donate_state, count_cache=False)
        if expect_digest is not None:
            if entry.pkey is None:
                entry.pkey = _compile_cache.entry_digest(
                    program, entry.skey_parts)
            if entry.pkey != expect_digest:
                raise ValueError(
                    "AOT executable digest %s does not match this "
                    "executor's computation digest %s (program/flag/"
                    "environment skew)" % (expect_digest[:12],
                                           entry.pkey[:12]))
        entry.aot = compiled
        entry.aot_failed = False
        return entry

    def _aot_compile(self, entry, state_rw, state_ro, feed_arrays):
        """Telemetry path for a compile-cache miss: AOT-compile the step
        (the jit call path would compile the same module again — the AOT
        executable is kept and used for every subsequent run) and record
        the XLA cost analysis (FLOPs / bytes accessed — the MFU and
        bandwidth-roofline numerators, cf. tools/mfu_probe.py). Its
        seconds go where the jit path's go: it runs inside the first
        call, so the compile ledger books its trace, lowering and compile
        to the entry's role."""
        with _tracing.span("executor:trace", key=entry.key_id):
            lowered = entry.fn.lower(state_rw, state_ro, feed_arrays)
        with _tracing.span("executor:compile", key=entry.key_id):
            compiled = lowered.compile()
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0] if ca else {}
            _STEP_FLOPS.labels(key=entry.key_id).set(
                float(ca.get("flops", 0.0)))
            _STEP_BYTES.labels(key=entry.key_id).set(
                float(ca.get("bytes accessed", 0.0)))
        except Exception:
            pass  # cost analysis is best-effort (backend-dependent)
        if entry.options:
            from .. import parallel as _parallel
            _ASYNC_COLLECTIVES.labels(role=entry.role).set(
                _parallel.async_collectives(compiled.as_text()))
        entry.aot = compiled

    def _wants_aot(self, entry):
        """Whether the step is still to be had as a ``jax.stages.Compiled``:
        the repo's persistent executable cache is armed (``entry.pkey``,
        set in _prepare only when compile_cache_dir is on, so the
        all-defaults path pays one telemetry flag check a run) or
        telemetry is, and no attempt failed. Under a strategy: the step
        was built with compiler options of the strategy's, whose effect
        is read from the compiled module's text."""
        if entry.aot is not None or entry.aot_failed:
            return False
        if self.strategy is not None:
            return bool(entry.options)
        from .. import config as _config
        return entry.pkey is not None or bool(_config.get_flag("telemetry"))

    def _make_aot(self, entry, state_rw, state_ro, feed_arrays):
        """The step as a ``jax.stages.Compiled``, deserialized from the
        repo's persistent cache or compiled here."""
        pcache = _compile_cache.active_cache() \
            if entry.pkey is not None else None
        if pcache is not None:
            # restart fast path: deserialize the executable a past
            # process compiled for this exact digest. load() never
            # raises — a corrupt entry is quarantined and reported
            # as a miss, and we fall through to a normal compile.
            entry.aot = pcache.load(entry.pkey)
        if entry.aot is None:
            # telemetry on (cost-analysis compile, reused for
            # execution) or persistent cache armed (compile once,
            # publish for the next process): AOT-compile the step
            # so the executed step and the artifact share ONE XLA
            # compilation
            try:
                self._aot_compile(entry, state_rw, state_ro, feed_arrays)
            except Exception:
                if entry.options:
                    # the jit call path would compile the same module
                    # under the same options: nothing to fall back to
                    raise
                entry.aot = None
                entry.aot_failed = True  # jit call path from here on
            else:
                if pcache is not None:
                    pcache.store(entry.pkey, entry.aot)

    def _call(self, entry, state_rw, state_ro, feed_arrays):
        if entry.aot is not None:
            try:
                return entry.aot(state_rw, state_ro, feed_arrays)
            except (TypeError, ValueError):
                # aval drift vs the AOT signature (e.g. a scope var
                # was replaced with a new shape): jit retraces, AOT
                # can't — and would flap if recompiled, so stay on
                # jit for good
                entry.aot = None
                entry.aot_failed = True
        return entry.fn(state_rw, state_ro, feed_arrays)

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, donate_state=True):
        if scope is None:
            scope = global_scope()
        if program is None:
            program = default_main_program()
        role = _role_of(program)
        # request-scoped tracing: a serving layer above may have
        # activated a request's TraceContext on this thread — the
        # device call then lands as a span on that request's trace.
        # One thread-local read; no config flag, no cost when off.
        _rt_ctx = _rtrace.current()
        # five clock readings around the four spans: the counters
        # (entry.book) read the spans' own seconds
        t0 = time.perf_counter()
        with _tracing.span("executor:prepare", role=role):
            entry, state_rw, state_ro, feed_arrays = self._prepare(
                program, feed, fetch_list, scope, donate_state)
        t1 = time.perf_counter()
        wants_aot = self._wants_aot(entry)
        first = wants_aot or not entry.called
        if first:
            # the entry's first call: whatever makes the step runnable
            # (an AOT compile or a deserialized executable; else the jit
            # call's own trace, lowering and compile or read of JAX's
            # cache) and the call, booked to the entry's role. A step
            # that telemetry, armed later, compiles ahead of time after
            # all is a first call again
            with _tracing.span("executor:first_call", role=entry.role,
                               key=entry.key_id), \
                    _ledger.attribute(entry.role):
                if wants_aot:
                    self._make_aot(entry, state_rw, state_ro, feed_arrays)
                new_state, fetches, guards = self._call(
                    entry, state_rw, state_ro, feed_arrays)
            entry.called = True
        else:
            # executor:call ends when the step is enqueued (dispatch is
            # asynchronous); the wait for its result is executor:fetch,
            # or the caller's own fetch under return_numpy=False
            with _tracing.span("executor:call", role=entry.role,
                               key=entry.key_id):
                new_state, fetches, guards = self._call(
                    entry, state_rw, state_ro, feed_arrays)
        t2 = time.perf_counter()
        with _tracing.span("executor:writeback"):
            for n, v in new_state.items():
                scope.set_var(n, v)
        t3 = time.perf_counter()
        t4 = None
        if return_numpy:
            with _tracing.span("executor:fetch"):
                fetches = [np.asarray(v) for v in fetches]
            t4 = time.perf_counter()
        entry.book(first, t0, t1, t2, t3, t4)
        if guards:
            # Per-op output scan (reference framework/executor.cc:120-128).
            bad = [k for k, ok in guards.items() if not bool(ok)]
            if bad:
                raise FloatingPointError(
                    "NaN/Inf detected in op outputs: %s" % ", ".join(bad))
        if _rt_ctx is not None:
            _rtrace.event(
                _rt_ctx, "deviceCall", key=entry.key_id,
                dur_ms=(time.perf_counter() - t0) * 1e3)
        return fetches

    def as_jax_function(self, program, feed_templates, fetch_list,
                        scope=None):
        """Export a Program block as a pure JAX function.

        Returns ``(fn, (state, feed))`` where ``fn(state, feed) -> fetches``
        is jittable and ``state`` is the persistable-variable dict read from
        ``scope`` (run the startup program first). Feeds/fetches as in
        ``run``. This is the seam for embedding programs in external JAX
        code (jit/grad/shard_map) and for AOT compile checks.
        """
        scope = scope or global_scope()
        block = program.global_block()
        fetch_names = [v.name if isinstance(v, Variable) else v
                       for v in fetch_list]
        feed = {}
        for name, value in feed_templates.items():
            var = block.var_or_none(name)
            dtype = convert_dtype(var.dtype) if var is not None else None
            feed[name] = jnp.asarray(value, dtype=dtype)
        read, written, needs_rng = _block_io(block)
        needs_vjp = {id(op.attrs["fwd_op"]) for op in block.ops
                     if op.type == "vjp_grad"}
        state = {}
        for n in sorted(read | written):
            if scope.has_var(n):
                state[n] = scope.find_var(n)
        if needs_rng:
            seed = program.random_seed if program.random_seed else 0
            state[RNG_STATE_VAR] = scope.find_var(RNG_STATE_VAR) \
                if scope.has_var(RNG_STATE_VAR) else jax.random.PRNGKey(seed)

        from .. import config as _config
        precision = _config.resolve_matmul_precision()
        amp = _config.get_flag("amp")

        def fn(state, feed):
            env = dict(state)
            env.update(feed)
            trace = _TraceState(needs_vjp, amp=amp)
            if precision is not None:
                with jax.default_matmul_precision(precision):
                    run_block(block, env, trace)
            else:
                run_block(block, env, trace)
            return [_lookup(env, n, None, block) for n in fetch_names]

        return fn, (state, feed)

    def _build(self, program, block, feed_sig, fetch_names, donate_state,
               check_nan_inf=False, amp=None, nonfinite_guard=False,
               ingest_specs=(), packed_sig=None, quant=None):
        read, written, needs_rng = _block_io(block)
        if needs_rng:
            written.add(RNG_STATE_VAR)
        if quant:
            # the per-channel scale sidecars live in the scope but are
            # not block vars, so _block_io can't see them — thread them
            # into the read set so state assembly ships them to the trace
            from ..ops import quant_ops as _quant_ops
            for _qn in quant["vars"]:
                read.add(_quant_ops.scale_var_name(_qn))
        needs_vjp = {id(op.attrs["fwd_op"]) for op in block.ops
                     if op.type == "vjp_grad"}
        written_t = tuple(sorted(written))
        read_t = tuple(sorted(read - written))

        from .. import config as _config
        from .. import parallel as _parallel
        precision = _config.resolve_matmul_precision()
        strategy = self.strategy

        packed_layout = packed_sig[0] if packed_sig is not None else None

        def fn(state_rw, state_ro, feed):
            # Ingest prologue: unpack the single-copy buffer (static
            # slices + bitcasts) and widen/normalize wire-dtype feeds to
            # their model dtype — all inside the compiled step, so the
            # wide batch exists only in HBM and XLA fuses the casts into
            # the first consumers.
            if packed_layout is not None:
                feed = _ingest.unpack(feed[_ingest.PACKED_FEED],
                                      packed_layout)
            if ingest_specs:
                feed = dict(feed)
                for name, tgt, scale, mean, std in ingest_specs:
                    feed[name] = _ingest.widen(feed[name], tgt,
                                               scale, mean, std)
            env = {}
            env.update(state_ro)
            env.update(state_rw)
            env.update(feed)
            trace = _TraceState(needs_vjp,
                                nan_guards={} if check_nan_inf else None,
                                amp=amp, quant=quant)
            prev = _parallel.set_current_strategy(strategy)
            try:
                if precision is not None:
                    with jax.default_matmul_precision(precision):
                        run_block(block, env, trace)
                else:
                    run_block(block, env, trace)
            finally:
                _parallel.set_current_strategy(prev)
            new_state = {n: env[n] for n in written_t if n in env}
            fetches = [_lookup(env, n, None, block) for n in fetch_names]
            if nonfinite_guard:
                # Guarded donated update (resilience/supervisor.py): if
                # any inexact fetch is non-finite the whole state update
                # becomes identity — a poisoned batch cannot corrupt
                # donated params/optimizer state. RNG is exempt so a
                # retried batch draws fresh randomness.
                ok = jnp.asarray(True)
                for v in fetches:
                    v = jnp.asarray(v)
                    if jnp.issubdtype(v.dtype, jnp.inexact):
                        ok = jnp.logical_and(ok, jnp.isfinite(v).all())
                new_state = {
                    n: (v if n == RNG_STATE_VAR or n not in state_rw
                        else jnp.where(ok, v, state_rw[n]))
                    for n, v in new_state.items()}
            return new_state, fetches, trace.nan_guards or {}

        # Donation: state updates are always in-place (argnum 0); a
        # packed ingest buffer (argnum 2) is consumed by exactly one
        # step, so donating it lets XLA reuse its HBM for the widened
        # batch — depth-2 prefetch without doubling ingest memory.
        donate = []
        if donate_state:
            donate.append(0)
        if packed_sig is not None:
            donate.append(2)
        jit_kwargs = {"donate_argnums": tuple(donate)} if donate else {}
        # the strategy says how its step is compiled (the data axis's
        # all-reduces beside the backward pass); with no strategy, or one
        # that has nothing to say, the jit call is what it always was. A
        # step that is fed nothing (a startup program) has no batch, so
        # no all-reduce to overlap, and is compiled as it always was too
        options = strategy.compiler_options() \
            if strategy is not None and feed_sig else {}
        if options:
            jit_kwargs["compiler_options"] = options
        # the step goes by its program's role: the HLO module is
        # jit_<role>, the profiler's host trace shows PjitFunction(<role>)
        fn.__name__ = fn.__qualname__ = _role_of(program)
        return (jax.jit(fn, **jit_kwargs), read_t, written_t, needs_rng,
                options)
