"""int8 quantized COMPUTE for mul / matmul / conv2d — weights stay int8
through the MXU instead of dequantizing to f32 before every contraction.

The round-5 probe measured int8 matmul at 1.71x bf16 on a v5e MXU;
serving/quant.py has carried int8 weights + per-output-channel scales in
the artifact since PR 2 but every load rebuilt the f32 copy. This module
is the compute half: when a program is ARMED (``serving/quant.py``
``arm_quant_compute`` / ``install_quant_compute`` tag it with
``program._quant_compute``), the executor routes the tagged weight's
consuming op here instead of the f32 op body:

* activations are quantized DYNAMICALLY per row (symmetric ``amax/127``,
  matmul/mul last axis; conv per sample) at trace time — no calibration
  pass, no activation statistics in the artifact;
* the contraction runs int8 x int8 accumulated in int32
  (``preferred_element_type=jnp.int32`` — exact: no rounding happens
  inside the dot), on the MXU's native s8 path on TPU;
* ONE f32 epilogue applies both scales:
  ``out = acc_i32.astype(f32) * x_scale * w_scale`` — the activation
  scale per row, the weight scale per output channel.

Numerics contract: the int8 dot is EXACT in int32, so the only error is
the two quantization roundings, and the dense XLA path and the fused
Pallas kernel are bit-identical to each other — same quantize
expressions, same epilogue expression, same association order. The
``quant_pallas`` path can therefore never change tokens relative to the
dense int8 path; both differ from f32 only by the documented
quantization error (per-channel int8 keeps decode top-1 agreement
>= 0.95, asserted in tests/test_quant_compute.py).

The Pallas kernel (decode hot path) fuses activation-quantize + int8
dot + scale epilogue into one VMEM pass: x never round-trips HBM as
int8, the i32 accumulator never materializes, and the weight is
streamed once per n-tile. Ragged geometry (compiled mode wants
m % 8 == 0, k % 128 == 0, n % 128 == 0) falls back to the dense int8
expression — identical numerics, so the fallback is invisible.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observability import metrics as _metrics
from . import kernel_path

__all__ = ["QUANT_COMPUTE_TYPES", "SCALE_SUFFIX", "scale_var_name",
           "quantize_rows", "quant_matmul_2d", "maybe_quant_compute"]

# op types the executor consults this module for (only on programs
# carrying a _quant_compute tag — untagged programs never reach here)
QUANT_COMPUTE_TYPES = ("mul", "matmul", "conv2d")

# weight slot per op type (mirrors serving/quant.py QUANT_OPS)
_WEIGHT_SLOT = {"mul": "Y", "matmul": "Y", "conv2d": "Filter"}

# scale sidecar variable naming: the per-output-channel f32 scales of a
# quantized weight live in the scope under this suffix (created by
# serving/quant.py at arm/install time, threaded through the executor's
# read set)
SCALE_SUFFIX = "@quant.scale"

# trace-time telemetry: one increment per compiled program per armed op
# — zero steady-state cost, no flag reads (cf. the repo's hot-path
# flag-check contract)
_QUANT_TRACED = _metrics.REGISTRY.counter(
    "paddle_quant_compute_ops_total",
    "Quantized-compute op lowerings traced, by op type and path "
    "(dense XLA int8 / fused Pallas kernel). Incremented at trace "
    "time only: one count per armed op per compiled program",
    labelnames=("op", "path"))


def scale_var_name(name):
    """Scope name of the per-output-channel scales for weight ``name``."""
    return name + SCALE_SUFFIX


def quantize_rows(x):
    """Dynamic symmetric int8 over the LAST axis: ``(q, scale)`` with
    ``scale = amax/127`` per row (1.0 for all-zero rows, so zeros stay
    exactly zero) and ``x ~= q * scale``. The SHARED quantize expression
    of the dense and Pallas paths — edit both or neither."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, jnp.ones_like(amax))
    q = jnp.clip(jnp.rint(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def _dense_int8_matmul(x2, wq, w_scale):
    """x2 f32 [m, k] x wq int8 [k, n] -> f32 [m, n]; w_scale f32 [n]."""
    xq, x_scale = quantize_rows(x2)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32,
                      precision=jax.lax.Precision.DEFAULT)
    return acc.astype(jnp.float32) * x_scale * w_scale[None, :]


def _dequant_matmul_kernel(x_ref, wq_ref, ws_ref, o_ref):
    """Fused quantize + int8 dot + scale epilogue, one n-tile per grid
    step. Expressions MATCH _dense_int8_matmul term for term — the two
    paths are bit-identical (the int8 dot is exact in int32)."""
    x = x_ref[:]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, jnp.ones_like(amax))
    xq = jnp.clip(jnp.rint(x / scale), -127.0, 127.0).astype(jnp.int8)
    acc = jax.lax.dot(xq, wq_ref[:], preferred_element_type=jnp.int32,
                      precision=jax.lax.Precision.DEFAULT)
    o_ref[:] = acc.astype(jnp.float32) * scale * ws_ref[:]


def _pallas_int8_matmul(x2, wq, w_scale, interpret=None):
    if interpret is None:
        interpret = kernel_path.interpret_mode()
    m, k = x2.shape
    n = wq.shape[1]
    if not interpret and (m % 8 or k % 128 or n % 128):
        # compiled Mosaic wants tileable sublanes/lanes; ragged shapes
        # take the dense expression (bit-identical, see kernel doc)
        kernel_path.record("int8_matmul")
        return _dense_int8_matmul(x2, wq, w_scale)
    kernel_path.record("int8_matmul", interpret)
    bn = next((b for b in (512, 256, 128) if n % b == 0), n)
    return pl.pallas_call(
        _dequant_matmul_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((k, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret)(x2, wq, w_scale.reshape(1, n))


def quant_matmul_2d(x2, wq, w_scale, pallas=False, interpret=None):
    """The shared 2-D quantized contraction behind mul and matmul:
    f32 [m, k] activations x int8 [k, n] weight with f32 [n] per-output
    -channel scales -> f32 [m, n]. ``pallas`` routes the fused kernel
    (bit-identical to the dense path by construction)."""
    if x2.dtype != jnp.float32:
        x2 = x2.astype(jnp.float32)
    w_scale = w_scale.astype(jnp.float32).reshape(-1)
    if pallas:
        return _pallas_int8_matmul(x2, wq, w_scale, interpret)
    return _dense_int8_matmul(x2, wq, w_scale)


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


def _quant_mul(op, x, wq, w_scale, pallas):
    """mul (flattening matmul, ops/math_ops.py): armed only for 2-D
    weights with y_num_col_dims == 1, so the weight's output channels
    ARE its last storage axis and the stored scales apply per column."""
    xd = op.attrs.get("x_num_col_dims", 1)
    xs = x.shape
    x2 = x.reshape(int(np.prod(xs[:xd])), int(np.prod(xs[xd:])))
    out = quant_matmul_2d(x2, wq, w_scale, pallas=pallas)
    return {"Out": out.reshape(xs[:xd] + wq.shape[1:])}


def _quant_matmul(op, x, wq, w_scale, pallas):
    """matmul: armed only for 2-D, non-transposed weights (transpose_Y
    would contract over the scaled axis). transpose_X and alpha mirror
    the f32 op body."""
    if op.attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    xs = x.shape
    k = xs[-1]
    n = wq.shape[1]
    out = quant_matmul_2d(x.reshape(-1, k), wq, w_scale, pallas=pallas)
    out = out.reshape(xs[:-1] + (n,))
    alpha = op.attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": out}


def _quant_conv2d(op, x, wq, w_scale):
    """conv2d: activations quantized per SAMPLE (amax over C,H,W — the
    channel axis is contracted, so per-channel input scales can't fold
    into the epilogue); zero padding quantizes to exactly zero, so the
    int8 conv pads correctly for free. Epilogue applies the sample
    scale and the per-output-channel weight scale in one f32 pass."""
    strides = _pair(op.attrs.get("strides", [1, 1]))
    pads = _pair(op.attrs.get("paddings", [0, 0]))
    dilations = _pair(op.attrs.get("dilations", [1, 1]))
    groups = op.attrs.get("groups", 1) or 1
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(1, 2, 3), keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, jnp.ones_like(amax))
    xq = jnp.clip(jnp.rint(x / scale), -127.0, 127.0).astype(jnp.int8)
    acc = jax.lax.conv_general_dilated(
        xq, wq, window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.int32,
        precision=jax.lax.Precision.DEFAULT)
    return {"Output": acc.astype(jnp.float32) * scale
            * w_scale.astype(jnp.float32).reshape(1, -1, 1, 1)}


def maybe_quant_compute(op, values, env, trace):
    """The executor's armed-program hook: run ``op`` on its int8 weight
    when the program tag covers it, else return None (f32 body runs).
    Called only for ops in QUANT_COMPUTE_TYPES on tagged programs."""
    quant = trace.quant
    slot = _WEIGHT_SLOT.get(op.type)
    names = op.inputs.get(slot) or ()
    if not names or names[0] not in quant["vars"]:
        return None
    wname = names[0]
    wq = values[slot][0]
    if wq is None or wq.dtype != jnp.int8:
        # scope was not actually quantized (e.g. a swap installed f32
        # weights): the f32 body handles it
        return None
    w_scale = env.get(scale_var_name(wname))
    if w_scale is None:
        return None
    pallas = bool(quant.get("pallas"))
    _QUANT_TRACED.labels(
        op=op.type,
        path="pallas" if (pallas and op.type != "conv2d") else
        "dense").inc()
    if op.type == "mul":
        return _quant_mul(op, values["X"][0], wq, w_scale, pallas)
    if op.type == "matmul":
        return _quant_matmul(op, values["X"][0], wq, w_scale, pallas)
    return _quant_conv2d(op, values["Input"][0], wq, w_scale)
