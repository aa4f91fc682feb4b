"""Fused conv + BN-stats: the conv's output is written ONCE and its
per-channel batch moments fall out of the same pass.

PROFILE round 4's gap analysis pinned the amp ResNet step at 93% of its
bandwidth roofline: conv_bn_layer's separate batch_norm re-READS the
conv output to compute mean/var, then reads it a third time to apply
the affine — three HBM trips for a tensor the MXU produced in one.
``conv2d_bn`` collapses conv2d + batch_norm into one op whose forward
emits ``(y, sum_c, sumsq_c)``; the BN finish (mean/var from the sums,
running-stat update, folded ``y*a + b``) is a few per-channel scalars
XLA fuses into the consumer.

Two forward paths share the op:

* a Pallas kernel for the dominant 1x1 / stride-1 / pad-0 geometry
  (ResNet bottleneck conv1/conv3 — most of the step's conv bytes):
  the conv is a [N*H*W, C] x [C, O] matmul tiled over rows, with the
  per-channel ``sum``/``sumsq`` of the OUTPUT accumulated in the
  epilogue of each tile (sequential TPU grid), template measured in
  tools/fused_conv_bn_probe.py;
* an XLA reference (``lax.conv_general_dilated`` + two reductions) for
  every other geometry, and the numeric contract of the kernel.

Backward is the reference's ``jax.vjp`` recomputed under
``custom_vjp`` — the flash-attention recipe: fast fused forward,
jnp-reference backward, no kernel transpose rules.

Armed by the ``fused_conv_bn`` flag (models/resnet.py reads it at
construction; default off keeps the conv2d + batch_norm program
byte-identical). Flag-on is a DIFFERENT program — parity with the
unfused pair is allclose (same math, different reduction order), which
tests/test_quant_compute.py asserts.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.registry import register_op
from . import kernel_path

__all__ = ["conv_bn_stats"]


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


def _reference(x, w, strides, pads, dils, groups):
    """XLA conv (exactly ops/nn_ops.py _conv2d) + f32 channel sums."""
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dils, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    ys = y if y.dtype == jnp.float32 else y.astype(jnp.float32)
    return y, jnp.sum(ys, axis=(0, 2, 3)), \
        jnp.sum(jnp.square(ys), axis=(0, 2, 3))


def _conv1x1_bn_kernel(x_ref, w_ref, y_ref, s_ref, ss_ref):
    """One row-tile: y = x @ w plus per-channel sum/sumsq of y carried
    across the sequential grid (probe template, BN-apply prologue
    dropped — stats here are of THIS conv's output)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)
        ss_ref[:] = jnp.zeros_like(ss_ref)

    y = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
    y_ref[:] = y.astype(y_ref.dtype)
    s_ref[:] += jnp.sum(y, axis=0, keepdims=True)
    ss_ref[:] += jnp.sum(y * y, axis=0, keepdims=True)


def _pallas_1x1(x, w, interpret):
    n, c, h, wd = x.shape
    o = w.shape[0]
    rows = x.transpose(0, 2, 3, 1).reshape(-1, c)   # [N*H*W, C]
    w2 = w.reshape(o, c).T                          # [C, O]
    r = rows.shape[0]
    br = next((b for b in (1024, 512, 256, 128) if r % b == 0), r)
    y2, s, ss = pl.pallas_call(
        _conv1x1_bn_kernel,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((c, o), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, o), lambda i: (i, 0)),
            pl.BlockSpec((1, o), lambda i: (0, 0)),
            pl.BlockSpec((1, o), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, o), x.dtype),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
        ],
        interpret=interpret)(rows, w2)
    y = y2.reshape(n, h, wd, o).transpose(0, 3, 1, 2)
    return y, s[0], ss[0]


def _forward(strides, pads, dils, groups, x, w):
    interpret = kernel_path.interpret_mode()
    kh, kw = w.shape[2], w.shape[3]
    if not (kh == 1 and kw == 1 and strides == (1, 1)
            and pads == (0, 0) and dils == (1, 1) and groups == 1):
        return _reference(x, w, strides, pads, dils, groups)
    fusable = x.dtype == jnp.float32
    if fusable and not interpret:
        # compiled Mosaic tiling: f32 wants 8x128-aligned blocks
        r = x.shape[0] * x.shape[2] * x.shape[3]
        fusable = (r % 8 == 0 and x.shape[1] % 128 == 0
                   and w.shape[0] % 128 == 0)
    if fusable:
        kernel_path.record("conv1x1_bn", interpret)
        return _pallas_1x1(x, w, interpret)
    kernel_path.record("conv1x1_bn")  # a 1x1 the kernel does not take
    return _reference(x, w, strides, pads, dils, groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def conv_bn_stats(strides, pads, dils, groups, x, w):
    """``(y, sum_c, sumsq_c)`` of ``conv2d(x, w)`` in one pass; the
    geometry args are static tuples/ints."""
    return _forward(strides, pads, dils, groups, x, w)


def _fwd(strides, pads, dils, groups, x, w):
    return _forward(strides, pads, dils, groups, x, w), (x, w)


def _bwd(strides, pads, dils, groups, res, ct):
    x, w = res
    _, vjp = jax.vjp(
        lambda xx, ww: _reference(xx, ww, strides, pads, dils, groups),
        x, w)
    return vjp(ct)


conv_bn_stats.defvjp(_fwd, _bwd)


@register_op("conv2d_bn")
def _conv2d_bn(ctx):
    """conv2d + batch_norm in one op: same slots/outputs as batch_norm
    (Y, MeanOut, VarianceOut, SavedMean, SavedVariance(=inv)) plus the
    conv's Input/Filter; the BN finish reproduces ops/nn_ops.py
    _batch_norm from the fused sums instead of a second activation
    pass."""
    x, w = ctx.input("Input"), ctx.input("Filter")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean, var = ctx.input("Mean"), ctx.input("Variance")
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dils = _pair(ctx.attr("dilations", [1, 1]))
    groups = int(ctx.attr("groups", 1) or 1)
    momentum = ctx.attr("momentum", 0.9)
    eps = ctx.attr("epsilon", 1e-5)
    is_test = ctx.attr("is_test", False)
    if is_test:
        # inference reads running stats — no stats pass at all
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=strides,
            padding=[(pads[0], pads[0]), (pads[1], pads[1])],
            rhs_dilation=dils, feature_group_count=groups,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        use_mean, use_var = mean, var
        new_mean, new_var = mean, var
    else:
        y, csum, csq = conv_bn_stats(strides, pads, dils, groups, x, w)
        count = y.shape[0] * y.shape[2] * y.shape[3]
        use_mean = csum / count
        use_var = csq / count - jnp.square(use_mean)
        new_mean = momentum * mean + (1.0 - momentum) * use_mean
        new_var = momentum * var + (1.0 - momentum) * use_var
    inv = jax.lax.rsqrt(use_var + eps)
    a = inv * scale
    b = bias - use_mean * a
    shape = [1] * y.ndim
    shape[1] = -1
    out = y * a.reshape(shape).astype(y.dtype) \
        + b.reshape(shape).astype(y.dtype)
    return {"Y": out, "MeanOut": new_mean, "VarianceOut": new_var,
            "SavedMean": use_mean, "SavedVariance": inv}
