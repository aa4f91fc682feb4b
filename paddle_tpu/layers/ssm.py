"""The Mamba-2 mixer, and Mamba-1's, as layers (ops/ssm_ops.py): their
parameters, with the initial values the architectures publish, and the op
over whole sequences, a prompt's prefill into a state row, or a decode step
over the state pool."""

from ..layer_helper import LayerHelper
from ..initializer import (ConstantInitializer, Initializer,
                           NormalInitializer, UniformInitializer)
from . import moe as _moe

__all__ = ["mamba2_mixer", "mamba1_mixer"]


class _Mamba2Initializer(Initializer):
    """``a_log`` / ``dt_bias`` as Mamba-2 publishes them (``A`` uniform in
    [1, 16], ``dt`` log-uniform in [1e-3, 1e-1]), or Mamba-1's
    ``a_log_rows`` (``A`` = 1..N for every channel): a uniform draw, then
    ``mamba2_param_init``. Normal(0, 0.02) there would forget the state
    within a few rows or never move it."""

    def __init__(self, what):
        self.what = what

    def __call__(self, var, block):
        block.append_op("uniform_random", outputs={"Out": [var.name]},
                        attrs={"shape": list(var.shape), "dtype": var.dtype,
                               "min": 0.0, "max": 1.0, "seed": 0},
                        infer_shape=False)
        block.append_op("mamba2_param_init", inputs={"U": [var.name]},
                        outputs={"Out": [var.name]},
                        attrs={"what": self.what}, infer_shape=False)


def mamba2_mixer(x, num_heads, head_dim, state_dim, conv_width, chunk,
                 prefix, epsilon=1e-5, dtype=None, std=0.02, state=None,
                 table=None, length=None, pos=None, n_groups=1, **kwargs):
    """The Mamba-2 mixer over x [B, T, d] -> [B, T, d] float32. Parameters
    ``<prefix>.in.w`` [d, 2HP + 2GN + H] and ``.out.w`` [HP, d] in ``dtype``;
    ``.conv.w`` [K, HP + 2GN], ``.conv.b``, ``.dt_bias``, ``.a_log``, ``.d``
    [H] and ``.norm.w`` [HP] in float32, for ``n_groups`` G groups of B and
    C (the gated norm then within each group's HP / G lanes). ``state`` is
    the layer's state pool ``(ssm, conv, at)`` with ``table``: and
    ``length`` for a prompt's prefill into its row, or ``pos`` for a decode
    step over every row.

    The convolution's taps start as Mamba-2's do (``nn.Conv1d``'s default:
    uniform within ``1 / sqrt(K)``), not at ``std``: taps of 0.02 leave
    ``x``, ``B`` and ``C`` near 0.03, and the state then carries a
    thousandth of ``y`` beside ``D x``, so that no comparison of outputs
    can tell a wrong state."""
    helper = LayerHelper("mamba2_mixer", **kwargs)
    d, di = x.shape[-1], num_heads * head_dim
    lanes = di + 2 * n_groups * state_dim
    dtype = dtype or x.dtype
    normal = NormalInitializer(0.0, std)
    tap = conv_width ** -0.5

    def param(name, shape, held, init):
        return helper.create_parameter("%s.%s" % (prefix, name), shape=shape,
                                       dtype=held, default_initializer=init)
    inputs = {
        "X": x, "WIn": param("in.w", [d, di + lanes + num_heads], dtype,
                             normal),
        "ConvW": param("conv.w", [conv_width, lanes], "float32",
                       UniformInitializer(-tap, tap)),
        "ConvB": param("conv.b", [lanes], "float32",
                       ConstantInitializer(0.0)),
        "DtBias": param("dt_bias", [num_heads], "float32",
                        _Mamba2Initializer("dt_bias")),
        "ALog": param("a_log", [num_heads], "float32",
                      _Mamba2Initializer("a_log")),
        "D": param("d", [num_heads], "float32", ConstantInitializer(1.0)),
        "NormW": param("norm.w", [di], "float32", ConstantInitializer(1.0)),
        "WOut": param("out.w", [di, d], dtype, normal)}
    out = helper.create_tmp_variable("float32")
    outputs = {"Out": [out.name]}
    op = "mamba2_mixer"
    if state is not None:
        ssm, conv, at = state
        inputs.update(Ssm=ssm, Conv=conv, At=at, Table=table)
        outputs.update(SsmOut=[ssm.name], ConvOut=[conv.name],
                       AtOut=[at.name])
        if pos is not None:
            op, inputs["Pos"] = "mamba2_mixer_decode", pos
        else:
            inputs["Len"] = length
    helper.append_op(
        type=op, inputs={k: [v.name] for k, v in inputs.items()},
        outputs=outputs,
        attrs=dict({"num_heads": num_heads, "head_dim": head_dim,
                    "state_dim": state_dim, "chunk": chunk,
                    "epsilon": epsilon},
                   **({"n_groups": n_groups} if n_groups > 1 else {})))
    return out


def mamba1_mixer(x, d_inner, state_dim, conv_width, dt_rank, prefix,
                 dtype=None, std=0.02, bc_std=None, state=None, table=None,
                 length=None, pos=None, **kwargs):
    """The Mamba-1 mixer over x [B, T, d] -> (out [B, T, d], m [B, T, D]),
    both float32: ``m`` is the scan's output ``y`` (with its ``D x`` term)
    before the gate, what a gated memory unit of a later layer reads.
    Parameters ``<prefix>.in.w`` [d, 2D], ``.x_dt.w`` [D, R], ``.x_bc.w``
    [D, 2N] (the published ``x_proj``'s columns in two matrices, so that B
    and C can start wider than the rest: ``bc_std``, absent ``std``),
    ``.dt.w`` [R, D] and ``.out.w`` [D, d] in ``dtype``; ``.conv.w`` [K, D],
    ``.conv.b``, ``.dt_bias``, ``.d`` [D] and ``.a_log`` [N, D] in float32.
    ``state`` is the layer's state pool ``(ssm [rows, N, D], conv
    [rows, K, D], at)`` with ``table``: and ``length`` for a prompt's
    prefill into its row, or ``pos`` for a decode step over every row.

    Initial values as Mamba-1 publishes them: ``A`` = 1..N for every
    channel, ``dt`` log-uniform in [1e-3, 1e-1], ``W_dt`` and the
    convolution's taps uniform within ``R^-1/2`` and ``K^-1/2``, ``D``
    ones."""
    helper = LayerHelper("mamba1_mixer", **kwargs)
    dtype = dtype or x.dtype
    tap, step = conv_width ** -0.5, dt_rank ** -0.5

    def param(name, shape, held, init):
        return helper.create_parameter("%s.%s" % (prefix, name), shape=shape,
                                       dtype=held, default_initializer=init)
    inputs = {
        "XZ": _moe.linear(x, 2 * d_inner, prefix + ".in.w", dtype, std),
        "ConvW": param("conv.w", [conv_width, d_inner], "float32",
                       UniformInitializer(-tap, tap)),
        "ConvB": param("conv.b", [d_inner], "float32",
                       ConstantInitializer(0.0)),
        "WR": param("x_dt.w", [d_inner, dt_rank], dtype,
                    NormalInitializer(0.0, std)),
        "WBC": param("x_bc.w", [d_inner, 2 * state_dim], dtype,
                     NormalInitializer(0.0, bc_std or std)),
        "WDt": param("dt.w", [dt_rank, d_inner], dtype,
                     UniformInitializer(-step, step)),
        "DtBias": param("dt_bias", [d_inner], "float32",
                        _Mamba2Initializer("dt_bias")),
        "ALog": param("a_log", [state_dim, d_inner], "float32",
                      _Mamba2Initializer("a_log_rows")),
        "D": param("d", [d_inner], "float32", ConstantInitializer(1.0))}
    gated = helper.create_tmp_variable("float32")
    m = helper.create_tmp_variable("float32")
    outputs = {"Out": [gated.name], "M": [m.name]}
    op = "mamba1_mixer"
    if state is not None:
        ssm, conv, at = state
        inputs.update(Ssm=ssm, Conv=conv, At=at, Table=table)
        outputs.update(SsmOut=[ssm.name], ConvOut=[conv.name],
                       AtOut=[at.name])
        if pos is not None:
            op, inputs["Pos"] = "mamba1_mixer_decode", pos
        else:
            inputs["Len"] = length
    helper.append_op(type=op,
                     inputs={k: [v.name] for k, v in inputs.items()},
                     outputs=outputs)
    return _moe.linear(gated, x.shape[-1], prefix + ".out.w", dtype, std), m
