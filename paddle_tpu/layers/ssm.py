"""The Mamba-2 mixer as a layer (ops/ssm_ops.py): its parameters, with the
initial values the architecture publishes, and the op over whole sequences,
a prompt's prefill into a state row, or a decode step over the state
pool."""

from ..layer_helper import LayerHelper
from ..initializer import (ConstantInitializer, Initializer,
                           NormalInitializer, UniformInitializer)

__all__ = ["mamba2_mixer"]


class _Mamba2Initializer(Initializer):
    """``a_log`` / ``dt_bias`` as Mamba-2 publishes them (``A`` uniform in
    [1, 16], ``dt`` log-uniform in [1e-3, 1e-1]): a uniform draw, then
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
