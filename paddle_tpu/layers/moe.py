"""Layers of the sparse-expert decoder block (ops/moe_ops.py): RMSNorm,
rotary positions, a bias-free projection held in a dtype of its own,
SwiGLU and the expert feed-forward."""

from ..layer_helper import LayerHelper
from ..initializer import ConstantInitializer, NormalInitializer
from . import nn as _nn
from . import ops as _ops

__all__ = ["rms_norm", "rotary_embedding", "linear", "swiglu", "ffn",
           "moe_ffn", "mla_attention", "eva_attention", "bias_add",
           "diff_attention_queries", "diff_attention_combine"]


def rms_norm(x, epsilon=1e-5, group_size=0, param_attr=None, name=None,
             offset=0.0, **kwargs):
    """RMSNorm over the last axis of ``x``, or with ``group_size`` over
    each group of that many lanes (one weight vector of ``group_size``
    shared by all groups: a per-head norm of a [.., H*D] projection).
    Float32 out, float32 weight. With ``offset`` the gain is ``offset +
    w`` and ``w`` starts at ``1 - offset``: a unit offset over zeros."""
    helper = LayerHelper("rms_norm", name=name, **kwargs)
    w = helper.create_parameter(
        param_attr, shape=[group_size or x.shape[-1]], dtype="float32",
        default_initializer=ConstantInitializer(1.0 - offset))
    out = helper.create_tmp_variable("float32")
    attrs = {"epsilon": epsilon, "group_size": group_size}
    if offset:
        attrs["offset"] = offset
    helper.append_op(type="rms_norm",
                     inputs={"X": [x.name], "Scale": [w.name]},
                     outputs={"Y": [out.name]}, attrs=attrs)
    return out


def rotary_embedding(x, head_dim, theta=10000.0, pos=None, per_row=False,
                     lanes=None, yarn=None, name=None, **kwargs):
    """Rotary positions on x [B, T, H*D]: ``pos`` [T] along the time axis
    (absent: 0..T-1), or with ``per_row`` [B], one per batch row. ``lanes``
    (lo, hi): only these lanes of each head turn; ``yarn``: the YaRN
    scaling of the frequencies (ops/moe_ops.py ``rotary_frequencies``)."""
    helper = LayerHelper("rotary_embedding", name=name, **kwargs)
    inputs = {"X": [x.name]}
    if pos is not None:
        inputs["Pos"] = [pos.name]
    attrs = {"head_dim": head_dim, "theta": theta, "per_row": per_row}
    if lanes is not None:
        attrs["lanes"] = tuple(lanes)
    if yarn:
        attrs["yarn"] = dict(yarn)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="rotary_embedding", inputs=inputs,
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def linear(x, size, param_attr, dtype=None, std=0.02, transpose_w=False,
           **kwargs):
    """``x @ W`` over the last axis with no bias, W [in, size] created and
    held in ``dtype`` (default: x's). The product is exact and float32
    whatever W is held in (ops/moe_ops.py ``linear``). ``transpose_w``: W
    is [size, in] and read as it lies: a head over the embedding's own
    parameter."""
    helper = LayerHelper("linear", **kwargs)
    shape = [x.shape[-1], size]
    w = helper.create_parameter(
        param_attr, shape=shape[::-1] if transpose_w else shape,
        dtype=dtype or x.dtype,
        default_initializer=NormalInitializer(0.0, std))
    out = helper.create_tmp_variable("float32")
    helper.append_op(type="linear", inputs={"X": [x.name], "W": [w.name]},
                     outputs={"Out": [out.name]},
                     attrs={"transpose_w": True} if transpose_w else {})
    return out


def bias_add(x, param_attr, std=0.02, **kwargs):
    """``x + b`` over the last axis, ``b`` float32 drawn Normal(0, std): a
    projection's bias, which :func:`linear` has none of."""
    helper = LayerHelper("bias_add", **kwargs)
    b = helper.create_parameter(
        param_attr, shape=[x.shape[-1]], dtype="float32",
        default_initializer=NormalInitializer(0.0, std))
    return _nn.elementwise_add(x, b, **kwargs)


def diff_attention_queries(q, head_dim, **kwargs):
    """q [.., 2P*D] -> [.., 2P*2D]: a differential pair's two queries as
    two heads over key rows ``(k1 | k2)`` (ops/attention_ops.py)."""
    helper = LayerHelper("diff_attention_queries", **kwargs)
    out = helper.create_tmp_variable(q.dtype)
    helper.append_op(type="diff_attention_queries", inputs={"Q": [q.name]},
                     outputs={"Out": [out.name]},
                     attrs={"head_dim": head_dim})
    return out


def diff_attention_combine(x, head_dim, lambda_init, prefix, epsilon=1e-5,
                           lambda_std=0.1, **kwargs):
    """x [.., 2P*2D], each pair's two attentions -> [.., P*2D] float32:
    ``RMSNorm(x1 - lambda x2) (1 - lambda_init)``. Parameters
    ``<prefix>.lambda_q1``, ``.lambda_k1``, ``.lambda_q2``, ``.lambda_k2``
    [D] drawn Normal(0, lambda_std) and ``.subln.w`` [2D] ones, float32."""
    helper = LayerHelper("diff_attention_combine", **kwargs)
    inputs = {"X": [x.name]}
    for slot, name in (("LambdaQ1", "lambda_q1"), ("LambdaK1", "lambda_k1"),
                       ("LambdaQ2", "lambda_q2"), ("LambdaK2", "lambda_k2")):
        inputs[slot] = [helper.create_parameter(
            "%s.%s" % (prefix, name), shape=[head_dim], dtype="float32",
            default_initializer=NormalInitializer(0.0, lambda_std)).name]
    inputs["NormW"] = [helper.create_parameter(
        prefix + ".subln.w", shape=[2 * head_dim], dtype="float32",
        default_initializer=ConstantInitializer(1.0)).name]
    out = helper.create_tmp_variable("float32")
    helper.append_op(type="diff_attention_combine", inputs=inputs,
                     outputs={"Out": [out.name]},
                     attrs={"lambda_init": lambda_init, "epsilon": epsilon})
    return out


def swiglu(x, d_ff, prefix, dtype=None, **kwargs):
    """``(silu(x Wg) * (x Wu)) Wd``: parameters ``<prefix>.gate.w``,
    ``.up.w`` [d, d_ff] and ``.down.w`` [d_ff, d]."""
    return ffn(x, d_ff, prefix, dtype, **kwargs)


def ffn(x, d_ff, prefix, dtype=None, act=None, **kwargs):
    """A dense feed-forward of ``d_ff`` inner lanes: SwiGLU
    (:func:`swiglu`), or with ``act`` ``relu2`` the two-matrix
    ``relu(x Wu)^2 Wd``: ``<prefix>.up.w`` [d, d_ff] and ``.down.w``
    [d_ff, d], no gate."""
    if act == "relu2":
        up = linear(x, d_ff, prefix + ".up.w", dtype, **kwargs)
        inner = _ops.square(_ops.relu(up, **kwargs), **kwargs)
    else:
        gate = linear(x, d_ff, prefix + ".gate.w", dtype, **kwargs)
        up = linear(x, d_ff, prefix + ".up.w", dtype, **kwargs)
        inner = _nn.elementwise_mul(_ops.silu(gate, **kwargs), up, **kwargs)
    return linear(inner, x.shape[-1], prefix + ".down.w", dtype, **kwargs)


def moe_ffn(x, num_experts, top_k, d_ff, prefix, route_norm=True,
            route_scale=1.0, expert_offset=0, experts_held=None,
            dtype=None, std=0.02, scoring="sigmoid", zero_experts=0,
            act=None, **kwargs):
    """The routed experts of a sparse feed-forward over x [.., d]
    (ops/moe_ops.py ``moe_ffn``): the router ``<prefix>.router.w`` [d, E]
    and the selection bias ``<prefix>.expert_bias`` [E] in float32, the
    held experts ``[expert_offset, expert_offset + experts_held)`` stacked
    as ``<prefix>.experts.gate.w``, ``.up.w`` [E_held, d, d_ff] and
    ``.down.w`` [E_held, d_ff, d] in ``dtype``. ``scoring`` is the
    router's (``sigmoid``; ``softmax_bias``: a softmax over all outputs;
    or ``softmax_topk``: a softmax over the chosen logits). With
    ``zero_experts`` Z the router and the bias are ``E + Z`` wide: the
    last Z outputs are identity experts. With ``act`` ``relu2`` an expert
    is two matrices, ``relu(x Wu^T)^2 Wd``: no ``.gate.w``, and ``.up.w``
    is held [E_held, d_ff, d] as ``.down.w`` is. Returns (out float32, counts
    [E_held] int32), and with ``zero_experts`` a third, the call's
    identity pairs [1] int32."""
    helper = LayerHelper("moe_ffn", **kwargs)
    d = x.shape[-1]
    held = num_experts if experts_held is None else experts_held
    dtype = dtype or x.dtype
    normal = NormalInitializer(0.0, std)
    router = helper.create_parameter(
        prefix + ".router.w", shape=[d, num_experts + zero_experts],
        dtype="float32", default_initializer=normal)
    bias = helper.create_parameter(
        prefix + ".expert_bias", shape=[num_experts + zero_experts],
        dtype="float32", default_initializer=ConstantInitializer(0.0))
    relu2 = act == "relu2"
    stacks = {slot: helper.create_parameter(
        "%s.experts.%s.w" % (prefix, which), shape=shape, dtype=dtype,
        default_initializer=normal)
        for slot, which, shape in (
            ("WGate", "gate", [held, d, d_ff]),
            ("WUp", "up", [held, d_ff, d] if relu2 else [held, d, d_ff]),
            ("WDown", "down", [held, d_ff, d]))
        if not (relu2 and which == "gate")}
    out = helper.create_tmp_variable("float32")
    counts = helper.create_tmp_variable("int32", stop_gradient=True)
    attrs = {"num_experts": num_experts, "top_k": top_k,
             "route_norm": route_norm, "route_scale": route_scale,
             "expert_offset": expert_offset}
    if scoring != "sigmoid":
        attrs["scoring"] = scoring
    if act:
        attrs["act"] = act
    outputs = {"Out": [out.name], "Counts": [counts.name]}
    if zero_experts:
        attrs["zero_experts"] = zero_experts
        zero_pairs = helper.create_tmp_variable("int32", stop_gradient=True)
        outputs["ZeroPairs"] = [zero_pairs.name]
    helper.append_op(
        type="moe_ffn",
        inputs=dict({"X": [x.name], "RouterW": [router.name],
                     "ExpertBias": [bias.name]},
                    **{slot: [w.name] for slot, w in stacks.items()}),
        outputs=outputs, attrs=attrs)
    return (out, counts, zero_pairs) if zero_experts else (out, counts)


def mla_attention(q, c, k_rope, num_heads, nope_dim, rope_dim, v_dim, scale,
                  param_attr, dtype=None, std=0.02, block_rows=None,
                  cache=None, pos=None, table=None, **kwargs):
    """Latent attention (ops/mla_ops.py) of rotated queries q
    [B, T, H*(nope + rope)] over the latents c [B, T, kv_rank] (normed) and
    k_rope [B, T, rope] (rotated), through the up-projection ``param_attr``
    [kv_rank, H*(nope + v)] held in ``dtype``: over the sequence's own
    rows, expanded, ``block_rows`` at a time; or with ``cache`` (the paged
    latent pool the rows were written to), ``pos`` and ``table``, one
    query a slot over its cached rows, absorbed. -> [B, T, H*v] float32."""
    helper = LayerHelper("mla_attention", **kwargs)
    w = helper.create_parameter(
        param_attr, shape=[c.shape[-1], num_heads * (nope_dim + v_dim)],
        dtype=dtype or q.dtype, default_initializer=NormalInitializer(0.0, std))
    attrs = {"num_heads": num_heads, "nope_dim": nope_dim,
             "rope_dim": rope_dim, "v_dim": v_dim, "scale": scale}
    out = helper.create_tmp_variable("float32")
    if cache is None:
        helper.append_op(
            type="mla_attention",
            inputs={"Q": [q.name], "C": [c.name], "KRope": [k_rope.name],
                    "WUKV": [w.name]},
            outputs={"Out": [out.name]},
            attrs=dict(attrs, block_rows=block_rows))
    else:
        helper.append_op(
            type="mla_attention_decode_paged",
            inputs={"Q": [q.name], "Cache": [cache.name], "Pos": [pos.name],
                    "Table": [table.name], "WUKV": [w.name]},
            outputs={"Out": [out.name]}, attrs=attrs)
    return out


def eva_attention(q, k, v, num_heads, window, chunk, prefix, dtype=None,
                  caches=None, tables=None, pos=None, hist=None, length=None,
                  **kwargs):
    """EVA attention (ops/eva_ops.py) of rotated queries and keys q, k and
    values v [B, T, H*D]: a row attends its own aligned ``window`` exactly
    and one summary for every ``chunk`` positions of the windows before
    it, pooled with the learned ``<prefix>.mu`` and ``<prefix>.phi`` [H*D]
    (held in ``dtype``; Normal(0, 1) held within a deviation, times
    ``D^-1/2``). Over the sequence's own rows; or with ``caches`` ((k, v)
    of the window pools, (k, v) of the chunk pools) and ``tables`` (the
    two kinds' table feeds), a prefill (``hist``, ``length``: the rows and
    the whole chunks' summaries are also written through the tables) or,
    with ``pos``, a decode step: the row appended, its block's summary
    written where the block is full, one query a slot over both pools.
    -> [B, T, H*D] float32."""
    helper = LayerHelper("eva_attention", **kwargs)
    dm = q.shape[-1]
    init = NormalInitializer(0.0, (dm // num_heads) ** -0.5, clip=1.0)
    mu, phi = (helper.create_parameter(
        "%s.%s" % (prefix, name), shape=[dm], dtype=dtype or q.dtype,
        default_initializer=init) for name in ("mu", "phi"))
    attrs = {"num_heads": num_heads, "window": window, "chunk": chunk}
    learned = {"Mu": [mu.name], "Phi": [phi.name]}
    out = helper.create_tmp_variable("float32")
    decode = pos is not None

    def summaries(rows, dtype):
        kbar, vbar = (helper.create_tmp_variable(dtype) for _ in "kv")
        helper.append_op(type="eva_summaries", inputs=dict(learned, **rows),
                         outputs={"KBar": [kbar.name], "VBar": [vbar.name]},
                         attrs={"num_heads": num_heads, "chunk": chunk})
        return kbar, vbar

    def write(pool, new, table, per_chunk):
        where = {"Pos": [pos.name]} if decode else \
            {"Hist": [hist.name], "Len": [length.name]}
        helper.append_op(
            type="kv_cache_append_paged" if decode
            else "kv_cache_write_paged",
            inputs=dict(where, Cache=[pool.name], New=[new.name],
                        Table=[table.name]),
            outputs={"Out": [pool.name]},
            attrs={"chunk": chunk} if per_chunk else {})

    if caches is not None:
        (ck, cv), (sk, sv) = caches
        wtab, ctab = tables
        write(ck, k, wtab, False)
        write(cv, v, wtab, False)
    # a decode step pools the block its row just went to, a sequence its
    # own rows
    kbar, vbar = summaries(
        {"CacheK": [ck.name], "CacheV": [cv.name], "Pos": [pos.name],
         "Table": [wtab.name]}, ck.dtype) if decode else \
        summaries({"K": [k.name], "V": [v.name]}, k.dtype)
    if caches is not None:
        write(sk, kbar, ctab, True)
        write(sv, vbar, ctab, True)
    if decode:
        helper.append_op(
            type="eva_attention_decode_paged",
            inputs={"Q": [q.name], "CacheK": [ck.name], "CacheV": [cv.name],
                    "ChunkK": [sk.name], "ChunkV": [sv.name],
                    "Pos": [pos.name], "Table": [wtab.name],
                    "ChunkTable": [ctab.name]},
            outputs={"Out": [out.name]}, attrs=attrs)
    else:
        helper.append_op(
            type="eva_attention",
            inputs={"Q": [q.name], "K": [k.name], "V": [v.name],
                    "KBar": [kbar.name], "VBar": [vbar.name]},
            outputs={"Out": [out.name]}, attrs=attrs)
    return out
