"""Transformer layers: multi-head attention, encoder layer, positional
embedding. (Capability upgrade over the reference's additive-attention NMT
demo; ring_axis enables sequence parallelism over the mesh.)"""

import numpy as np

from ..layer_helper import LayerHelper
from ..initializer import NormalInitializer
from . import nn as _nn
from . import ops as _ops

__all__ = ["multi_head_attention", "multi_head_attention_cached",
           "transformer_encoder_layer", "positional_encoding",
           "positional_encoding_window"]


def multi_head_attention(queries, keys, values, d_model, num_heads,
                         causal=False, key_length=None, ring_axis=None,
                         param_attr=None, name=None, **kwargs):
    """Full MHA with input/output projections. queries/keys/values:
    [B, T, D]. ``ring_axis``: mesh axis name for ring (sequence-parallel)
    attention."""
    helper = LayerHelper("multi_head_attention", name=name, **kwargs)
    # default param names carry tp-able suffixes: .qkv.* weights are
    # column-parallel ([D, D] sharded on dim 1), .o.* row-parallel —
    # see models.transformer.transformer_tp_rules
    from ..core import unique_name
    prefix = name or unique_name.generate("mha")

    def attr(suffix):
        return param_attr if param_attr is not None else \
            "%s.%s.w" % (prefix, suffix)
    q = _nn.fc(queries, d_model, num_flatten_dims=2, bias_attr=False,
               param_attr=attr("qkv_q"), **kwargs)
    k = _nn.fc(keys, d_model, num_flatten_dims=2, bias_attr=False,
               param_attr=attr("qkv_k"), **kwargs)
    v = _nn.fc(values, d_model, num_flatten_dims=2, bias_attr=False,
               param_attr=attr("qkv_v"), **kwargs)
    inputs = {"Q": [q.name], "K": [k.name], "V": [v.name]}
    if key_length is not None:
        inputs["KeyLength"] = [key_length.name]
    ctx_out = helper.create_tmp_variable(queries.dtype)
    helper.append_op(type="multihead_attention", inputs=inputs,
                     outputs={"Out": [ctx_out.name]},
                     attrs={"num_heads": num_heads, "causal": causal,
                            "ring_axis": ring_axis})
    return _nn.fc(ctx_out, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=attr("o"), **kwargs)


def multi_head_attention_cached(x, cache, d_model, num_heads,
                                key_length=None, param_attr=None,
                                name=None, **kwargs):
    """KV-cached MHA for autoregressive generation — the SAME
    projections (and parameter names) as :func:`multi_head_attention`,
    with K/V routed through persistable per-layer cache variables
    (ops/generation_ops.py) instead of being recomputed from history.

    ``cache``: dict with ``k``/``v`` ([num_blocks, block_size, d_model]
    persistable block POOLS), ``table`` (the block table the ops route
    through; ops/generation_ops.py) and ``mode``:

    * ``"prefill"`` — a suffix-window prefill: x [1, P, D] is the
      UNSHARED tail of one prompt, ``cache["hist"]`` rows are already
      cached (shared prefix blocks); the window's K/V rows are written
      at positions [hist, hist + key_length) through the table
      (``key_length`` masks right-padding) and the window attends the
      cached prefix plus itself causally.
    * ``"decode"`` — x is one token per slot [S, 1, D]; K/V rows are
      appended at per-slot positions ``cache["pos"]`` through each
      slot's table row and the single query attends cache rows
      [0, pos] per slot (its own row included).

    Because the q/k/v/o parameter names match the uncached layer
    (same ``unique_name`` sequence), programs built under the same
    ``unique_name.guard()`` discipline share weights through the scope
    — the cached decode path serves a scope trained by the standard
    transformer program."""
    helper = LayerHelper("multi_head_attention", name=name, **kwargs)
    from ..core import unique_name
    prefix = name or unique_name.generate("mha")

    def attr(suffix):
        return param_attr if param_attr is not None else \
            "%s.%s.w" % (prefix, suffix)
    q = _nn.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
               param_attr=attr("qkv_q"), **kwargs)
    k = _nn.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
               param_attr=attr("qkv_k"), **kwargs)
    v = _nn.fc(x, d_model, num_flatten_dims=2, bias_attr=False,
               param_attr=attr("qkv_v"), **kwargs)
    ck, cv = cache["k"], cache["v"]
    ctx_out = helper.create_tmp_variable(x.dtype)
    table = cache["table"]
    if cache["mode"] == "prefill":
        hist = cache["hist"]
        # window rows land at positions [hist, hist+key_length)
        # through the block table; padding rows drop. Cache writes
        # alias the cache variable name: the executor marks it written
        # (state_rw) and donates it, so the update is in place in HBM
        for cvar, proj in ((ck, k), (cv, v)):
            helper.append_op(type="kv_cache_write_paged",
                             inputs={"Cache": [cvar.name],
                                     "New": [proj.name],
                                     "Table": [table.name],
                                     "Hist": [hist.name],
                                     "Len": [key_length.name]},
                             outputs={"Out": [cvar.name]})
        helper.append_op(type="multihead_attention_prefill_paged",
                         inputs={"Q": [q.name], "CacheK": [ck.name],
                                 "CacheV": [cv.name],
                                 "Table": [table.name],
                                 "Hist": [hist.name],
                                 "Len": [key_length.name]},
                         outputs={"Out": [ctx_out.name]},
                         attrs={"num_heads": num_heads})
    elif cache["mode"] == "decode":
        pos = cache["pos"]
        for cvar, proj in ((ck, k), (cv, v)):
            helper.append_op(type="kv_cache_append_paged",
                             inputs={"Cache": [cvar.name],
                                     "New": [proj.name],
                                     "Pos": [pos.name],
                                     "Table": [table.name]},
                             outputs={"Out": [cvar.name]})
        helper.append_op(type="multihead_attention_decode_paged",
                         inputs={"Q": [q.name], "CacheK": [ck.name],
                                 "CacheV": [cv.name],
                                 "Pos": [pos.name],
                                 "Table": [table.name]},
                         outputs={"Out": [ctx_out.name]},
                         attrs={"num_heads": num_heads})
    else:
        raise ValueError("cache mode must be 'prefill' or 'decode', "
                         "got %r" % (cache["mode"],))
    return _nn.fc(ctx_out, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=attr("o"), **kwargs)


def transformer_encoder_layer(x, d_model, num_heads, d_ff, causal=False,
                              key_length=None, ring_axis=None,
                              dropout_prob=0.0, is_test=False, name=None,
                              cache=None, **kwargs):
    """Pre-norm transformer block: x + MHA(LN(x)); x + FFN(LN(x)).
    ``cache`` (see :func:`multi_head_attention_cached`) swaps the
    attention for the KV-cached prefill/decode variant; every
    parameter name is unchanged."""
    ln1 = _nn.layer_norm(x, begin_norm_axis=2, **kwargs)
    if cache is not None:
        if ring_axis:
            raise ValueError(
                "cache= is incompatible with ring_axis (the cached "
                "decode path is single-mesh; ring attention shards "
                "the sequence dim the cache keeps local)")
        if not causal:
            raise ValueError("cached attention is causal-only")
        att = multi_head_attention_cached(ln1, cache, d_model, num_heads,
                                          key_length=key_length, **kwargs)
    else:
        att = multi_head_attention(ln1, ln1, ln1, d_model, num_heads,
                                   causal=causal, key_length=key_length,
                                   ring_axis=ring_axis, **kwargs)
    if dropout_prob:
        att = _nn.dropout(att, dropout_prob, is_test=is_test, **kwargs)
    x = _nn.elementwise_add(x, att, **kwargs)
    ln2 = _nn.layer_norm(x, begin_norm_axis=2, **kwargs)
    from ..core import unique_name
    prefix = name or unique_name.generate("enc")
    ff = _nn.fc(ln2, d_ff, num_flatten_dims=2, act="gelu",
                param_attr="%s.ffn1.w" % prefix,
                bias_attr="%s.ffn1.b" % prefix, **kwargs)
    ff = _nn.fc(ff, d_model, num_flatten_dims=2,
                param_attr="%s.ffn2.w" % prefix,
                bias_attr="%s.ffn2.b" % prefix, **kwargs)
    if dropout_prob:
        ff = _nn.dropout(ff, dropout_prob, is_test=is_test, **kwargs)
    return _nn.elementwise_add(x, ff, **kwargs)


def positional_encoding(x, max_len=None, name=None, **kwargs):
    """Learned positional embedding added to [B, T, D] input."""
    helper = LayerHelper("pos_encoding", name=name, **kwargs)
    t, d = x.shape[1], x.shape[2]
    pos = helper.create_parameter(
        None, shape=[t, d], dtype=x.dtype,
        default_initializer=NormalInitializer(0.0, 0.02))
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="elementwise_add",
                     inputs={"X": [x.name], "Y": [pos.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": 1})
    return out


def positional_encoding_window(x, max_len, pos=None, window_rows=False,
                               name=None, **kwargs):
    """A window of the SAME learned position table as
    :func:`positional_encoding` (identical parameter name when built
    under the same ``unique_name`` sequence, so a full-sequence train
    program and the cached-decode programs share it):

    * ``pos=None`` (prefill): rows [0, x.shape[1]) of the [max_len, D]
      table are added to x [1, P, D].
    * ``pos`` given (decode): row ``pos[s]`` is gathered per slot and
      added to x [S, 1, D] — one position embedding per in-flight
      sequence, each at its own depth.
    * ``pos`` given with ``window_rows=True`` (paged suffix prefill):
      ``pos`` is one index PER WINDOW ROW ([P], typically
      hist + arange(P)) and the gathered rows are added along x's
      time axis [1, P, D] — a prompt window starting at an arbitrary
      cached depth."""
    helper = LayerHelper("pos_encoding", name=name, **kwargs)
    d = x.shape[2]
    table = helper.create_parameter(
        None, shape=[max_len, d], dtype=x.dtype,
        default_initializer=NormalInitializer(0.0, 0.02))
    out = helper.create_tmp_variable(x.dtype)
    if pos is None:
        t = x.shape[1]
        if t > max_len:
            raise ValueError("prefill window %d exceeds the position "
                             "table length %d" % (t, max_len))
        win = helper.create_tmp_variable(x.dtype)
        helper.append_op(type="slice", inputs={"Input": [table.name]},
                         outputs={"Out": [win.name]},
                         attrs={"axes": [0], "starts": [0],
                                "ends": [t]})
        helper.append_op(type="elementwise_add",
                         inputs={"X": [x.name], "Y": [win.name]},
                         outputs={"Out": [out.name]}, attrs={"axis": 1})
    else:
        rows = helper.create_tmp_variable(x.dtype)
        helper.append_op(type="gather",
                         inputs={"X": [table.name], "Index": [pos.name]},
                         outputs={"Out": [rows.name]})
        rows3 = helper.create_tmp_variable(x.dtype)
        # window mode: rows line up with x's TIME axis [1, P, D];
        # decode mode: one row per slot along the batch axis [S, 1, D]
        shape3 = [1, -1, d] if window_rows else [-1, 1, d]
        helper.append_op(type="reshape", inputs={"X": [rows.name]},
                         outputs={"Out": [rows3.name]},
                         attrs={"shape": shape3})
        helper.append_op(type="elementwise_add",
                         inputs={"X": [x.name], "Y": [rows3.name]},
                         outputs={"Out": [out.name]}, attrs={})
    return out
