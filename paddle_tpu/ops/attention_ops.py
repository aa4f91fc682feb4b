"""Attention ops: fused multi-head attention + ring (sequence-parallel)
attention.

The reference predates transformers — attention capability is an upgrade
(its closest analog is the NMT demo's additive attention built from
primitive layers). Here attention is a first-class fused op so XLA maps it
onto the MXU as two batched matmuls + softmax, and the ring variant
(parallel/ring_attention.py) scales the sequence dimension across the mesh
(SURVEY §2.3 gap: SP/CP).
"""

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .. import parallel


def _grouped_window_attention(q, k, v, nh, nkv, causal, window, scale=None):
    """Dense attention of Q [B, T, H*D] on K, V [B, T, Hkv*D]: query head
    h on KV head ``h // (H / Hkv)``; with a ``window``, key j is visible
    to query i iff ``i - j < window``. The whole-sequence form of what the
    paged cache ops do a window or a step at a time."""
    b, tq, dm = q.shape
    tk, hd = k.shape[1], dm // nh
    qh = q.reshape(b, tq, nkv, nh // nkv, hd)
    kh = k.reshape(b, tk, nkv, hd)
    vh = v.reshape(b, tk, nkv, hd)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qh, kh,
                   preferred_element_type=jnp.float32) * \
        (hd ** -0.5 if scale is None else scale)
    rows, cols = jnp.arange(tq)[:, None], jnp.arange(tk)[None, :]
    mask = cols <= rows if causal else jnp.ones((tq, tk), bool)
    if window:
        mask = mask & (rows - cols < window)
    s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqc,bckd->bqkgd", p, vh).reshape(b, tq, dm)


@register_op("diff_attention_queries")
def _diff_attention_queries(ctx):
    """Q [.., 2P*D], the query heads of P differential pairs ``(q1_p,
    q2_p)`` = heads ``(2p, 2p+1)`` -> Out [.., 2P*2D]: head ``(p, 1)`` is
    ``(q1_p | 0)`` and ``(p, 2)`` is ``(0 | q2_p)``. Against a key row
    ``(k1 | k2)`` of 2D lanes the first scores ``q1 . k1`` and the second
    ``q2 . k2``, so that a pair's two softmaxes are two heads of an
    ordinary grouped-query attention over KV heads of 2D lanes: the keys
    and the values ``(v1 | v2)`` lie as the projection wrote them, and a
    cached page is read once for both."""
    q = ctx.input("Q")
    hd = ctx.attr("head_dim")
    pairs = q.reshape(q.shape[:-1] + (-1, 2, 1, hd))
    own = jnp.eye(2, dtype=q.dtype)[:, :, None]
    return {"Out": (pairs * own).reshape(q.shape[:-1] + (-1,))}


def diff_lambda(q1, k1, q2, k2, init):
    """A layer's ``lambda = exp(q1 . k1) - exp(q2 . k2) + lambda_init``
    from its four learned vectors."""
    def dot(a, b):
        return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32))
    return jnp.exp(dot(q1, k1)) - jnp.exp(dot(q2, k2)) + init


@register_op("diff_attention_combine")
def _diff_attention_combine(ctx):
    """X [.., 2P*W]: heads ``(p, 1)`` and ``(p, 2)`` of W lanes, a pair's
    two attentions over its one value (Differential Transformer, Ye et al.
    2024); LambdaQ1, LambdaK1, LambdaQ2, LambdaK2 [D]; NormW [W]; attrs
    lambda_init, epsilon. Out [.., P*W] float32 =
    ``RMSNorm_W(x1 - lambda x2; NormW) (1 - lambda_init)`` with ``lambda =
    exp(q1 . k1) - exp(q2 . k2) + lambda_init``, one number a layer."""
    x = ctx.input("X").astype(jnp.float32)
    w = ctx.input("NormW").astype(jnp.float32)
    init = ctx.attr("lambda_init")
    lam = diff_lambda(*(ctx.input(slot) for slot in (
        "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2")), init)
    pairs = x.reshape(x.shape[:-1] + (-1, 2, w.shape[0]))
    o = pairs[..., 0, :] - lam * pairs[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + ctx.attr("epsilon", 1e-5)) * w * (1.0 - init)
    return {"Out": o.reshape(x.shape[:-1] + (-1,))}


@register_op("multihead_attention")
def _multihead_attention(ctx):
    """Q,K,V: [B, T, H*D] packed; attrs num_heads, causal; optional
    KeyLength [B] masking padded keys. Out: [B, T, H*D]. With attrs
    num_kv_heads (K, V are [B, T, Hkv*D]) or window: the dense grouped,
    windowed form (no KeyLength, no kernel), whose scores take attr
    ``scale`` where the model has one (absent: ``D^-1/2``)."""
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    nh = ctx.attr("num_heads")
    causal = ctx.attr("causal", False)
    b, tq, dm = q.shape
    tk = k.shape[1]
    hd = dm // nh
    if ctx.attr("num_kv_heads") or ctx.attr("window"):
        return {"Out": _grouped_window_attention(
            q, k, v, nh, ctx.attr("num_kv_heads") or nh, causal,
            ctx.attr("window"), ctx.attr("scale"))}
    qh = q.reshape(b, tq, nh, hd)
    kh = k.reshape(b, tk, nh, hd)
    vh = v.reshape(b, tk, nh, hd)

    strategy = parallel.current_strategy()
    use_ring = ctx.attr("ring_axis") and strategy is not None and \
        ctx.attr("ring_axis") in strategy.mesh.axis_names and tq == tk
    if use_ring:
        out = parallel.ring_attention(qh, kh, vh, strategy.mesh,
                                      axis_name=ctx.attr("ring_axis"),
                                      causal=causal)
        return {"Out": out.reshape(b, tq, dm)}

    from .. import config as _config
    flash = _config.get_flag("flash_attention")
    if flash and tq == tk:
        from .pallas_attention import flash_attention
        seg = None
        if ctx.has_input("KeyLength"):
            klen = ctx.input("KeyLength").reshape(-1)
            seg = (jnp.arange(tk)[None, :] <
                   klen[:, None]).astype(jnp.int32)
        if strategy is None:
            out = flash_attention(qh.transpose(0, 2, 1, 3),
                                  kh.transpose(0, 2, 1, 3),
                                  vh.transpose(0, 2, 1, 3),
                                  causal=causal, segment_ids=seg)
            return {"Out": out.transpose(0, 2, 1, 3).reshape(b, tq, dm)}
        # Sharded trace: pallas_call is an opaque custom call GSPMD
        # cannot partition, but attention is embarrassingly parallel
        # over batch and heads — run the kernel PER-SHARD under
        # shard_map (dp shards B, tp shards H; T stays local — the
        # ring path above is the T-sharded long-context answer).
        sizes = dict(zip(strategy.mesh.axis_names,
                         strategy.mesh.devices.shape))
        daxis = strategy.data_axis
        if daxis is not None and b % sizes.get(daxis, 1) != 0:
            daxis = None
        maxis = getattr(strategy, "model_axis", None)
        if maxis is not None and nh % sizes.get(maxis, 1) != 0:
            maxis = None
        if daxis is not None or maxis is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as SP
            spec = SP(daxis, maxis, None, None)

            if seg is None:
                def body(qs, ks, vs):
                    return flash_attention(qs, ks, vs, causal=causal)
                fn = shard_map(body, mesh=strategy.mesh,
                               in_specs=(spec, spec, spec),
                               out_specs=spec, check_vma=False)
                out = fn(qh.transpose(0, 2, 1, 3),
                         kh.transpose(0, 2, 1, 3),
                         vh.transpose(0, 2, 1, 3))
            else:
                sspec = SP(daxis, None)

                def body(qs, ks, vs, ss):
                    return flash_attention(qs, ks, vs, causal=causal,
                                           segment_ids=ss)
                fn = shard_map(body, mesh=strategy.mesh,
                               in_specs=(spec, spec, spec, sspec),
                               out_specs=spec, check_vma=False)
                out = fn(qh.transpose(0, 2, 1, 3),
                         kh.transpose(0, 2, 1, 3),
                         vh.transpose(0, 2, 1, 3), seg)
            return {"Out": out.transpose(0, 2, 1, 3).reshape(b, tq, dm)}
        # no shardable axis applies -> dense path below

    if flash:
        # armed but not taken (cross attention, or a mesh no axis of
        # which divides the batch or the heads): the dense O(T^2) path
        from . import kernel_path
        kernel_path.record("flash_attention")
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask[None, None], s, neg)
    p_zero = None
    if ctx.has_input("KeyLength"):
        klen = ctx.input("KeyLength").reshape(-1)
        kmask = jnp.arange(tk)[None, :] < klen[:, None]
        s = jnp.where(kmask[:, None, None, :], s, neg)
        if tq == tk:
            # padded query rows -> zero output (matches the flash
            # kernel's segment-mask convention)
            p_zero = kmask[:, None, :, None]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if p_zero is not None:
        p = p * p_zero.astype(p.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh)
    return {"Out": out.reshape(b, tq, dm)}
