"""Latent attention (MLA): a token's keys and values for every head are
products of ONE latent row, ``(c, k_r)``: ``c`` [kv_rank] after its
RMSNorm and ``k_r`` [rope] after its rotary turn. Head h's key is
``(c W_uk[h], k_r)`` and its value ``c W_uv[h]``, where ``W_ukv``
[kv_rank, H * (nope + v)] holds ``(W_uk[h], W_uv[h])`` head after head.
The row is what a layer cache keeps, and there are two ways to attend it:

* ``mla_attention`` — **expanded**: keys and values of a whole sequence
  are made from its latents once (``mla_expand_kv``), and the rows attend
  them causally in blocks of ``block_rows`` under a running softmax, key
  width ``nope + rope`` and value width ``v``. Whole sequences and a
  prompt's prefill, whose rows see the prompt's own latents and nothing
  before them.
* ``mla_attention_decode_paged`` — **absorbed**: ``W_uk`` goes into the
  query (``mla_absorb_q``: ``q~_h = W_uk[h] q_nope_h``), the 64 heads
  attend the paged latent rows themselves as one KV head whose value is
  the leading ``kv_rank`` lanes of its key's row
  (``pallas_attention.decode_attention_paged``), and ``W_uv`` comes after
  the sum (``mla_absorb_o``). Nothing is expanded: a step reads each
  cached row once.

Both give the same numbers up to float32 rounding. Products with the
bfloat16-held ``W_ukv`` are exact (``moe_ops._pieces``), for the reason
``moe_ops`` gives; the cache is attended at ``cache_precision``.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .generation_ops import _largest_divisor
from .moe_ops import _add_pieces, _pieces

_HIGHEST = jax.lax.Precision.HIGHEST


def exact_einsum(spec, x, w):
    """``jnp.einsum(spec, x, w)`` for float32 ``x`` whose leading axis is
    in the result, every product exact: a bfloat16 ``w`` meets x's three
    pieces, stacked along that axis, in one pass."""
    if w.dtype != jnp.bfloat16:
        return jnp.einsum(spec, x.astype(jnp.float32),
                          w.astype(jnp.float32), precision=_HIGHEST)
    y = jnp.einsum(spec, jnp.concatenate(_pieces(x), axis=0), w,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.DEFAULT)
    return _add_pieces(y.reshape((3, x.shape[0]) + y.shape[1:]))


def _heads(ctx, w):
    """(H, nope, rope, v, W_ukv as [kv_rank, H, nope + v])."""
    nh, nope, rope, dv = (ctx.attr("num_heads"), ctx.attr("nope_dim"),
                          ctx.attr("rope_dim"), ctx.attr("v_dim"))
    return nh, nope, rope, dv, w.reshape(w.shape[0], nh, nope + dv)


def expanded_attention(q, c, kr, w3, nope, scale, r):
    """One sequence: q [T, H, nope + rope], c [T, kv_rank], kr [T, rope],
    w3 [kv_rank, H, nope + v] -> [T, H, v]. ``r`` rows at a time against
    the chunks of ``r`` rows at or before them."""
    t, nh, _ = q.shape
    with jax.named_scope("mla_expand_kv"):
        kv = exact_einsum("tc,chd->thd", c, w3)         # [T, H, nope + v]
    kn = kv[..., :nope].transpose(1, 0, 2).reshape(nh, t // r, r, nope)
    v = kv[..., nope:].transpose(1, 0, 2).reshape(nh, t // r, r, -1)
    qh = q.transpose(1, 0, 2).reshape(nh, t // r, r, -1)
    krc = kr.reshape(t // r, r, -1)
    offs = jnp.arange(r, dtype=jnp.int32)

    def block(b):
        qn, qr = qh[:, b, :, :nope], qh[:, b, :, nope:]

        def chunk(j, carry):
            m, l, acc = carry
            s = jnp.einsum("hqd,hkd->hqk", qn, kn[:, j], precision=_HIGHEST,
                           preferred_element_type=jnp.float32) + \
                jnp.einsum("hqd,kd->hqk", qr, krc[j], precision=_HIGHEST,
                           preferred_element_type=jnp.float32)
            mask = (j * r + offs)[None, :] <= (b * r + offs)[:, None]
            s = jnp.where(mask, s * scale, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            prob = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            pv = jnp.einsum("hqk,hkd->hqd", prob, v[:, j],
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
            return (m_new, alpha * l + jnp.sum(prob, -1, keepdims=True),
                    alpha * acc + pv)

        _, l, acc = jax.lax.fori_loop(
            0, b + 1, chunk,
            (jnp.full((nh, r, 1), -1e30, jnp.float32),
             jnp.zeros((nh, r, 1), jnp.float32),
             jnp.zeros((nh, r, v.shape[-1]), jnp.float32)))
        return acc / jnp.maximum(l, 1e-30)               # [H, r, v]

    out = jax.lax.map(block, jnp.arange(t // r, dtype=jnp.int32))
    return out.transpose(0, 2, 1, 3).reshape(t, nh, -1)


@register_op("mla_attention")
def _mla_attention(ctx):
    """Q [B, T, H*(nope + rope)] (rotated), C [B, T, kv_rank] (normed),
    KRope [B, T, rope] (rotated), WUKV [kv_rank, H*(nope + v)]; attrs
    num_heads, nope_dim, rope_dim, v_dim, scale, block_rows. Out
    [B, T, H*v] float32: row i attends rows [0, i] of its own sequence,
    through keys and values expanded from the sequence's latents."""
    q, c, kr = ctx.input("Q"), ctx.input("C"), ctx.input("KRope")
    nh, nope, rope, dv, w3 = _heads(ctx, ctx.input("WUKV"))
    b, t, _ = q.shape
    r = _largest_divisor(t, ctx.attr("block_rows") or t)
    out = jax.vmap(lambda q1, c1, k1: expanded_attention(
        q1.reshape(t, nh, nope + rope), c1, k1, w3, nope,
        ctx.attr("scale"), r))(q.astype(jnp.float32),
                               c.astype(jnp.float32),
                               kr.astype(jnp.float32))
    return {"Out": out.reshape(b, t, nh * dv)}


@register_op("mla_attention_decode_paged")
def _mla_attention_decode_paged(ctx):
    """Q [S, 1, H*(nope + rope)] (rotated), Cache [NB, BS, W] the paged
    latent pool (a row is ``(c, k_r)`` and zeros up to W, whole lane
    tiles), Pos [S] (the row each slot's token was just written to),
    Table [S, MB], WUKV [kv_rank, H*(nope + v)]; attrs as
    ``mla_attention``'s. Out [S, 1, H*v] float32: each slot's query
    attends its cached rows [0, Pos[s]], absorbed (the module's
    docstring). ``flash_attention`` routes to the Pallas kernel; the XLA
    fallback gathers the same rows."""
    q, pool = ctx.input("Q"), ctx.input("Cache")
    nh, nope, rope, dv, w3 = _heads(ctx, ctx.input("WUKV"))
    rank = w3.shape[0]
    s = q.shape[0]
    length = ctx.input("Pos").reshape(-1).astype(jnp.int32) + 1
    qh = q.astype(jnp.float32).reshape(s, nh, nope + rope)
    with jax.named_scope("mla_absorb_q"):
        q_lat = exact_einsum("shd,chd->shc", qh[..., :nope],
                             w3[..., :nope])            # [S, H, kv_rank]
    # a query as wide as a cached row: (q~, q_rope, zeros)
    q_row = jnp.concatenate(
        [q_lat, qh[..., nope:],
         jnp.zeros((s, nh, pool.shape[2] - rank - rope), jnp.float32)], -1)
    args = (q_row.reshape(s, 1, -1), pool, None, length, ctx.input("Table"),
            nh)
    kw = dict(num_kv_heads=1, v_width=rank, scale=ctx.attr("scale"))

    from .. import config as _config
    if _config.get_flag("flash_attention"):
        from .pallas_attention import decode_attention_paged
        o_lat = decode_attention_paged(*args, **kw)
    else:
        from .pallas_attention import _decode_paged_reference
        o_lat = _decode_paged_reference(*args, **kw)
    with jax.named_scope("mla_absorb_o"):
        out = exact_einsum("shc,chd->shd", o_lat.reshape(s, nh, rank),
                           w3[..., nope:])              # [S, H, v]
    return {"Out": out.reshape(s, 1, nh * dv)}
