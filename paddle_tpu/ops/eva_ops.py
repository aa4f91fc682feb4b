"""EVA attention (Zheng et al., *Efficient Attention via Control
Variates*, arXiv:2302.04542, as the EvaByte models use it): a query attends
the keys of **its own aligned window** of ``window`` positions exactly and,
for every chunk of ``chunk`` positions of the **earlier** windows, ONE
learned summary key and value, all under one softmax. Per head, with the
learned ``mu`` and ``phi`` [D]::

    chunk c = positions [C c, C c + C):
      kbar_c = sum_j softmax_j(mu  . k_j) k_j
      vbar_c = sum_j softmax_j(phi . k_j) v_j     # weights from the KEYS
    query i attends  { j : j // W == i // W, j <= i }  and
                     { c : C (c + 1) <= W (i // W) }

A window is a whole number of chunks, so a summary is of a complete chunk
and a query never sees one of its own window: within the first ``window``
positions this is causal softmax attention.

* ``eva_summaries`` — the two poolings, of a sequence's rows (every whole
  chunk of it) or, for a decode step, of the window pool's block that holds
  each slot's newest row. The pooling is a function of the chunk's rows
  alone, so a step that runs twice writes the same summary twice. The
  rows are pooled as they are held, ``[.., chunk, H*D]``, never split into
  heads (``pool_chunks``).
* ``eva_attention`` — whole sequences and a prompt's prefill, as two
  attentions merged under one softmax, like the decode form: every window
  is a causal sequence of its own, so the windows go through the flash
  forward as its batch (``pallas_attention.flash_attention_stats``: the
  float32 result and each row's log-sum-exp), window ``n`` attends the
  ``n window / chunk`` summaries before it in plain XLA (no mask: all of
  them lie behind every row of the window), and ``merge_walks``
  normalises once (``flash_windowed_attention``). That form is taken
  where ``flash_attention`` is set and the kernel can tile a window
  (whole lane tiles of rows); anything else takes ``windowed_attention``,
  the XLA form: 256 queries at a time against their window's rows and
  every summary, the causal half and the summaries not yet behind the
  window computed and masked.
* ``eva_attention_decode_paged`` — one query a slot over two paged pools,
  the window's rows and the summaries: two walks of
  ``pallas_attention.decode_attention_paged`` (the window's aligned, the
  summaries' up to the window's edge), each with its maximum and sum, and
  one normalisation (``merge_walks``).

Scores, softmaxes and sums are float32 whatever flows in; operands go to
the MXU as they arrive (bfloat16 under ``amp``), float32 ones at the
highest precision, but for the flash forward, which multiplies at the
MXU's default precision as it does for ``multihead_attention``.
"""

import numpy as np

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from . import kernel_path, pallas_attention as _pa
from .generation_ops import _largest_divisor
from .moe_ops import _pieces
from .pallas_attention import cache_precision

# queries the XLA whole-sequence form takes at a time: float32 scores of
# [H, 256, window + summaries] are 84 MB at the published sizes
_BLOCK_ROWS = 256


def pool_chunks(k, v, mu, phi, num_heads, chunk):
    """k, v [.., T, H*D] (rows as they are held, T a whole number of
    chunks), mu, phi [H*D] -> (kbar, vbar) [.., T / chunk, H*D] float32:
    per head and chunk the rows' sums under the softmaxes of ``mu . k``
    and ``phi . k`` over the chunk, unscaled.

    The rows are never split into heads, nor reshaped before they are
    read: on the chip ``[.., C, H*D] -> [.., C, H, D]`` moves the tiled
    pair of dimensions from (rows, lanes) to (heads, lanes), a copy of
    every row, in float32 once the rows were made float32 for it (2.0 ms a
    layer at 8,192 positions: PERF.md, PR 44). What is per head goes
    through the ``[H*D, H]`` indicator of the heads' lanes instead: both
    poolings' scores are ONE product of the rows with the indicator's
    columns weighed by ``mu`` and by ``phi`` (``[.., T, 2H]``; the
    operands as they arrive, float32 ones at the highest precision,
    float32 sums), the softmaxes run over each chunk's rows, and the
    weights go back over their head's lanes by the indicator's transpose,
    exactly: a float32 weight is three bfloat16 pieces that add up to it
    (``moe_ops._pieces``), stacked along the product's contraction, each
    times a one. The weighted sum over a chunk's rows is then elementwise
    on the rows as they lie."""
    t, dm = k.shape[-2:]
    chunks = k.shape[:-2] + (t // chunk, chunk)
    heads = jnp.arange(dm, dtype=jnp.int32)[:, None] // (dm // num_heads) \
        == jnp.arange(num_heads, dtype=jnp.int32)[None, :]     # [H*D, H]
    dt = jnp.result_type(k.dtype, mu.dtype)
    both = jnp.concatenate([heads * mu.astype(dt)[:, None],
                            heads * phi.astype(dt)[:, None]], axis=1)
    s = jnp.einsum("...tl,lh->...th", k.astype(dt), both,
                   precision=cache_precision(dt),
                   preferred_element_type=jnp.float32)
    w = jax.nn.softmax(s.reshape(chunks + (2 * num_heads,)), axis=-2) \
        .reshape(s.shape)                           # over a chunk's rows
    ones = jnp.tile(heads.T.astype(jnp.bfloat16), (3, 1))      # [3H, H*D]

    def pooled(w, rows):
        lanes = jnp.einsum("...th,hl->...tl",
                           jnp.concatenate(_pieces(w), axis=-1), ones,
                           precision=jax.lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)
        return jnp.sum((lanes * rows.astype(jnp.float32))
                       .reshape(chunks + (dm,)), axis=-2)
    return pooled(w[..., :num_heads], k), pooled(w[..., num_heads:], v)


@register_op("eva_summaries")
def _eva_summaries(ctx):
    """Mu, Phi [H*D]; attrs num_heads, chunk. Either K, V [B, T, H*D]
    (rotated keys, values) -> KBar, VBar [B, ceil(T / chunk), H*D]: one
    row a chunk (a last, partial chunk is pooled with zero rows: nothing
    attends it, and a prefill writes whole chunks only). Or CacheK, CacheV
    [NB, BS, H*D] (the window pools, BS = chunk), Pos [S], Table [S, MB]
    -> KBar, VBar [S, 1, H*D]: of the block that holds row Pos[s] (what
    it is worth before the block is full is the writer's to drop). Out in
    the rows' dtype."""
    nh, c = ctx.attr("num_heads"), ctx.attr("chunk")
    mu, phi = ctx.input("Mu").reshape(-1), ctx.input("Phi").reshape(-1)
    with jax.named_scope("eva.summaries"):
        if ctx.has_input("K"):
            k, v = ctx.input("K"), ctx.input("V")
            b, t, dm = k.shape
            n = -(-t // c)
            whole = ((0, 0), (0, n * c - t), (0, 0))
            kbar, vbar = pool_chunks(jnp.pad(k, whole), jnp.pad(v, whole),
                                     mu, phi, nh, c)
        else:
            ck, cv = ctx.input("CacheK"), ctx.input("CacheV")
            nb, bs, dm = ck.shape
            if bs != c:
                raise ValueError("a block of the window pool is one chunk: "
                                 "%d rows a block, chunks of %d" % (bs, c))
            pos = ctx.input("Pos").reshape(-1).astype(jnp.int32)
            table = ctx.input("Table").astype(jnp.int32)
            s = pos.shape[0]
            blk = jnp.clip(table[jnp.arange(s), jnp.clip(
                pos // bs, 0, table.shape[1] - 1)], 0, nb - 1)
            k = ck[blk]
            kbar, vbar = pool_chunks(k, cv[blk], mu, phi, nh, c)
    return {"KBar": kbar.astype(k.dtype), "VBar": vbar.astype(k.dtype)}


def windowed_attention(q, k, v, kbar, vbar, window, chunk, scale, r):
    """One sequence: q, k, v [T, H, D], kbar, vbar [ceil(T / chunk), H,
    D] -> [T, H, D] float32. T is at most one window or a whole number of
    them; ``r`` queries at a time against their window's rows (causal) and the
    summaries of the windows before it, one softmax over both."""
    t, nh, hd = q.shape
    w = min(window, t)
    prec = cache_precision(k.dtype)
    cols = jnp.arange(w, dtype=jnp.int32)
    chunks = jnp.arange(kbar.shape[0], dtype=jnp.int32)
    offs = jnp.arange(r, dtype=jnp.int32)

    def scores(qb, keys):
        return jnp.einsum("qhd,khd->hqk", qb, keys, precision=prec,
                          preferred_element_type=jnp.float32) * scale

    def block(b):
        lo = b * r // w * w                 # the window's first position
        qb = jax.lax.dynamic_slice_in_dim(q, b * r, r)
        kw = jax.lax.dynamic_slice_in_dim(k, lo, w)
        vw = jax.lax.dynamic_slice_in_dim(v, lo, w)
        seen = (lo + cols)[None, :] <= (b * r + offs)[:, None]   # [r, w]
        behind = jnp.broadcast_to((chunks + 1) * chunk <= lo,
                                  (r, chunks.shape[0]))
        mask = jnp.concatenate([seen, behind], axis=1)[None]
        s = jnp.where(mask, jnp.concatenate(
            [scores(qb, kw), scores(qb, kbar)], axis=-1), -1e30)
        p = jnp.where(mask, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        p = (p / jnp.sum(p, -1, keepdims=True)).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p[..., :w], vw, precision=prec,
                          preferred_element_type=jnp.float32) + \
            jnp.einsum("hqk,khd->qhd", p[..., w:], vbar, precision=prec,
                       preferred_element_type=jnp.float32)

    out = jax.lax.map(block, jnp.arange(t // r, dtype=jnp.int32))
    return out.reshape(t, nh, hd)


def flash_windowed_attention(q, k, v, kbar, vbar, window, chunk):
    """Sequences: q, k, v [B, T, H, D], kbar, vbar [B, ceil(T / chunk), H,
    D] -> [B, T, H, D] float32, or None where the flash forward cannot
    tile a window (``flash_attention_stats``). T is at most one window or
    a whole number of them. What ``windowed_attention`` computes, as two
    parts under one softmax: the windows, each a causal sequence of the
    kernel's batch, and for window ``n >= 1`` its rows against the
    summaries of the windows before it, all of them seen, scores (scaled
    ``D^-1/2``, as the kernel's) and sums float32, one static step a window
    (the scores of a step are ``[B, H, window, n window / chunk]``: 100 MB
    at the last window of 8,192 positions, where all rows against all
    summaries would be 403)."""
    b, t, nh, hd = q.shape
    w = min(window, t)
    nw = t // w

    def heads_first(x):                 # [B, T, H, D] -> [B nW H, W, D]
        return x.reshape(b, nw, w, nh, hd).transpose(0, 1, 3, 2, 4) \
            .reshape(b * nw * nh, w, hd)
    qh = heads_first(q)
    part = _pa.flash_attention_stats(qh, heads_first(k), heads_first(v),
                                     causal=True)
    if part is None:
        return None
    prec = cache_precision(k.dtype)
    o, lse = (x.reshape(b, nw, nh * w, -1) for x in part)
    qh = qh.reshape(b, nw, nh, w, hd)
    rows = b * nh * w       # a row of a head is a walk's slot: [rows, 1, D]
    out = [o[:, 0]]
    for n in range(1, nw):              # window n: its n w / chunk summaries
        kb, vb = kbar[:, :n * w // chunk], vbar[:, :n * w // chunk]
        s = jnp.einsum("bhqd,bkhd->bhqk", qh[:, n], kb, precision=prec,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        m = jnp.max(s, -1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, -1, keepdims=True)
        summed = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
                            precision=prec,
                            preferred_element_type=jnp.float32) / l
        exact = lse[:, n].reshape(rows, 1, 1)
        out.append(_pa.merge_walks(
            [(o[:, n].reshape(rows, 1, hd), exact, jnp.ones_like(exact)),
             (summed.reshape(rows, 1, hd), m.reshape(rows, 1, 1),
              l.reshape(rows, 1, 1))], 1))
    return jnp.concatenate(
        [x.reshape(b, nh, w, hd).transpose(0, 2, 1, 3) for x in out], 1)


def _eva_attention_shape(op, block):
    """Out is Q's shape in float32: said, not traced, so that the kernel
    path counters count the forms that are compiled and a program that is
    built and never run (a startup's whole-sequence model) counts none."""
    q, out = (block.var_or_none(n) for n in (op.input("Q"), op.output("Out")))
    if op.attrs["window"] % op.attrs["chunk"]:
        raise ValueError("a window of %d is no whole number of chunks of %d"
                         % (op.attrs["window"], op.attrs["chunk"]))
    if q is not None and q.shape is not None and out is not None:
        out.shape, out.dtype = tuple(q.shape), np.dtype("float32")


@register_op("eva_attention", infer_shape=_eva_attention_shape)
def _eva_attention(ctx):
    """Q, K, V [B, T, H*D] (rotated), KBar, VBar [B, ceil(T / chunk),
    H*D] (``eva_summaries`` of the same rows); attrs num_heads, window,
    chunk. Out [B, T, H*D] float32, scores scaled ``D^-1/2``: row i
    attends its own window's rows up to itself and the summaries of every
    chunk of the windows before it. A T that is longer than a window and
    no whole number of them is padded to one (the padding lies after every
    real row, so none attends it). ``flash_attention`` routes the windows
    to the Pallas flash forward where it can tile one
    (``flash_windowed_attention``); the XLA form (``windowed_attention``)
    computes the same."""
    from .. import config as _config
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    kbar, vbar = ctx.input("KBar"), ctx.input("VBar")
    nh, w, c = ctx.attr("num_heads"), ctx.attr("window"), ctx.attr("chunk")
    b, t, dm = q.shape
    hd = dm // nh
    tp = t if t <= w else -(-t // w) * w

    def rows(x, n):
        x = jnp.pad(x, ((0, 0), (0, n - x.shape[1]), (0, 0)))
        return x.reshape(b, n, nh, hd)
    args = (rows(q, tp), rows(k, tp), rows(v, tp),
            rows(kbar, -(-tp // c)), rows(vbar, -(-tp // c)))
    with jax.named_scope("eva.prefill"):
        out = None
        if _config.get_flag("flash_attention"):
            out = flash_windowed_attention(*args, w, c)
            if out is None:
                kernel_path.record("flash_attention")   # armed, not taken
        if out is None:
            r = _largest_divisor(min(w, tp), _BLOCK_ROWS)
            out = jax.vmap(lambda *a: windowed_attention(
                *a, w, c, hd ** -0.5, r))(*args)
    return {"Out": out[:, :t].reshape(b, t, dm)}


@register_op("eva_attention_decode_paged")
def _eva_attention_decode_paged(ctx):
    """Q [S, 1, H*D] (rotated), CacheK/CacheV [NB, BS, H*D] (the window
    pools, the step's row already appended), ChunkK/ChunkV [NBc, BSc, H*D]
    (the summaries, one row a chunk), Pos [S] (the query's position), Table
    [S, MB], ChunkTable [S, MBc]; attrs num_heads, window, chunk. Out
    [S, 1, H*D] float32, scores scaled ``D^-1/2``: slot s's query attends
    the window pool's rows ``[window (Pos // window), Pos]`` and the chunk
    pool's rows ``[0, window (Pos // window) / chunk)`` under one softmax.
    ``flash_attention`` routes both walks to the Pallas kernel; the XLA
    fallback gathers the same rows."""
    from .. import config as _config
    q = ctx.input("Q")
    pos = ctx.input("Pos").reshape(-1).astype(jnp.int32)
    nh, w, c = ctx.attr("num_heads"), ctx.attr("window"), ctx.attr("chunk")
    walk = _pa.decode_attention_paged if _config.get_flag("flash_attention") \
        else _pa._decode_paged_reference
    with jax.named_scope("eva.decode"):
        exact = walk(q, ctx.input("CacheK"), ctx.input("CacheV"), pos + 1,
                     ctx.input("Table"), nh, window=w, aligned=True,
                     stats=True)
        summed = walk(q, ctx.input("ChunkK"), ctx.input("ChunkV"),
                      pos // w * (w // c), ctx.input("ChunkTable"), nh,
                      stats=True)
        return {"Out": _pa.merge_walks([exact, summed], nh)}
