"""Autoregressive-generation ops: the on-device KV cache, a pool of
blocks, + single-query decode attention.

The reference generated through RecurrentGradientMachine's per-step
kernel dispatch; the fluid-era answer (and transformer_lm_generate's
reference path) re-encodes the full token history every step — O(L^2)
per sequence. These ops are the state-layout change that makes decode
O(L): per-layer K/V caches live in the Scope as persistable
[num_blocks, block_size, d_model] pools, each step writes one row per
sequence in place (a scatter under executor donation, so the update
never copies the cache in HBM) and attends a single query row against
the live prefix. A sequence's logical position p lives at pool row
``table[p // block_size] * block_size + p % block_size`` where
``table`` is its host-side block table (serving/paged_cache.py).

* ``kv_cache_write_paged``  — prefill a token WINDOW: rows of the
  window land at positions [Hist, Hist+Len) through the table (the
  prefix-cache suffix prefill: Hist > 0 means the first Hist
  positions are already cached, shared from another sequence).
* ``kv_cache_append_paged`` — decode: one row per slot through its
  own table row; dead table entries (>= num_blocks) DROP the write
  (inactive/starved slots can't scribble on blocks they don't own).
* ``multihead_attention_decode_paged`` / the prefill variant — one
  query token per slot (or a window's rows) against the cache under a
  per-slot length mask, with K/V gathered through
  the table: the Pallas kernel (``decode_attention_paged``: one
  program per slot walks that slot's live pages, all heads at once)
  when ``flash_attention`` is on, an XLA gather sharing identical
  semantics otherwise — the flag never changes tokens.
  Latent attention's two paths over a latent pool (one row a token, key
  and value at once) are in ``mla_ops``; its rows are written by the two
  write ops above, a width being all that differs.
* ``kv_block_copy`` — one block pool-to-pool (copy-on-write: a
  sequence about to write into a shared block copies it first).

All writes keep the donation contract: Out aliases the pool variable
name, the scatter/dynamic_update_slice lands in place in HBM.

All shapes here are static (slots and cache_len are compile-time
bucket sizes; block tables are fixed-width feeds padded with dead
entries): the executor compile cache sees exactly one decode entry per
(slot-bucket, cache-bucket) pair and one prefill entry per prompt
bucket, plus one block-copy program — the shape set stays closed.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register_op


@register_op("kv_cache_write_paged")
def _kv_cache_write_paged(ctx):
    """Cache [NB, BS, D] pool, New [1, T, D], Table [MB] int, Hist [1]
    int, Len [1] int -> Out = pool with New's rows i in [0, Len)
    written at logical positions Hist+i through Table. Rows at or past
    Len scatter out of bounds and DROP (window padding never lands);
    Out aliases the pool variable, so the donated state update keeps
    the scatter in place. With attr ``chunk`` a row of the pool (and of
    New) stands for that many positions: Hist and Len, given in
    positions, count whole chunks."""
    pool = ctx.input("Cache")
    new = ctx.input("New")
    table = ctx.input("Table").reshape(-1).astype(jnp.int32)
    chunk = ctx.attr("chunk") or 1
    hist = ctx.input("Hist").reshape(-1)[0].astype(jnp.int32) // chunk
    ln = ctx.input("Len").reshape(-1)[0].astype(jnp.int32) // chunk
    nb, bs, d = pool.shape
    t = new.shape[1]
    idx = jnp.arange(t, dtype=jnp.int32)
    pos = hist + idx
    blk = table[jnp.clip(pos // bs, 0, table.shape[0] - 1)]
    rows = blk * bs + pos % bs
    rows = jnp.where(idx < ln, rows, nb * bs)   # padding -> dropped
    flat = pool.reshape(nb * bs, d)
    flat = flat.at[rows].set(new[0].astype(pool.dtype), mode="drop")
    return {"Out": flat.reshape(nb, bs, d)}


@register_op("kv_cache_append_paged")
def _kv_cache_append_paged(ctx):
    """Cache [NB, BS, D] pool, New [S, 1, D], Pos [S] int, Table
    [S, MB] int -> Out = pool with slot s's row written at its
    table-mapped position. A dead table entry (>= NB — how the host
    marks inactive or pool-starved slots) pushes the scatter out of
    bounds, so the write DROPS instead of corrupting a block another
    sequence owns. With attr ``chunk`` a row of the pool stands for that
    many positions: slot s's row is ``Pos[s] // chunk``, and it is
    written only by the chunk's last position (else the write drops)."""
    pool = ctx.input("Cache")
    new = ctx.input("New")
    pos = ctx.input("Pos").reshape(-1).astype(jnp.int32)
    table = ctx.input("Table").astype(jnp.int32)
    nb, bs, d = pool.shape
    s = new.shape[0]
    chunk = ctx.attr("chunk") or 1
    last, pos = pos % chunk == chunk - 1, pos // chunk
    bi = jnp.clip(pos // bs, 0, table.shape[1] - 1)
    blk = jnp.where(last, table[jnp.arange(s), bi], nb)
    rows = blk * bs + pos % bs       # blk >= NB -> out of bounds
    flat = pool.reshape(nb * bs, d)
    flat = flat.at[rows].set(new[:, 0, :].astype(pool.dtype),
                             mode="drop")
    return {"Out": flat.reshape(nb, bs, d)}


@register_op("kv_block_copy")
def _kv_block_copy(ctx):
    """Cache [NB, BS, D] pool, Src [1] int, Dst [1] int -> Out = pool
    with block Dst overwritten by block Src — the copy-on-write
    primitive: a sequence about to write into a shared block copies it
    into a fresh one first, so co-resident sequences never see each
    other's writes. In place via donation like every cache op."""
    pool = ctx.input("Cache")
    src = ctx.input("Src").reshape(-1)[0].astype(jnp.int32)
    dst = ctx.input("Dst").reshape(-1)[0].astype(jnp.int32)
    _, bs, d = pool.shape
    zero = jnp.int32(0)
    blk = jax.lax.dynamic_slice(pool, (src, zero, zero), (1, bs, d))
    return {"Out": jax.lax.dynamic_update_slice(pool, blk,
                                                (dst, zero, zero))}


@register_op("multihead_attention_decode_paged")
def _multihead_attention_decode_paged(ctx):
    """Q [S, 1, H*D], CacheK/CacheV [NB, BS, Hkv*D] pools, Pos [S] int
    (the row each slot's new token was just written to), Table [S, MB]
    int; attrs num_heads, and where the model has them num_kv_heads
    (heads the pools hold; absent: num_heads), window (absent: none) and
    scale (the scores' factor; absent: ``D^-1/2``).
    Out [S, 1, H*D]: each slot's single query
    attends its table-gathered cache rows [0, Pos[s]], with a window the
    last ``window`` of them (token parity with the O(L^2) reference
    path is a test invariant). ``flash_attention`` routes to the Pallas kernel that
    walks each slot's live pages; the XLA fallback gathers the same
    rows densely. A slot whose table row is dead gets zeros from the
    kernel and clamped rows from the gather: nobody reads either."""
    q = ctx.input("Q")
    ck = ctx.input("CacheK")
    cv = ctx.input("CacheV")
    length = ctx.input("Pos").reshape(-1).astype(jnp.int32) + 1
    table = ctx.input("Table")
    nh = ctx.attr("num_heads")
    nkv, window = ctx.attr("num_kv_heads"), ctx.attr("window")

    from .. import config as _config
    if _config.get_flag("flash_attention"):
        from .pallas_attention import decode_attention_paged
        return {"Out": decode_attention_paged(
            q, ck, cv, length, table, nh, num_kv_heads=nkv,
            window=window, scale=ctx.attr("scale"))}
    from .pallas_attention import _decode_paged_reference
    return {"Out": _decode_paged_reference(q, ck, cv, length, table,
                                           nh, nkv, window,
                                           scale=ctx.attr("scale"))}


def _largest_divisor(n, cap):
    return next(r for r in range(min(n, cap), 0, -1) if n % r == 0)


def _prefill_paged_dense(q, ck, cv, table, hist, nh):
    """Every row against the whole table at once: float32 scores
    ``[H, P, MB*BS]``."""
    _, p, dm = q.shape
    nb, bs, _ = ck.shape
    c = table.shape[0] * bs
    hd = dm // nh
    tbl = jnp.clip(table, 0, nb - 1)
    k = ck[tbl].reshape(c, dm)
    v = cv[tbl].reshape(c, dm)
    qh = q.reshape(p, nh, hd).transpose(1, 0, 2)        # [H, P, hd]
    kh = k.reshape(c, nh, hd).transpose(1, 0, 2)        # [H, C, hd]
    vh = v.reshape(c, nh, hd).transpose(1, 0, 2)
    s = jnp.einsum("hqd,hkd->hqk", qh, kh,
                   preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5)
    cols = jnp.arange(c, dtype=jnp.int32)
    rows = hist + jnp.arange(p, dtype=jnp.int32)
    mask = cols[None, None, :] <= rows[None, :, None]
    s = jnp.where(mask, s, -1e30)
    prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("hqk,hkd->hqd", prob, vh)
    return out.transpose(1, 0, 2).reshape(1, p, dm)


def _prefill_paged_blocked(q, ck, cv, table, hist, nh, nkv, window, r,
                           scale=None):
    """``r`` rows at a time, one block after the other, each against the
    chunks of ``r`` cached rows it can see and no others: from the chunk
    that holds the first row inside the window (or row 0) to the chunk
    that holds the block's last row, with a running softmax over them
    (float32 maximum, sum and accumulator, as the kernels keep them). The
    scores are ``[H, r, r]`` whatever the prompt and the cache. On a
    float32 cache the products are exact and the weights stay float32."""
    from .pallas_attention import cache_precision
    prec = cache_precision(ck.dtype)
    _, p, dm = q.shape
    nb, bs, _ = ck.shape
    hd, group = dm // nh, nh // nkv
    scale = hd ** -0.5 if scale is None else scale
    pages = -(-r // bs)                     # pages a chunk
    c = pages * bs
    mb = table.shape[0]
    n_chunks = -(-mb // pages)
    # dead entries, and the padding to whole chunks, gather block 0: their
    # rows lie behind the window or beyond every row's own position
    tbl = jnp.zeros(n_chunks * pages, jnp.int32).at[:mb].set(
        jnp.where(table < nb, table, 0))
    # [blocks, Hkv, G*r, hd]: a KV head's query heads side by side
    qh = q.reshape(p // r, r, nkv, group, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(p // r, nkv, group * r, hd)
    offs = jnp.tile(jnp.arange(r, dtype=jnp.int32), group)

    def block(args):
        b, qb = args
        rows = hist + b * r + offs                          # [G*r]
        lo = 0 if window is None else \
            jnp.maximum(hist + b * r - window + 1, 0) // c
        hi = (hist + b * r + r - 1) // c + 1

        def chunk(j, carry):
            m, l, acc = carry
            ids = jax.lax.dynamic_slice(tbl, (j * pages,), (pages,))
            kh = ck[ids].reshape(c, nkv, hd).transpose(1, 0, 2)
            vh = cv[ids].reshape(c, nkv, hd).transpose(1, 0, 2)
            s = jnp.einsum("hqd,hkd->hqk", qb, kh, precision=prec,
                           preferred_element_type=jnp.float32)
            s = s * scale
            cols = j * c + jnp.arange(c, dtype=jnp.int32)
            mask = cols[None, :] <= rows[:, None]
            if window is not None:
                mask = mask & (rows[:, None] - cols[None, :] < window)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            prob = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            pv = jnp.einsum("hqk,hkd->hqd", prob.astype(q.dtype), vh,
                            precision=prec,
                            preferred_element_type=jnp.float32)
            return (m_new, alpha * l + jnp.sum(prob, -1, keepdims=True),
                    alpha * acc + pv)

        _, l, acc = jax.lax.fori_loop(
            lo, jnp.minimum(hi, n_chunks), chunk,
            (jnp.full((nkv, group * r, 1), -1e30, jnp.float32),
             jnp.zeros((nkv, group * r, 1), jnp.float32),
             jnp.zeros((nkv, group * r, hd), jnp.float32)))
        out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
        return out.reshape(nkv, group, r, hd).transpose(2, 0, 1, 3) \
            .reshape(r, dm)

    out = jax.lax.map(block, (jnp.arange(p // r, dtype=jnp.int32), qh))
    return out.reshape(1, p, dm)


@register_op("multihead_attention_prefill_paged")
def _multihead_attention_prefill_paged(ctx):
    """Q [1, P, H*D] (a prompt-suffix window whose K/V rows were just
    written through the table), CacheK/CacheV [NB, BS, Hkv*D] pools,
    Table [MB] int, Hist [1] int, Len [1] int; attrs num_heads, and
    where the model has them num_kv_heads (absent: num_heads), window
    (absent: none), block_rows (absent: every row at once) and, with
    block_rows, scale (the scores' factor; absent: ``D^-1/2``).
    Out [1, P, H*D]: window row i (logical position Hist+i) attends
    table-gathered cache rows [0, Hist+i], with a window the last
    ``window`` of them — causal over the cached
    prefix PLUS the window itself, which is what lets a shared-prefix
    admission prefill only its unshared suffix. Rows at or past Len
    are padding: they compute garbage that is neither fetched nor
    written (the paged write op drops their K/V), and real rows never
    attend them (their positions are beyond every real row's mask).
    With ``block_rows`` the rows are taken that many at a time against
    the chunks of the cache they can see (``_prefill_paged_blocked``), so
    that neither the temporaries nor the work grow as P x cache.
    Dense XLA only — this runs once per admission, not per step; the
    per-step Pallas path is the decode op."""
    q = ctx.input("Q")
    ck = ctx.input("CacheK")
    cv = ctx.input("CacheV")
    table = ctx.input("Table").reshape(-1).astype(jnp.int32)
    hist = ctx.input("Hist").reshape(-1)[0].astype(jnp.int32)
    nh = ctx.attr("num_heads")
    if not ctx.attr("block_rows"):
        if ctx.attr("num_kv_heads") or ctx.attr("window"):
            raise ValueError("grouped queries and a window need block_rows")
        return {"Out": _prefill_paged_dense(q, ck, cv, table, hist, nh)}
    return {"Out": _prefill_paged_blocked(
        q, ck, cv, table, hist, nh, ctx.attr("num_kv_heads") or nh,
        ctx.attr("window"),
        _largest_divisor(q.shape[1], ctx.attr("block_rows")),
        ctx.attr("scale"))}
