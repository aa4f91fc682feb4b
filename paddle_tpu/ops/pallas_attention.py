"""Pallas flash attention — the hand-scheduled TPU kernel for the one
op where XLA's default schedule materializes an O(T^2) intermediate.

The fused kernel streams K/V from VMEM against one Q block at a time:
scores, causal mask, softmax, and the P@V contraction all happen
on-chip, so the [T, T] probability matrix never exists in HBM (the XLA
fallback in ops/attention_ops.py writes it out between the two
einsums). Both kernels hand the MXU their operands in the dtype the
model gives them (bfloat16 under amp; float32 inputs stay float32) and
round the probabilities to it where they enter a product, as
``_reference`` does; scores, statistics and sums are float32. A causal
tile wholly above the diagonal does no arithmetic and fetches nothing:
its index map stays on a block that is in VMEM already. The forward
takes tiles of up to 1,024 x 1,024 rows (``_tiles``), the backward of
512 x 512. Forward and backward are Pallas kernels under
jax.custom_vjp: the forward under differentiation also keeps the
log-sum-exp of every query row ([BH, 1, T] float32), and the backward
(``flash_attention_bwd``) rebuilds the probabilities from it a
(k-block, q-block) tile at a time in VMEM, so no [.., T] array is
written in either direction; residuals are q, k, v, the output and the
row statistics. A length that is not whole lane tiles (T % 128) keeps
q, k, v alone and differentiates the XLA reference.

Used by the multihead_attention op when the ``flash_attention`` config
flag is on (interpret mode on CPU keeps it testable everywhere);
`/opt`-guide tiling notes: blocks keep the last dim = head_dim and
block_q rows per grid step.

Decode has a single-query kernel beside it.
``decode_attention_paged`` reads the block pool of the KV cache
with one program per slot: the pools stay in HBM, the program walks
the slot's live pages, fetches each whole (all heads) by hand-issued,
double-buffered copies and attends all heads at once, so a decode step
costs what its live context costs.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_path

_NEG = -1e30


def _reference(q, k, v, causal, seg=None):
    """Plain jnp attention over [BH, T, D] (ragged lengths, both ways).
    seg: [BH, T] int32 segment ids, 0 = padding — a key is attendable
    by a query iff their ids match and the key's id is nonzero (covers
    both padding masks and packed-sequence masks, SURVEY §5.7)."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    t = q.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None], s, _NEG)
    if seg is not None:
        m = (seg[:, :, None] == seg[:, None, :]) & (seg[:, None, :] != 0)
        s = jnp.where(m, s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if seg is not None:
        # fully-masked (padding) query rows: zero output, not uniform
        p = p * (seg != 0)[:, :, None].astype(p.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _mxu(a, b, contract):
    """One MXU product inside a kernel, float32 out. ``contract``: the
    contracted dim of each operand. The explicit Precision: the
    executor's ambient default_matmul_precision('BF16_BF16_F32') is a
    DotAlgorithmPreset that Mosaic's dot lowering rejects; inside the
    kernel the MXU path is already bf16-multiply/f32-acc."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)


_NT, _NN, _TN = (1, 1), (1, 0), (0, 0)     # a @ b.T, a @ b, a.T @ b


def _tile_mask(qi, ki, block_q, block_k, causal, sq_ref, sk_ref,
               keys_first=False):
    """Which (query, key) pairs of tile (qi, ki) attend: bool
    ``[bq, bk]`` (``[bk, bq]`` with ``keys_first``), or None where every
    pair does. sq_ref/sk_ref (optional) carry the FULL [1, 1, T] int32
    segment-id row, 0 = padding (Mosaic needs block dims divisible by
    (8,128) or whole-array; a (1,bq) block is neither) — the window is
    sliced in-kernel; key attendable iff ids match and nonzero."""
    shape = (block_k, block_q) if keys_first else (block_q, block_k)
    qdim = 1 if keys_first else 0
    mask = None
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, qdim)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 - qdim)
        mask = rows >= cols
    if sq_ref is not None:
        sq = sq_ref[0, :, pl.ds(qi * block_q, block_q)]  # [1, bq]
        sk = sk_ref[0, :, pl.ds(ki * block_k, block_k)]  # [1, bk]
        if keys_first:
            sk = sk.reshape(block_k, 1)
        else:
            sq = sq.reshape(block_q, 1)
        seg_mask = (sq == sk) & (sk != 0)
        mask = seg_mask if mask is None else (mask & seg_mask)
    return mask


def _live(qi, ki, block_q, block_k, causal):
    """False for a causal tile wholly above the diagonal: its last query
    row lies before its first key."""
    return (qi * block_q + block_q - 1 >= ki * block_k) if causal else True


def _body(q_ref, k_ref, v_ref, *refs, segmented, with_lse, scale, causal,
          block_q, block_k, nk):
    """One (q-block, k-block) step of flash attention with online
    softmax. The k axis is the innermost (sequential) grid dim, so the
    VMEM scratch (acc, running max m, running sum l) carries across
    k blocks of the same q block. ``refs``: where ``segmented`` the
    segment-id rows sq_ref, sk_ref (the padding / packed-sequence mask,
    :func:`_tile_mask`); o_ref; ``with_lse`` (the forward under
    differentiation) lse_ref, the whole [1, 1, T] float32 row of a
    batch-head: the log-sum-exp of every query row, ``m + log l``, which
    the backward kernel rebuilds the probabilities from; the scratch.
    A step's cost has a part that goes with ``block_q`` alone (the
    float32 max, sum and accumulator ``[bq, 128]`` are read, rescaled and
    written every step), so wide k blocks pay it less often: at
    ``[64, 2048, 128]`` bfloat16 blocks of (1024, 1024) take 0.87 ms
    where (256, 512) take 1.77 (my chip runs, PR 41)."""
    sq_ref, sk_ref = refs[:2] if segmented else (None, None)
    o_ref = refs[2 * segmented]
    lse_ref = refs[2 * segmented + 1] if with_lse else None
    acc_ref, m_ref, l_ref = refs[-3:]
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, _NEG, m_ref.dtype)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_live(qi, ki, block_q, block_k, causal))
    def _step():
        # q, k and v reach the MXU in the one operand dtype _forward
        # gave them, p rounded to it at P V as in _reference; the
        # scores, the statistics and the sums are float32. (Float32
        # operands at Precision.DEFAULT are one bfloat16 pass on the
        # chip too: a float32 copy of bfloat16 tiles gave the same bits
        # and the same time, my chip runs, PR 41.)
        v = v_ref[0]
        s = _mxu(q_ref[0], k_ref[0], _NT) * scale
        mask = _tile_mask(qi, ki, block_q, block_k, causal, sq_ref, sk_ref)
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:]                          # [bq, 128]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        if segmented:
            # a row no key of which attends so far (padding) has
            # m_new = -1e30 and exp gave 1. Under the causal mask alone
            # every row has seen key 0 by its first step, m_new is a
            # score, and exp gave a masked pair exactly 0
            p = jnp.where(mask, p, 0.0)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=-1,
                                              keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + _mxu(
            p.astype(v.dtype), v, _NN)
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        if lse_ref is not None:
            # a column of row statistics [bq, 128] -> lanes [1, bq]
            lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))
            lse_ref[0, :, pl.ds(qi * block_q, block_q)] = lse.T[:1]


def _block_size(t, cap, align=16):
    """Largest divisor of t that is <= cap, >= 128 and ``align``-ed
    (16 covers f32/bf16 sublane tiles; the segmented kernel needs 128 —
    its in-kernel pl.ds slices of the id row must be lane-aligned) —
    avoids silently falling back to the dense path for tileable lengths
    like 768 or 1280, while genuinely ragged lengths (e.g. 100) return
    0 so the caller uses the XLA reference instead of an unaligned
    kernel."""
    if t <= cap:
        return t if t % align == 0 else 0
    for b in range(cap, 127, -1):
        if t % b == 0 and b % align == 0:
            return b
    return 0


def _row_spec(t):
    """A batch-head's whole [1, 1, T] row of per-position numbers (segment
    ids, row statistics), resident across its grid steps: whole rows
    satisfy Mosaic's (8,128)-or-whole-dim tiling rule where a (1, bq)
    block does not, and are sliced in-kernel."""
    return pl.BlockSpec((1, 1, t), lambda b, i, j: (b, 0, 0))


def _seg_rows(seg):
    """[BH, T] segment ids as the kernels take them, twice (the queries'
    and the keys'), and their specs."""
    if seg is None:
        return [], []
    bh, t = seg.shape
    return [seg.reshape(bh, 1, t)] * 2, [_row_spec(t)] * 2


def _k_block(i, j, bq, bk, causal):
    """The k block that grid step (q block ``i``, k block ``j``) of the
    forward names: ``j`` itself for a live tile; a causal tile wholly
    above the diagonal (not :func:`_live`) stays on the q block's last
    live k block, which is in VMEM already, so its step fetches
    nothing. The mirror of the backward's ``q_of``."""
    return jnp.minimum(j, (i * bq + bq - 1) // bk) if causal else j


def _forward(q, k, v, seg, causal, bq, bk, interpret, with_lse=False,
             out_dtype=None):
    """The forward kernel on ``bq`` x ``bk`` rows, operands in the widest of
    their dtypes; ``with_lse``: also every query row's log-sum-exp [BH, 1, T]
    float32 (``bq`` whole lane tiles); ``out_dtype``: o's, default q's."""
    from jax.experimental.pallas import tpu as pltpu
    bh, t, d = q.shape
    segs, seg_specs = _seg_rows(seg)
    out_shape = jax.ShapeDtypeStruct((bh, t, d), out_dtype or q.dtype)
    out_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    if with_lse:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((bh, 1, t), jnp.float32))
        out_spec = (out_spec, _row_spec(t))
    mxu = jnp.promote_types(q.dtype, k.dtype)   # one operand dtype
    q, k, v = (x.astype(mxu) for x in (q, k, v))
    k_spec = pl.BlockSpec(
        (1, bk, d), lambda b, i, j: (b, _k_block(i, j, bq, bk, causal), 0))
    return pl.pallas_call(
        functools.partial(_body, segmented=bool(segs), with_lse=with_lse,
                          scale=d ** -0.5, causal=causal, block_q=bq,
                          block_k=bk, nk=t // bk),
        name="flash_attention_fwd" + ("_seg" if segs else ""),
        grid=(bh, t // bq, t // bk),
        in_specs=[pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  k_spec, k_spec] + seg_specs,
        out_shape=out_shape, out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),     # acc
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum
        ],
        interpret=interpret)(q, k, v, *segs)


# -- the backward: one kernel over the saved row statistics ---------------

def _bwd_body(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
              segmented, scale, causal, block_q, block_k, nq, nk):
    """One (k-block, q-block) tile of the backward: the probabilities
    ``p = exp(s - lse)`` and ``ds = p * (dp - delta)`` are rebuilt in
    VMEM, keys first (``[bk, bq]``), so the row statistics apply as the
    lane rows ``[1, bq]`` they are stored as and dV, dK are plain
    products. The q axis is the innermost grid dim: dK and dV of the k
    block carry across it in ``dk_acc``/``dv_acc``; dQ, which sums over k
    blocks, is kept for the whole batch-head in ``dq_acc`` ``[T, d]`` and
    written out a q block at a time under the last k block. A masked
    pair, and every pair of a fully masked row (whose lse is -1e30),
    gets p = 0 by the select, whatever exp gave. ``refs``: where
    ``segmented`` the segment-id rows, then the three outputs and the
    three sums."""
    sq_ref, sk_ref = refs[:2] if segmented else (None, None)
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[-6:]
    ki, qi = pl.program_id(1), pl.program_id(2)
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(qi == 0)
    def _init_k_block():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)
    def _init_q_block():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    @pl.when(_live(qi, ki, block_q, block_k, causal))
    def _step():
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        p = jnp.exp(_mxu(k, q, _NT) * scale - lse_ref[0, :, rows])
        mask = _tile_mask(qi, ki, block_q, block_k, causal, sq_ref, sk_ref,
                          keys_first=True)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        ds = (p * (_mxu(v_ref[0], do, _NT) - delta_ref[0, :, rows])
              ).astype(q.dtype)
        dv_acc[:] += _mxu(p.astype(do.dtype), do, _NN)
        dk_acc[:] += _mxu(ds, q, _NN)
        dq_acc[rows, :] += _mxu(ds, k, _TN)

    @pl.when(qi == nq - 1)
    def _finish_k_block():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finish_q_block():
        dq_ref[0] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)


# rows of q and of k a backward tile takes, at most (my chip runs, PR 32:
# PERF.md section 7), and the largest [T, d] float32 sum of dQ a
# batch-head may keep in VMEM
_BWD_BLOCK = 512
_BWD_DQ_BYTES = 64 << 20


def _backward(q, k, v, o, lse, seg, do, causal, interpret):
    """(dq, dk, dv) of flash attention from the forward's output and row
    statistics: ``delta = rowsum(dO * O)`` once (XLA), then one kernel on
    the grid (BH, T/bk, T/bq) that writes no [.., T] array. Causal tiles
    wholly above the diagonal do nothing, and the index map holds their q
    blocks at the k block's first live one, so nothing is fetched for
    them."""
    from jax.experimental.pallas import tpu as pltpu
    bh, t, d = q.shape
    mxu = jnp.promote_types(q.dtype, k.dtype)   # one operand dtype
    q, k, v, do = (x.astype(mxu) for x in (q, k, v, do))
    bq = _block_size(t, _BWD_BLOCK, 128)
    bk = _block_size(t, _BWD_BLOCK, 128 if seg is not None else 16)
    nq, nk = t // bq, t // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, None, :]
    segs, seg_specs = _seg_rows(seg)

    def q_of(b, j, i):
        return (b, jnp.maximum(i, (j * bk) // bq) if causal else i, 0)

    q_spec = pl.BlockSpec((1, bq, d), q_of)
    k_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    row = _row_spec(t)
    # the scoped VMEM a kernel gets by default holds a dQ sum of 8 MiB
    # beside the tiles; a longer one asks for its room
    dq_bytes = t * d * 4
    params = pltpu.CompilerParams(
        vmem_limit_bytes=dq_bytes + (16 << 20)) \
        if dq_bytes > (8 << 20) else None
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_body, segmented=bool(segs),
                          scale=d ** -0.5, causal=causal, block_q=bq,
                          block_k=bk, nq=nq, nk=nk),
        name="flash_attention_bwd" + ("_seg" if segs else ""),
        grid=(bh, nk, nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row, row] + seg_specs,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        # dq's block is written under the last k block alone: until then
        # the map stays on block 0, which is not written back before the
        # kernel has filled it
        out_specs=(pl.BlockSpec(
            (1, bq, d),
            lambda b, j, i: (b, jnp.where(j == nk - 1, i, 0), 0)),
            k_spec, k_spec),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret)(q, k, v, do, lse, delta, *segs)
    return dq, dk, dv


# rows of q and of k a forward tile takes, at most, and the bytes of one
# operand tile (1,024 rows of a 128-wide float32 head), which keep a wider
# head's tiles within the VMEM a kernel gets (my chip runs, PR 41:
# CHANGES.md has the table of pairs)
_FWD_BLOCK = 1024
_FWD_TILE_BYTES = 512 << 10


def _tiles(q, k, block_q, segmented, lanes=False):
    """(bq, bk) the forward kernel tiles q, k [BH, T, D] with, or None for
    a ragged length: the largest aligned divisors of T within
    ``_FWD_BLOCK`` rows and ``_FWD_TILE_BYTES`` a tile of the operand
    dtype (``block_q``, where a caller gives one, in place of the q
    rows). ``lanes``: q blocks of whole lane tiles, which the row
    statistics need (they are stored and sliced along lanes)."""
    _, t, d = q.shape
    row = d * jnp.promote_types(q.dtype, k.dtype).itemsize
    cap = min(_FWD_BLOCK, max(128, _FWD_TILE_BYTES // row))
    align = 128 if segmented else 16
    bq = _block_size(t, block_q or cap, 128 if lanes else align)
    bk = _block_size(t, cap, align)
    return (bq, bk) if bq and bk else None


def _primal(q, k, v, seg, causal, block_q, interpret):
    tiles = _tiles(q, k, block_q, seg is not None)
    if tiles is None:
        kernel_path.record("flash_attention")
        return _reference(q, k, v, causal, seg)  # ragged: XLA path
    kernel_path.record("flash_attention", interpret)
    return _forward(q, k, v, seg, causal, *tiles, interpret)


_flash = jax.custom_vjp(_primal, nondiff_argnums=(4, 5, 6))


def _flash_fwd(q, k, v, seg, causal, block_q, interpret):
    """Where the length is whole lane tiles (and its dQ sum fits VMEM)
    the forward keeps its row statistics and the backward is the kernel;
    any other length keeps (q, k, v) alone and differentiates the
    reference."""
    _, t, d = q.shape
    tiles = _tiles(q, k, block_q, seg is not None, lanes=True)
    if tiles is None or t * d * 4 > _BWD_DQ_BYTES:
        return _primal(q, k, v, seg, causal, block_q, interpret), \
            (q, k, v, None, None, seg)
    kernel_path.record("flash_attention", interpret)
    o, lse = _forward(q, k, v, seg, causal, *tiles, interpret,
                      with_lse=True)
    return o, (q, k, v, o, lse, seg)


def _flash_bwd(causal, block_q, interpret, res, g):
    q, k, v, o, lse, seg = res
    seg_ct = (None if seg is None else
              np.zeros(seg.shape, jax.dtypes.float0))
    if lse is None:
        kernel_path.record("flash_attention_bwd")
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference(q_, k_, v_, causal, seg),
            q, k, v)
        return vjp(g) + (seg_ct,)
    kernel_path.record("flash_attention_bwd", interpret)
    return _backward(q, k, v, o, lse, seg, g, causal, interpret) + (seg_ct,)


_flash.defvjp(_flash_fwd, _flash_bwd)


# -- decode mode: one query row against a KV cache -----------------------

def _decode_reference(q, k, v, lengths):
    """Dense XLA single-query attention over a contiguous cache:
    q [BH, 1, D], k/v [BH, C, D], lengths [BH] (valid cache rows per
    batch-head). What :func:`_decode_paged_reference` is after its
    gather, and so the numeric contract the kernel must match: a cache
    row is attendable iff its index < length."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    mask = jnp.arange(k.shape[1])[None, None, :] < \
        lengths[:, None, None]
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


# -- paged decode: block-table gather over a block-pool cache ------------

def cache_precision(cache_dtype):
    """How the paged attention ops multiply: a bfloat16 cache in one MXU
    pass as before; a float32 cache, which a model asks for when a
    rounded key would change what it computes (models/moe_lm.py), at the
    highest precision, products exact."""
    return jax.lax.Precision.HIGHEST if cache_dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT


def window_edge(lengths, window, aligned, xp=jnp):
    """The first row the query at row ``lengths - 1`` attends: with a
    sliding window the row ``window - 1`` before it, with an **aligned**
    one the first row of the query's own block of ``window`` rows. ``xp``:
    ``numpy`` for the host's books."""
    if aligned:
        return xp.maximum(lengths - 1, 0) // window * window
    return xp.maximum(lengths - window, 0)


def paged_walk(lengths, block_size, max_blocks, window=None, aligned=False,
               xp=jnp):
    """The pages of a table row that the paged decode kernel's walk takes
    for a sequence of ``lengths`` rows: the first, which holds the first
    row the query sees, and how many, up to the page of its newest row and
    never past the row's ``max_blocks`` entries. The kernel walks by it and
    the session's books count by it (``paged_cache.LayerCache``, with
    ``xp=numpy``)."""
    first = 0
    if window is not None:
        first = xp.minimum(
            window_edge(lengths, window, aligned, xp) // block_size,
            max_blocks - 1)
    return first, xp.minimum(
        (lengths + block_size - 1) // block_size, max_blocks) - first


def _decode_paged_reference(q, k_pool, v_pool, lengths, tables,
                            num_heads, num_kv_heads=None, window=None,
                            v_width=None, scale=None, aligned=False,
                            stats=False):
    """Dense XLA single-query attention over a PAGED cache: q [S, 1, H*D]
    (one query token per slot), k/v pools [NB, BS, Hkv*D], lengths [S]
    (live rows per slot), tables [S, MB] block ids mapping slot s's
    logical rows [j*BS, (j+1)*BS) to pool block tables[s, j]. Table
    entries >= NB mark dead/unallocated rows (clipped for the gather;
    the length mask keeps them unattendable). ``num_kv_heads`` (default:
    ``num_heads``) is the number of heads the pools hold: query head h
    attends KV head ``h // (H / Hkv)``. With ``window``, row j is
    attendable iff ``length - window <= j < length``: the query sits at
    ``length - 1`` and sees itself and the ``window - 1`` rows before it;
    rows behind the window may sit in blocks the table no longer names;
    an ``aligned`` window starts at a multiple of ``window``
    (:func:`window_edge`). With ``stats`` the result is float32 and comes
    with the softmax's maximum and sum, each [S, H, 1]: what a second
    walk's result is merged by (:func:`merge_walks`).
    With ``v_width`` there is no V pool (``v_pool`` None): the pool holds
    one head whose value is the leading ``v_width`` lanes of its key's
    own row, and the result is [S, 1, H*v_width]. ``scale`` multiplies
    the scores (default ``D ** -0.5``).
    The flag-off fallback AND the numeric contract the paged kernel must
    match: after the gather this is exactly :func:`_decode_reference` on
    the logical [S, MB*BS] cache."""
    s, _, dm = q.shape
    nb, bs, dkv = k_pool.shape
    nkv = num_kv_heads or num_heads
    mb = tables.shape[1]
    c = mb * bs
    hd = dm // num_heads
    tbl = jnp.clip(tables.astype(jnp.int32), 0, nb - 1)
    rows = k_pool[tbl]
    values = rows[..., :v_width] if v_width else v_pool[tbl]
    # [S, C, Hkv, hd] -> every query head beside its KV head's rows
    kh = jnp.repeat(rows.reshape(s, c, nkv, hd), num_heads // nkv,
                    axis=2).transpose(0, 2, 1, 3)
    vh = jnp.repeat(values.reshape(s, c, nkv, -1), num_heads // nkv,
                    axis=2).transpose(0, 2, 1, 3)
    qh = q.reshape(s * num_heads, 1, hd)
    kh = kh.reshape(s * num_heads, c, hd)
    vh = vh.reshape(s * num_heads, c, vh.shape[-1])
    lens = jnp.broadcast_to(
        jnp.asarray(lengths).reshape(s, 1), (s, num_heads)).reshape(-1)
    if window is None and not v_width and scale is None and not stats:
        return _decode_reference(qh, kh, vh, lens).reshape(s, 1, dm)
    prec = cache_precision(k_pool.dtype)
    sc = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=prec,
                    preferred_element_type=jnp.float32) \
        * (hd ** -0.5 if scale is None else scale)
    cols = jnp.arange(c)[None, None, :]
    mask = cols < lens[:, None, None]
    if window is not None:
        mask = mask & (cols >= window_edge(lens, window,
                                           aligned)[:, None, None])
    sc = jnp.where(mask, sc, _NEG)
    if not stats:
        p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
        return jnp.einsum("bqk,bkd->bqd", p, vh,
                          precision=prec).reshape(s, 1, -1)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(sc - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bqk,bkd->bqd", p.astype(vh.dtype), vh, precision=prec,
                     preferred_element_type=jnp.float32) \
        / jnp.maximum(l, 1e-30)
    return (out.reshape(s, 1, -1), m.reshape(s, num_heads, 1),
            l.reshape(s, num_heads, 1))


def merge_walks(walks, num_heads):
    """One softmax over the rows of several walks: ``walks`` holds each
    walk's ``(out [S, 1, H*D], m [S, H, 1], l [S, H, 1])`` as ``stats``
    returns them. A walk that attended nothing (sum 0) adds nothing."""
    top = functools.reduce(jnp.maximum, [m for _, m, _ in walks])
    weights = [l * jnp.exp(m - top) for _, m, l in walks]
    total = jnp.maximum(sum(weights), 1e-30)
    s = walks[0][0].shape[0]
    out = sum(o.reshape(s, num_heads, -1) * w
              for (o, _, _), w in zip(walks, weights)) / total
    return out.reshape(s, 1, -1)


# VMEM for the paged kernel's page buffers: K and V, each double-buffered
_PAGED_BUFFER_BYTES = 2 << 20


def _pages_in_buffers(layer_block_bytes, max_blocks):
    """Pages the paged decode kernel's buffers hold: they keep, twice
    over and within ``_PAGED_BUFFER_BYTES``, what one block holds in one
    layer (a page of K and one of V, or a latent pool's one page); never
    more than a table row has."""
    return int(max(1, min(max_blocks,
                          _PAGED_BUFFER_BYTES // (2 * layer_block_bytes))))


def _paged_block_pages(block_size, d_model, dtype, max_blocks, buffers=4):
    """Pages (pool blocks) the paged decode kernel fetches and attends
    at once, a block of its walk: what the page buffers (K and V twice
    over, or with one pool for both its rows twice over) hold within
    ``_PAGED_BUFFER_BYTES``, and no more than a table row has. 8 pages
    (128 rows) for bf16 blocks of 16 x 2048. It is also how many copies a
    pool the kernel awaits at once where a block is full, and what the
    session's books count a walk's blocks by
    (``paged_cache.LayerCache.walk_pages``)."""
    page = block_size * d_model * jnp.dtype(dtype).itemsize
    return _pages_in_buffers(buffers // 2 * page, max_blocks)


def _decode_paged_kernel(lens_ref, tab_ref, q_ref, kp_ref, *refs,
                         block_size, max_blocks, num_blocks, pages,
                         num_heads, num_kv_heads, window, scale, v_width,
                         aligned, stats):
    """One slot of single-query flash decode THROUGH a block table, all
    heads at once. The pools stay in HBM; the program walks its slot's
    LIVE pages only, ``pages`` of them a compute block, each page
    (``[BS, Hkv*D]``, contiguous) copied whole into one of two VMEM
    buffers while the block before it is attended. The last step of a
    slot starts the first block of the next one, so consecutive
    programs overlap too; ``base_ref`` carries which buffer that block
    went to. A block's copies start in a loop over its live pages. A
    block all of whose ``pages`` pages are live (every block of a walk
    but its last) is awaited once a pool, through a descriptor that names
    the whole buffer half: a DMA semaphore counts bytes. Only a walk's
    last block, where it is partial, is awaited page by page; which of
    the two is read from the slot's length, nothing else. The compiler's
    bounds checks of the copies are off (they were most of what a start
    cost, :func:`_decode_paged_call`): EVERY INDEX OF THIS KERNEL IS KEPT
    IN RANGE BY HAND, a table entry by ``clip`` into the pool, a page's
    place in its buffer half by the loop's bound, a table row's entries by
    :func:`paged_walk`'s ``max_blocks``, and one added later has to be
    too (``tests/test_paged_cache.py`` feeds the kernel hostile books).
    With a ``window`` the walk starts at the page that holds row
    ``length - window`` and masks that page's rows behind it: pages
    before it are never read (the session has freed them). A slot whose
    first live table entry is dead (>= NB: inactive or starved) has no
    pages: it fetches nothing and writes zeros. An ``aligned`` window's
    walk starts at the first page of the query's own block of ``window``
    rows (:func:`window_edge`). With ``stats`` a second output
    ``[H, 128]`` holds the softmax's maximum in lane 0 and its sum in
    the others, for :func:`merge_walks`.

    With ``v_width`` the pool holds ONE head whose value is the leading
    ``v_width`` lanes of its key's own row (a latent cache): there is no
    V pool and no V buffer, a page is fetched once and used as both, and
    the output is ``[H, v_width]``.

    Heads share one pass over a block through a block-diagonal query
    ``[H, Hkv*D]``: row h holds head h's lanes in the columns of KV head
    ``h // (H / Hkv)``, so scores are ``[H, rows]``, ``P @ V`` is
    ``[H, Hkv*D]`` and head h's output is its KV head's block of row h.
    The query block is ``[1, H*D]`` where every head has a KV head of its
    own and ``[H, D]`` where heads share one: the layout each model has.
    The surplus products are free: the kernel runs at the copies'
    speed."""
    from jax.experimental.pallas import tpu as pltpu
    refs = list(refs)
    vp_ref = None if v_width else refs.pop(0)
    o_ref = refs.pop(0)
    st_ref = refs.pop(0) if stats else None
    kbuf = refs.pop(0)
    vbuf = kbuf if v_width else refs.pop(0)
    sem, base_ref = refs
    bs, mb, nb = block_size, max_blocks, num_blocks
    si, ns = pl.program_id(0), pl.num_programs(0)
    dkv = kbuf.shape[-1]
    dv = v_width or dkv
    hd = dkv // num_kv_heads
    group = num_heads // num_kv_heads
    rows = pages * bs
    mxu = jnp.promote_types(q_ref.dtype, kbuf.dtype)
    prec = cache_precision(kbuf.dtype)

    def walk_of(slot):
        """The first page a slot's query can see and how many pages its
        walk takes (:func:`paged_walk`): none where the first is dead."""
        first, n = paged_walk(lens_ref[slot], bs, mb, window, aligned)
        return first, jnp.where(tab_ref[slot * mb + first] < nb, n, 0)

    def live_pages(n_pages, blk):
        return jnp.clip(n_pages - blk * pages, 0, pages)

    def for_live_pages(slot, first, n_pages, blk, buf, act):
        """``act`` on the K and V copies of block ``blk``'s live pages.
        The compiler's bounds checks are off (:func:`_decode_paged_call`):
        the ``clip`` is what keeps a page in the pool, ``i < pages`` the
        destination in its buffer half."""
        def one(i, _):
            page = jnp.clip(
                tab_ref[slot * mb + first + blk * pages + i], 0, nb - 1)
            dst = pl.ds(pl.multiple_of(i * bs, bs), bs)
            act(pltpu.make_async_copy(
                kp_ref.at[page], kbuf.at[buf, dst], sem.at[0, buf]))
            if vp_ref is not None:
                act(pltpu.make_async_copy(
                    vp_ref.at[page], vbuf.at[buf, dst], sem.at[1, buf]))
        jax.lax.fori_loop(0, live_pages(n_pages, blk), one, None)

    def start(slot, first, n_pages, blk, buf):
        for_live_pages(slot, first, n_pages, blk, buf,
                       lambda c: c.start())

    def wait(blk, buf):
        """Wait for this slot's block ``blk``. A DMA semaphore counts
        bytes, so a full block is awaited ONCE a pool, through a
        descriptor that names the whole buffer half (the bytes of its
        ``pages`` copies). A partial block waits page by page, through
        descriptors equal to those that started its copies, and the
        pages it did not fetch are zeroed."""
        live = live_pages(n_pages, blk)

        @pl.when(live == pages)
        def _():
            for pool, half in enumerate(
                    (kbuf,) if vp_ref is None else (kbuf, vbuf)):
                pltpu.make_async_copy(half.at[buf], half.at[buf],
                                      sem.at[pool, buf]).wait()

        @pl.when(live < pages)
        def _():
            for_live_pages(si, first, n_pages, blk, buf,
                           lambda c: c.wait())
            # pages of the last block that were not fetched hold
            # whatever VMEM held. The mask covers their scores; V's rows
            # go to zero (0 x NaN is NaN)
            def zero_page(i, _):
                vbuf[buf, pl.ds(pl.multiple_of(i * bs, bs), bs), :] = \
                    jnp.zeros((bs, dkv), vbuf.dtype)
            jax.lax.fori_loop(live, pages, zero_page, None)

    @pl.when(si == 0)
    def _():
        base_ref[0] = 0

    base = base_ref[0]          # the buffer of this slot's first block
    first, n_pages = walk_of(si)
    n_blocks = (n_pages + pages - 1) // pages
    started = (si > 0) & (walk_of(jnp.maximum(si - 1, 0))[1] > 0)
    nxt = jnp.minimum(si + 1, ns - 1)
    nxt_first, nxt_pages = walk_of(nxt)
    nxt_pages = jnp.where(si + 1 < ns, nxt_pages, 0)

    @pl.when((n_blocks > 0) & jnp.logical_not(started))
    def _():
        start(si, first, n_pages, 0, base)

    length = lens_ref[si]
    # query head h against KV head h // group: its lanes in that head's
    # columns, zeros elsewhere
    diag = jax.lax.broadcasted_iota(jnp.int32, (num_heads, dkv), 0) \
        // group == \
        jax.lax.broadcasted_iota(jnp.int32, (num_heads, dkv), 1) // hd
    q = q_ref[0].astype(jnp.float32)
    if group == 1:      # q [1, H*D]: every row is every head's lanes
        q = jnp.broadcast_to(q, (num_heads, dkv))
    else:               # q [H, D]: a head's lanes under each KV head
        q = jnp.concatenate([q] * num_kv_heads, axis=1)
    q_bd = jnp.where(diag, q, 0.0).astype(mxu)

    def step(b, carry):
        m, l, acc = carry
        buf = (base + b) % 2

        @pl.when(b + 1 < n_blocks)
        def _():
            start(si, first, n_pages, b + 1, 1 - buf)

        @pl.when((b + 1 == n_blocks) & (nxt_pages > 0))
        def _():
            start(nxt, nxt_first, nxt_pages, 0, 1 - buf)

        wait(b, buf)
        # explicit Precision, as in _body. A query wider than the pool
        # (f32 on bf16 blocks) upcasts the block, the reference's
        # promotion; equal dtypes go to the MXU as they are, float32
        # blocks at the highest precision
        s = jax.lax.dot_general(
            q_bd, kbuf[buf].astype(mxu), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * scale                          # [H, rows]
        row = (first + b * pages) * bs + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = row < length
        if window is not None:
            mask = mask & (row >= window_edge(length, window, aligned))
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + jnp.dot(
                    p.astype(mxu), vbuf[buf][:, :dv].astype(mxu),
                    preferred_element_type=jnp.float32,
                    precision=prec))

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, step,
        (jnp.full((num_heads, 1), _NEG, jnp.float32),
         jnp.zeros((num_heads, 1), jnp.float32),
         jnp.zeros((num_heads, dv), jnp.float32)))
    base_ref[0] = (base + n_blocks) % 2
    out = acc / jnp.maximum(l, 1e-30)    # one head's rows with v_width
    if not v_width:
        # row h is zero outside its KV head's block: with a head each, the
        # rows add up to [1, H*D]; with grouped queries, the blocks to [H, D]
        out = jnp.where(diag, out, 0.0)
        if group == 1:
            out = jnp.sum(out, axis=0, keepdims=True)
        else:
            out = sum(out[:, g * hd:(g + 1) * hd]
                      for g in range(num_kv_heads))
    o_ref[0] = out.astype(o_ref.dtype)
    if stats:
        lane = jax.lax.broadcasted_iota(jnp.int32, st_ref.shape[1:], 1)
        st_ref[0] = jnp.where(lane == 0, m, l)


def decode_attention_paged(q, k_pool, v_pool, lengths, tables,
                           num_heads, interpret=None, num_kv_heads=None,
                           window=None, v_width=None, scale=None,
                           aligned=False, stats=False):
    """Single-query flash decode (inference only, no vjp: generation
    never differentiates through the cache) where K/V live in a PAGED
    pool and the kernel streams exactly the live blocks of each
    sequence — never the whole pool, never a gathered dense copy.

    q: [S, 1, H*D] (one query per slot); k_pool/v_pool: [NB, BS, Hkv*D]
    with ``num_kv_heads`` heads (default ``num_heads``; fewer: grouped
    queries, head h on KV head ``h // (H / Hkv)``); lengths: [S]; tables:
    [S, MB] int block ids (entries >= NB are dead — clamped, masked by
    length); ``window``: attend rows ``[length - window, length)`` only,
    or with ``aligned`` the rows from the last multiple of ``window``
    below ``length`` on. ``stats``: the result in float32 with the
    softmax's maximum and sum ``[S, H, 1]`` behind it, so that walks over
    two pools give one softmax (:func:`merge_walks`).
    ``v_width``: the pool holds one head whose value is the leading
    ``v_width`` lanes of its key's row (a latent cache: ``v_pool`` is
    None, ``num_kv_heads`` 1); a page is read once for both and the
    result is [S, 1, H*v_width]. ``scale`` multiplies the scores
    (default ``D ** -0.5``).
    Returns [S, 1, H*D]. One program per slot
    (:func:`_decode_paged_kernel`): ``lengths`` and the table are
    scalar-prefetched, the pools are not blocked, and the program
    fetches its slot's live pages by hand — ``cdiv(length, BS)`` of
    them, less those wholly behind the window — whole and for all heads,
    so a slot costs what its context costs and a slot whose table row is
    dead (how the session marks inactive and starved slots) costs
    nothing and returns zeros, where the reference attends clamped rows
    nobody reads. Pool geometry Mosaic cannot tile falls back to the
    dense gather reference — same semantics, so the flag never changes
    tokens. ``interpret=None`` auto-selects interpreter mode off-TPU."""
    if interpret is None:
        interpret = kernel_path.interpret_mode()
    nkv = num_kv_heads or num_heads
    if v_width and (v_pool is not None or nkv != 1):
        raise ValueError("a pool whose value is the head of its key's row "
                         "holds one head and has no V pool")
    bs, dkv = k_pool.shape[1], k_pool.shape[2]
    hd = q.shape[-1] // num_heads
    sublanes = 32 // jnp.dtype(k_pool.dtype).itemsize
    if not interpret and (bs % sublanes != 0 or dkv % 128 != 0 or
                          (v_width or 0) % 128 != 0 or
                          (hd % 128 != 0 and num_heads != 1)):
        # compiled Mosaic wants a page to land in its buffer on whole
        # tiles: BS rows a multiple of the dtype's sublane tile (8 for
        # f32, 16 for bf16), Hkv*D whole lanes. Heads narrower than a
        # lane tile (e.g. head_dim 64) have not run compiled. Anything
        # else takes the XLA gather path (identical semantics)
        kernel_path.record("decode_attention_paged")
        return _decode_paged_reference(q, k_pool, v_pool, lengths,
                                       tables, num_heads, nkv, window,
                                       v_width, scale, aligned, stats)
    kernel_path.record("decode_attention_paged", interpret)
    pages = _paged_block_pages(bs, dkv, k_pool.dtype, tables.shape[1],
                               2 if v_width else 4)
    return _decode_paged_call(q, k_pool, v_pool, lengths, tables,
                              num_heads, pages, interpret, nkv, window,
                              v_width, hd ** -0.5 if scale is None
                              else float(scale), bool(aligned), bool(stats))


@functools.partial(jax.jit, static_argnums=tuple(range(5, 14)))
def _decode_paged_call(q, k_pool, v_pool, lengths, tables, num_heads,
                       pages, interpret, num_kv_heads, window, v_width,
                       scale, aligned, stats):
    """The kernel call, under a jit of its own: a model's layers share
    their geometry, so the body is traced once a process and lowered
    once a program, not once a layer."""
    from jax.experimental.pallas import tpu as pltpu
    s, _, dm = q.shape
    nb, bs, dkv = k_pool.shape
    hd = dm // num_heads
    mb = tables.shape[1]
    lens = jnp.asarray(lengths).reshape(s).astype(jnp.int32)
    tab = jnp.asarray(tables).reshape(s * mb).astype(jnp.int32)

    # a slot's query and output: one row of all heads' lanes where every
    # head has its own KV head (the layout the model hands over), a row a
    # head where query heads share one
    rows = (1, dm) if num_kv_heads == num_heads else (num_heads, hd)
    out_rows = (num_heads, v_width) if v_width else rows
    # behind the result, where asked for, the softmax's maximum and sum
    out_shapes = [out_rows] + [(num_heads, 128)] * stats
    out_specs = [pl.BlockSpec((1,) + shape, lambda si, lr, tr: (si, 0, 0))
                 for shape in out_shapes]
    out_shape = [jax.ShapeDtypeStruct(
        (s,) + shape, jnp.float32 if stats else q.dtype)
        for shape in out_shapes]
    pools = (k_pool,) if v_width else (k_pool, v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[pl.BlockSpec((1,) + rows, lambda si, lr, tr: (si, 0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=out_specs if stats else out_specs[0],
        scratch_shapes=[pltpu.VMEM((2, pages * bs, dkv), pool.dtype)
                        for pool in pools] + [
            pltpu.SemaphoreType.DMA((2, 2)),      # (K|V, buffer)
            pltpu.SMEM((1,), jnp.int32),          # first block's buffer
        ])
    out = pl.pallas_call(
        functools.partial(_decode_paged_kernel, block_size=bs,
                          max_blocks=mb, num_blocks=nb, pages=pages,
                          num_heads=num_heads, num_kv_heads=num_kv_heads,
                          window=window, scale=scale, v_width=v_width,
                          aligned=aligned, stats=stats),
        grid_spec=grid_spec,
        out_shape=out_shape if stats else out_shape[0],
        # programs run in order: each hands its successor a block. The
        # compiler's own checks of every copy's two ends (two chains of
        # scalar code before each start: most of what a start cost) are
        # off: the kernel keeps its indices in range itself, and says how
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        name="decode_attention_paged",
        interpret=interpret)(lens, tab, q.reshape((s,) + rows), *pools)
    if not stats:
        return out.reshape(s, 1, -1)
    return (out[0].reshape(s, 1, -1), out[1][:, :, :1], out[1][:, :, 1:2])


def flash_attention(q, k, v, causal=False, segment_ids=None,
                    block_q=None, interpret=None):
    """q, k, v: [B, H, T, D] (or [BH, T, D]) -> same-shape output.
    Fused Pallas forward and backward. ``segment_ids``:
    [B, T] int32, 0 = padding — a key is attendable iff its id matches
    the query's and is nonzero (one mask covering the padded-batch
    convention AND packed sequences, SURVEY §5.7). Padded query rows
    yield zeros. ``block_q``: the most query rows a forward tile takes
    (default: :func:`_tiles`' own). ``interpret=None`` auto-selects
    interpreter mode off-TPU."""
    if interpret is None:
        interpret = kernel_path.interpret_mode()
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[None], k[None], v[None]
        if segment_ids is not None and segment_ids.ndim == 1:
            segment_ids = segment_ids[None]
    b, h, t, d = q.shape
    seg = None
    if segment_ids is not None:
        seg = jnp.broadcast_to(
            segment_ids.astype(jnp.int32)[:, None, :],
            (b, h, t)).reshape(b * h, t)
    out = _flash(q.reshape(b * h, t, d), k.reshape(b * h, t, d),
                 v.reshape(b * h, t, d), seg, causal, block_q,
                 interpret)
    out = out.reshape(b, h, t, d)
    return out[0] if squeeze else out


def flash_attention_stats(q, k, v, causal=False, interpret=None):
    """The flash forward for an op that merges its result with a second
    attention's under one softmax (inference only, no vjp): q, k, v
    [BH, T, D] -> ``(o [BH, T, D], lse [BH, 1, T])``, both float32: the
    kernel's own sums over its normaliser, not rounded to the operands'
    dtype, and every query row's log-sum-exp, which stands for the part's
    maximum and sum in :func:`merge_walks` (maximum ``lse``, sum 1). The
    forward's body as it is; None where :func:`_tiles` finds no blocks of
    whole lane tiles for T (the caller keeps its XLA form, and counts
    it)."""
    if interpret is None:
        interpret = kernel_path.interpret_mode()
    tiles = _tiles(q, k, None, False, lanes=True)
    if tiles is None:
        return None
    kernel_path.record("flash_attention", interpret)
    return _forward(q, k, v, None, causal, *tiles, interpret, with_lse=True,
                    out_dtype=jnp.float32)
