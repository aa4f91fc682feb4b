"""The experts' grouped matmuls as a weight stream: each touched expert's
weights pass through VMEM exactly once, at the HBM's speed, while its
rows are multiplied against them.

``jax.lax.ragged_dot`` lowers to a row-tiled grouped matmul whose tiles
are sized for many rows a group. A decode step has 4 pairs an expert (12
rows with the three pieces of ``moe_ops._pieces``), and those kernels
then read at 37% of the HBM's speed (PERF.md section 6, PR 28). Here a
grid step is one (expert, row tile) pair: it takes the expert's WHOLE
``[d, f]`` matrix (or the widest column tile of it that fits
``_WEIGHT_TILE_BYTES``) as one block, and the pipeline has the next
touched expert's block in flight while this one is multiplied.
Consecutive steps on one expert keep its block, an expert that took no
row is in no step and is never read, and steps past the work are empty.

The products are ``moe_ops.exact_ragged_dot``'s: a float32 row tile is
taken apart into the same three bfloat16 pieces inside the kernel
(:func:`_pieces`), the pieces meet the bfloat16 weight as three times the
rows in one pass with float32 accumulation, and the partial results are
added smallest first. Only the order of the float32 sums over ``d`` may
differ (on the chip it did not: the results were ``ragged_dot``'s bit
for bit at every size measured).

An expert's width that is no whole lane tiles (1856 = 14.5 x 128) has no
column tile, and held ``[d, f]`` XLA lays such a stack out with ``d`` on
the lanes and copies it whole, every call, into the layout the kernel asks
for (660 MB a layer at 64 experts of 2688 x 1856: the compiler's own HLO,
PR 46). So such an expert's first matrix is held **as the down matrix
lies**, ``[f, d]`` with the model's width on the lanes: nothing is padded
in the HBM, the whole matrix is one block as tall as the array, and the
product contracts the lanes of both operands. That is the two-matrix
form, ``act`` ``relu2``: one stack, held so, the relu squared in the
epilogue. There is no other knob, and no SwiGLU stack held ``[f, d]``.

Rows are sorted by expert and lie in aligned tiles of ``_ROW_TILE`` rows
(Mosaic proves no alignment of a data-dependent row start), so a tile
that two experts share is visited by both, each keeping the other's rows
as they are. Rows past the last group are nobody's: whatever they hold,
the op adds 0 for them.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The mean pairs a held expert up to which a call takes these kernels:
# the largest measured, not a crossover, because none was found. The three
# matmuls alone on a v5e, 128 experts of 2048 x 1024, float32 rows in three
# pieces (tools/moe_ffn_probe.py, PR 28), ragged_dot / these kernels, ms:
# 64 tokens (4 pairs an expert) 4.87 / 2.10, 256 (16) 6.07 / 2.63, 1,024
# (64) 12.45 / 3.97, 2,048 (128) 21.97 / 5.70, 4,096 (256) 40.31 / 9.06.
MAX_PAIRS_PER_EXPERT = 256

# rows of a tile. Measured with the above at 8 to 256: 64 is the fastest or
# within 1% of it at every size (a decode step: 16 rows 2.16 ms, 32 2.11,
# 64 2.10, 128 3.05; 4,096 tokens: 64 rows 9.06, 128 9.74, 256 12.81)
_ROW_TILE = 64

# one weight block in VMEM (two are in flight for each matrix): the whole
# expert at Trinity-Mini's 2048 x 1024 in bfloat16
_WEIGHT_TILE_BYTES = 4 << 20

# a matrix whose width is no whole lane tiles is one block whatever it
# weighs, up to this: 1856 x 2688 in bfloat16 is 9.98 MB, two in flight
# 20 MB of the chip's 128 MiB of VMEM
_WHOLE_MATRIX_BYTES = 12 << 20

# the rows of a bfloat16 block come in sublane tiles of this many
_SUBLANES = 16


def _pieces(x):
    """``moe_ops._pieces`` in integer arithmetic, bit for bit: Mosaic
    lowers no ``reduce_precision``, and a cast there and back is a pair
    a compiler may drop. Rounding a float32 to 8 bits of significand,
    ties to even, is adding ``0x7fff`` and the lowest kept bit to its
    bits and clearing the low half."""
    out, rest = [], x
    for _ in range(3):
        u = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        u = (u + 0x7fff + ((u >> 16) & 1)) & jnp.uint32(0xffff0000)
        top = jax.lax.bitcast_convert_type(u, jnp.float32)
        out.append(top.astype(jnp.bfloat16))
        rest = rest - top
    return out


def _col_tile(d, f):
    """Columns of a weight block: all ``f`` where ``[d, f]`` bfloat16 fits
    ``_WEIGHT_TILE_BYTES``, else the most whole lane tiles that divide
    ``f`` and fit; an ``f`` that is no whole lane tiles is one block."""
    if f % 128:
        return f
    return max([t for t in range(128, f + 1, 128)
                if f % t == 0 and d * t * 2 <= _WEIGHT_TILE_BYTES] or [128])


def work_items(counts, tiles, tm):
    """The (expert, row tile) pairs a call visits, in the rows' own order.
    ``counts`` [G] rows a group, the rows sorted by group in ``tiles``
    tiles of ``tm`` -> int32 arrays of ``tiles + G`` items (more than any
    call needs; the surplus repeats the last, so no block moves for it):
    ``expert``, ``tile``, the expert's first row, the row past its last,
    and [1] how many items are work."""
    g = counts.shape[0]
    end = jnp.cumsum(counts)
    start = end - counts
    first = start // tm
    n_tiles = jnp.where(counts > 0, (end - 1) // tm - first + 1, 0)
    item_end = jnp.cumsum(n_tiles)
    total = item_end[-1]
    i = jnp.minimum(jnp.arange(tiles + g), jnp.maximum(total - 1, 0))
    e = jnp.minimum(jnp.searchsorted(item_end, i, side="right"), g - 1)
    t = jnp.clip(first[e] + i - (item_end[e] - n_tiles[e]), 0, tiles - 1)
    return tuple(a.astype(jnp.int32) for a in
                 (e, t, start[e], end[e], total.reshape(1)))


def _kernel(expert_ref, tile_ref, lo_ref, hi_ref, n_ref, x_ref, *refs,
            tm, act):
    """One (expert, row tile) step: the tile's rows in three pieces
    against the expert's block of each weight; the rows that are the
    expert's take the result, the others stay. With two weights the
    result is ``silu(x W0) * (x W1)``; with one and ``act`` ``relu2`` it
    is ``relu(x W0^T)^2``, the block ``[f, d]`` contracted over its
    lanes."""
    transposed = act == "relu2"
    del expert_ref                        # the weights' index map reads it
    w_refs, o_ref = refs[:-1], refs[-1]
    i = pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        p = jnp.concatenate(_pieces(x_ref[...]), axis=0)    # [3 tm, d]
        ys = []
        for w_ref in w_refs:
            # explicit Precision: the executor traces TPU steps under a
            # default Mosaic does not lower
            y = jax.lax.dot_general(
                p, w_ref[0], (((1,), (1 if transposed else 0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            ys.append((y[2 * tm:] + y[tm:2 * tm]) + y[:tm])
        if len(ys) == 2:
            y = jax.nn.silu(ys[0]) * ys[1]
        elif transposed:
            y = jnp.square(jnp.maximum(ys[0], 0.0))
        else:
            y = ys[0]
        row = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        o_ref[...] = jnp.where((row >= lo_ref[i]) & (row < hi_ref[i]), y,
                               o_ref[...])


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def grouped_matmul(xs, ws, items, tm, interpret, act=None):
    """xs [n, d] float32, rows sorted by group, ``n`` whole tiles of
    ``tm``; ws: one or two ``[G, d, f]`` bfloat16; ``items`` from
    :func:`work_items`. -> [n, f] float32: a row of group g is ``xs @
    ws[0][g]``, or ``silu(xs @ ws[0][g]) * (xs @ ws[1][g])``, every
    product exact. ``act`` ``relu2`` is the first product of a two-matrix
    expert, and ONE form: ws is one ``[G, f, d]`` stack read as it lies
    (the module's docstring) and a row is ``relu(xs @ ws[0][g]^T)^2``.
    Under a jit of its own: a model's layers share their geometry, so the
    body is traced once a process."""
    from jax.experimental.pallas import tpu as pltpu
    transposed = act == "relu2"
    if act not in (None, "relu2") or (transposed and len(ws) != 1):
        raise ValueError("act is None or 'relu2' over one [G, f, d] stack")
    n, d = xs.shape
    f = ws[0].shape[1 if transposed else 2]
    tn = _col_tile(d, f)
    w_spec = pl.BlockSpec((1, tn, d), lambda c, i, e, *_: (e[i], c, 0)) \
        if transposed else \
        pl.BlockSpec((1, d, tn), lambda c, i, e, *_: (e[i], 0, c))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # columns outermost: a row tile's visits stay consecutive, so its
        # output block is written back once it is whole
        grid=(f // tn, items[0].shape[0]),
        in_specs=[pl.BlockSpec((tm, d), lambda c, i, e, t, *_: (t[i], 0))]
        + [w_spec] * len(ws),
        out_specs=pl.BlockSpec((tm, tn), lambda c, i, e, t, *_: (t[i], c)))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the weights' blocks twice over, the row tiles, and room
            vmem_limit_bytes=4 * len(ws) * d * tn + (16 << 20)),
        name="moe_grouped_matmul",
        interpret=interpret)(*items, xs, *ws)


def admits(n_pairs, w, act=None):
    """The static test for taking these kernels, read off the call's
    shapes: ``n_pairs`` rows over the experts of ``w``, a matrix of the
    first product, ``[G, d, f]`` or, of a two-matrix expert (``act``
    ``relu2``), ``[G, f, d]``. The weights are held in bfloat16, the
    model's width ``d`` is whole lane tiles, the expert's width ``f`` is
    whole lane tiles too, or (held ``[G, f, d]`` only: the module's
    docstring) whole sublane tiles of a matrix that is one block, and an
    expert has at most ``MAX_PAIRS_PER_EXPERT`` rows in the mean."""
    g, d, f = w.shape
    transposed = act == "relu2"
    if transposed:
        d, f = f, d
    whole = f % 128 == 0 or (transposed and f % _SUBLANES == 0
                             and 2 * d * f <= _WHOLE_MATRIX_BYTES)
    return (w.dtype == jnp.bfloat16 and d % 128 == 0 and whole
            and n_pairs <= MAX_PAIRS_PER_EXPERT * g)


def expert_ffn(xs, wg, wu, wd, counts, interpret, tm=_ROW_TILE, act=None):
    """``silu(xs WGate) * (xs WUp)`` then ``WDown`` for rows sorted by
    expert, through :func:`grouped_matmul`: xs [n, d] float32, the
    weights stacked ``[G, ..]`` in bfloat16, ``counts`` [G] -> [n, d]
    float32. With ``act`` ``relu2`` there is no ``wg``, ``wu`` is held
    ``[G, f, d]`` as ``wd`` is, and the inner rows are ``relu(xs
    WUp^T)^2``. ``tm``: rows of a tile (the probe that sized it asks for
    others)."""
    n = xs.shape[0]
    tiles = -(-n // tm)
    xs = jnp.pad(xs, ((0, tiles * tm - n), (0, 0)))
    items = work_items(counts, tiles, tm)
    if act == "relu2":
        inner = grouped_matmul(xs, (wu,), items, tm, interpret, act)
    else:
        inner = grouped_matmul(xs, (wg, wu), items, tm, interpret)
    return grouped_matmul(inner, (wd,), items, tm, interpret)[:n]
