"""Ops of the sparse-expert decoder block: RMSNorm, rotary positions, the
projection and the expert feed-forward.

**Every product of this block is exact.** Which eight experts a token goes
to is a discontinuous function of its activations: an error of a bfloat16
ulp anywhere upstream of a router moves the 8th and 9th scores past each
other for a few tokens in a hundred, and such a token's logits then differ
from the model's by a tenth to a third of the largest (PERF.md section 6,
PR 27). So activations are float32, and a float32 activation is multiplied
with a weight held in bfloat16 without rounding it: it is taken apart into
three bfloat16 pieces that add up to it exactly (:func:`_pieces`), the
pieces go through the MXU as three times the rows in ONE pass over the
weight, and the three partial results are added in float32. A decode step,
which is bound by the bytes of the weights, pays little for it; a prefill
pays three times the products.

* ``linear`` - ``x @ W`` with no bias, exact, float32 out. (Under the
  ``amp`` flag the executor hands it ``x`` in bfloat16: a model with no
  router, served in the operands it was published in, multiplies in one
  pass.)

* ``rms_norm`` — ``x * w / sqrt(mean(x^2) + eps)`` over the last axis (or
  over each group of ``group_size`` lanes of it: one head's lanes of a
  ``[.., H*D]`` projection), computed and returned in float32 whatever
  flows in.
* ``rotary_embedding`` — rotary positions on ``[B, T, H*D]`` in the
  half-split pairing (lane ``i`` of a head turns with lane ``i + D/2``),
  positions along the time axis (a prompt window, a training batch) or one
  per batch row (a decode step: its rows come from behind an optimization
  barrier, so that the projection before it reads its weight where it
  lies, :func:`_fence_rows`); optionally over a range of each head's
  lanes only, with YaRN-blended frequencies (:func:`rotary_frequencies`).
* ``moe_ffn`` — route, sort by expert, grouped matmul (exact), weighted
  combine. The grouped matmuls run in ``pallas_moe``'s kernels, which
  stream each touched expert's weights once, where the call's shapes pass
  ``pallas_moe.admits`` (weights held in bfloat16, whole lane tiles, no
  more pairs an expert than were measured), else in ``exact_ragged_dot``:
  the same products either way, and the path is counted
  (``kernel_path``, ``moe_grouped_matmul``).
  Every token-expert pair is computed whatever the imbalance: there is no
  capacity and no padding to a per-expert size. The op routes over all
  ``num_experts`` and returns the part of the result that the experts it
  holds, ``[expert_offset, expert_offset + E_held)``, give; the parts of
  disjoint holders add up to the whole layer. Beside the result it returns
  how many pairs each held expert took. A holder of every expert takes all
  ``n * top_k`` pairs in one pass; **a holder of a share** takes the pairs
  that fall on its experts, which lie first in the sorted order, in passes
  of at most ``SHARE_ROWS`` rows until none is left: its work and its
  temporaries follow the held pairs, and still no pair is dropped.
  With attr ``act`` ``relu2`` an expert is **two matrices**,
  ``relu(x WUp^T)^2 WDown``: no gate, and ``WUp`` held ``[E, f, d]`` as
  ``WDown`` is (``pallas_moe`` says why), which is what lets a width that
  is no whole lane tiles take the kernels.
  With ``zero_experts`` the router is that much wider than the experts:
  an output past the last real expert is an **identity expert**, whose
  pair adds ``w x`` and costs no matmul (``zero_expert_combine``). Every
  holder adds it for its own rows, so over shares it counts once.
"""

import math

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..observability import metrics as _metrics
from . import kernel_path, pallas_moe


_HIGHEST = jax.lax.Precision.HIGHEST

# trace-time only, like paddle_executor_fenced_updates_total: counts when a
# step is traced (every compile), never on the steady-state path
_ROTARY_FENCED = _metrics.REGISTRY.counter(
    "paddle_rotary_fenced_total",
    "Rotary turns of a decode step (one position a batch row) traced with "
    "their input behind an optimization barrier, so that XLA leaves the "
    "head split out of the projection that made the input and reads that "
    "projection's weight where it lies",
    labelnames=("op",))


def _pieces(x):
    """x float32 -> three bfloat16 arrays that add up to x exactly (8 + 8
    + 8 bits of significand). ``reduce_precision`` and not a cast there
    and back: the compiler may drop such a pair and leave nothing to
    subtract."""
    out, rest = [], x.astype(jnp.float32)
    for _ in range(3):
        top = jax.lax.reduce_precision(rest, exponent_bits=8,
                                       mantissa_bits=7)
        out.append(top.astype(jnp.bfloat16))
        rest = rest - top
    return out


def _add_pieces(y):
    """The partial results [3, ..], smallest first."""
    return (y[2] + y[1]) + y[0]


def exact_dot(x, w, transposed=False):
    """x [n, d] float32 @ w [d, f] (``transposed``: w [f, d], contracted
    over its second axis where it lies), every product exact and the sums
    in float32: a bfloat16 ``w`` meets x's three pieces as [3n, d] in one
    pass; any other is multiplied at the highest precision. An ``x`` that
    arrives in bfloat16 (under ``amp``: a model served in bfloat16
    operands) is one piece already: one pass, float32 sums."""
    dims = (((1,), (1 if transposed else 0,)), ((), ()))
    if x.dtype == jnp.bfloat16 and w.dtype == jnp.bfloat16:
        return jax.lax.dot_general(x, w, dims,
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.DEFAULT)
    if w.dtype != jnp.bfloat16:
        return jax.lax.dot_general(x.astype(jnp.float32),
                                   w.astype(jnp.float32), dims,
                                   precision=_HIGHEST)
    y = jax.lax.dot_general(jnp.concatenate(_pieces(x), axis=0), w, dims,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.DEFAULT)
    return _add_pieces(y.reshape((3, x.shape[0], y.shape[1])))


def exact_ragged_dot(xs, w, counts):
    """:func:`exact_dot` for rows sorted by group: xs [n, d] float32, w
    [G, d, f], ``counts`` [G] rows a group. A row's three pieces lie one
    after the other, so every group is three times as long."""
    if w.dtype != jnp.bfloat16:
        return jax.lax.ragged_dot(
            xs.astype(jnp.float32), w.astype(jnp.float32), counts,
            precision=_HIGHEST, preferred_element_type=jnp.float32)
    n, d = xs.shape
    y = jax.lax.ragged_dot(
        jnp.stack(_pieces(xs), axis=1).reshape(3 * n, d), w, 3 * counts,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    return _add_pieces(jnp.moveaxis(y.reshape(n, 3, w.shape[2]), 1, 0))


@register_op("linear")
def _linear(ctx):
    """X [.., d], W [d, f] held in any dtype; Out float32 [.., f] =
    ``X @ W``, exact (:func:`exact_dot`). With attr ``transpose_w`` W is
    [f, d] and read as it lies (a head tied to the embedding)."""
    x, w = ctx.input("X"), ctx.input("W")
    tied = bool(ctx.attr("transpose_w"))
    y = exact_dot(x.reshape(-1, x.shape[-1]), w, tied)
    return {"Out": y.reshape(x.shape[:-1] + (w.shape[0 if tied else 1],))}


@register_op("rms_norm")
def _rms_norm(ctx):
    """X [.., d], Scale [d] or [group_size]; attrs epsilon, group_size (0:
    the whole last axis) and offset (absent: 0; the gain is ``offset +
    Scale``, a unit offset for a Scale that starts at zero). Y float32,
    X's shape."""
    x = ctx.input("X").astype(jnp.float32)
    w = ctx.input("Scale").astype(jnp.float32)
    if ctx.attr("offset"):
        w = ctx.attr("offset") + w
    eps = ctx.attr("epsilon", 1e-5)
    group = ctx.attr("group_size", 0)
    shape = x.shape
    if group:
        x = x.reshape(shape[:-1] + (shape[-1] // group, group))
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
    return {"Y": y.reshape(shape)}


def rotary_frequencies(rot, theta, yarn=None):
    """The ``rot // 2`` angular frequencies of a rotary turn over ``rot``
    lanes: ``theta^(-2i/rot)``, and with ``yarn`` (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``)
    the YaRN blend: pair ``i`` keeps its frequency below ``lo``, is
    slowed by ``factor`` above ``hi`` and is between the two in between,
    where ``lo`` / ``hi`` are the pairs that turn ``beta_fast`` /
    ``beta_slow`` times over the original context."""
    half = rot // 2
    i = jnp.arange(half, dtype=jnp.float32)
    freq = theta ** (-i * 2.0 / rot)
    if not yarn:
        return freq

    def corr(turns):
        return rot * math.log(yarn["original_max_position_embeddings"]
                              / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    lo = max(math.floor(corr(yarn["beta_fast"])), 0)
    hi = min(math.ceil(corr(yarn["beta_slow"])), rot - 1)
    ramp = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / yarn["factor"] * ramp


def _fence_rows(ctx, x):
    """``x`` [B, 1, H*D], a decode step's rows on their way into a per-head
    op, behind an optimization barrier (the identity on values).

    Why: the op splits the lanes into heads (``[B, 1, H, D]``), and left
    alone XLA fuses that split into the result of the projection that made
    ``x``, wants the projection's weight as ``[H, D, d]`` for it and copies
    the weight into that layout in every step: 33.5 MB written and read
    again to spare re-tiling 0.4 MB of rows (PERF.md, PR 44). A step's
    rows are the slots, always far fewer than a weight's, so the weight's
    side is never the cheaper one to relay: behind the barrier the product
    is compiled apart and reads its weight as it lies. Counted where the
    executor traces a step, not where a program's shapes are inferred."""
    if ctx.trace is not None:
        _ROTARY_FENCED.labels(op=ctx.op.type).inc()
    return jax.lax.optimization_barrier(x)


@register_op("rotary_embedding")
def _rotary_embedding(ctx):
    """X [B, T, H*D], Pos int (optional): [T] positions along the time
    axis, or with attr ``per_row`` [B], one position per batch row; absent,
    the positions are 0..T-1. attrs head_dim, theta; where the model has
    them ``lanes`` (lo, hi): the lanes of each head that turn (absent:
    all; the others pass), and ``yarn`` (:func:`rotary_frequencies`).
    Out: X's shape and dtype, the angles taken in float32.

    A ``per_row`` turn (a decode step: a row a slot) takes X from behind
    an optimization barrier (:func:`_fence_rows`)."""
    x = ctx.input("X")
    if ctx.attr("per_row", False):
        x = _fence_rows(ctx, x)
    hd = ctx.attr("head_dim")
    theta = ctx.attr("theta", 10000.0)
    lo, hi = ctx.attr("lanes") or (0, hd)
    b, t, dm = x.shape
    if ctx.has_input("Pos"):
        pos = ctx.input("Pos").reshape(-1).astype(jnp.float32)
        pos = pos.reshape((b, 1) if ctx.attr("per_row", False) else (1, t))
    else:
        pos = jnp.arange(t, dtype=jnp.float32).reshape(1, t)
    half = (hi - lo) // 2
    freq = rotary_frequencies(hi - lo, theta, ctx.attr("yarn"))
    ang = pos[..., None, None] * freq                   # [b|1, 1|t, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xh = x.astype(jnp.float32).reshape(b, t, dm // hd, hd)
    x1, x2 = xh[..., lo:lo + half], xh[..., lo + half:hi]
    out = jnp.concatenate([xh[..., :lo], x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin, xh[..., hi:]], -1)
    return {"Out": out.reshape(b, t, dm).astype(x.dtype)}


def route(x, router_w, bias, top_k, route_norm, route_scale,
          scoring="sigmoid"):
    """The router of ``moe_ffn``: x [n, d] -> (sel [n, k] expert ids,
    w [n, k] float32 weights), from float32 logits taken at the highest
    precision. ``scoring`` ``sigmoid``: scores are the logits' sigmoid,
    ``bias`` moves the selection only; ``softmax_bias``: the same with a
    softmax over all outputs for scores; ``softmax_topk``: the top k of
    the logits themselves, weighed by a softmax over the chosen k."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax_topk":
        top, sel = jax.lax.top_k(logits, top_k)
        return sel, jax.nn.softmax(top, axis=-1) * route_scale
    if scoring not in ("sigmoid", "softmax_bias"):
        raise ValueError("scoring is 'sigmoid', 'softmax_bias' or "
                         "'softmax_topk', not %r" % (scoring,))
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" else \
        jax.nn.softmax(logits, axis=-1)
    _, sel = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * route_scale


# Rows of one pass over the held experts where the op holds a share of
# them: what the 4,096-token prefill of a holder of 12 of 384 experts at
# top-8 expects twice over (4096 * 8 * 12 / 384 = 1,024 pairs)
SHARE_ROWS = 2048


def _expert_rows(xs, wg, wu, wd, counts, act=None):
    """``(silu(xs WGate) * (xs WUp)) WDown`` of rows sorted by expert,
    ``counts`` [E_held] rows an expert from row 0: xs [r, d] -> [r, d]
    float32, zeros in the rows past the last expert's. With ``act``
    ``relu2`` ``relu(xs WUp^T)^2 WDown``: no ``wg``, ``wu`` [E, f, d].
    Through ``pallas_moe``'s kernels where they admit the shapes."""
    relu2 = act == "relu2"
    if pallas_moe.admits(xs.shape[0], wu, act):
        interpret = kernel_path.interpret_mode()
        kernel_path.record("moe_grouped_matmul", interpret)
        ys = pallas_moe.expert_ffn(xs, wg, wu, wd, counts, interpret,
                                   act=act)
    else:
        kernel_path.record("moe_grouped_matmul")
        if relu2:
            inner = jnp.square(jax.nn.relu(exact_ragged_dot(
                xs, jnp.swapaxes(wu, 1, 2), counts)))
        else:
            inner = jax.nn.silu(exact_ragged_dot(xs, wg, counts)) * \
                exact_ragged_dot(xs, wu, counts)
        ys = exact_ragged_dot(inner, wd, counts)        # [r, d] float32
    # rows past the last group are nobody's: whatever they hold, they add 0
    return jnp.where((jnp.arange(xs.shape[0]) < jnp.sum(counts))[:, None],
                     ys, 0.0)


@register_op("moe_ffn")
def _moe_ffn(ctx):
    """X [.., d]; RouterW [d, E] and ExpertBias [E] (float32); WGate, WUp
    [E_held, d, f] and WDown [E_held, f, d], the held experts stacked;
    attrs num_experts, top_k, route_norm, route_scale, expert_offset, and
    where the router is no sigmoid ``scoring`` (:func:`route`).
    Out float32, X's shape: sum over the token's selected experts that
    are held of ``w * (silu(x WGate) * (x WUp)) WDown``. Counts [E_held]
    int32: the pairs each held expert took in this call.
    With attr ``act`` ``relu2`` (absent: SwiGLU) there is no WGate, WUp is
    [E_held, f, d] and the expert is ``relu(x WUp^T)^2 WDown``.
    With attr ``zero_experts`` Z (absent: 0) RouterW and ExpertBias are
    ``E + Z`` wide and a selected output ``>= E`` is an identity pair: it
    adds ``w * x`` to Out in float32, falls in no expert's group, and
    ZeroPairs [1] int32 counts the call's."""
    x = ctx.input("X")
    act = ctx.attr("act") or None
    wg = None if act == "relu2" else ctx.input("WGate")
    wu, wd = ctx.input("WUp"), ctx.input("WDown")
    k = ctx.attr("top_k")
    offset = ctx.attr("expert_offset", 0)
    held, d = wd.shape[0], x.shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    router_w = ctx.input("RouterW")
    zero = ctx.attr("zero_experts") or 0
    if router_w.shape[1] != ctx.attr("num_experts") + zero:
        raise ValueError("moe_ffn routes over %d experts, RouterW has %d"
                         % (ctx.attr("num_experts") + zero,
                            router_w.shape[1]))
    sel, w = route(x2, router_w, ctx.input("ExpertBias"), k,
                   ctx.attr("route_norm", True),
                   ctx.attr("route_scale", 1.0),
                   ctx.attr("scoring") or "sigmoid")
    # the n*k pairs sorted by held expert; pairs of experts held elsewhere
    # (and identity pairs, whose ids lie past every real expert's) sort
    # last, fall in no group and weigh nothing
    local = sel.reshape(-1) - offset
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key)                            # stable
    counts = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    pair_w = jnp.where(mine, w.reshape(-1), 0.0)
    # a share's pass is never longer than the kernels were measured for
    rows = n * k if held == ctx.attr("num_experts") else \
        min(n * k, SHARE_ROWS, pallas_moe.MAX_PAIRS_PER_EXPERT * held)
    if rows == n * k:
        # every pair in one pass
        ys = _expert_rows(x2[order // k], wg, wu, wd, counts, act)
        # back to the pairs' own order, then the weighted sum over a
        # token's k
        y = ys[jnp.argsort(order)] * pair_w[:, None]
        out = jnp.sum(y.reshape(n, k, d), axis=1)
    else:
        # a share of the experts takes a share of the pairs: the held
        # pairs lie first in the sorted order, and they are taken ``rows``
        # at a time until none is left. However many there are, none is
        # dropped, and the work and the temporaries are a pass's
        total = jnp.sum(counts)
        end = jnp.cumsum(counts)
        start = end - counts
        order = jnp.pad(order, (0, rows))

        def one_pass(i, out):
            first = i * rows
            pairs = jax.lax.dynamic_slice(order, (first,), (rows,))
            here = jnp.clip(jnp.minimum(end, first + rows)
                            - jnp.maximum(start, first), 0, rows)
            ys = _expert_rows(x2[pairs // k], wg, wu, wd, here, act)
            ys = jnp.where((first + jnp.arange(rows) < total)[:, None],
                           ys * pair_w[pairs][:, None], 0.0)
            return out.at[pairs // k].add(ys)

        out = jax.lax.fori_loop(0, (total + rows - 1) // rows, one_pass,
                                jnp.zeros((n, d), jnp.float32))
    if not zero:
        return {"Out": out.reshape(x.shape), "Counts": counts}
    with jax.named_scope("zero_expert_combine"):
        identity = sel >= ctx.attr("num_experts")
        out = out + jnp.sum(jnp.where(identity, w, 0.0), axis=1,
                            keepdims=True) * x2.astype(jnp.float32)
    return {"Out": out.reshape(x.shape), "Counts": counts,
            "ZeroPairs": jnp.sum(identity, dtype=jnp.int32).reshape(1)}
