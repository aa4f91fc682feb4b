"""The sparse-expert decoder LM (``models/moe_lm.py``) and what it forced:
the ops of its block each against a ``jax.numpy`` line of its own, the
expert feed-forward under imbalance and as shares of its experts, the
paged decode kernel with grouped queries and a window, and the whole model
through a session with two kinds of layer cache against the plain
reference of ``benchmarks/reference/afmoe.py``. CPU, small sizes."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.moe_lm import MoeLM, moe_lm, moe_lm_session
from paddle_tpu.models.transformer import transformer_lm_session
from paddle_tpu.observability import metrics, tracing
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.serving.paged_cache import (BLOCKS_IN_USE, CacheKind,
                                            LayerCache)

from benchmarks.reference import afmoe as ref

pytestmark = [pytest.mark.generation, pytest.mark.paged]

SLIDING, FULL = "sliding_attention", "full_attention"


def _run(build, feed):
    """Build a program with ``build() -> fetch vars``, run its startup and
    the program on ``feed``; -> (outputs, scope)."""
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 11
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.unique_name.guard(), \
            ptpu.program_guard(main, startup):
        fetch = build()
        exe = ptpu.Executor()
        exe.run(startup)
        outs = exe.run(main, feed=feed, fetch_list=list(fetch))
    return [np.asarray(o) for o in outs], scope


def _randomize(scope, names, rs, scale=1.0):
    for n in names:
        cur = np.asarray(scope.find_var(n))
        scope.set_var(n, jnp.asarray(
            scale * rs.standard_normal(cur.shape), cur.dtype))


# -- the ops, each against a line of its own ---------------------------------

@pytest.mark.parametrize("group", [0, 8], ids=["whole_axis", "per_head"])
def test_rms_norm_against_its_line(group):
    rs = np.random.RandomState(0)
    x = rs.randn(2, 5, 32).astype(np.float32)
    w = rs.randn(group or 32).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[5, 32], dtype="float32")
        return [layers.rms_norm(xv, epsilon=1e-5, group_size=group,
                                param_attr="n.w")]
    main, startup = ptpu.Program(), ptpu.Program()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        out, = build()
        exe = ptpu.Executor()
        exe.run(startup)
        scope.set_var("n.w", jnp.asarray(w))
        got, = exe.run(main, feed={"x": x}, fetch_list=[out])
    xs = x.reshape(2, 5, -1, group) if group else x
    want = xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, want.reshape(x.shape), rtol=1e-5,
                               atol=1e-6)


def _rotary_line(x, pos, hd, theta):
    """x [T, H, D] at positions pos [T], half-split pairs."""
    half = hd // 2
    ang = pos[:, None, None] * theta ** (-np.arange(half) * 2.0 / hd)
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def test_rotary_embedding_along_time_against_its_line():
    rs = np.random.RandomState(1)
    x = rs.randn(1, 6, 4 * 8).astype(np.float32)
    pos = np.array([3, 4, 5, 6, 7, 8], np.int32)

    def build():
        xv = layers.data("x", shape=[1, 6, 32], dtype="float32",
                         append_batch_size=False)
        pv = layers.data("p", shape=[6], dtype="int32",
                         append_batch_size=False)
        return [layers.rotary_embedding(xv, 8, theta=100.0, pos=pv),
                layers.rotary_embedding(xv, 8, theta=100.0)]
    (at_pos, from_zero), _ = _run(build, {"x": x, "p": pos})
    want = _rotary_line(x[0].reshape(6, 4, 8), pos.astype(np.float64), 8,
                        100.0)
    np.testing.assert_allclose(at_pos[0], want.reshape(6, 32), atol=1e-5)
    zero = _rotary_line(x[0].reshape(6, 4, 8), np.arange(6.0), 8, 100.0)
    np.testing.assert_allclose(from_zero[0], zero.reshape(6, 32), atol=1e-5)


def test_rotary_embedding_per_row_is_a_decode_steps_positions():
    """One position per batch row: row s of a decode step turns as row
    pos[s] of a sequence does; position 0 turns nothing; a turn keeps a
    head's length."""
    rs = np.random.RandomState(2)
    x = rs.randn(3, 1, 2 * 8).astype(np.float32)
    pos = np.array([0, 5, 17], np.int32)

    def build():
        xv = layers.data("x", shape=[3, 1, 16], dtype="float32",
                         append_batch_size=False)
        pv = layers.data("p", shape=[3], dtype="int32",
                         append_batch_size=False)
        return [layers.rotary_embedding(xv, 8, pos=pv, per_row=True)]
    (got,), _ = _run(build, {"x": x, "p": pos})
    for s in range(3):
        want = _rotary_line(x[s].reshape(1, 2, 8), pos[s:s + 1] * 1.0, 8,
                            10000.0)
        np.testing.assert_allclose(got[s, 0], want.reshape(16), atol=1e-5)
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)
    np.testing.assert_allclose(np.linalg.norm(got.reshape(3, 2, 8), axis=-1),
                               np.linalg.norm(x.reshape(3, 2, 8), axis=-1),
                               rtol=1e-5)


def test_swiglu_and_linear_hold_their_weights_in_the_dtype_given():
    rs = np.random.RandomState(3)
    x = rs.randn(2, 4, 16).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[4, 16], dtype="float32")
        return [layers.swiglu(xv, 24, "f32"),
                layers.swiglu(xv, 24, "bf", dtype="bfloat16")]
    (f32, bf), scope = _run(build, {"x": x})
    g, u, d = (np.asarray(scope.find_var("f32.%s.w" % n))
               for n in ("gate", "up", "down"))
    a = x @ g
    want = (a / (1 + np.exp(-a)) * (x @ u)) @ d
    np.testing.assert_allclose(f32, want, rtol=1e-4, atol=1e-6)
    assert scope.find_var("bf.gate.w").dtype == jnp.bfloat16
    assert bf.dtype == np.float32 and f32.dtype == np.float32
    # float32 activations against weights held in bfloat16, nothing rounded
    g, u, d = (np.asarray(scope.find_var("bf.%s.w" % n)).astype(np.float64)
               for n in ("gate", "up", "down"))
    a = x.astype(np.float64) @ g
    want = (a / (1 + np.exp(-a)) * (x @ u)) @ d
    np.testing.assert_allclose(bf, want, rtol=2e-6, atol=1e-9)


def test_three_bfloat16_pieces_are_the_float32_number():
    from paddle_tpu.ops.moe_ops import _pieces, exact_dot, exact_ragged_dot
    rs = np.random.RandomState(11)
    x = (rs.randn(6, 40) * np.exp(rs.randn(6, 40) * 3)).astype(np.float32)
    parts = _pieces(jnp.asarray(x))
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    total = sum(np.asarray(p).astype(np.float64) for p in parts)
    np.testing.assert_array_equal(total.astype(np.float32), x)
    # one pass over a bfloat16 weight gives what float64 gives, to float32
    w = jnp.asarray(rs.randn(40, 24) * 0.02, jnp.bfloat16)
    want = x.astype(np.float64) @ np.asarray(w).astype(np.float64)
    scale = np.abs(x).astype(np.float64) @ np.abs(np.asarray(w)
                                                   .astype(np.float64))
    got = np.asarray(exact_dot(jnp.asarray(x), w))
    assert np.max(np.abs(got - want) / scale) < 5e-7
    # against the rounded activation it is a bfloat16 ulp off, not exact
    rounded = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                         .astype(jnp.float32)).astype(np.float64)
    off = rounded @ np.asarray(w).astype(np.float64)
    assert np.max(np.abs(off - want) / scale) > 1e-4
    # rows sorted by group, a group empty: row by row the same numbers
    ws = jnp.asarray(rs.randn(3, 40, 24) * 0.02, jnp.bfloat16)
    counts = jnp.asarray([4, 0, 2], jnp.int32)
    got = np.asarray(exact_ragged_dot(jnp.asarray(x), ws, counts))
    for row, g in enumerate([0, 0, 0, 0, 2, 2]):
        want = x[row].astype(np.float64) @ \
            np.asarray(ws[g]).astype(np.float64)
        np.testing.assert_allclose(got[row], want, rtol=0,
                                   atol=5e-7 * np.abs(x[row]).sum() * 0.1)


# -- the expert feed-forward -------------------------------------------------

E, K, D, F = 16, 4, 24, 12


def _moe(x, offset=0, held=None, bias=None, scale=2.0, seed=5):
    """The op on x [n, D] with seeded weights: -> (out, counts, weights)."""
    def build():
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        return layers.moe_ffn(xv, E, K, F, "m", route_scale=scale,
                              expert_offset=offset, experts_held=held)
    main, startup = ptpu.Program(), ptpu.Program()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        out, counts = build()
        exe = ptpu.Executor()
        exe.run(startup)
        rs = np.random.RandomState(seed)
        full = {"router": rs.randn(D, E), "gate": rs.randn(E, D, F) * 0.3,
                "up": rs.randn(E, D, F) * 0.3, "down": rs.randn(E, F, D) * 0.3}
        n_held = held or E
        scope.set_var("m.router.w", jnp.asarray(full["router"], jnp.float32))
        if bias is not None:
            scope.set_var("m.expert_bias", jnp.asarray(bias, jnp.float32))
        for part in ("gate", "up", "down"):
            scope.set_var("m.experts.%s.w" % part, jnp.asarray(
                full[part][offset:offset + n_held], jnp.float32))
        got = exe.run(main, feed={"x": x}, fetch_list=[out, counts])
    full["bias"] = np.zeros(E) if bias is None else np.asarray(bias)
    return np.asarray(got[0]), np.asarray(got[1]), full


def _moe_line(x, w, scale=2.0, experts=range(E)):
    """Dense routing: every token through every expert, weighted by a mask
    of its normalised top-k scores."""
    s = 1 / (1 + np.exp(-(x @ w["router"])))
    sel = np.argsort(-(s + w["bias"]), axis=1, kind="stable")[:, :K]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = s[t, sel[t]]
        top = top / (top.sum() + 1e-20) * scale
        for e, wt in zip(sel[t], top):
            if e in experts:
                a = x[t] @ w["gate"][e]
                out[t] += wt * ((a / (1 + np.exp(-a)) * (x[t] @ w["up"][e]))
                                @ w["down"][e])
    return out, sel


def test_moe_ffn_against_dense_routing():
    x = np.random.RandomState(6).randn(10, D).astype(np.float32)
    got, counts, w = _moe(x)
    want, sel = _moe_line(x.astype(np.float64), w)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(counts, np.bincount(sel.ravel(),
                                                      minlength=E))
    assert counts.sum() == 10 * K


def test_moe_ffn_loses_no_token_when_all_go_to_the_same_experts():
    """A bias that sends every token to experts 3, 4, 5 and 9: four groups
    of 40 rows and twelve of none; every pair is computed."""
    x = np.random.RandomState(7).randn(40, D).astype(np.float32)
    bias = np.zeros(E)
    bias[[3, 4, 5, 9]] = 10.0
    got, counts, w = _moe(x, bias=bias)
    want, sel = _moe_line(x.astype(np.float64), w)
    assert set(sel.ravel()) == {3, 4, 5, 9}
    np.testing.assert_array_equal(counts[[3, 4, 5, 9]], [40] * 4)
    assert counts.sum() == 40 * K
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_moe_ffn_bias_moves_the_selection_not_the_weights():
    x = jnp.asarray(np.random.RandomState(8).randn(6, D), jnp.float32)
    rw = jnp.asarray(np.random.RandomState(9).randn(D, E), jnp.float32)
    bias = jnp.zeros(E).at[2].set(10.0)
    sel0, w0 = moe_ops.route(x, rw, jnp.zeros(E), K, True, 2.826)
    sel1, w1 = moe_ops.route(x, rw, bias, K, True, 2.826)
    assert (np.asarray(sel1) == 2).any(axis=1).all()
    np.testing.assert_allclose(np.asarray(w0).sum(1), 2.826, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(1), 2.826, rtol=1e-5)
    # the weight of expert 2 is its score's share, not its biased score's
    s = np.asarray(jax.nn.sigmoid(x @ rw))
    t = 0
    picked = np.asarray(sel1)[t]
    share = s[t, picked] / s[t, picked].sum() * 2.826
    np.testing.assert_allclose(np.asarray(w1)[t], share, rtol=1e-5)


def test_moe_ffn_shares_add_up_to_the_uncut_reference_layer():
    """16 experts held 4 at a time: the four partial results, with the
    shared expert counted once, equal the reference's whole layer."""
    x = np.random.RandomState(10).randn(12, D).astype(np.float32)
    parts, all_counts = [], []
    for offset in range(0, E, 4):
        got, counts, w = _moe(x, offset=offset, held=4)
        parts.append(got)
        all_counts.append(counts)
    rs = np.random.RandomState(11)
    shared = {n: rs.randn(*s) * 0.3 for n, s in
              (("gate", (D, F)), ("up", (D, F)), ("down", (F, D)))}
    cfg = dict(num_experts_per_tok=K, route_norm=True, route_scale=2.0)
    weights = {"l.router": w["router"], "l.expert_bias": np.zeros(E),
               "l.experts.gate": w["gate"], "l.experts.up": w["up"],
               "l.experts.down": w["down"]}
    weights = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    xj = jnp.asarray(x)
    sh = ref._swiglu(xj, *(jnp.asarray(shared[n], jnp.float32)
                           for n in ("gate", "up", "down")))
    whole = np.asarray(sh + ref._experts(xj, weights, "l.", cfg))
    np.testing.assert_allclose(sum(parts) + np.asarray(sh), whole,
                               rtol=2e-4, atol=2e-5)
    assert np.concatenate(all_counts).sum() == 12 * K
    # and the reference, given one share, gives that share
    cfg["expert_offset"] = 8
    weights.update({"l.experts." + n: weights["l.experts." + n][8:12]
                    for n in ("gate", "up", "down")})
    np.testing.assert_allclose(
        parts[2], np.asarray(ref._experts(xj, weights, "l.", cfg)),
        rtol=2e-4, atol=2e-5)


def test_moe_ffn_refuses_a_router_of_another_width():
    def build():
        xv = layers.data("x", shape=[4, D], dtype="float32",
                         append_batch_size=False)
        helper_out = layers.moe_ffn(xv, E, K, F, "m")
        # the op is told 2 x E experts but its router has E columns
        ptpu.default_main_program().global_block().ops[-1].attrs[
            "num_experts"] = 2 * E
        return helper_out
    with pytest.raises(Exception, match="routes over"):
        _run(build, {"x": np.zeros((4, D), np.float32)})


# -- the experts' matmuls as a weight stream (ops/pallas_moe.py) -------------

GE, GK, GD, GF = 8, 2, 256, 128         # a geometry that passes the gate


def _ffn_line(x, wg, wu, wd, counts):
    """float64 products of the three pieces, group by group; the inner
    activation held in float32 as the kernels hold it."""
    from paddle_tpu.ops.moe_ops import _pieces
    f64 = lambda a: np.asarray(a.astype(jnp.float32)).astype(  # noqa: E731
        np.float64)
    x64 = lambda a: sum(f64(p) for p in _pieces(jnp.asarray(a)))  # noqa: E731
    out, row = np.zeros((x.shape[0], wd.shape[2])), 0
    for g, c in enumerate(counts):
        rows = slice(row, row + c)
        a = x64(x[rows]) @ f64(wg[g])
        inner = a / (1 + np.exp(-a)) * (x64(x[rows]) @ f64(wu[g]))
        out[rows] = x64(inner.astype(np.float32)) @ f64(wd[g])
        row += c
    return out


@pytest.mark.parametrize("counts,n", [
    ([3, 0, 5, 17, 0, 1, 9, 2], 37),
    ([0, 0, 0, 150, 0, 0, 0, 0], 150),
    ([7, 13, 1, 3, 11, 5, 9, 2], 51),
    ([10, 0, 25, 5, 0, 0, 0, 0], 96),
    ([64, 64, 0, 0, 0, 0, 0, 64], 192),
    ([0] * 8, 16),
], ids=["experts_that_take_no_row", "one_expert_takes_every_row_of_three_tiles",
        "counts_off_the_sublane_tile", "rows_of_experts_held_elsewhere_last",
        "tiles_filled_to_the_row", "no_row_at_all"])
def test_streamed_expert_matmuls_against_ragged_dot_and_float64(counts, n):
    """The kernels in the interpreter: the rows that are some expert's
    equal ``exact_ragged_dot``'s and the float64 line to float32's
    sum-order noise; the rows past the last group are nobody's."""
    from paddle_tpu.ops import pallas_moe
    rs = np.random.RandomState(sum(counts) + n)
    x = (rs.randn(n, GD) * np.exp(rs.randn(n, GD))).astype(np.float32)
    wg, wu = (jnp.asarray(rs.randn(GE, GD, GF) * 0.05, jnp.bfloat16)
              for _ in range(2))
    wd = jnp.asarray(rs.randn(GE, GF, GD) * 0.05, jnp.bfloat16)
    c = jnp.asarray(counts, jnp.int32)
    got = np.asarray(pallas_moe.expert_ffn(jnp.asarray(x), wg, wu, wd, c,
                                           True))
    assert got.shape == (n, GD) and got.dtype == np.float32
    total = sum(counts)
    inner = jax.nn.silu(moe_ops.exact_ragged_dot(x, wg, c)) * \
        moe_ops.exact_ragged_dot(x, wu, c)
    ragged = np.asarray(moe_ops.exact_ragged_dot(inner, wd, c))[:total]
    line = _ffn_line(x, wg, wu, wd, counts)[:total]
    largest = np.abs(line).max() if total else 1.0
    assert np.abs(got[:total] - ragged).max(initial=0.0) < 1e-6 * largest
    assert np.abs(got[:total] - line).max(initial=0.0) < 1e-6 * largest


def test_the_kernels_pieces_are_the_ops_pieces_bit_for_bit():
    from paddle_tpu.ops import pallas_moe
    rs = np.random.RandomState(12)
    x = rs.randn(64, 256) * np.exp(rs.randn(64, 256) * 8)
    x[0, :4] = [0.0, -0.0, 1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -9]   # ties
    x = jnp.asarray(x, jnp.float32)
    for mine, theirs in zip(pallas_moe._pieces(x), moe_ops._pieces(x)):
        assert mine.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(mine.astype(jnp.float32)),
            np.asarray(theirs.astype(jnp.float32)))


def _moe_stream(x, dtype="bfloat16", d_ff=GF, offset=0, held=None):
    """``moe_ffn`` over GE experts on x [n, d] with seeded weights held in
    ``dtype``: -> (out, counts)."""
    def build():
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        return layers.moe_ffn(xv, GE, GK, d_ff, "m", route_scale=2.0,
                              expert_offset=offset, experts_held=held,
                              dtype=dtype, std=0.3)
    return _run(build, {"x": x})[0]


def _paths():
    from paddle_tpu.ops import kernel_path
    return dict(kernel_path.counts().get("moe_grouped_matmul", {}))


_GATE_CASES = [
    ("held_in_bfloat16", {}, "interpret"),
    ("on_a_chip", None, "compiled"),
    ("held_in_float32", {"dtype": "float32"}, "xla"),
    ("f_no_whole_lane_tile", {"d_ff": 64}, "xla"),
    ("more_pairs_an_expert_than_measured", {}, "xla"),
]


@pytest.mark.parametrize("case,how,path", _GATE_CASES,
                         ids=[c[0] for c in _GATE_CASES])
def test_moe_ffn_counts_the_path_its_shapes_chose(case, how, path,
                                                  monkeypatch):
    """The gate is a test of the call's shapes; each outcome is counted
    once a traced call in ``paddle_kernel_lowerings_total``."""
    from paddle_tpu.ops import kernel_path, pallas_moe
    x = np.random.RandomState(13).randn(24, GD).astype(np.float32)
    before = _paths()
    if case == "more_pairs_an_expert_than_measured":
        # 24 x 2 pairs on 8 experts are 6 each
        monkeypatch.setattr(pallas_moe, "MAX_PAIRS_PER_EXPERT", 5)
    if case == "on_a_chip":
        # building the program infers the op's shapes, which traces it and
        # counts; nothing is lowered
        monkeypatch.setattr(kernel_path, "interpret_mode", lambda: False)
        with ptpu.unique_name.guard(), ptpu.program_guard(
                ptpu.Program(), ptpu.Program()):
            xv = layers.data("x", shape=list(x.shape), dtype="float32",
                             append_batch_size=False)
            layers.moe_ffn(xv, GE, GK, GF, "m", dtype="bfloat16")
    else:
        _moe_stream(x, **how)
    after = _paths()
    grew = {p for p in after if after[p] != before.get(p, 0)}
    assert grew == {path}, (before, after)


@pytest.mark.parametrize("offset,held", [(0, None), (4, 4), (0, 2)],
                         ids=["all_held", "the_upper_half", "two_of_eight"])
def test_moe_ffn_through_the_stream_is_moe_ffn_through_ragged_dot(
        offset, held, monkeypatch):
    """The same op on the same weights with the gate open and shut: the
    same ``Counts``, and ``Out`` to float32's sum-order noise; the rows
    of experts held elsewhere add 0 either way."""
    from paddle_tpu.ops import pallas_moe
    x = np.random.RandomState(14).randn(40, GD).astype(np.float32)
    before = _paths()
    got, counts = _moe_stream(x, offset=offset, held=held)
    assert _paths().get("interpret", 0) > before.get("interpret", 0)
    monkeypatch.setattr(pallas_moe, "admits", lambda *a: False)
    want, want_counts = _moe_stream(x, offset=offset, held=held)
    np.testing.assert_array_equal(counts, want_counts)
    assert counts.sum() <= 40 * GK and (held is None) == (
        counts.sum() == 40 * GK)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-6 * np.abs(want).max()


# -- grouped queries and a window in the paged decode kernel -----------------

S, H, HKV, HD, BS, MB, NB, WINDOW = 5, 8, 2, 16, 4, 8, 40, 10
LENS = np.array([1, 7, 13, 30, 22])     # 13 - 10 and 30 - 10: mid-page


def _paged_case(window):
    rs = np.random.RandomState(12)
    q = jnp.asarray(rs.randn(S, 1, H * HD), jnp.float32)
    kp = jnp.asarray(rs.randn(NB, BS, HKV * HD), jnp.float32)
    vp = jnp.asarray(rs.randn(NB, BS, HKV * HD), jnp.float32)
    tabs = np.full((S, MB), NB, np.int32)
    perm = rs.permutation(NB)
    for s in range(S):
        n = -(-LENS[s] // BS)
        tabs[s, :n] = perm[s * 8:s * 8 + n]
    want = np.zeros((S, H * HD), np.float32)
    for s in range(S):
        lo = 0 if window is None else max(0, LENS[s] - window)
        rows = [tabs[s, j // BS] * BS + j % BS for j in range(lo, LENS[s])]
        kk = np.asarray(kp).reshape(NB * BS, HKV, HD)[rows]
        vv = np.asarray(vp).reshape(NB * BS, HKV, HD)[rows]
        for h in range(H):
            g = h // (H // HKV)
            sc = kk[:, g] @ np.asarray(q)[s, 0, h * HD:(h + 1) * HD] \
                / np.sqrt(HD)
            p = np.exp(sc - sc.max())
            want[s, h * HD:(h + 1) * HD] = (p / p.sum()) @ vv[:, g]
    if window is not None:
        # as a window kind of cache leaves it: entries behind the window dead
        for s in range(S):
            tabs[s, :max(0, LENS[s] - window) // BS] = NB
    return q, kp, vp, jnp.asarray(LENS), jnp.asarray(tabs), want


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_paged_decode_with_grouped_queries_against_plain_attention(
        path, window):
    q, kp, vp, lens, tabs, want = _paged_case(window)
    if path == "kernel":
        got = pa.decode_attention_paged(q, kp, vp, lens, tabs, H,
                                        interpret=True, num_kv_heads=HKV,
                                        window=window)
    else:
        got = pa._decode_paged_reference(q, kp, vp, lens, tabs, H, HKV,
                                         window)
    np.testing.assert_allclose(np.asarray(got)[:, 0], want, atol=2e-5)


def test_paged_decode_kernel_reads_nothing_behind_the_window():
    """Poison every block the window's table no longer names: the result
    does not move. A slot whose first LIVE entry is dead gives zeros."""
    q, kp, vp, lens, tabs, want = _paged_case(WINDOW)
    live = set(int(b) for b in np.asarray(tabs).ravel() if b < NB)
    dead = [b for b in range(NB) if b not in live]
    kp = kp.at[jnp.asarray(dead)].set(jnp.nan)
    vp = vp.at[jnp.asarray(dead)].set(jnp.nan)
    got = pa.decode_attention_paged(q, kp, vp, lens, tabs, H, interpret=True,
                                    num_kv_heads=HKV, window=WINDOW)
    np.testing.assert_allclose(np.asarray(got)[:, 0], want, atol=2e-5)
    starved = np.asarray(tabs).copy()
    starved[3, :] = NB
    got = pa.decode_attention_paged(q, kp, vp, lens, jnp.asarray(starved), H,
                                    interpret=True, num_kv_heads=HKV,
                                    window=WINDOW)
    assert (np.asarray(got)[3] == 0).all()
    np.testing.assert_allclose(np.asarray(got)[4, 0], want[4], atol=2e-5)


@pytest.mark.parametrize("window", [None, 6], ids=["full", "window"])
def test_prefill_in_row_blocks_against_plain_attention(window):
    """A 12-row window after 5 cached rows, taken 4 rows at a time: each
    block gathers only the pages its rows see."""
    from paddle_tpu.core import registry
    rs = np.random.RandomState(13)
    hist, p = 5, 12
    q = rs.randn(1, p, H * HD).astype(np.float32)
    kp = rs.randn(NB, BS, HKV * HD).astype(np.float32)
    vp = rs.randn(NB, BS, HKV * HD).astype(np.float32)
    table = np.full(MB, NB, np.int32)
    table[:5] = [7, 3, 11, 2, 30]

    class Op:
        type = "multihead_attention_prefill_paged"
        attrs = {"num_heads": H, "num_kv_heads": HKV, "block_rows": 4}
        inputs = outputs = {}
    if window:
        Op.attrs = dict(Op.attrs, window=window)
    vals = {"Q": [jnp.asarray(q)], "CacheK": [jnp.asarray(kp)],
            "CacheV": [jnp.asarray(vp)], "Table": [jnp.asarray(table)],
            "Hist": [jnp.asarray([hist])], "Len": [jnp.asarray([p])]}
    got = np.asarray(registry.get_op_def(Op.type).compute(
        registry.ExecContext(Op, vals))["Out"])[0]
    kk = kp.reshape(NB * BS, HKV, HD)
    vv = vp.reshape(NB * BS, HKV, HD)
    for i in range(p):
        pos = hist + i
        lo = 0 if not window else max(0, pos - window + 1)
        rows = [table[j // BS] * BS + j % BS for j in range(lo, pos + 1)]
        for h in range(H):
            g = h // (H // HKV)
            sc = kk[rows, g] @ q[0, i, h * HD:(h + 1) * HD] / np.sqrt(HD)
            pr = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                got[i, h * HD:(h + 1) * HD], (pr / pr.sum()) @ vv[rows, g],
                atol=2e-5)


# -- the cache kinds ---------------------------------------------------------

def test_layer_cache_frees_blocks_wholly_behind_the_window():
    kind = LayerCache(CacheKind("window", 8, 12, 2, "p", "d"), 4, 2, 6,
                      2 << 10)
    kind.tables[0] = [kind.pool.alloc() for _ in range(5)]   # rows 0..19
    assert list(kind.first_seen([9, 11, 19])) == [0, 1, 3]
    assert kind.trim(0, 0) == 0           # next query 9 sees rows 2..9
    assert kind.trim(0, 1) == 1           # 11 sees 4..11: block 0 goes
    assert kind.trim(0, 3) == 2           # 19 sees 12..19: blocks 1, 2 go
    assert kind.tables[0][:3] == [12, 12, 12] and kind.first[0] == 3
    row = np.full(6, 12, np.int32)
    kind.feed_row(row, 0)
    assert list(row[:3]) == [12] * 3 and all(row[3:5] < 12) and row[5] == 12
    kind.check_invariant()
    assert kind.pool.used_count() == 2
    kind.release(0)
    assert kind.pool.used_count() == 0 and kind.first[0] == 0
    kind.pool.close()


SIZES = dict(vocab_size=50, d_model=32, num_heads=4, num_kv_heads=2,
             head_dim=8, d_ff=48, moe_d_ff=16, num_experts=8, top_k=2,
             layer_types=[SLIDING, SLIDING, FULL, FULL], num_dense_layers=2,
             sliding_window=8, route_scale=2.0, embed_scale=32 ** 0.5)
CFG = dict(num_hidden_layers=4, num_dense_layers=2,
           layer_types=SIZES["layer_types"], num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, rms_norm_eps=1e-5,
           rope_theta=10000.0, sliding_window=8, num_experts_per_tok=2,
           route_norm=True, route_scale=2.0, mup_enabled=True, hidden_size=32)
T = 28


@pytest.fixture(autouse=True)
def _no_flash():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


@pytest.fixture(scope="module")
def model_scope():
    """A scope with the model's weights, randomised so that logits are of
    order one, and the whole-sequence program."""
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 7
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        loss, logits = moe_lm(toks, lbls, **SIZES)
        ptpu.Executor().run(startup)
    rs = np.random.RandomState(21)
    _randomize(scope, [n for n in scope.var_names()
                       if n.startswith("moe_lm.") and "norm" not in n
                       and "expert_bias" not in n], rs, scale=0.3)
    return scope, main, loss, logits


def _session(scope, flash=False, **kw):
    ptpu.config.set_flags(flash_attention=flash)
    args = dict(slots=3, cache_len=32, prompt_buckets=(8, 16), block_size=4,
                num_blocks=24, window_num_blocks=20)
    args.update(kw)
    return GenerationSession(moe_lm_session(**args, **SIZES), scope=scope)


def _decode_logits_name(spec):
    for op in spec.decode_program.global_block().ops:
        if op.type == "arg_max" and spec.decode_fetch in sum(
                op.outputs.values(), []):
            return op.inputs["X"][0]


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_past_the_window_equals_the_references_forward(
        model_scope, flash):
    """13 tokens prefilled at hist 0, then 15 decode steps: three windows
    past the window of 8, through both kinds of cache. Logits, not tokens."""
    scope, main, _, logits = model_scope
    rs = np.random.RandomState(22)
    seq = rs.randint(2, 50, T)
    w = ref.gather_weights(scope.find_var, CFG)
    want = np.asarray(ref.logits_at(w, jnp.asarray(seq), jnp.arange(T), CFG))
    assert np.abs(want).max() > 1.0
    with ptpu.scope_guard(scope):
        full = np.asarray(ptpu.Executor().run(
            main, feed={"toks": seq[None], "lbls": seq[None]},
            fetch_list=[logits])[0])[0]
    np.testing.assert_allclose(full, want, atol=5e-5)
    sess = _session(scope, flash=flash)
    name = _decode_logits_name(sess.spec)
    slot, first = sess.admit(seq[:13])
    assert first == int(want[12].argmax())
    for i in range(13, T):
        prepared = sess.step_prepare()
        prepared[2]["gen.dtok"][slot, 0] = seq[i]
        got = np.asarray(sess.exe.run(
            sess.spec.decode_program, feed=prepared[2],
            fetch_list=[name, sess.spec.decode_fetch], scope=scope)[0])
        sess.lengths[slot] += 1
        np.testing.assert_allclose(got[slot], want[i], atol=5e-5)
        sess.check_pool_invariant()
    window = sess.kinds[1]
    assert window.first[slot] == (T + 1 - 8) // 4
    assert window.pool.used_count() == -(-T // 4) - window.first[slot]
    assert sess.pool.used_count() == -(-T // 4)      # the full kind keeps all
    sess.close()


def _choices(scores, k):
    return np.sort(np.argsort(-scores, axis=-1)[..., :k], axis=-1)


def test_the_reference_handed_its_own_selections_gives_its_own_logits(
        model_scope):
    """``routed`` returns the expert layers' scores, and with every row's
    selection given as the reference itself makes it nothing moves."""
    scope = model_scope[0]
    seq = jnp.asarray(np.random.RandomState(23).randint(2, 50, T))
    w = ref.gather_weights(scope.find_var, CFG)
    want = np.asarray(ref.logits_at(w, seq, jnp.arange(T), CFG))
    free, scores = ref.routed(w, seq, jnp.arange(T), CFG)
    np.testing.assert_array_equal(np.asarray(free), want)
    assert scores.shape == (2, T, 8)
    ids = _choices(np.asarray(scores), 2)
    given, again = ref.routed(
        w, seq, jnp.arange(T), CFG,
        [(jnp.ones(T, bool), jnp.asarray(ids[li])) for li in range(2)])
    np.testing.assert_allclose(np.asarray(given), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(again), np.asarray(scores),
                               atol=1e-6)


def test_one_swapped_expert_moves_a_rows_logits_far_more_than_rounding(
        model_scope):
    """The discontinuity the serving check meets: row 20 of the first
    expert layer takes its third-best expert in place of its second-best.
    Rows before it do not move and row 20 moves by a large share of the
    largest logit; in the layer alone only the row handed a choice moves."""
    scope = model_scope[0]
    seq = jnp.asarray(np.random.RandomState(24).randint(2, 50, T))
    w = ref.gather_weights(scope.find_var, CFG)
    free, scores = map(np.asarray, ref.routed(w, seq, jnp.arange(T), CFG))
    order = np.argsort(-scores[0, 20])
    swapped = np.zeros((T, 2), np.int32)
    swapped[20] = sorted((order[0], order[2]))
    rows = np.zeros(T, bool)
    rows[20] = True
    given = np.asarray(ref.routed(
        w, seq, jnp.arange(T), CFG,
        [(jnp.asarray(rows), jnp.asarray(swapped)), None])[0])
    np.testing.assert_array_equal(given[:20], free[:20])
    moved = np.abs(given - free).max(axis=1) / np.abs(free).max()
    assert moved[20] > 0.05
    m = np.random.RandomState(25).randn(6, 32).astype(np.float32)
    weights = {"l." + k: w["l2." + k] for k in (
        "router", "expert_bias", "experts.gate", "experts.up",
        "experts.down")}
    s = np.asarray(jax.nn.sigmoid(m @ np.asarray(weights["l.router"])))
    ids = _choices(s, 2)
    ids[3] = sorted(np.argsort(-s[3])[[0, 2]])
    want = np.asarray(ref._experts(
        jnp.asarray(m), weights, "l.", CFG,
        (jnp.arange(6) == 3, jnp.asarray(ids))))
    free_layer = np.asarray(ref._experts(jnp.asarray(m), weights, "l.", CFG))
    assert np.abs(want[3] - free_layer[3]).max() > 1e-3
    np.testing.assert_array_equal(np.delete(want, 3, 0),
                                  np.delete(free_layer, 3, 0))


def test_window_blocks_are_freed_and_the_books_balance(model_scope):
    scope = model_scope[0]
    sess = _session(scope)
    freed0 = _counter("paddle_generation_kv_window_blocks_freed_total")
    slots = [sess.admit(np.arange(2, 2 + n))[0] for n in (15, 5)]
    window, full = sess.kinds[1], sess.kinds[0]
    # a prompt of 15 holds rows 8.. for the query at 15: blocks 0 and 1 go
    assert window.first[slots[0]] == 2 and window.first[slots[1]] == 0
    assert window.tables[slots[0]][:2] == [20, 20]
    for _ in range(10):
        sess.step()
        sess.check_pool_invariant()
    assert [len(t) for t in full.tables[:2]] == [7, 4]
    # trimmed as the last step was prepared, at lengths 24 and 14: the
    # queries at 24 and 14 see rows 17.. and 7..
    assert list(window.first[:2]) == [(24 + 1 - 8) // 4, (14 + 1 - 8) // 4]
    assert window.pool.used_count() == (7 - 4) + (4 - 1)
    assert full.pool.used_count() == 7 + 4
    assert _counter("paddle_generation_kv_window_blocks_freed_total") \
        - freed0 == 4 + 1
    sess.retire(slots[0])
    sess.check_pool_invariant()
    assert window.pool.used_count() == 3 and full.pool.used_count() == 4
    sess.close()


def test_each_kind_has_its_own_gauge_and_pool(model_scope):
    sess = _session(model_scope[0])
    labels = {k.pool._label for k in sess.kinds}
    assert len(labels) == 2
    sess.admit(np.arange(2, 17))         # 15 rows: 4 blocks, 2 of them seen
    got = {dict(l)["pool"]: v for l, v in _children(BLOCKS_IN_USE)
           if dict(l)["pool"] in labels}
    assert got == {sess.kinds[0].pool._label: 4, sess.kinds[1].pool._label: 2}
    sess.close()


def test_admission_waits_for_the_window_pool_too(model_scope):
    sess = _session(model_scope[0], window_num_blocks=5)
    assert sess.admit_ok(16) and not sess.admit_ok(24)
    assert sess.storable(32)            # 4 blocks of a prompt, or 8/4 + 2
    sess.admit(np.arange(2, 18))        # 4 blocks, trimmed to 2
    assert sess.kinds[1].pool.free_count() == 3
    assert sess.admit_ok(12) and not sess.admit_ok(16)
    sess.close()
    tight = _session(model_scope[0], window_num_blocks=3)
    assert not tight.storable(16)
    tight.close()


def test_a_window_kind_takes_neither_prefix_cache_nor_speculation(
        model_scope):
    spec = moe_lm_session(slots=2, cache_len=32, prompt_buckets=(8,),
                          block_size=4, num_blocks=16, window_num_blocks=16,
                          **SIZES)
    spec.prefix_cache = True
    with pytest.raises(ValueError, match="window kind"):
        GenerationSession(spec, scope=model_scope[0])
    with pytest.raises(ValueError, match="speculative draft"):
        MoeLM(**SIZES).draft(None)


def test_a_model_of_window_layers_alone_has_one_kind(model_scope):
    sizes = dict(SIZES, layer_types=[SLIDING] * 4)
    spec = moe_lm_session(slots=2, cache_len=32, prompt_buckets=(8, 16),
                          block_size=4, num_blocks=16, **sizes)
    assert [k.name for k in spec.cache_kinds] == ["window"]
    assert spec.cache_kinds[0].layers == 4
    sess = GenerationSession(spec, scope=model_scope[0])
    slot, _ = sess.admit(np.arange(2, 16))
    for _ in range(8):
        sess.step()
        sess.check_pool_invariant()
    assert sess.pool.used_count() == 6 - (22 + 1 - 8) // 4
    sess.close()


def test_unknown_layer_type_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        MoeLM(**dict(SIZES, layer_types=["linear_attention"] * 4))


def _digest(spec):
    """Every op, variable and attribute of a spec's programs, its feeds
    and its cache variables; where the spec has them, its verify program
    and its kinds of layer cache too."""
    out = []
    progs = [("decode", spec.decode_program), ("copy", spec.copy_program)] + \
        [("prefill%d" % b, p)
         for b, p in sorted(spec.prefill_programs.items())]
    if spec.verify_program is not None:
        progs.append(("verify", spec.verify_program))
    for tag, prog in progs:
        for blk in prog.blocks:
            for name in sorted(blk.vars):
                v = blk.vars[name]
                out.append((tag, "var", name, tuple(v.shape or ()),
                            str(v.dtype), bool(v.persistable)))
            for op in blk.ops:
                out.append((tag, "op", op.type, sorted(op.inputs.items()),
                            sorted(op.outputs.items()),
                            sorted((k, repr(v))
                                   for k, v in op.attrs.items())))
    out.append((spec.cache_vars, spec.prefill_feeds, spec.decode_feeds,
                spec.prefill_fetch, spec.decode_fetch, spec.num_blocks,
                spec.max_blocks, spec.copy_feeds))
    # a spec of the one full kind named no kinds when these were digested
    kinds = spec.cache_kinds
    if [(k.name, k.window, k.prefill_table) for k in kinds] == [
            ("full", None, "gen.ptab")]:
        kinds = None
    more = (spec.verify_feeds, spec.verify_fetch, kinds, spec.stats_fetch)
    if any(m is not None for m in more):
        out.append(more)
    return hashlib.sha256(repr(out).encode()).hexdigest()


def test_a_one_kind_specs_programs_are_the_parents_byte_for_byte():
    """The GPT-2 block's paged programs, digested at the parent commit
    (8f57ee0, before ``lm_session`` was cut out of
    ``transformer_lm_session`` and the ops took their new attributes)."""
    spec = transformer_lm_session(
        29, d_model=16, num_heads=2, d_ff=32, num_layers=2, max_len=24,
        slots=3, cache_len=24, prompt_buckets=(4, 8), bos_id=0, eos_id=1,
        paged=True, block_size=4, num_blocks=24, prefix_cache=False,
        cache_ns="kv", decode_policy=None)
    assert _digest(spec) == ("08b806694281316436c1e61cf58a455d"
                             "00eaafe3d70b950487c947a726d05c26")
    assert spec.cache_kinds == (CacheKind(
        "full", None, 24, 2, "gen.ptab", "gen.dtab"),)
    assert spec.stats_fetch is None
    sess = GenerationSession(spec, scope=ptpu.Scope())
    assert len(sess.kinds) == 1 and not sess._window_kinds
    assert sess.pool is sess.kinds[0].pool \
        and sess.tables is sess.kinds[0].tables
    assert sess._decode_fetches == [spec.decode_fetch]
    sess.close()


def test_a_two_kind_specs_programs_are_the_parents_byte_for_byte():
    """This file's model, full and window layers, digested at 119a506,
    before the dense per-slot layout left ``lm_session``. (Digested again
    in PR 42: ``CacheKind`` took the fields ``aligned`` and ``chunk``,
    which its ``repr`` in the digest shows; with the kinds written as the
    parent wrote them the programs still give 9c946466...b1059d. And in PR
    49, for the field ``borrowers``: written without it the digest is still
    PR 42's bc6009de...c85038.)"""
    spec = moe_lm_session(slots=3, cache_len=32, prompt_buckets=(8, 16),
                          block_size=4, num_blocks=24, window_num_blocks=20,
                          cache_ns="kv", **SIZES)
    assert _digest(spec) == ("caa58ff4171535fcd14a2c6f2bc38871"
                             "9aaddb2f92597cc32144270abebe87c2")


@pytest.mark.parametrize("policy,digest", [
    (dict(kind="greedy"), "4437c28b911221139a86c01a0d50008e"
                          "be87399dd894dca9800185eda2435424"),
    (dict(kind="sample", temperature=0.8, top_k=5),
     "9d0804ce2a4fddca976f5e6876b0eb6e2e3c2e6aa54de3595a06b23e6d5daaf1")],
    ids=["greedy", "sampled"])
def test_a_speculative_targets_programs_are_the_parents_byte_for_byte(
        policy, digest):
    """The GPT-2 block's decode, copy, prefill and verify programs under
    ``speculate_k=2`` with the prefix index armed, digested at 119a506.
    The draft's programs are not in it: they are the ones that changed,
    from dense rows to a pool."""
    from paddle_tpu.serving.decoding import DecodePolicy
    spec = transformer_lm_session(
        29, d_model=16, num_heads=2, d_ff=32, num_layers=2, max_len=24,
        slots=3, cache_len=24, prompt_buckets=(4, 8), bos_id=0, eos_id=1,
        paged=True, block_size=4, num_blocks=24, prefix_cache=True,
        cache_ns="kv", decode_policy=DecodePolicy(speculate_k=2, **policy))
    assert _digest(spec) == digest
    draft = spec.draft_spec
    assert (draft.block_size, draft.num_blocks, draft.prefix_cache) == \
        (4, 3 * 6, False)
    assert draft.policy is None and draft.draft_spec is None


# -- counters and spans ------------------------------------------------------

def _children(metric):
    """(labels, value) of a labelled metric's children."""
    return [(tuple(sorted(l.items())), p)
            for n, _, _, _, ch in metrics.REGISTRY.snapshot()
            if n == metric.name for l, p in ch]


def _counter(name):
    for n, kind, _, _, children in metrics.REGISTRY.snapshot():
        if n == name:
            return sum(float(p) for _, p in children)
    return 0.0


COUNTERS = ("paddle_generation_moe_layer_steps_total",
            "paddle_generation_experts_touched_total",
            "paddle_generation_expert_assignments_total",
            "paddle_generation_expert_max_load_total",
            "paddle_generation_window_context_tokens_total")


def test_the_routing_and_window_counters_add_up_by_hand(model_scope):
    """Four decode steps of a 3-slot session with two expert layers and
    two window layers; the routing of each step is read back from the
    step's own fetch and counted by hand."""
    scope = model_scope[0]
    sess = _session(scope)
    sess.admit(np.arange(2, 9))          # 7 rows
    sess.admit(np.arange(3, 15))         # 12 rows
    before = {c: _counter(c) for c in COUNTERS}
    by_hand = dict.fromkeys(COUNTERS, 0)
    for _ in range(4):
        prepared = sess.step_prepare()
        counts = np.asarray(sess.exe.run(
            sess.spec.decode_program, feed=prepared[2],
            fetch_list=[sess.spec.stats_fetch], scope=scope)[0])
        assert counts.shape == (2, 8) and (counts.sum(1) == 3 * 2).all()
        out = sess.step_run(prepared)
        assert sorted(out) == [0, 1]
        by_hand[COUNTERS[0]] += 2
        by_hand[COUNTERS[1]] += int((counts > 0).sum())
        by_hand[COUNTERS[2]] += 2 * 3 * 2
        by_hand[COUNTERS[3]] += int(counts.max(1).sum())
        by_hand[COUNTERS[4]] += 2 * sum(min(int(sess.lengths[s]), 8)
                                        for s in out)
    assert {c: _counter(c) - before[c] for c in COUNTERS} == by_hand
    # 7 -> 8, 8, 8, 8 and 12 -> 8 each step: both at the window's width
    assert by_hand[COUNTERS[4]] == 2 * 4 * (8 + 8)
    sess.close()


def test_window_trim_lies_inside_step_prepare(model_scope):
    scope = model_scope[0]
    sess = _session(scope)
    sess.generate(np.arange(2, 8), max_new_tokens=2)       # compile
    tracing.start(clear=True)
    try:
        sched = GenerationScheduler(sess, deadline_ms=0)
        out = sched.submit(np.arange(2, 12), max_new_tokens=9,
                           eos_id=-1).result(timeout=120)
        tid = sched._thread.ident
        sched.close()
    finally:
        tracing.stop()
    events = [e for e in tracing.events()
              if e["ph"] == "X" and e["tid"] == tid]
    tracing.clear()
    assert len(out) == 9
    trims = [e for e in events if e["name"] == "session:window_trim"]
    prepares = [e for e in events if e["name"] == "session:step_prepare"]
    assert len(trims) == len(prepares) == 8
    for t in trims:
        inside = [p for p in prepares
                  if p["ts"] - 0.5 <= t["ts"] and
                  t["ts"] + t["dur"] <= p["ts"] + p["dur"] + 0.5]
        assert len(inside) == 1
        assert t["args"]["round"] == inside[0]["args"]["round"]
    sess.close()


def test_the_scheduler_serves_what_the_session_generates(model_scope):
    scope = model_scope[0]
    prompts = [np.arange(2, 2 + n) for n in (5, 11, 14)]
    sess = _session(scope)
    alone = [sess.generate(p, max_new_tokens=12, eos_id=-1) for p in prompts]
    sched = GenerationScheduler(sess, deadline_ms=0)
    futures = [sched.submit(p, max_new_tokens=12, eos_id=-1)
               for p in prompts]
    together = [f.result(timeout=120) for f in futures]
    sched.close()
    for a, b in zip(alone, together):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sess.check_pool_invariant()
    assert all(k.pool.used_count() == 0 for k in sess.kinds)
    sess.close()
