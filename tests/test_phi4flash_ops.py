"""The Mamba-1 mixer's two ops (``ops/ssm_ops.py``) against the sequential
recurrence and differential attention in its packed form
(``ops/attention_ops.py``) against its equations, each beside the plain
reference of ``benchmarks/reference/phi4flash.py``. CPU, small sizes;
``tests/test_phi4flash_lm.py`` drives the whole model."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers

from benchmarks.architectures import phi4flash as arch
from benchmarks.reference import phi4flash as ref

pytestmark = [pytest.mark.generation, pytest.mark.paged]

# the catalog's keys at a small size: twelve layers are the plan's smallest
# with two cross layers and two gated memory units; 8 heads of 8 lanes in 4
# pairs on 2 KV pairs, a window of 8 rows, a state of 4 numbers a channel
CFG = dict(
    hidden_act="silu", tie_word_embeddings=True, mlp_bias=False,
    lm_head_bias=False, mb_per_layer=2, layer_norm_eps=1e-5,
    max_position_embeddings=4096, torch_dtype="float32",
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=True,
    hidden_size=32, num_attention_heads=8, num_key_value_heads=4,
    intermediate_size=48, sliding_window=8, mamba_d_state=4, mamba_d_conv=4,
    mamba_expand=2, mamba_dt_rank=4, num_hidden_layers=12, vocab_size=96,
    initializer_range=0.15, mamba_bc_init_factor=2.0, memory_from=6,
    kv_from=7)
CFG["layer_types"] = arch.layer_plan(CFG)
D, DI, N, K, R = 32, 64, 4, 4, 4


def _run(build, feed, sets=None, scope=None):
    """Build a program with ``build() -> fetch vars``, run its startup, set
    ``sets`` {name: array} and run it on ``feed``; -> (outputs, scope)."""
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 11
    scope = scope or ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.unique_name.guard(), \
            ptpu.program_guard(main, startup):
        fetch = build()
        exe = ptpu.Executor()
        exe.run(startup)
        for name, value in (sets or {}).items():
            scope.set_var(name, jnp.asarray(value))
        outs = exe.run(main, feed=feed, fetch_list=list(fetch))
    return [np.asarray(o) for o in outs], scope


def test_the_layer_plan_is_the_published_one():
    plan = arch.layer_plan(dict(num_hidden_layers=32, mb_per_layer=2))
    assert plan[:16] == ["mamba", "sliding_attention"] * 8
    assert plan[16:18] == ["mamba", "full_attention"]
    assert plan[18:] == ["gmu", "cross_attention"] * 7
    assert CFG["layer_types"] == (
        ["mamba", "sliding_attention"] * 3 + ["mamba", "full_attention"]
        + ["gmu", "cross_attention"] * 2)


# -- the mixer's two ops against the sequential recurrence --------------------

def _mixer_weights(seed=3):
    """Seeded weights of one mixer under the layer's names and, for the
    reference, under its own."""
    rs = np.random.RandomState(seed)
    w = {"in.w": rs.randn(D, 2 * DI) * 0.3, "x_dt.w": rs.randn(DI, R) * 0.3,
         "x_bc.w": rs.randn(DI, 2 * N) * 0.3, "dt.w": rs.randn(R, DI) * 0.4,
         "out.w": rs.randn(DI, D) * 0.3, "conv.w": rs.randn(K, DI) * 0.5,
         "conv.b": rs.randn(DI) * 0.1, "dt_bias": rs.uniform(-4, -1, DI),
         "a_log": np.log(rs.uniform(1, 16, (N, DI))), "d": rs.randn(DI)}
    w = {k: v.astype(np.float32) for k, v in w.items()}
    theirs = {"m." + mine: jnp.asarray(w[held]) for mine, held in ref._MIXER}
    return {"mix." + k: v for k, v in w.items()}, theirs


def _reference_mixer(x, theirs):
    """(out [T, d], m [T, D], the state after the last row, the
    convolution's last K inputs) of the reference's recurrence over x
    [T, d] alone."""
    with jax.default_matmul_precision("highest"):
        raw, _, xs, dt, b, c = ref.mamba_inputs(jnp.asarray(x), theirs, "m.")
        _, last = ref.mamba_scan(xs, dt, -jnp.exp(theirs["m.a_log"]), b, c)
        out, m = ref._mamba(jnp.asarray(x), theirs, "m.")
    tail = jnp.pad(raw, ((K, 0), (0, 0)))[x.shape[0]:]
    return tuple(np.asarray(v) for v in (out, m, last, tail))


def _pool_vars(rows):
    block = ptpu.default_main_program().global_block()
    return tuple(block.create_var(name="pool." + name, shape=shape,
                                  dtype=dtype, persistable=True,
                                  stop_gradient=True)
                 for name, shape, dtype in (
                     ("ssm", (rows, N, DI), "float32"),
                     ("conv", (rows, K, DI), "float32"),
                     ("at", (rows,), "int32")))


def _pool_values(rows, fill=0.0):
    return {"pool.ssm": np.full((rows, N, DI), fill, np.float32),
            "pool.conv": np.full((rows, K, DI), fill, np.float32),
            "pool.at": np.full((rows,), -7, np.int32)}


MAMBA = dict(d_inner=DI, state_dim=N, conv_width=K, dt_rank=R)


@pytest.mark.parametrize("bucket,length", [
    (24, 24), (32, 21), (32, 3), (8, 8)],
    ids=["unpadded", "ends_inside_the_bucket",
         "shorter_than_the_convolution", "one_trip_of_the_loop"])
def test_the_prefill_scan_leaves_the_recurrences_state_at_the_true_length(
        bucket, length):
    """The scan over a padded bucket against the reference's recurrence
    over the unpadded rows: the outputs and ``m`` of the real rows, and in
    the slot's row the state after row ``length - 1``, the last four
    inputs of the convolution before ``length`` and ``length`` itself.
    Other rows of the pool keep what they held."""
    mine, theirs = _mixer_weights()
    x = np.random.RandomState(bucket + length).randn(1, bucket, D) \
        .astype(np.float32)

    def build():
        xv = layers.data("x", shape=[1, bucket, D], dtype="float32",
                         append_batch_size=False)
        ln = layers.data("len", shape=[1], dtype="int32",
                         append_batch_size=False)
        tab = layers.data("tab", shape=[1], dtype="int32",
                          append_batch_size=False)
        pool = _pool_vars(3)
        out, m = layers.mamba1_mixer(xv, prefix="mix", dtype="float32",
                                     state=pool, table=tab, length=ln,
                                     **MAMBA)
        return [out, m] + list(pool)
    (out, m, ssm, conv, at), _ = _run(
        build, {"x": x, "len": np.array([length], np.int32),
                "tab": np.array([1], np.int32)},
        dict(mine, **_pool_values(3, fill=0.5)))
    want, want_m, last, tail = _reference_mixer(x[0, :length], theirs)
    np.testing.assert_allclose(out[0, :length], want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(m[0, :length], want_m, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ssm[1], last, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(conv[1], tail, rtol=1e-5, atol=1e-5)
    assert at.tolist() == [-7, length, -7]
    assert (ssm[[0, 2]] == 0.5).all() and (conv[[0, 2]] == 0.5).all()


def test_whole_sequences_scan_from_a_zero_state_each():
    mine, theirs = _mixer_weights()
    x = np.random.RandomState(5).randn(3, 11, D).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[3, 11, D], dtype="float32",
                         append_batch_size=False)
        return layers.mamba1_mixer(xv, prefix="mix", dtype="float32",
                                   **MAMBA)
    (out, m), _ = _run(build, {"x": x}, mine)
    for b in range(3):
        want, want_m, _, _ = _reference_mixer(x[b], theirs)
        np.testing.assert_allclose(out[b], want, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(m[b], want_m, rtol=1e-4, atol=1e-5)


def test_a_dead_table_entry_drops_the_prefills_write():
    mine, _ = _mixer_weights()
    x = np.random.RandomState(1).randn(1, 8, D).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[1, 8, D], dtype="float32",
                         append_batch_size=False)
        ln = layers.data("len", shape=[1], dtype="int32",
                         append_batch_size=False)
        tab = layers.data("tab", shape=[1], dtype="int32",
                          append_batch_size=False)
        pool = _pool_vars(2)
        layers.mamba1_mixer(xv, prefix="mix", dtype="float32", state=pool,
                            table=tab, length=ln, **MAMBA)
        return list(pool)
    (ssm, conv, at), _ = _run(
        build, {"x": x, "len": np.array([5], np.int32),
                "tab": np.array([2], np.int32)},
        dict(mine, **_pool_values(2, fill=0.5)))
    assert (ssm == 0.5).all() and (conv == 0.5).all() and (at == -7).all()


def _decode_program(slots):
    def build():
        xv = layers.data("x", shape=[slots, 1, D], dtype="float32",
                         append_batch_size=False)
        pos = layers.data("pos", shape=[slots], dtype="int32",
                          append_batch_size=False)
        tab = layers.data("tab", shape=[slots, 1], dtype="int32",
                          append_batch_size=False)
        pool = _pool_vars(slots)
        out, m = layers.mamba1_mixer(xv, prefix="mix", dtype="float32",
                                     state=pool, table=tab, pos=pos, **MAMBA)
        return [out, m] + list(pool)
    return build


def test_decode_steps_walk_the_recurrence_row_by_row():
    """Three slots at lengths of their own, stepped from a zero state: each
    step's output, its ``m`` and the rows left behind are the recurrence's;
    a slot whose table entry is dead (slot 1 in the odd steps) moves
    nothing and catches up later."""
    mine, theirs = _mixer_weights()
    rs = np.random.RandomState(4)
    xs = rs.randn(3, 9, D).astype(np.float32)       # slot, row, d
    scope = ptpu.Scope()
    state = dict(_pool_values(3), **{"pool.at": np.zeros(3, np.int32)})
    done = [0, 0, 0]
    outs, ms = [[], [], []], [[], [], []]
    for step in range(9):
        live = [True, step % 2 == 0, done[2] < 5]
        x = np.stack([xs[s, min(done[s], 8)] for s in range(3)])[:, None]
        (out, m, ssm, conv, at), _ = _run(
            _decode_program(3),
            {"x": x, "pos": np.asarray(done, np.int32),
             "tab": np.asarray([[s if live[s] else 3] for s in range(3)],
                               np.int32)}, dict(mine, **state), scope)
        for s in range(3):
            if live[s]:
                outs[s].append(out[s, 0])
                ms[s].append(m[s, 0])
                done[s] += 1
            else:
                np.testing.assert_array_equal(ssm[s], state["pool.ssm"][s])
                np.testing.assert_array_equal(conv[s], state["pool.conv"][s])
        state = {"pool.ssm": ssm, "pool.conv": conv, "pool.at": at}
        assert at.tolist() == done
    for s in range(3):
        want, want_m, last, tail = _reference_mixer(xs[s, :done[s]], theirs)
        np.testing.assert_allclose(np.stack(outs[s]), want, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.stack(ms[s]), want_m, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(state["pool.ssm"][s], last, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(state["pool.conv"][s], tail, rtol=1e-5,
                                   atol=1e-5)


def test_a_position_stepped_twice_is_absorbed_once():
    """The same feeds twice: the second run finds the rows one token
    further than its position, leaves them and gives the first run's
    output and ``m`` from the rows as stored."""
    mine, _ = _mixer_weights()
    rs = np.random.RandomState(6)
    state = {"pool.ssm": rs.randn(2, N, DI).astype(np.float32),
             "pool.conv": rs.randn(2, K, DI).astype(np.float32),
             "pool.at": np.asarray([11, 4], np.int32)}
    feed = {"x": rs.randn(2, 1, D).astype(np.float32),
            "pos": np.asarray([11, 4], np.int32),
            "tab": np.asarray([[0], [1]], np.int32)}
    first, _ = _run(_decode_program(2), feed, dict(mine, **state))
    again, _ = _run(_decode_program(2), feed, dict(mine, **{
        "pool.ssm": first[2], "pool.conv": first[3], "pool.at": first[4]}))
    assert first[4].tolist() == again[4].tolist() == [12, 5]
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    assert np.abs(first[2] - state["pool.ssm"]).max() > 1e-3


def test_a_state_pool_is_one_row_a_slot():
    with pytest.raises(Exception, match="row of its own index"):
        def build():
            xv = layers.data("x", shape=[2, 1, D], dtype="float32",
                             append_batch_size=False)
            pos = layers.data("pos", shape=[2], dtype="int32",
                              append_batch_size=False)
            tab = layers.data("tab", shape=[2, 1], dtype="int32",
                              append_batch_size=False)
            return list(layers.mamba1_mixer(
                xv, prefix="mix", dtype="float32", state=_pool_vars(3),
                table=tab, pos=pos, **MAMBA))
        _run(build, {})


# -- differential attention: the packed form against the equations ------------

def _diff_by_hand(q, k, v, lam, init, w, window=None):
    """The issue's equations in float64: q [T, H, D], k, v [T, Hkv, D] ->
    [T, H*D]: pair p's two softmaxes apart on heads of D lanes over the one
    value of 2D, ``o1 - lambda o2``, the norm over 2D lanes."""
    t, nh, hd = q.shape
    pairs, kv_pairs = nh // 2, k.shape[1] // 2
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = cols <= rows
    if window:
        mask &= rows - cols < window
    out = np.zeros((t, pairs, 2 * hd))
    for p in range(pairs):
        g = p // (pairs // kv_pairs)
        value = np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=1)
        o = []
        for s in (0, 1):
            score = q[:, 2 * p + s] @ k[:, 2 * g + s].T / np.sqrt(hd)
            score = np.where(mask, score, -np.inf)
            prob = np.exp(score - score.max(-1, keepdims=True))
            o.append(prob / prob.sum(-1, keepdims=True) @ value)
        d = o[0] - lam * o[1]
        out[:, p] = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) \
            * w * (1 - init)
    return out.reshape(t, nh * hd)


@pytest.mark.parametrize("window", [None, 5], ids=["full", "window"])
def test_differential_attention_at_heads_of_64_is_the_equations(window):
    """Heads of 64 lanes, so that a pair is one lane tile: the packed form
    (queries ``(q1 | 0)`` and ``(0 | q2)`` of 128 lanes on KV heads ``(k1 |
    k2)`` with the value ``(v1 | v2)``, one grouped-query attention, then
    the combine) against the two softmaxes computed apart."""
    t, nh, nkv, hd = 12, 8, 4, 64
    rs = np.random.RandomState(2)
    q, k, v = (rs.randn(1, t, h * hd).astype(np.float32) * 0.5
               for h in (nh, nkv, nkv))
    vecs = rs.randn(4, hd).astype(np.float32) * 0.1
    w = (1 + 0.1 * rs.randn(2 * hd)).astype(np.float32)
    init = 0.8 - 0.6 * np.exp(-0.3 * 3)

    def build():
        qv, kv, vv = (layers.data(n, shape=list(a.shape), dtype="float32",
                                  append_batch_size=False)
                      for n, a in (("q", q), ("k", k), ("v", v)))
        packed = layers.diff_attention_queries(qv, hd)
        assert packed.shape == (1, t, 2 * nh * hd)
        helper = ptpu.layer_helper.LayerHelper("attention")
        out = helper.create_tmp_variable("float32")
        attrs = {"num_heads": nh, "num_kv_heads": nkv // 2, "causal": True,
                 "ring_axis": None, "scale": hd ** -0.5}
        if window:
            attrs["window"] = window
        helper.append_op(
            type="multihead_attention",
            inputs={"Q": [packed.name], "K": [kv.name], "V": [vv.name]},
            outputs={"Out": [out.name]}, attrs=attrs)
        return [layers.diff_attention_combine(out, hd, init, "da")]
    sets = {"da.lambda_q1": vecs[0], "da.lambda_k1": vecs[1],
            "da.lambda_q2": vecs[2], "da.lambda_k2": vecs[3],
            "da.subln.w": w}
    (got,), _ = _run(build, {"q": q, "k": k, "v": v}, sets)
    lam = np.exp(vecs[0] @ vecs[1]) - np.exp(vecs[2] @ vecs[3]) + init
    want = _diff_by_hand(q[0].reshape(t, nh, hd).astype(np.float64),
                         k[0].reshape(t, nkv, hd).astype(np.float64),
                         v[0].reshape(t, nkv, hd).astype(np.float64),
                         lam, init, w.astype(np.float64), window)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    # and the reference's own form gives the same
    theirs = {"a.lambda_q1": vecs[0], "a.lambda_k1": vecs[1],
              "a.lambda_q2": vecs[2], "a.lambda_k2": vecs[3],
              "a.subln": w, "a.o": np.eye(nh * hd, dtype=np.float32),
              "a.o_b": np.zeros(nh * hd, np.float32)}
    theirs = {n: jnp.asarray(a) for n, a in theirs.items()}
    with jax.default_matmul_precision("highest"):
        plain = ref.diff_attention(
            jnp.asarray(q[0]).reshape(t, nh, hd),
            jnp.asarray(k[0]).reshape(t, nkv, hd),
            jnp.asarray(v[0]).reshape(t, nkv, hd), theirs, "a.", 3,
            dict(layer_norm_eps=1e-5), window)
    np.testing.assert_allclose(np.asarray(plain), want, rtol=2e-4, atol=2e-5)


def test_a_packed_query_scores_its_own_half_of_the_key_row():
    q = np.arange(1, 17, dtype=np.float32).reshape(1, 1, 16)

    def build():
        qv = layers.data("q", shape=[1, 1, 16], dtype="float32",
                         append_batch_size=False)
        return [layers.diff_attention_queries(qv, 4)]
    (got,), _ = _run(build, {"q": q})
    got = got.reshape(2, 2, 8)          # pair, which of the two, 2D lanes
    for p in range(2):
        first, second = q.reshape(2, 2, 4)[p]
        assert got[p, 0].tolist() == first.tolist() + [0] * 4
        assert got[p, 1].tolist() == [0] * 4 + second.tolist()


