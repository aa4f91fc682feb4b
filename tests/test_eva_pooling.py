"""``eva_ops.pool_chunks`` on rows that are never split into heads (PR 44)
against the form it replaced, which split them (``[.., C, H, D]``, every
row made float32 first) and is kept here as the reference: the function on
the shapes the op's two forms hand it, and the op ``eva_summaries`` in both forms
through a Program. CPU; chunks of 16, heads 4 x 16 lanes and the
published 32 x 128."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import eva_ops

C = 16
HEADS = [(4, 16), (32, 128)]
DTYPES = [jnp.bfloat16, jnp.float32]
# float32 results, as a share of the largest value pooled
TOL = 1e-6


def split_pool_chunks(k, v, mu, phi):
    """The parent's ``pool_chunks``: k, v [.., C, H, D], mu, phi [H, D]
    -> (kbar, vbar) [.., H, D] float32."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    def weights(w):
        s = jnp.sum(k32 * w.astype(jnp.float32), axis=-1, keepdims=True)
        return jax.nn.softmax(s, axis=-3)               # over the C rows
    return (jnp.sum(weights(mu) * k32, axis=-3),
            jnp.sum(weights(phi) * v32, axis=-3))


def _draw(seed, shape, nh, hd, dtype):
    """Rows k, v of ``shape + (H*D,)`` and the two learned vectors as the
    model draws them (Normal(0, 1) within a deviation, times D^-1/2), in
    ``dtype``."""
    rs = np.random.RandomState(seed)
    k, v = (jnp.asarray(rs.randn(*shape, nh * hd), dtype) for _ in "kv")
    mu, phi = (jnp.asarray(np.clip(rs.randn(nh * hd), -1, 1) * hd ** -0.5,
                           dtype) for _ in "mp")
    return k, v, mu, phi


def _close(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= TOL * np.abs(want).max(), \
        np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("nh,hd", HEADS, ids=["4x16", "32x128"])
@pytest.mark.parametrize("shape", [(2, 3 * C), (5, C)],
                         ids=["rows", "blocks"])
def test_pooling_the_rows_as_they_lie_is_the_split_pooling(shape, nh, hd,
                                                           dtype):
    """A prompt's rows ``[B, n C, H*D]`` and a decode step's gathered
    blocks ``[S, C, H*D]``: float32 sums within 1e-6 of the values' scale of
    the form that split the heads, float32 out whatever the rows are."""
    k, v, mu, phi = _draw(0, shape, nh, hd, dtype)
    kbar, vbar = eva_ops.pool_chunks(k, v, mu, phi, nh, C)
    assert kbar.dtype == vbar.dtype == jnp.float32
    assert kbar.shape == vbar.shape == (shape[0], shape[1] // C, nh * hd)
    heads = (shape[0], shape[1] // C, C, nh, hd)
    rk, rv = split_pool_chunks(k.reshape(heads), v.reshape(heads),
                               mu.reshape(nh, hd), phi.reshape(nh, hd))
    _close(kbar, rk.reshape(kbar.shape))
    _close(vbar, rv.reshape(vbar.shape))


def test_a_weight_goes_back_over_its_heads_lanes_exactly():
    """Chunks of one row: the softmax over it is 1, spread over the lanes
    as three pieces times a one each, so the summary is the row itself, bit
    for bit; and a chunk of equal rows is that row to a rounding."""
    k, v, mu, phi = _draw(1, (7, 3), 4, 16, jnp.float32)
    kbar, vbar = eva_ops.pool_chunks(k, v, mu, phi, 4, 1)
    assert np.array_equal(np.asarray(kbar), np.asarray(k))
    assert np.array_equal(np.asarray(vbar), np.asarray(v))
    same = jnp.repeat(k, C, axis=1)
    kbar, _ = eva_ops.pool_chunks(same, same, mu, phi, 4, C)
    np.testing.assert_allclose(np.asarray(kbar), np.asarray(k),
                               rtol=3e-7, atol=1e-7)


def _run(build, feed):
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.scope_guard(ptpu.Scope()), ptpu.unique_name.guard(), \
            ptpu.program_guard(main, startup):
        fetch = build()
        outs = ptpu.Executor().run(main, feed=feed, fetch_list=list(fetch))
    return [np.asarray(o) for o in outs]


def _summaries(inputs, nh):
    helper = LayerHelper("t")
    outs = [helper.create_tmp_variable("float32") for _ in "kv"]
    helper.append_op(type="eva_summaries",
                     inputs={s: [x.name] for s, x in inputs.items()},
                     outputs={"KBar": [outs[0].name], "VBar": [outs[1].name]},
                     attrs={"num_heads": nh, "chunk": C})
    return outs


def _data(name, array):
    return layers.data(name, shape=list(array.shape),
                       dtype=str(np.asarray(array).dtype),
                       append_batch_size=False)


@pytest.mark.parametrize("nh,hd", HEADS, ids=["4x16", "32x128"])
def test_the_op_pools_a_prompts_rows_with_a_partial_last_chunk(nh, hd):
    """K, V [B, T, H*D] with T no whole number of chunks: one row a chunk,
    the last pooled with zero rows, as the split form did."""
    t = 2 * C + 5
    k, v, mu, phi = (np.asarray(x) for x in
                     _draw(2, (2, t), nh, hd, jnp.float32))
    kbar, vbar = _run(lambda: _summaries(
        {"K": _data("k", k), "V": _data("v", v), "Mu": _data("mu", mu),
         "Phi": _data("phi", phi)}, nh), {"k": k, "v": v, "mu": mu,
                                          "phi": phi})
    assert kbar.shape == vbar.shape == (2, 3, nh * hd)
    pad = ((0, 0), (0, 3 * C - t), (0, 0))
    heads = (2, 3, C, nh, hd)
    rk, rv = split_pool_chunks(
        jnp.pad(k, pad).reshape(heads), jnp.pad(v, pad).reshape(heads),
        jnp.asarray(mu).reshape(nh, hd), jnp.asarray(phi).reshape(nh, hd))
    _close(kbar, rk.reshape(kbar.shape))
    _close(vbar, rv.reshape(vbar.shape))


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
def test_the_op_pools_a_decode_steps_blocks_through_the_table(dtype):
    """CacheK, CacheV [NB, C, H*D], Pos, Table: the block that holds row
    Pos[s], in the pools' dtype; a dead table entry (the pool's size, what
    an idle slot's row holds) reads some block of the pool and fails
    nothing."""
    nh, hd, nb = 4, 16, 6
    ck, cv, mu, phi = _draw(3, (nb, C), nh, hd, dtype)
    pos = np.array([C + 3, 2 * C - 1, 0, 5], np.int32)
    table = np.array([[4, 1, nb], [2, 5, nb], [3, nb, nb], [nb, nb, nb]],
                     np.int32)
    feed = {"ck": np.asarray(ck.astype(jnp.float32)),
            "cv": np.asarray(cv.astype(jnp.float32)),
            "mu": np.asarray(mu.astype(jnp.float32)),
            "phi": np.asarray(phi.astype(jnp.float32)),
            "pos": pos, "tab": table}

    def build():
        cast = {n: layers.cast(_data(n, feed[n]), jnp.dtype(dtype).name)
                for n in ("ck", "cv", "mu", "phi")}
        return _summaries(
            {"CacheK": cast["ck"], "CacheV": cast["cv"], "Mu": cast["mu"],
             "Phi": cast["phi"], "Pos": _data("pos", pos),
             "Table": _data("tab", table)}, nh)
    kbar, vbar = _run(build, feed)
    assert kbar.shape == vbar.shape == (4, 1, nh * hd)
    assert kbar.dtype == vbar.dtype == jnp.dtype(dtype)
    blocks = jnp.asarray([1, 5, 3])
    heads = (3, C, nh, hd)
    rk, rv = split_pool_chunks(
        ck[blocks].reshape(heads), cv[blocks].reshape(heads),
        mu.reshape(nh, hd), phi.reshape(nh, hd))
    for got, want in ((kbar, rk), (vbar, rv)):
        want = np.asarray(want.astype(dtype).astype(jnp.float32))
        got = np.asarray(jnp.asarray(got).astype(jnp.float32))
        # in the pools' dtype: equal but where a sum lies on a rounding edge
        np.testing.assert_allclose(
            got[:3, 0], want.reshape(3, -1), rtol=0,
            atol=(2 ** -7 if dtype == jnp.bfloat16 else TOL)
            * np.abs(want).max())
    assert np.isfinite(np.asarray(kbar, np.float32)[3]).all()
