"""Latent attention (``ops/mla_ops.py``), the latent kind of layer cache and
the expert feed-forward as a share of its experts, and the whole
``kimi_k2``-shaped model (``models/moe_lm.py`` with the latent block)
through a session against the plain reference of
``benchmarks/reference/kimi_k2.py``. CPU, small sizes."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.moe_lm import moe_lm, moe_lm_session
from paddle_tpu.observability import metrics
from paddle_tpu.ops import mla_ops, moe_ops
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.serving.paged_cache import BLOCKS_IN_USE

from benchmarks.reference import kimi_k2 as ref

pytestmark = [pytest.mark.generation, pytest.mark.paged]

YARN = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
            mscale_all_dim=1, original_max_position_embeddings=4096,
            type="yarn")


def _run(build, feed, sets=None):
    """Build a program with ``build() -> fetch vars``, run its startup, set
    ``sets`` {name: array} and run it on ``feed``; -> (outputs, scope)."""
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 11
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.unique_name.guard(), \
            ptpu.program_guard(main, startup):
        fetch = build()
        exe = ptpu.Executor()
        exe.run(startup)
        for name, value in (sets or {}).items():
            scope.set_var(name, jnp.asarray(value))
        outs = exe.run(main, feed=feed, fetch_list=list(fetch))
    return [np.asarray(o) for o in outs], scope


@pytest.fixture()
def flash_off():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


# -- rotary positions: YaRN and a lane range ---------------------------------

def _yarn_line(rot, theta, y):
    """ISSUE 31's formula, in float64."""
    i = np.arange(rot // 2, dtype=np.float64)
    f = theta ** (-2 * i / rot)

    def corr(n):
        return rot * math.log(y["original_max_position_embeddings"]
                              / (2 * math.pi * n)) / (2 * math.log(theta))
    lo, hi = math.floor(corr(y["beta_fast"])), math.ceil(corr(y["beta_slow"]))
    r = np.clip((i - lo) / (hi - lo), 0, 1)
    return f * (1 - r) + f / y["factor"] * r, lo, hi


def test_yarn_bounds_are_the_issues():
    _, lo, hi = _yarn_line(64, 50000.0, YARN)
    assert (lo, hi) == (8, 20)
    freq = np.asarray(moe_ops.rotary_frequencies(64, 50000.0, YARN))
    plain = np.asarray(moe_ops.rotary_frequencies(64, 50000.0))
    np.testing.assert_allclose(freq[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(freq[20:], plain[20:] / 64, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(ref.yarn_frequencies(dict(
            qk_rope_head_dim=64, rope_theta=50000, rope_scaling=YARN))), freq)


@pytest.mark.parametrize("position", [4097, 20000, 131071])
def test_yarn_rotation_past_the_original_context(position):
    """One head of 64 lanes at a position past 4,096 against the formula:
    lane i with lane i + 32, by position x the blended frequency."""
    x = np.random.RandomState(position % 97).randn(1, 1, 64) \
        .astype(np.float32)

    def build():
        xv = layers.data("x", shape=[1, 1, 64], dtype="float32",
                         append_batch_size=False)
        pv = layers.data("p", shape=[1], dtype="int32",
                         append_batch_size=False)
        return [layers.rotary_embedding(xv, 64, theta=50000.0, pos=pv,
                                        per_row=True, yarn=YARN)]
    (got,), _ = _run(build, {"x": x, "p": np.array([position], np.int32)})
    freq, _, _ = _yarn_line(64, 50000.0, YARN)
    ang = position * freq
    x1, x2 = x[0, 0, :32].astype(np.float64), x[0, 0, 32:].astype(np.float64)
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)])
    # float32 angles: a position of 1e5 times a frequency near 1 has an
    # ulp of 8e-3 radians
    np.testing.assert_allclose(got[0, 0], want, atol=2e-2)
    np.testing.assert_allclose(got[0, 0, 20:32], want[20:32], atol=1e-4)


def test_rotary_lane_range_turns_those_lanes_only():
    """Three heads of 12 lanes, lanes [8, 12) turn as a head of 4 would;
    lanes [0, 8) pass."""
    x = np.random.RandomState(3).randn(2, 5, 36).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[2, 5, 36], dtype="float32",
                         append_batch_size=False)
        tail = layers.slice(layers.reshape(xv, [2, 5, 3, 12]), [3], [8], [12])
        return [layers.rotary_embedding(xv, 12, theta=100.0, lanes=(8, 12)),
                layers.rotary_embedding(layers.reshape(tail, [2, 5, 12]), 4,
                                        theta=100.0)]
    (got, tail), _ = _run(build, {"x": x})
    got = got.reshape(2, 5, 3, 12)
    np.testing.assert_array_equal(got[..., :8], x.reshape(2, 5, 3, 12)[..., :8])
    np.testing.assert_allclose(got[..., 8:], tail.reshape(2, 5, 3, 4),
                               atol=1e-6)


# -- latent attention, each path against a line of its own -------------------

H, NOPE, ROPE, DV, RANK = 4, 8, 4, 8, 12
SCALE = (NOPE + ROPE) ** -0.5 * 1.3


def _latents(t, seed, batch=1):
    rs = np.random.RandomState(seed)
    return (rs.randn(batch, t, H * (NOPE + ROPE)).astype(np.float32),
            rs.randn(batch, t, RANK).astype(np.float32),
            rs.randn(batch, t, ROPE).astype(np.float32),
            (rs.randn(RANK, H * (NOPE + DV)) * 0.5).astype(np.float32))


def _expanded_line(q, c, kr, w):
    """One sequence, float64: expand, then plain causal attention."""
    t = q.shape[0]
    kv = (c.astype(np.float64) @ w.astype(np.float64)).reshape(
        t, H, NOPE + DV)
    k = np.concatenate([kv[..., :NOPE],
                        np.broadcast_to(kr[:, None], (t, H, ROPE))], -1)
    s = np.einsum("qhd,khd->hqk", q.reshape(t, H, -1).astype(np.float64),
                  k) * SCALE
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, kv[..., NOPE:]).reshape(t, H * DV)


def _mla_build(t, batch, **kw):
    def build():
        qv = layers.data("q", shape=[batch, t, H * (NOPE + ROPE)],
                         dtype="float32", append_batch_size=False)
        cv = layers.data("c", shape=[batch, t, RANK], dtype="float32",
                         append_batch_size=False)
        kv = layers.data("kr", shape=[batch, t, ROPE], dtype="float32",
                         append_batch_size=False)
        return [layers.mla_attention(qv, cv, kv, H, NOPE, ROPE, DV, SCALE,
                                     "w_ukv", **kw)]
    return build


@pytest.mark.parametrize("block_rows", [None, 4, 5],
                         ids=["at_once", "in_blocks", "no_divisor"])
def test_expanded_attention_against_its_line(block_rows):
    q, c, kr, w = _latents(12, 4, batch=2)
    (got,), _ = _run(_mla_build(12, 2, block_rows=block_rows),
                     {"q": q, "c": c, "kr": kr}, {"w_ukv": w})
    for b in range(2):
        np.testing.assert_allclose(
            got[b], _expanded_line(q[b], c[b], kr[b], w), atol=2e-5)


def test_exact_einsum_keeps_a_bfloat16_weights_products_exact():
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(6, H, NOPE), jnp.float32)
    w = jnp.asarray(rs.randn(RANK, H, NOPE), jnp.bfloat16)
    got = np.asarray(mla_ops.exact_einsum("shd,chd->shc", x, w))
    want = np.einsum("shd,chd->shc", np.asarray(x, np.float64),
                     np.asarray(w.astype(jnp.float32), np.float64))
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()
    one_pass = np.asarray(jnp.einsum(
        "shd,chd->shc", x.astype(jnp.bfloat16), w,
        preferred_element_type=jnp.float32))
    assert np.abs(one_pass - want).max() > 1e-3 * np.abs(want).max()


BS, NB, MB, WIDTH = 4, 16, 5, 128


def _latent_pool(lens, seed):
    """A paged latent pool holding ``lens`` sequences' rows at shuffled
    blocks: -> (pool [NB, BS, WIDTH], tables [S, MB], rows per slot)."""
    rs = np.random.RandomState(seed)
    pool = rs.randn(NB, BS, WIDTH).astype(np.float32)    # garbage elsewhere
    blocks = list(rs.permutation(NB))
    tables = np.full((len(lens), MB), NB, np.int32)
    rows = []
    for s, n in enumerate(lens):
        seq = rs.randn(n, RANK + ROPE).astype(np.float32)
        rows.append(seq)
        for j in range(-(-n // BS)):
            blk = blocks.pop()
            tables[s, j] = blk
            part = seq[j * BS:(j + 1) * BS]
            pool[blk, :len(part), :RANK + ROPE] = part
            pool[blk, :len(part), RANK + ROPE:] = 0.0
    return pool, tables, rows


def _absorbed_line(q, rows, w):
    """One slot's query [H*(nope+rope)] over its rows [n, rank + rope]:
    expanded, float64, the last row's output."""
    n = rows.shape[0]
    qs = np.zeros((n, H * (NOPE + ROPE)))
    qs[-1] = q
    return _expanded_line(qs, rows[:, :RANK], rows[:, RANK:], w)[-1]


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernel"])
def test_absorbed_decode_over_the_paged_pool_equals_expanded(flash):
    """Three slots (one whole block, one mid-block, one of a single row)
    and a dead one: W_uk into the query, the pool's rows as key and value,
    W_uv after the sum."""
    lens = [8, 14, 1]
    pool, tables, rows = _latent_pool(lens, 6)
    tables = np.concatenate([tables, np.full((1, MB), NB, np.int32)])
    rs = np.random.RandomState(7)
    q = rs.randn(4, 1, H * (NOPE + ROPE)).astype(np.float32)
    w = (rs.randn(RANK, H * (NOPE + DV)) * 0.5).astype(np.float32)
    pos = np.array([n - 1 for n in lens] + [0], np.int32)
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=flash)

    def build():
        qv = layers.data("q", shape=list(q.shape), dtype="float32",
                         append_batch_size=False)
        pv = layers.data("pool", shape=list(pool.shape), dtype="float32",
                         append_batch_size=False)
        posv = layers.data("pos", shape=[4], dtype="int32",
                           append_batch_size=False)
        tv = layers.data("tab", shape=[4, MB], dtype="int32",
                         append_batch_size=False)
        cv = layers.data("c", shape=[4, 1, RANK], dtype="float32",
                         append_batch_size=False)
        kv = layers.data("kr", shape=[4, 1, ROPE], dtype="float32",
                         append_batch_size=False)
        return [layers.mla_attention(qv, cv, kv, H, NOPE, ROPE, DV, SCALE,
                                     "w_ukv", cache=pv, pos=posv, table=tv)]
    try:
        (got,), _ = _run(build, {
            "q": q, "pool": pool, "pos": pos, "tab": tables,
            "c": np.zeros((4, 1, RANK), np.float32),
            "kr": np.zeros((4, 1, ROPE), np.float32)}, {"w_ukv": w})
    finally:
        ptpu.config.set_flags(flash_attention=prev)
    for s, seq in enumerate(rows):
        np.testing.assert_allclose(got[s, 0], _absorbed_line(q[s, 0], seq, w),
                                   atol=3e-5)
    assert np.isfinite(got).all()


def _plain_latent_attention(q, rows, scale):
    """q [H, W] over rows [n, W], value the leading RANK lanes."""
    s = (q.astype(np.float64) @ rows.T.astype(np.float64)) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return p @ rows[:, :RANK].astype(np.float64)


@pytest.mark.parametrize("how", ["reference", "kernel"])
def test_paged_decode_on_a_latent_pool_against_plain_attention(how):
    """One KV head under H query heads, the value the leading lanes of the
    key's own row, a scale of its own; the kernel fetches a page once."""
    lens = [9, 16, 3]
    pool, tables, rows = _latent_pool(lens, 8)
    q = np.random.RandomState(9).randn(3, 1, H * WIDTH).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(pool), None, jnp.asarray(lens),
            jnp.asarray(tables), H)
    kw = dict(num_kv_heads=1, v_width=RANK, scale=0.21)
    if how == "kernel":
        got = pa.decode_attention_paged(*args, interpret=True, **kw)
    else:
        got = pa._decode_paged_reference(*args, **kw)
    got = np.asarray(got)
    assert got.shape == (3, 1, H * RANK)
    for s, seq in enumerate(rows):
        padded = np.zeros((len(seq), WIDTH), np.float32)
        padded[:, :RANK + ROPE] = seq
        want = _plain_latent_attention(q[s, 0].reshape(H, WIDTH), padded, 0.21)
        np.testing.assert_allclose(got[s, 0].reshape(H, RANK), want,
                                   atol=2e-5)


def test_a_latent_pool_has_one_head_and_no_v_pool():
    pool = jnp.zeros((NB, BS, WIDTH))
    q = jnp.zeros((2, 1, H * WIDTH))
    lens, tables = jnp.ones(2, jnp.int32), jnp.zeros((2, MB), jnp.int32)
    with pytest.raises(ValueError, match="one head"):
        pa.decode_attention_paged(q, pool, pool, lens, tables, H,
                                  num_kv_heads=1, v_width=RANK)
    with pytest.raises(ValueError, match="one head"):
        pa.decode_attention_paged(q, pool, None, lens, tables, H,
                                  num_kv_heads=2, v_width=RANK)


@pytest.mark.parametrize("nkv,window", [(4, None), (2, None), (2, 6)],
                         ids=["a_head_each", "grouped", "grouped_window"])
def test_paged_decode_on_k_and_v_pools_is_unchanged(nkv, window):
    """The GPT-2 block's and the afmoe layers' geometries: the kernel and
    the reference still agree with each other and with plain attention."""
    rs = np.random.RandomState(10)
    hd, lens = 8, [7, 16]
    kp = rs.randn(NB, BS, nkv * hd).astype(np.float32)
    vp = rs.randn(NB, BS, nkv * hd).astype(np.float32)
    tables = np.full((2, MB), NB, np.int32)
    tables[0, :2], tables[1, :4] = [3, 9], [1, 4, 12, 6]
    q = rs.randn(2, 1, 4 * hd).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(lens), jnp.asarray(tables), 4)
    got = np.asarray(pa.decode_attention_paged(
        *args, interpret=True, num_kv_heads=nkv, window=window))
    want = np.asarray(pa._decode_paged_reference(*args, nkv, window))
    np.testing.assert_allclose(got, want, atol=2e-5)
    for s, n in enumerate(lens):
        ids = tables[s, :-(-n // BS)]
        k = kp[ids].reshape(-1, nkv, hd)[:n]
        v = vp[ids].reshape(-1, nkv, hd)[:n]
        lo = 0 if window is None else max(n - window, 0)
        for h in range(4):
            g = h // (4 // nkv)
            sc = (k[lo:, g] @ q[s, 0, h * hd:(h + 1) * hd]) * hd ** -0.5
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                got[s, 0, h * hd:(h + 1) * hd], (p / p.sum()) @ v[lo:, g],
                atol=2e-5)


# -- the expert feed-forward as a share of its experts ------------------------

D, F, E, K = 16, 8, 16, 2


def _weights(seed=5):
    rs = np.random.RandomState(seed)
    return {"router": rs.randn(D, E), "gate": rs.randn(E, D, F) * 0.3,
            "up": rs.randn(E, D, F) * 0.3, "down": rs.randn(E, F, D) * 0.3}


def _moe(x, offset=0, held=None, bias=None, scale=2.0):
    """The op on x [n, D] with seeded weights: -> (out, counts)."""
    full = _weights()
    n_held = held or E
    sets = {"m.router.w": full["router"].astype(np.float32)}
    if bias is not None:
        sets["m.expert_bias"] = np.asarray(bias, np.float32)
    for part in ("gate", "up", "down"):
        sets["m.experts.%s.w" % part] = \
            full[part][offset:offset + n_held].astype(np.float32)

    def build():
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        return layers.moe_ffn(xv, E, K, F, "m", route_scale=scale,
                              expert_offset=offset, experts_held=held)
    (out, counts), _ = _run(build, {"x": x}, sets)
    return out, counts


def _moe_line(x, bias=None, scale=2.0, experts=range(E)):
    """Dense routing in float64: (the part of the layer that ``experts``
    give, the selections)."""
    w = _weights()
    x = x.astype(np.float64)
    s = 1 / (1 + np.exp(-(x @ w["router"])))
    b = np.zeros(E) if bias is None else np.asarray(bias)
    sel = np.argsort(-(s + b), axis=1, kind="stable")[:, :K]
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


@pytest.mark.parametrize("share_rows", [None, 5],
                         ids=["one_pass", "passes_of_5"])
def test_the_shares_add_up_to_the_uncut_reference_layer(share_rows,
                                                        monkeypatch):
    """16 experts held 4 at a time + the shared expert once = the
    reference's whole layer, at this model's routing (sigmoid, top-k,
    normalised, scaled); with the pairs in one pass and in passes of a
    few rows."""
    if share_rows:
        monkeypatch.setattr(moe_ops, "SHARE_ROWS", share_rows)
    x = np.random.RandomState(10).randn(12, D).astype(np.float32)
    parts, all_counts = zip(*[_moe(x, offset=o, held=4, scale=2.827)
                              for o in range(0, E, 4)])
    w = _weights()
    rs = np.random.RandomState(11)
    shared = [jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
              for s in ((D, F), (D, F), (F, D))]
    cfg = dict(num_experts_per_tok=K, norm_topk_prob=True,
               routed_scaling_factor=2.827)
    weights = {"l.router": w["router"], "l.expert_bias": np.zeros(E),
               "l.experts.gate": w["gate"], "l.experts.up": w["up"],
               "l.experts.down": w["down"]}
    weights = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    xj = jnp.asarray(x)
    sh = ref._swiglu(xj, *shared)
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


@pytest.mark.parametrize("share_rows", [None, 16], ids=["one_pass", "passes"])
def test_a_share_drops_no_token_when_every_token_picks_its_experts(
        share_rows, monkeypatch):
    """A bias sends all 40 tokens to experts 5 and 6, both held by the
    share [4, 8): 80 pairs on two experts, none on the other two, every
    pair computed, in one pass or in five."""
    if share_rows:
        monkeypatch.setattr(moe_ops, "SHARE_ROWS", share_rows)
    x = np.random.RandomState(12).randn(40, D).astype(np.float32)
    bias = np.zeros(E)
    bias[[5, 6]] = 10.0
    got, counts = _moe(x, offset=4, held=4, bias=bias)
    want, sel = _moe_line(x, bias=bias, experts=range(4, 8))
    assert set(sel.ravel()) == {5, 6}
    np.testing.assert_array_equal(counts, [0, 40, 40, 0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    everyone, _ = _moe_line(x, bias=bias)
    np.testing.assert_allclose(got, everyone, rtol=2e-4, atol=2e-5)


def test_a_share_whose_experts_nobody_picks_adds_nothing():
    x = np.random.RandomState(13).randn(40, D).astype(np.float32)
    bias = np.zeros(E)
    bias[[5, 6]] = 10.0
    got, counts = _moe(x, offset=8, held=4, bias=bias)
    np.testing.assert_array_equal(counts, [0, 0, 0, 0])
    np.testing.assert_array_equal(got, np.zeros_like(x))


def test_a_shares_temporaries_follow_its_pass_not_all_pairs(monkeypatch):
    """The rows a share gathers and computes are a pass's: no array of the
    traced op has as many rows as there are pairs."""
    from types import SimpleNamespace
    from paddle_tpu.core.registry import ExecContext
    monkeypatch.setattr(moe_ops, "SHARE_ROWS", 32)
    n = 100                                     # 200 pairs
    op = SimpleNamespace(attrs={"num_experts": E, "top_k": K})
    slots = ("X", "RouterW", "ExpertBias", "WGate", "WUp", "WDown")

    def widest(held):
        shapes = ((n, D), (D, E), (E,), (held, D, F), (held, D, F),
                  (held, F, D))
        jaxpr = jax.make_jaxpr(lambda *vals: moe_ops._moe_ffn(ExecContext(
            op, {slot: [v] for slot, v in zip(slots, vals)}))["Out"])(
                *[jnp.zeros(s) for s in shapes])
        return max(_rows_of(jaxpr.jaxpr, D) + _rows_of(jaxpr.jaxpr, F))
    assert widest(4) == n           # the tokens themselves, and a pass's 32
    assert widest(E) == n * K       # a whole holder gathers every pair


def _rows_of(jaxpr, width):
    """Leading sizes of every [rows, width] float array a jaxpr makes, its
    sub-jaxprs' too."""
    out = []
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if len(shape) == 2 and shape[1] == width:
                out.append(shape[0])
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _rows_of(inner, width)
    return out or [0]


# -- the whole model through a session ----------------------------------------

SIZES = dict(vocab_size=50, d_model=32, num_heads=4, num_kv_heads=1,
             head_dim=12, d_ff=48, moe_d_ff=16, num_experts=16, top_k=2,
             experts_held=4, expert_offset=4,
             layer_types=["full_attention"] * 3, num_dense_layers=1,
             sliding_window=None, rope_theta=50000.0, route_scale=2.827,
             embed_scale=None, attention="latent", post_norms=False,
             latent=dict(q_rank=16, kv_rank=12, nope_dim=8, rope_dim=4,
                         v_dim=8),
             rope_scaling=dict(YARN, original_max_position_embeddings=8))
CFG = dict(num_hidden_layers=3, first_k_dense_replace=1,
           num_attention_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=8, kv_lora_rank=12, rms_norm_eps=1e-5,
           rope_theta=50000.0, rope_scaling=SIZES["rope_scaling"],
           num_experts_per_tok=2, norm_topk_prob=True,
           routed_scaling_factor=2.827, expert_offset=4)
T = 30


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
    for n in scope.var_names():
        if n.startswith("moe_lm.") and "norm" not in n \
                and "expert_bias" not in n:
            cur = np.asarray(scope.find_var(n))
            scope.set_var(n, jnp.asarray(
                0.3 * rs.standard_normal(cur.shape), cur.dtype))
    return scope, main, loss, logits


def _session(scope, flash=False, **kw):
    ptpu.config.set_flags(flash_attention=flash)
    args = dict(slots=3, cache_len=32, prompt_buckets=(8, 16), block_size=4,
                num_blocks=24)
    args.update(kw)
    return GenerationSession(moe_lm_session(**args, **SIZES), scope=scope)


def _decode_logits_name(spec):
    for op in spec.decode_program.global_block().ops:
        if op.type == "arg_max" and spec.decode_fetch in sum(
                op.outputs.values(), []):
            return op.inputs["X"][0]


def test_the_model_holds_the_parameters_the_equations_name(model_scope):
    scope = model_scope[0]
    shapes = {n: tuple(np.shape(scope.find_var(n)))
              for n in scope.var_names() if n.startswith("moe_lm.l1.")}
    assert shapes == {
        "moe_lm.l1.norm_in.w": (32,), "moe_lm.l1.norm_pre_mlp.w": (32,),
        "moe_lm.l1.attn.q_a.w": (32, 16), "moe_lm.l1.attn.q_a_norm.w": (16,),
        "moe_lm.l1.attn.q_b.w": (16, 4 * 12),
        "moe_lm.l1.attn.kv_a.w": (32, 12 + 4),
        "moe_lm.l1.attn.kv_a_norm.w": (12,),
        "moe_lm.l1.attn.kv_b.w": (12, 4 * 16),
        "moe_lm.l1.attn.o.w": (4 * 8, 32),
        "moe_lm.l1.moe.router.w": (32, 16),
        "moe_lm.l1.moe.expert_bias": (16,),
        "moe_lm.l1.moe.shared.gate.w": (32, 16),
        "moe_lm.l1.moe.shared.up.w": (32, 16),
        "moe_lm.l1.moe.shared.down.w": (16, 32),
        "moe_lm.l1.moe.experts.gate.w": (4, 32, 16),
        "moe_lm.l1.moe.experts.up.w": (4, 32, 16),
        "moe_lm.l1.moe.experts.down.w": (4, 16, 32)}


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_equals_the_references_forward(model_scope,
                                                           flash):
    """Two sequences prefilled through the expanded path and decoded
    through the absorbed path and the latent cache, each against the
    reference's full expanded forward. Logits, not tokens."""
    scope, main, _, logits = model_scope
    rs = np.random.RandomState(22)
    w = ref.gather_weights(scope.find_var, CFG)
    sess = _session(scope, flash=flash)
    try:
        name = _decode_logits_name(sess.spec)
        for n0 in (13, 5):
            seq = rs.randint(2, 50, T)
            want = np.asarray(ref.logits_at(w, jnp.asarray(seq),
                                            jnp.arange(T), CFG))
            assert np.abs(want).max() > 1.0
            if n0 == 13:
                with ptpu.scope_guard(scope):
                    full = np.asarray(ptpu.Executor().run(
                        main, feed={"toks": seq[None], "lbls": seq[None]},
                        fetch_list=[logits])[0])[0]
                np.testing.assert_allclose(full, want, atol=5e-5)
            slot, first = sess.admit(seq[:n0])
            assert first == int(want[n0 - 1].argmax())
            for i in range(n0, T):
                prepared = sess.step_prepare()
                prepared[2]["gen.dtok"][slot, 0] = seq[i]
                got = np.asarray(sess.exe.run(
                    sess.spec.decode_program, feed=prepared[2],
                    fetch_list=[name, sess.spec.decode_fetch],
                    scope=scope)[0])
                sess.lengths[slot] += 1
                np.testing.assert_allclose(got[slot], want[i], atol=5e-5)
                sess.check_pool_invariant()
            # the latent kind's books are the full kind's: nothing trimmed
            assert sess.pool.used_count() == -(-T // 4)
            sess.retire(slot)
            sess.check_pool_invariant()
            assert sess.pool.used_count() == 0
    finally:
        sess.close()
        ptpu.config.set_flags(flash_attention=True)


def test_the_latent_kind_is_named_by_the_spec(model_scope):
    """One pool a layer, 128 lanes for a row of 16; one kind, named; the
    copy program copies each layer's pool once."""
    spec = moe_lm_session(slots=3, cache_len=32, prompt_buckets=(8,),
                          block_size=4, num_blocks=24, cache_ns="kv", **SIZES)
    assert spec.cache_vars == tuple(
        ("kv.l%d.c" % i, (24, 4, 128), "float32") for i in range(3))
    kind, = spec.cache_kinds
    assert (kind.name, kind.window, kind.num_blocks, kind.layers) == \
        ("latent", None, 24, 3)
    assert (kind.prefill_table, kind.decode_table) == ("gen.ptab", "gen.dtab")
    copies = [op for op in spec.copy_program.global_block().ops
              if op.type == "kv_block_copy"]
    assert [op.inputs["Cache"] for op in copies] == \
        [["kv.l%d.c" % i] for i in range(3)]
    ops = [op.type for op in spec.decode_program.global_block().ops]
    assert ops.count("mla_attention_decode_paged") == 3
    assert ops.count("kv_cache_append_paged") == 3
    assert "multihead_attention_decode_paged" not in ops
    pre = [op.type for op in spec.prefill_programs[8].global_block().ops]
    assert pre.count("mla_attention") == 3
    assert pre.count("kv_cache_write_paged") == 3
    assert spec.routed_pairs == 3 * 2 * 2       # slots x top-k x layers
    sess = GenerationSession(spec, scope=model_scope[0])
    assert sess.pool_stats()["bytes_per_block"] == 4 * 128 * 4 * 3
    sess.close()


@pytest.mark.parametrize("what", ["prefix_cache", "speculate_k"])
def test_a_latent_spec_refuses_what_its_prefill_cannot_serve(what):
    from paddle_tpu.models.moe_lm import MoeLM
    from paddle_tpu.models.transformer import lm_session
    from paddle_tpu.serving.decoding import DecodePolicy
    kw = dict(prefix_cache=True, decode_policy=None) \
        if what == "prefix_cache" else dict(
            prefix_cache=False, decode_policy=DecodePolicy(speculate_k=2))
    with pytest.raises(ValueError, match="neither prefix_cache nor"):
        lm_session(MoeLM(**SIZES), max_len=32, slots=2, cache_len=32,
                   prompt_buckets=(8,), block_size=4, num_blocks=16, **kw)


def _counter(name):
    for n, _, _, _, children in metrics.REGISTRY.snapshot():
        if n == name:
            return sum(float(p) for _, p in children)
    return 0.0


COUNTERS = ("paddle_generation_routed_pairs_total",
            "paddle_generation_expert_assignments_total",
            "paddle_generation_experts_touched_total",
            "paddle_generation_expert_max_load_total",
            "paddle_generation_latent_rows_attended_total")


def test_the_new_counters_add_up_by_hand(model_scope, flash_off):
    """Four decode steps of a 3-slot session with two expert layers that
    hold 4 of 16 experts and three latent layers; the routing of each step
    is read back from the step's own fetch and counted by hand."""
    scope = model_scope[0]
    sess = _session(scope)
    sess.admit(np.arange(2, 9))          # 7 rows
    sess.admit(np.arange(3, 15))         # 12 rows
    before = {c: _counter(c) for c in COUNTERS}
    by_hand = dict.fromkeys(COUNTERS, 0)
    held_pairs = 0
    for _ in range(4):
        prepared = sess.step_prepare()
        counts = np.asarray(sess.exe.run(
            sess.spec.decode_program, feed=prepared[2],
            fetch_list=[sess.spec.stats_fetch], scope=scope)[0])
        assert counts.shape == (2, 4) and (counts.sum(1) <= 3 * 2).all()
        out = sess.step_run(prepared)
        assert sorted(out) == [0, 1]
        held_pairs += int(counts.sum())
        by_hand[COUNTERS[0]] += 2 * 3 * 2        # layers x slots x top-k
        by_hand[COUNTERS[1]] += int(counts.sum())
        by_hand[COUNTERS[2]] += int((counts > 0).sum())
        by_hand[COUNTERS[3]] += int(counts.max(1).sum())
        by_hand[COUNTERS[4]] += 3 * sum(int(sess.lengths[s]) for s in out)
    assert {c: _counter(c) - before[c] for c in COUNTERS} == by_hand
    assert by_hand[COUNTERS[4]] == 3 * sum(
        (7 + i) + (12 + i) for i in range(1, 5))
    assert 0 < held_pairs < by_hand[COUNTERS[0]]
    sess.close()


def test_the_latent_pools_gauge_goes_by_the_kinds_name(model_scope,
                                                       flash_off):
    def gauges():
        return {l["pool"]: float(p)
                for n, _, _, _, ch in metrics.REGISTRY.snapshot()
                if n == BLOCKS_IN_USE.name for l, p in ch
                if l["pool"].startswith("latent.")}
    scope = model_scope[0]
    before = set(gauges())
    sess = _session(scope)
    sess.admit(np.arange(2, 12))         # 10 rows: 3 blocks of 4
    assert [(k.name, k.layers) for k in sess.spec.cache_kinds] == [
        ("latent", 3)]
    assert sess.pool._label.startswith("latent.p")
    assert gauges()[sess.pool._label] == 3.0
    other = _session(scope)              # a second session, a second child
    assert set(gauges()) - before == {sess.pool._label, other.pool._label}
    assert gauges()[other.pool._label] == 0.0
    other.close()
    sess.close()
    assert set(gauges()) == before


def test_the_scheduler_serves_the_model_a_step_ahead(model_scope, flash_off):
    scope = model_scope[0]
    sess = _session(scope)
    want = sess.generate(np.arange(2, 9), max_new_tokens=6, eos_id=-1)
    ahead0 = _counter("paddle_generation_decode_steps_ahead_total")
    assert sess.lookahead
    sched = GenerationScheduler(sess, deadline_ms=0)
    futures = [sched.submit(np.arange(2, 9), max_new_tokens=6, eos_id=-1),
               sched.submit(np.arange(5, 16), max_new_tokens=9, eos_id=-1)]
    outs = [np.asarray(f.result(timeout=120)) for f in futures]
    sched.close()
    np.testing.assert_array_equal(outs[0], np.asarray(want))
    assert len(outs[1]) == 9
    assert _counter("paddle_generation_decode_steps_ahead_total") > ahead0
    sess.check_pool_invariant()
    sess.close()
