"""EVA attention (``ops/eva_ops.py``), its two kinds of layer cache (an
aligned window and a chunk kind: ``serving/paged_cache.py``) and the whole
``evabyte``-shaped model (``models/moe_lm.py`` with the ``eva`` attention)
through a session against the plain reference of
``benchmarks/reference/evabyte.py``. CPU, small sizes: d 64, 4 heads of 16,
window 32, chunks of 4, 3 layers, seeded."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.moe_lm import MoeLM, moe_lm, moe_lm_session
from paddle_tpu.models.transformer import lm_session
from paddle_tpu.observability import metrics
from paddle_tpu.ops import eva_ops, moe_ops
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.serving import GenerationSession
from paddle_tpu.serving.decoding import DecodePolicy
from paddle_tpu.serving.paged_cache import CacheKind, LayerCache

from benchmarks.architectures import evabyte as arch
from benchmarks.harness import lm as bench_lm
from benchmarks.reference import evabyte as ref

pytestmark = [pytest.mark.generation, pytest.mark.paged]

W, C, V, HEADS = 32, 4, 64, 3
CFG = dict(
    attention_class="eva", hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, rope_scaling=None, num_chunks=None,
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=96, window_size=W, chunk_size=C, num_hidden_layers=3,
    vocab_size=V, num_pred_heads=HEADS, rope_theta=100000,
    rms_norm_eps=1e-5, torch_dtype="float32", init_std=0.1,
    norm_add_unit_offset=True)
SIZES = arch.sizes(CFG)
T = 150                     # four windows and a part of a fifth
# program against reference, float32 both, as a share of the largest logit
TOL = 2e-5


def _tokens(seed, n=T):
    return np.random.RandomState(seed).randint(2, V, n).astype(np.int64)


@pytest.fixture(scope="module")
def model():
    """(scope, whole-sequence program, its logits' name): weights from the
    seed, the norm offsets moved off zero so that the unit offset shows."""
    scope = ptpu.Scope()
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 7
    with ptpu.scope_guard(scope), ptpu.unique_name.guard(), \
            ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        _, logits = moe_lm(toks, lbls, **SIZES)
        ptpu.Executor().run(startup)
    rs = np.random.RandomState(3)
    for name in ref.weight_names(CFG).values():
        if "norm" in name:
            scope.set_var(name, jnp.asarray(
                rs.uniform(-0.3, 0.3, scope.find_var(name).shape),
                jnp.float32))
    return scope, main, logits.name


def _whole(model, seq):
    scope, main, name = model
    with ptpu.scope_guard(scope):
        return np.asarray(ptpu.Executor().run(
            main, feed={"toks": seq[None], "lbls": seq[None]},
            fetch_list=[name])[0])[0]


def _reference(model, seq, positions=None, cfg=CFG):
    w = ref.gather_weights(model[0].find_var, cfg)
    pos = np.arange(len(seq)) if positions is None else positions
    out = ref.all_heads_at(w, jnp.asarray(seq), jnp.asarray(pos), cfg)
    return np.asarray(out).reshape(len(pos), -1)


def _session(model, slots=3, buckets=(32, 64, 128), cache_len=192, **more):
    spec = moe_lm_session(slots=slots, cache_len=cache_len,
                          prompt_buckets=buckets, block_size=C,
                          num_blocks=slots * W // C,
                          chunk_num_blocks=slots * 12, **dict(SIZES, **more))
    return GenerationSession(spec, scope=model[0])


def _decode(sess, slot, first, steps, twice=False):
    """Decode ``steps`` steps the way the benchmark's check does: the step
    run once with its logits fetched, then as the session runs it. ->
    (tokens, logits [steps, V]); with ``twice`` the pools after each of the
    two runs too."""
    spec = sess.spec
    name = bench_lm.logits_var(spec.decode_program, spec.decode_fetch)
    toks, rows, pools = [first], [], []

    def snapshot():
        return [np.asarray(sess.scope.find_var(n)) for n, _, _ in
                spec.cache_vars]
    for _ in range(steps):
        prepared = sess.step_prepare()
        lg = sess.exe.run(spec.decode_program, feed=prepared[2],
                          fetch_list=[name, spec.decode_fetch],
                          scope=sess.scope)[0]
        if twice:
            pools.append(snapshot())
        out = sess.step_run(prepared)
        if twice:
            pools.append(snapshot())
        rows.append(np.asarray(lg)[slot])
        toks.append(out[slot])
    return toks, np.stack(rows), pools


# -- the whole-sequence form -------------------------------------------------

def test_whole_sequences_agree_with_the_reference_on_every_head(model):
    seq = _tokens(0)
    got, want = _whole(model, seq), _reference(model, seq)
    assert got.shape == (T, HEADS * V)
    assert np.abs(got - want).max() < TOL * np.abs(want).max()
    # the heads differ: the comparison is of all of them
    assert np.abs(want[:, :V] - want[:, V:2 * V]).max() > 0.1


def test_within_one_window_eva_is_causal_attention(model):
    """A window no shorter than the sequence: the same weights under plain
    grouped-query attention with rotary positions (``multihead_attention``,
    causal) give the same logits."""
    scope = model[0]
    plain = dict(SIZES, attention="gqa", eva=None,
                 layer_types=["sliding_attention"] * 3, sliding_window=T,
                 qk_norm=False, attn_gate=False)
    wide = dict(SIZES, eva=dict(window=-(-T // C) * C, chunk=C))
    seq = _tokens(1)
    outs = []
    for sizes in (plain, wide):
        main = ptpu.Program()
        with ptpu.scope_guard(scope), ptpu.unique_name.guard(), \
                ptpu.program_guard(main, ptpu.Program()):
            toks = layers.data("toks", shape=[T], dtype="int64")
            lbls = layers.data("lbls", shape=[T], dtype="int64")
            _, logits = moe_lm(toks, lbls, **sizes)
            outs.append(np.asarray(ptpu.Executor().run(
                main, feed={"toks": seq[None], "lbls": seq[None]},
                fetch_list=[logits.name])[0])[0])
    assert np.abs(outs[0] - outs[1]).max() < TOL * np.abs(outs[0]).max()
    # and with the model's own window of 32 the summaries are at work
    assert np.abs(_whole(model, seq) - outs[0])[W:].max() > \
        1e3 * TOL * np.abs(outs[0]).max()


def test_the_reference_sees_no_summary_of_a_querys_own_window(model):
    """Changing a token moves no logit of an earlier position, and none
    through a chunk that is not complete and behind the window's edge."""
    seq = _tokens(2)
    base = _reference(model, seq)
    moved = seq.copy()
    moved[70] = (moved[70] + 1) % V
    after = _reference(model, moved)
    assert np.array_equal(base[:70], after[:70])
    assert np.abs(base[70:] - after[70:]).max() > 1e-3


# -- prefill and decode through the two pools --------------------------------

@pytest.mark.parametrize("n,steps,why", [
    (30, 8, "a chunk's edge (31) and the first window's (32) in the steps"),
    (61, 6, "the second window's edge inside the steps, the first's inside "
            "the prompt"),
    (100, 30, "three windows and chunk edges inside the prompt, the "
              "fourth's edge inside the steps"),
    (64, 5, "a prompt that ends on a window's edge: no row of it is kept"),
])
def test_prefill_and_decode_agree_with_the_reference(model, n, steps, why):
    sess = _session(model)
    seq = _tokens(n)
    slot, first = sess.admit(seq[:n])
    toks, logits, _ = _decode(sess, slot, first, steps)
    full = np.concatenate([seq[:n], toks])
    want = _reference(model, full, np.arange(n - 1, n + steps))[:, :V]
    assert first == want[0].argmax(), why
    scale = np.abs(want).max()
    assert np.abs(logits - want[1:]).max() < TOL * scale, why
    sess.check_pool_invariant()
    sess.retire(slot)
    sess.check_pool_invariant()
    sess.close()


def _worst_error(model, wrong, n=61, steps=6):
    """The check above with something wrong in the program: ``wrong(sess)``
    is called after the prefill."""
    sess = _session(model)
    seq = _tokens(n)
    slot, first = sess.admit(seq[:n])
    wrong(sess)
    toks, logits, _ = _decode(sess, slot, first, steps)
    full = np.concatenate([seq[:n], toks])
    want = _reference(model, full, np.arange(n - 1, n + steps))[:, :V]
    sess.retire(slot)
    sess.close()
    return np.abs(logits - want[1:]).max() / np.abs(want).max()


def test_the_check_sees_a_zeroed_chunk_pool(model):
    def zero(sess):
        for name, shape, dtype in sess.spec.cache_vars:
            # a layer's second site is its chunk kind's
            if int(name.split(".l")[-1].split(".")[0]) % 2:
                sess.scope.set_var(name, jnp.zeros(shape, dtype))
    assert _worst_error(model, lambda sess: None) < TOL
    assert _worst_error(model, zero) > 10 * TOL


def test_the_check_sees_a_pooling_by_the_mean(model, monkeypatch):
    """The two softmaxes replaced by the chunk's mean, in the program (the
    prefill's summaries and the decode step's): over ten times the
    tolerance."""
    def mean(k, v, mu, phi, num_heads, chunk):
        del mu, phi, num_heads
        chunks = k.shape[:-2] + (-1, chunk, k.shape[-1])
        return (jnp.mean(k.astype(jnp.float32).reshape(chunks), axis=-2),
                jnp.mean(v.astype(jnp.float32).reshape(chunks), axis=-2))
    monkeypatch.setattr(eva_ops, "pool_chunks", mean)
    assert _worst_error(model, lambda sess: None) > 10 * TOL


def test_a_decode_step_run_twice_changes_nothing(model):
    """The benchmark's check runs the decode program twice a step: pools
    and logits after the second run are those after the first, across a
    chunk's edge (the summary is a function of its block alone)."""
    sess = _session(model)
    seq = _tokens(5)
    slot, first = sess.admit(seq[:29])
    toks, logits, pools = _decode(sess, slot, first, 8, twice=True)
    for once, again in zip(pools[0::2], pools[1::2]):
        for a, b in zip(once, again):
            assert np.array_equal(a, b)
    sess.retire(slot)
    slot, first = sess.admit(seq[:29])
    once = []
    for _ in range(8):
        once.append(sess.step()[slot])
    assert [first] + once == toks
    sess.retire(slot)
    sess.close()


def test_flash_off_takes_the_gather_and_agrees(model):
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    try:
        sess = _session(model)
        seq = _tokens(61)
        slot, first = sess.admit(seq[:61])
        toks, logits, _ = _decode(sess, slot, first, 6)
        sess.retire(slot)
        sess.close()
    finally:
        ptpu.config.set_flags(flash_attention=prev)
    full = np.concatenate([seq[:61], toks])
    want = _reference(model, full, np.arange(60, 67))[:, :V]
    assert np.abs(logits - want[1:]).max() < TOL * np.abs(want).max()


def test_served_in_bfloat16_operands_under_amp():
    """The configuration's precision: bfloat16 weights, operands and pools,
    one pass a product (``amp``), float32 sums, against the float32
    reference on the same weights within the benchmark's 2.5e-2."""
    cfg = dict(CFG, torch_dtype="bfloat16", init_std=0.05)
    scope = ptpu.Scope()
    prev = ptpu.config.get_flag("amp")
    ptpu.config.set_flags(amp="bfloat16")
    try:
        with ptpu.scope_guard(scope), ptpu.unique_name.guard():
            main, startup = ptpu.Program(), ptpu.Program()
            main.random_seed = startup.random_seed = 5
            with ptpu.program_guard(main, startup):
                toks = layers.data("toks", shape=[8], dtype="int64")
                lbls = layers.data("lbls", shape=[8], dtype="int64")
                moe_lm(toks, lbls, **arch.sizes(cfg))
            ptpu.Executor().run(startup)
        assert scope.find_var("moe_lm.l0.attn.q.w").dtype == jnp.bfloat16
        assert scope.find_var("moe_lm.l0.attn.mu").dtype == jnp.bfloat16
        spec = moe_lm_session(
            slots=2, cache_len=128, prompt_buckets=(64,), block_size=C,
            num_blocks=2 * W // C, chunk_num_blocks=16, kv_dtype="bfloat16",
            **arch.sizes(cfg))
        sess = GenerationSession(spec, scope=scope)
        assert sess.scope.find_var(spec.cache_vars[0][0]).dtype == \
            jnp.bfloat16
        seq = _tokens(9)
        slot, first = sess.admit(seq[:61])
        toks, logits, _ = _decode(sess, slot, first, 6)
        sess.retire(slot)
        sess.close()
    finally:
        ptpu.config.set_flags(amp=prev)
    full = np.concatenate([seq[:61], toks])
    want = _reference((scope,), full, np.arange(60, 67), cfg)[:, :V]
    err = np.abs(logits - want[1:]).max() / np.abs(want).max()
    assert 1e-4 < err < 2.5e-2          # rounded, and within the limit


# -- the books ---------------------------------------------------------------

def _counter(name):
    total = 0.0
    for n, _, _, _, children in metrics.REGISTRY.snapshot():
        if n == name:
            total += sum(v for _, v in children)
    return total


def test_the_books_over_five_windows(model):
    """One sequence from a prompt of 40 to 165 positions, five windows of
    32: the window kind never holds more than a window's 8 blocks and frees
    8 at every edge; the chunk kind has a row every 4 positions; the
    counters are the sums by hand."""
    names = ("paddle_generation_kv_window_blocks_freed_total",
             "paddle_generation_eva_window_rows_total",
             "paddle_generation_eva_chunk_rows_total",
             "paddle_generation_eva_chunks_written_total",
             "paddle_generation_window_context_tokens_total")
    before = {n: _counter(n) for n in names}
    sess = _session(model, slots=1, buckets=(64,), cache_len=192)
    window, chunks = sess.kinds
    assert window.kind.aligned and window.kind.window == W
    assert chunks.kind.chunk == C and chunks.kind.window is None
    assert sess.storable(192) and sess.admit_ok(64)
    slot, _ = sess.admit(_tokens(8)[:40])
    # the prompt's first window was never written: dead entries, no blocks
    assert window.pool.used_count() == 2 and window.first[slot] == 8
    assert chunks.pool.used_count() == 3          # 10 rows of 4 a block
    rows = summaries = written = 0
    for pos in range(40, 165):
        sess.step()
        edge = pos // W * W
        rows += pos + 1 - edge
        summaries += edge // C
        written += (pos + 1) % C == 0
        assert window.pool.used_count() == (pos - edge) // C + 1 <= W // C
        assert chunks.pool.used_count() == -(-((pos + 1) // C) // C)
        sess.check_pool_invariant()
    delta = {n: _counter(n) - before[n] for n in names}
    assert delta[names[0]] == 4 * 8           # the edges at 64, 96, 128, 160
    assert delta[names[1]] == 3 * rows
    assert delta[names[2]] == 3 * summaries
    assert delta[names[3]] == 3 * (written + 40 // C)
    assert delta[names[4]] == 0               # no sliding window here
    sess.retire(slot)
    assert window.pool.used_count() == chunks.pool.used_count() == 0
    sess.check_pool_invariant()
    sess.close()


def test_layer_cache_of_an_aligned_window_and_of_a_chunk_kind():
    aligned = LayerCache(CacheKind("window", 32, 16, 3, None, None,
                                   aligned=True), 4, 2, 48, 3 << 10)
    sliding = LayerCache(CacheKind("window", 32, 16, 3, None, None), 4, 2, 48,
                         3 << 10)
    chunks = LayerCache(CacheKind("chunk", None, 8, 3, None, None, chunk=4),
                        4, 2, 48, 3 << 10)
    lengths = np.array([0, 31, 32, 33, 63, 64, 100])
    assert list(aligned.first_seen(lengths)) == [0, 0, 8, 8, 8, 16, 24]
    assert list(sliding.first_seen(lengths)) == [0, 0, 0, 0, 8, 8, 17]
    assert [aligned.blocks_for(n) for n in (1, 32, 33, 64, 100)] == \
        [1, 0, 1, 0, 1]
    assert [chunks.blocks_for(n) for n in (3, 4, 16, 17, 100)] == \
        [0, 1, 1, 1, 7]
    table = []
    aligned.extend(table, 70, 0)
    dead = aligned.pool.num_blocks
    assert table[:16] == [dead] * 16 and len(table) == 18
    aligned.tables[0] = table
    assert aligned.trim(0, 16) == 0 and aligned.first[0] == 16
    aligned.extend(table, 96, 0)
    assert aligned.pool.used_count() == 8
    assert aligned.trim(0, aligned.first_seen(96)) == 8
    aligned.check_invariant()
    aligned.release(0)
    assert aligned.pool.used_count() == 0
    rows = []
    chunks.extend(rows, 15, 1)
    assert len(rows) == 1           # 3 whole chunks: one block of 4 rows
    chunks.extend(rows, 17, 1)
    assert len(rows) == 1
    chunks.extend(rows, 20, 1)
    assert len(rows) == 2
    chunks.drop(rows)
    assert chunks.pool.used_count() == 0


@pytest.mark.parametrize("what", ["prefix_cache", "speculate_k"])
def test_a_chunk_kind_refuses_what_its_prefill_cannot_serve(what, model):
    kw = dict(prefix_cache=True, decode_policy=None) \
        if what == "prefix_cache" else dict(
            prefix_cache=False, decode_policy=DecodePolicy(speculate_k=2))
    with pytest.raises(ValueError, match="a chunk kind of layer cache takes "
                       "neither prefix_cache nor speculate_k: its prefill"):
        lm_session(MoeLM(**SIZES), max_len=64, slots=2, cache_len=64,
                   prompt_buckets=(32,), block_size=C, num_blocks=16,
                   kind_blocks={"chunk": 8}, **kw)
    if what == "prefix_cache":
        spec = moe_lm_session(slots=2, cache_len=64, prompt_buckets=(32,),
                              block_size=C, num_blocks=16,
                              chunk_num_blocks=8, **SIZES)
        spec.prefix_cache = True
        with pytest.raises(ValueError, match="a chunk kind"):
            GenerationSession(spec, scope=model[0])


def test_what_the_model_refuses():
    with pytest.raises(ValueError, match="windows of whole chunks"):
        MoeLM(**dict(SIZES, eva=dict(window=30, chunk=4)))
    with pytest.raises(ValueError, match="a KV head of its own"):
        MoeLM(**dict(SIZES, num_kv_heads=2))
    with pytest.raises(ValueError, match="'gqa', 'latent' or 'eva'"):
        MoeLM(**dict(SIZES, attention="linear"))


# -- the ops -----------------------------------------------------------------

def _pools(rs, slots, lengths, heads, hd, bs, dtype=np.float32):
    """Paged pools holding ``lengths`` rows a slot, blocks in a shuffled
    order, and the dense rows they hold."""
    dm = heads * hd
    mb = -(-max(lengths) // bs) + 1
    nb = slots * mb
    order = rs.permutation(nb)
    k = rs.randn(nb, bs, dm).astype(dtype)
    v = rs.randn(nb, bs, dm).astype(dtype)
    tables = order.reshape(slots, mb).astype(np.int32)
    return k, v, tables, k[tables].reshape(slots, mb * bs, dm), \
        v[tables].reshape(slots, mb * bs, dm)


@pytest.mark.parametrize("lengths", [(1, 32, 33, 70), (64, 65, 17, 96)])
def test_an_aligned_walk_attends_from_the_windows_edge(lengths):
    """The kernel (interpreted) and the gather, with ``aligned``, against
    dense attention over rows ``[W (len - 1) // W, len)``."""
    rs = np.random.RandomState(sum(lengths))
    heads, hd, bs = 4, 16, 4
    k, v, tables, dk, dv = _pools(rs, 4, lengths, heads, hd, bs)
    q = rs.randn(4, 1, heads * hd).astype(np.float32)
    lens = np.asarray(lengths, np.int32)
    want = np.zeros((4, heads, hd), np.float32)
    for s, n in enumerate(lengths):
        lo = (n - 1) // W * W
        qs = q[s, 0].reshape(heads, hd)
        ks = dk[s, lo:n].reshape(n - lo, heads, hd)
        vs = dv[s, lo:n].reshape(n - lo, heads, hd)
        p = jax.nn.softmax(np.einsum("hd,khd->hk", qs, ks) * hd ** -0.5, -1)
        want[s] = np.einsum("hk,khd->hd", p, vs)
    for walk in (pa.decode_attention_paged, pa._decode_paged_reference):
        got = walk(q, k, v, lens, tables, heads, window=W, aligned=True)
        np.testing.assert_allclose(np.asarray(got).reshape(4, heads, hd),
                                   want, atol=2e-5)
    assert list(np.asarray(pa.window_edge(lens, W, True))) == \
        [(n - 1) // W * W for n in lengths]
    assert list(np.asarray(pa.window_edge(lens, W, False))) == \
        [max(n - W, 0) for n in lengths]


@pytest.mark.parametrize("walk", [pa.decode_attention_paged,
                                  pa._decode_paged_reference],
                         ids=["kernel", "gather"])
def test_two_walks_merge_into_one_softmax(walk):
    """Each walk's result with its maximum and sum: merged, the softmax
    over both pools' rows; a walk of no rows adds nothing."""
    rs = np.random.RandomState(4)
    heads, hd, bs = 4, 16, 4
    la, lb = (9, 1, 20), (5, 0, 12)
    ka, va, ta, dka, dva = _pools(rs, 3, la, heads, hd, bs)
    kb, vb, tb, dkb, dvb = _pools(rs, 3, lb, heads, hd, bs)
    q = rs.randn(3, 1, heads * hd).astype(np.float32)
    a = walk(q, ka, va, np.asarray(la, np.int32), ta, heads, stats=True)
    b = walk(q, kb, vb, np.asarray(lb, np.int32), tb, heads, stats=True)
    assert a[0].dtype == jnp.float32 and a[1].shape == (3, heads, 1)
    assert float(b[2][1].max()) == 0.0          # no rows: a sum of zero
    got = np.asarray(pa.merge_walks([a, b], heads)).reshape(3, heads, hd)
    for s in range(3):
        ks = np.concatenate([dka[s, :la[s]], dkb[s, :lb[s]]]) \
            .reshape(-1, heads, hd)
        vs = np.concatenate([dva[s, :la[s]], dvb[s, :lb[s]]]) \
            .reshape(-1, heads, hd)
        p = jax.nn.softmax(np.einsum(
            "hd,khd->hk", q[s, 0].reshape(heads, hd), ks) * hd ** -0.5, -1)
        np.testing.assert_allclose(got[s], np.einsum("hk,khd->hd", p, vs),
                                   atol=2e-5)
    # alone, a walk with its maximum and sum is the walk without
    plain = walk(q, ka, va, np.asarray(la, np.int32), ta, heads)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(plain),
                               atol=2e-6)


def test_pool_chunks_is_the_two_softmaxes():
    rs = np.random.RandomState(6)
    k, v = rs.randn(5, C, 4, 16), rs.randn(5, C, 4, 16)
    mu, phi = rs.randn(4, 16), rs.randn(4, 16)
    kbar, vbar = (x.reshape(5, 4, 16) for x in eva_ops.pool_chunks(
        *(jnp.asarray(x.reshape(-1, 64), jnp.float32) for x in (k, v)),
        *(jnp.asarray(x.reshape(64), jnp.float32) for x in (mu, phi)), 4, C))
    rk, rv = ref.summaries(jnp.asarray(k.reshape(5 * C, 4, 16), jnp.float32),
                           jnp.asarray(v.reshape(5 * C, 4, 16), jnp.float32),
                           jnp.asarray(mu, jnp.float32),
                           jnp.asarray(phi, jnp.float32), C)
    np.testing.assert_allclose(np.asarray(kbar), np.asarray(rk), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vbar), np.asarray(rv), atol=1e-5)
    for j in range(5):      # by hand, one chunk and head
        wk = np.exp(k[j, :, 1] @ mu[1])
        np.testing.assert_allclose(
            np.asarray(kbar)[j, 1], (wk / wk.sum()) @ k[j, :, 1], atol=1e-5)


def _run(build, feed, scope=None):
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 11
    scope = scope or ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.unique_name.guard(), \
            ptpu.program_guard(main, startup):
        fetch = build()
        exe = ptpu.Executor()
        exe.run(startup)
        outs = exe.run(main, feed=feed, fetch_list=list(fetch))
    return [np.asarray(o) for o in outs], scope


def test_a_chunk_rows_paged_writes():
    """``kv_cache_append_paged`` with ``chunk``: row ``pos // chunk``,
    written by the chunk's last position only; ``kv_cache_write_paged``
    with ``chunk``: the whole chunks of ``Len`` positions."""
    from paddle_tpu.layer_helper import LayerHelper
    pool0 = np.zeros((6, 2, 8), np.float32)
    new = np.arange(3 * 8, dtype=np.float32).reshape(3, 1, 8) + 1
    table = np.array([[4, 1, 6], [2, 6, 6], [0, 3, 6]], np.int32)

    def build():
        pool = layers.data("pool", shape=[6, 2, 8], dtype="float32",
                           append_batch_size=False)
        n = layers.data("new", shape=[3, 1, 8], dtype="float32",
                        append_batch_size=False)
        pos = layers.data("pos", shape=[3], dtype="int32",
                          append_batch_size=False)
        tab = layers.data("tab", shape=[3, 3], dtype="int32",
                          append_batch_size=False)
        LayerHelper("t").append_op(
            type="kv_cache_append_paged",
            inputs={"Cache": [pool.name], "New": [n.name],
                    "Pos": [pos.name], "Table": [tab.name]},
            outputs={"Out": [pool.name]}, attrs={"chunk": 4})
        return [pool]
    # positions 7 (chunk 1's last), 6 (not a last), 11 (chunk 2's last)
    (got,), _ = _run(build, {"pool": pool0, "new": new, "tab": table,
                             "pos": np.array([7, 6, 11], np.int32)})
    want = pool0.copy()
    want[4, 1] = new[0, 0]          # row 1 = block table[0][0], offset 1
    want[3, 0] = new[2, 0]          # row 2 = block table[2][1], offset 0
    assert np.array_equal(got, want)

    rows = np.arange(5 * 8, dtype=np.float32).reshape(1, 5, 8) + 1

    def build_write():
        pool = layers.data("pool", shape=[6, 2, 8], dtype="float32",
                           append_batch_size=False)
        n = layers.data("new", shape=[1, 5, 8], dtype="float32",
                        append_batch_size=False)
        tab = layers.data("tab", shape=[3], dtype="int32",
                          append_batch_size=False)
        hist = layers.data("hist", shape=[1], dtype="int32",
                           append_batch_size=False)
        ln = layers.data("len", shape=[1], dtype="int32",
                         append_batch_size=False)
        LayerHelper("t").append_op(
            type="kv_cache_write_paged",
            inputs={"Cache": [pool.name], "New": [n.name],
                    "Table": [tab.name], "Hist": [hist.name],
                    "Len": [ln.name]},
            outputs={"Out": [pool.name]}, attrs={"chunk": 4})
        return [pool]
    (got,), _ = _run(build_write, {
        "pool": pool0, "new": rows, "tab": np.array([5, 2, 6], np.int32),
        "hist": np.zeros(1, np.int32), "len": np.array([14], np.int32)})
    want = pool0.copy()             # 14 positions: 3 whole chunks
    want[5, 0], want[5, 1], want[2, 0] = rows[0, 0], rows[0, 1], rows[0, 2]
    assert np.array_equal(got, want)


def test_the_unit_offset_norm_and_the_clipped_normal():
    x = np.random.RandomState(0).randn(2, 3, 16).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[2, 3, 16], dtype="float32",
                         append_batch_size=False)
        return [layers.rms_norm(xv, param_attr="n0.w"),
                layers.rms_norm(xv, param_attr="n1.w", offset=1.0)]
    (plain, offset), scope = _run(build, {"x": x})
    assert float(np.asarray(scope.find_var("n1.w")).max()) == 0.0
    assert float(np.asarray(scope.find_var("n0.w")).min()) == 1.0
    np.testing.assert_allclose(plain, offset, atol=1e-6)
    scope.set_var("n1.w", jnp.full((16,), 0.5, jnp.float32))
    with ptpu.scope_guard(scope), ptpu.unique_name.guard():
        main = ptpu.Program()
        with ptpu.program_guard(main, ptpu.Program()):
            xv = layers.data("x", shape=[2, 3, 16], dtype="float32",
                             append_batch_size=False)
            out = layers.rms_norm(xv, param_attr="n1.w", offset=1.0)
        got = np.asarray(ptpu.Executor().run(main, feed={"x": x},
                                             fetch_list=[out.name])[0])
    np.testing.assert_allclose(got, 1.5 * plain, rtol=1e-6)

    def draws():
        from paddle_tpu.initializer import NormalInitializer
        from paddle_tpu.layer_helper import LayerHelper
        LayerHelper("t").create_parameter(
            "clipped.w", shape=[4096], dtype="float32",
            default_initializer=NormalInitializer(0.0, 0.25, clip=1.0))
        return []
    _, scope = _run(draws, {})
    w = np.asarray(scope.find_var("clipped.w"))
    assert np.abs(w).max() == pytest.approx(0.25)
    assert 0.25 < (np.abs(w) == np.abs(w).max()).mean() < 0.40    # 31.7%


def test_a_projection_of_bfloat16_operands_takes_one_pass():
    """``exact_dot``: three pieces of a float32 input, the one piece of a
    bfloat16 one; the ``amp`` flag hands ``linear`` the latter."""
    rs = np.random.RandomState(1)
    x = rs.randn(8, 64).astype(np.float32)
    w = jnp.asarray(rs.randn(64, 32), jnp.bfloat16)
    exact = np.asarray(moe_ops.exact_dot(jnp.asarray(x), w))
    rounded = np.asarray(moe_ops.exact_dot(jnp.asarray(x, jnp.bfloat16), w))
    assert rounded.dtype == np.float32
    want = x.astype(np.float64) @ np.asarray(w, np.float64)
    assert np.abs(exact - want).max() < 1e-4
    assert 1e-3 < np.abs(rounded - want).max() < 0.2
    text = jax.jit(moe_ops.exact_dot).lower(
        jax.ShapeDtypeStruct((8, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)).as_text()
    assert "reduce_precision" not in text and "24x64" not in text
    from paddle_tpu.core import executor
    assert {"linear", "eva_attention", "eva_summaries",
            "eva_attention_decode_paged"} <= executor.AMP_WHITE


def test_programs_of_the_three_modes_hold_the_ops(model):
    """The prefill writes both pools through the two tables; the decode
    step appends, pools its block, appends the summary and walks both."""
    spec = moe_lm_session(slots=2, cache_len=64, prompt_buckets=(32,),
                          block_size=C, num_blocks=16, chunk_num_blocks=8,
                          **SIZES)
    kinds = spec.cache_kinds
    assert [(k.name, k.window, k.aligned, k.chunk, k.layers)
            for k in kinds] == [("window", W, True, 1, 3),
                                ("chunk", None, False, C, 3)]
    assert (kinds[1].prefill_table, kinds[1].decode_table) == \
        ("gen.ptab.chunk", "gen.dtab.chunk")
    assert len(spec.cache_vars) == 3 * 4
    decode = [op.type for op in spec.decode_program.global_block().ops]
    prefill = [op.type for op in
               spec.prefill_programs[32].global_block().ops]
    assert decode.count("eva_attention_decode_paged") == 3
    assert decode.count("eva_summaries") == 3
    assert decode.count("kv_cache_append_paged") == 12
    assert prefill.count("eva_attention") == 3
    assert prefill.count("kv_cache_write_paged") == 12
    chunked = [op for op in spec.decode_program.global_block().ops
               if op.type == "kv_cache_append_paged"
               and op.attrs.get("chunk")]
    assert len(chunked) == 6 and all(
        op.inputs["Table"] == ["gen.dtab.chunk"] for op in chunked)
    norms = [op for op in spec.decode_program.global_block().ops
             if op.type == "rms_norm"]
    assert len(norms) == 7 and all(op.attrs["offset"] == 1.0 for op in norms)
    # the next byte is head 0's: the argmax reads 64 of the head's 192
    block = spec.decode_program.global_block()
    row = block.var(bench_lm.logits_var(spec.decode_program,
                                        spec.decode_fetch))
    assert tuple(row.shape) == (2, V)


# -- a decode step's rotary turns take their rows from behind a fence --------

def _traced(exe, program, feed, fetch, scope):
    """(barriers in the traced step, rotary turns counted fenced by that
    trace, the jittable step and its arguments)."""
    fn, args = exe.as_jax_function(program, feed, fetch, scope=scope)
    before = _counter("paddle_rotary_fenced_total")
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    return (jaxpr.count("optimization_barrier"),
            _counter("paddle_rotary_fenced_total") - before, fn, args)


def _shaped_feed(program, names):
    block = program.global_block()
    return {n: np.ones(block.var(n).shape, "int32") for n in names}


def test_a_decode_step_is_traced_with_its_rotary_rows_fenced(model,
                                                             monkeypatch):
    """The q and the k of each of the three layers go into their rotary
    turn from behind an ``optimization_barrier`` (so that XLA reads the
    two projections' weights where they lie: PERF.md, PR 44), the counter
    moves by as many at the trace, and the step's logits equal, bit for
    bit, those of the same step traced with the fence patched out, which
    moves no counter."""
    sess = _session(model)
    spec = sess.spec
    slot, _ = sess.admit(_tokens(9)[:37])
    feed = sess.step_prepare()[2]
    name = bench_lm.logits_var(spec.decode_program, spec.decode_fetch)
    rotary = [op for op in spec.decode_program.global_block().ops
              if op.type == "rotary_embedding"]
    assert len(rotary) == 6 and all(op.attrs["per_row"] for op in rotary)

    barriers, counted, fn, args = _traced(
        sess.exe, spec.decode_program, feed, [name], sess.scope)
    assert (barriers, counted) == (6, 6)
    fenced = np.asarray(jax.jit(fn)(*args)[0])

    monkeypatch.setattr(moe_ops, "_fence_rows", lambda ctx, x: x)
    barriers, counted, fn, args = _traced(
        sess.exe, spec.decode_program, feed, [name], sess.scope)
    assert (barriers, counted) == (0, 0)
    plain = np.asarray(jax.jit(fn)(*args)[0])
    assert np.abs(plain[slot]).max() > 0.1
    assert np.array_equal(fenced, plain)
    sess.close()


@pytest.mark.parametrize("what", ["prefill", "whole"])
def test_only_a_steps_rows_are_fenced(model, what):
    """A prefill's rotary turns (positions along the time axis) and a
    whole-sequence program's (no positions fed) are traced as they were:
    no barrier, no count. There the rows are as many as a weight's."""
    if what == "prefill":
        sess = _session(model)
        spec = sess.spec
        program = spec.prefill_programs[32]
        feed = _shaped_feed(program, list(spec.prefill_feeds[:6]) + [
            k.prefill_table for k in spec.cache_kinds])
        exe, scope, fetch = sess.exe, sess.scope, [spec.prefill_fetch]
    else:
        scope, program, name = model
        seq = _tokens(1)[None]
        exe, feed, fetch = ptpu.Executor(), {"toks": seq, "lbls": seq}, [name]
    rotary = [op for op in program.global_block().ops
              if op.type == "rotary_embedding"]
    assert len(rotary) == 6 and not any(op.attrs["per_row"] for op in rotary)
    assert _traced(exe, program, feed, fetch, scope)[:2] == (0, 0)


def test_a_rotary_turn_of_rows_counts_where_a_step_is_traced_only():
    """A ``per_row`` turn built into a program: inferring its shape (the
    program's build) counts nothing, the executor's trace counts one, and
    the turn is the one the time-axis form makes of the same positions."""
    x = np.random.RandomState(2).randn(3, 1, 32).astype("float32")
    pos = np.array([5, 0, 17], np.int32)
    before = _counter("paddle_rotary_fenced_total")

    def build():
        xv = layers.data("x", shape=[3, 1, 32], append_batch_size=False)
        pv = layers.data("pos", shape=[3], dtype="int32",
                         append_batch_size=False)
        out = layers.rotary_embedding(xv, 8, pos=pv, per_row=True)
        assert _counter("paddle_rotary_fenced_total") == before
        return [out]
    (rows,), _ = _run(build, {"x": x, "pos": pos})
    assert _counter("paddle_rotary_fenced_total") - before == 1

    def along():
        xv = layers.data("x", shape=[1, 3, 32], append_batch_size=False)
        pv = layers.data("pos", shape=[3], dtype="int32",
                         append_batch_size=False)
        return [layers.rotary_embedding(xv, 8, pos=pv)]
    (time,), _ = _run(along, {"x": x.reshape(1, 3, 32), "pos": pos})
    assert _counter("paddle_rotary_fenced_total") - before == 1
    assert np.array_equal(rows.reshape(3, 32), time.reshape(3, 32))
