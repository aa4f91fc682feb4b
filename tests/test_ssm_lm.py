"""The Mamba-2 mixer (``ops/ssm_ops.py``), the state kind of layer cache,
the softmax-of-the-chosen router, and the whole ``granitemoehybrid``-shaped
model (``models/moe_lm.py`` with state-space layers beside an attention
layer) through a session against the plain reference of
``benchmarks/reference/granitemoehybrid.py``. CPU, small sizes."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.moe_lm import MoeLM, moe_lm, moe_lm_session
from paddle_tpu.models.transformer import lm_session
from paddle_tpu.observability import metrics, tracing
from paddle_tpu.ops import moe_ops
from paddle_tpu.resilience import faults
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.serving.decoding import DecodePolicy
from paddle_tpu.serving.paged_cache import BLOCKS_IN_USE

from benchmarks.architectures import granitemoehybrid as arch
from benchmarks.harness import lm as bench_lm
from benchmarks.harness.serve import CHECK_STEPS, LOGIT_RTOL
from benchmarks.reference import granitemoehybrid as ref

pytestmark = [pytest.mark.generation, pytest.mark.paged]

# the catalog's keys at a small size: chunks of 8 rows, 8 heads of 4 lanes
# over a state of 16, one attention layer between state-space layers, 8
# experts of which a share can be held; multipliers that are none of them 1.
# ``initializer_range`` 0.3 at 16 lanes gives a projection's output the
# spread the published 0.02 gives at 4,096 (0.3 x 4 = 1.2; 0.02 x 64 = 1.28)
CFG = dict(
    attention_bias=False, hidden_act="silu", mamba_conv_bias=True,
    mamba_proj_bias=False, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
    position_embedding_type="nope", normalization_function="rmsnorm",
    tie_word_embeddings=True, rms_norm_eps=1e-5, initializer_range=0.3,
    torch_dtype="float32", max_position_embeddings=4096,
    hidden_size=16, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=4, mamba_d_state=16, mamba_chunk_size=8,
    intermediate_size=8, shared_intermediate_size=16,
    num_local_experts_published=8, num_local_experts=8, expert_offset=0,
    num_experts_per_tok=3, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"], vocab_size=96,
    embedding_multiplier=2, attention_multiplier=0.3, residual_multiplier=0.5,
    logits_scaling=4)
SIZES = arch.sizes(CFG)
MAMBA = SIZES["mamba"]
H, P, N, K = 8, 4, 16, 4
LANES = H * P + 2 * N


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


@pytest.fixture()
def flash_off():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


# -- the mixer's two ops against the sequential recurrence --------------------

def _mixer_weights(seed=3):
    """Seeded weights of one mixer under the layer's names and, for the
    reference, under its own."""
    rs = np.random.RandomState(seed)
    d = 16
    w = {"in.w": rs.randn(d, 2 * H * P + 2 * N + H) * 0.3,
         "conv.w": rs.randn(K, LANES) * 0.5, "conv.b": rs.randn(LANES) * 0.1,
         "dt_bias": rs.uniform(-4, -1, H), "a_log": np.log(
             rs.uniform(1, 16, H)), "d": rs.randn(H),
         "norm.w": 1 + 0.1 * rs.randn(H * P), "out.w": rs.randn(H * P, d) * 0.3}
    w = {k: v.astype(np.float32) for k, v in w.items()}
    # the reference's names: in, conv_w, conv_b, dt_bias, a_log, d, norm, out
    theirs = {"m." + (k.replace(".", "_") if k.startswith("conv")
                      else k.replace(".w", "")): jnp.asarray(v)
              for k, v in w.items()}
    return {"mix." + k: v for k, v in w.items()}, theirs


def _reference_mixer(x, theirs):
    """(out [T, d], the state after the last row, the convolution's last K
    inputs) of the reference's recurrence over x [T, d] alone."""
    z, raw, act, dt = ref.mamba_inputs(jnp.asarray(x), theirs, "m.", CFG)
    t = x.shape[0]
    _, last = ref.mamba_scan(
        act[:, :H * P].reshape(t, H, P), dt, -jnp.exp(theirs["m.a_log"]),
        act[:, H * P:H * P + N], act[:, H * P + N:], theirs["m.d"])
    tail = jnp.pad(raw, ((K, 0), (0, 0)))[t:]
    with jax.default_matmul_precision("highest"):
        out = ref._mamba(jnp.asarray(x), theirs, "m.", CFG)
    return np.asarray(out), np.asarray(last), np.asarray(tail)


def _pool_vars(rows):
    block = ptpu.default_main_program().global_block()
    return tuple(block.create_var(name="pool." + name, shape=shape,
                                  dtype=dtype, persistable=True,
                                  stop_gradient=True)
                 for name, shape, dtype in (
                     ("ssm", (rows, H, P, N), "float32"),
                     ("conv", (rows, K, LANES), "float32"),
                     ("at", (rows,), "int32")))


def _pool_values(rows, fill=0.0):
    return {"pool.ssm": np.full((rows, H, P, N), fill, np.float32),
            "pool.conv": np.full((rows, K, LANES), fill, np.float32),
            "pool.at": np.full((rows,), -7, np.int32)}


@pytest.mark.parametrize("bucket,length", [
    (24, 24), (32, 21), (32, 16), (32, 3), (8, 8)],
    ids=["whole_chunks_unpadded", "ends_inside_a_chunk", "ends_on_an_edge",
         "shorter_than_the_convolution", "one_chunk"])
def test_chunked_mixer_leaves_the_recurrences_state_at_the_true_length(
        bucket, length):
    """The chunked form over a padded bucket against the sequential
    recurrence over the unpadded rows: the outputs of the real rows, and in
    the slot's row the state after row ``length - 1``, the last four
    inputs of the convolution before ``length`` and ``length`` itself.
    Other rows of the pool keep what they held."""
    mine, theirs = _mixer_weights()
    x = np.random.RandomState(bucket + length).randn(1, bucket, 16) \
        .astype(np.float32)

    def build():
        xv = layers.data("x", shape=[1, bucket, 16], dtype="float32",
                         append_batch_size=False)
        ln = layers.data("len", shape=[1], dtype="int32",
                         append_batch_size=False)
        tab = layers.data("tab", shape=[1], dtype="int32",
                          append_batch_size=False)
        pool = _pool_vars(3)
        out = layers.mamba2_mixer(xv, prefix="mix", dtype="float32",
                                  state=pool, table=tab, length=ln, **MAMBA)
        return [out] + list(pool)
    (out, ssm, conv, at), _ = _run(
        build, {"x": x, "len": np.array([length], np.int32),
                "tab": np.array([1], np.int32)},
        dict(mine, **_pool_values(3, fill=0.5)))
    want, last, tail = _reference_mixer(x[0, :length], theirs)
    np.testing.assert_allclose(out[0, :length], want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ssm[1], last, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(conv[1], tail, rtol=1e-5, atol=1e-5)
    assert at.tolist() == [-7, length, -7]
    assert (ssm[[0, 2]] == 0.5).all() and (conv[[0, 2]] == 0.5).all()


def test_a_dead_table_entry_drops_the_prefills_write():
    mine, _ = _mixer_weights()
    x = np.random.RandomState(1).randn(1, 8, 16).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[1, 8, 16], dtype="float32",
                         append_batch_size=False)
        ln = layers.data("len", shape=[1], dtype="int32",
                         append_batch_size=False)
        tab = layers.data("tab", shape=[1], dtype="int32",
                          append_batch_size=False)
        pool = _pool_vars(2)
        layers.mamba2_mixer(xv, prefix="mix", dtype="float32", state=pool,
                            table=tab, length=ln, **MAMBA)
        return list(pool)
    (ssm, conv, at), _ = _run(
        build, {"x": x, "len": np.array([5], np.int32),
                "tab": np.array([2], np.int32)},
        dict(mine, **_pool_values(2, fill=0.5)))
    assert (ssm == 0.5).all() and (conv == 0.5).all() and (at == -7).all()


def _decode_program(slots):
    def build():
        xv = layers.data("x", shape=[slots, 1, 16], dtype="float32",
                         append_batch_size=False)
        pos = layers.data("pos", shape=[slots], dtype="int32",
                          append_batch_size=False)
        tab = layers.data("tab", shape=[slots, 1], dtype="int32",
                          append_batch_size=False)
        pool = _pool_vars(slots)
        out = layers.mamba2_mixer(xv, prefix="mix", dtype="float32",
                                  state=pool, table=tab, pos=pos, **MAMBA)
        return [out] + list(pool)
    return build


def test_decode_steps_walk_the_recurrence_row_by_row():
    """Three slots at lengths of their own, stepped from a zero state: each
    step's output and the rows left behind are the recurrence's; a slot
    whose table entry is dead (slot 1 in the odd steps) moves nothing and
    catches up later."""
    mine, theirs = _mixer_weights()
    rs = np.random.RandomState(4)
    xs = rs.randn(3, 9, 16).astype(np.float32)      # slot, row, d
    scope = ptpu.Scope()
    state = dict(_pool_values(3), **{"pool.at": np.zeros(3, np.int32)})
    done = [0, 0, 0]
    outs = [[], [], []]
    for step in range(9):
        live = [True, step % 2 == 0, done[2] < 5]
        x = np.stack([xs[s, min(done[s], 8)] for s in range(3)])[:, None]
        (out, ssm, conv, at), _ = _run(
            _decode_program(3),
            {"x": x, "pos": np.asarray(done, np.int32),
             "tab": np.asarray([[s if live[s] else 3] for s in range(3)],
                               np.int32)}, dict(mine, **state), scope)
        for s in range(3):
            if live[s]:
                outs[s].append(out[s, 0])
                done[s] += 1
            else:
                np.testing.assert_array_equal(ssm[s], state["pool.ssm"][s])
                np.testing.assert_array_equal(conv[s], state["pool.conv"][s])
        state = {"pool.ssm": ssm, "pool.conv": conv, "pool.at": at}
        assert at.tolist() == done
    for s in range(3):
        want, last, tail = _reference_mixer(xs[s, :done[s]], theirs)
        np.testing.assert_allclose(np.stack(outs[s]), want, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(state["pool.ssm"][s], last, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(state["pool.conv"][s], tail, rtol=1e-5,
                                   atol=1e-5)


def test_a_position_stepped_twice_is_absorbed_once():
    """The same feeds twice: the second run finds the rows one token
    further than its position, leaves them and gives the first run's
    output from the rows as stored."""
    mine, _ = _mixer_weights()
    rs = np.random.RandomState(6)
    state = {"pool.ssm": rs.randn(2, H, P, N).astype(np.float32),
             "pool.conv": rs.randn(2, K, LANES).astype(np.float32),
             "pool.at": np.asarray([11, 4], np.int32)}
    feed = {"x": rs.randn(2, 1, 16).astype(np.float32),
            "pos": np.asarray([11, 4], np.int32),
            "tab": np.asarray([[0], [1]], np.int32)}
    first, _ = _run(_decode_program(2), feed, dict(mine, **state))
    again, _ = _run(_decode_program(2), feed, dict(mine, **{
        "pool.ssm": first[1], "pool.conv": first[2], "pool.at": first[3]}))
    assert first[3].tolist() == again[3].tolist() == [12, 5]
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    assert np.abs(first[1] - state["pool.ssm"]).max() > 1e-3


def test_a_state_pool_is_one_row_a_slot():
    with pytest.raises(Exception, match="row of its own index"):
        def build():
            xv = layers.data("x", shape=[2, 1, 16], dtype="float32",
                             append_batch_size=False)
            pos = layers.data("pos", shape=[2], dtype="int32",
                              append_batch_size=False)
            tab = layers.data("tab", shape=[2, 1], dtype="int32",
                              append_batch_size=False)
            return [layers.mamba2_mixer(
                xv, prefix="mix", dtype="float32", state=_pool_vars(3),
                table=tab, pos=pos, **MAMBA)]
        _run(build, {})


# -- the router: a softmax over the chosen, and the sigmoid as it was ---------

D, F, E, TOPK = 16, 8, 8, 3


def _expert_weights(seed=5):
    rs = np.random.RandomState(seed)
    return {"router": rs.randn(D, E), "gate": rs.randn(E, D, F) * 0.3,
            "up": rs.randn(E, D, F) * 0.3, "down": rs.randn(E, F, D) * 0.3}


def _moe(x, offset=0, held=None, scoring="softmax_topk"):
    full = _expert_weights()
    n_held = held or E
    sets = {"m.router.w": full["router"].astype(np.float32)}
    for part in ("gate", "up", "down"):
        sets["m.experts.%s.w" % part] = \
            full[part][offset:offset + n_held].astype(np.float32)

    def build():
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        return layers.moe_ffn(xv, E, TOPK, F, "m", expert_offset=offset,
                              experts_held=held, scoring=scoring)
    (out, counts), _ = _run(build, {"x": x}, sets)
    return out, counts


@pytest.mark.parametrize("share_rows", [None, 4],
                         ids=["one_pass", "passes_of_4"])
def test_the_four_shares_add_up_to_the_uncut_layer(share_rows, monkeypatch):
    """8 experts held 2 at a time + the shared expert once = the
    reference's whole layer under the softmax over the chosen three; with
    the pairs in one pass and in passes of a few rows."""
    if share_rows:
        monkeypatch.setattr(moe_ops, "SHARE_ROWS", share_rows)
    x = np.random.RandomState(10).randn(12, D).astype(np.float32)
    parts, all_counts = zip(*[_moe(x, offset=o, held=2)
                              for o in range(0, E, 2)])
    w = _expert_weights()
    rs = np.random.RandomState(11)
    shared = [jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
              for s in ((D, 2 * F), (D, 2 * F), (2 * F, D))]
    cfg = dict(num_experts_per_tok=TOPK)
    weights = {"l.router": w["router"], "l.experts.gate": w["gate"],
               "l.experts.up": w["up"], "l.experts.down": w["down"]}
    weights = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    xj = jnp.asarray(x)
    sh = ref._swiglu(xj, *shared)
    whole = np.asarray(sh + ref._experts(xj, weights, "l.", cfg))
    np.testing.assert_allclose(sum(parts) + np.asarray(sh), whole,
                               rtol=2e-4, atol=2e-5)
    assert np.concatenate(all_counts).sum() == 12 * TOPK
    # the holder of every expert gives the same in one piece
    np.testing.assert_allclose(_moe(x)[0] + np.asarray(sh), whole,
                               rtol=2e-4, atol=2e-5)
    # and the reference, given one share, gives that share
    cfg["expert_offset"] = 4
    weights.update({"l.experts." + n: weights["l.experts." + n][4:6]
                    for n in ("gate", "up", "down")})
    np.testing.assert_allclose(
        parts[2], np.asarray(ref._experts(xj, weights, "l.", cfg)),
        rtol=2e-4, atol=2e-5)


def test_softmax_over_the_chosen_by_hand():
    x = np.random.RandomState(2).randn(5, D).astype(np.float32)
    w = _expert_weights()["router"].astype(np.float32)
    sel, weights = moe_ops.route(jnp.asarray(x), jnp.asarray(w),
                                 jnp.zeros(E), TOPK, True, 1.0,
                                 "softmax_topk")
    logits = x.astype(np.float64) @ w.astype(np.float64)
    for t in range(5):
        order = np.argsort(-logits[t])[:TOPK]
        assert np.asarray(sel)[t].tolist() == order.tolist()
        e = np.exp(logits[t, order] - logits[t, order].max())
        np.testing.assert_allclose(np.asarray(weights)[t], e / e.sum(),
                                   rtol=1e-5)
    with pytest.raises(ValueError, match="scoring is"):
        moe_ops.route(jnp.asarray(x), jnp.asarray(w), jnp.zeros(E), TOPK,
                      True, 1.0, "softmax")


@pytest.mark.parametrize("route_norm", [True, False])
def test_sigmoid_routing_is_bit_for_bit_what_it_was(route_norm):
    """``route`` before it took its scoring as data, line for line."""
    rs = np.random.RandomState(9)
    x = jnp.asarray(rs.randn(40, D), jnp.float32)
    w = jnp.asarray(rs.randn(D, E), jnp.float32)
    bias = jnp.asarray(rs.randn(E) * 0.1, jnp.float32)

    def was(x, router_w, bias, top_k, route_norm, route_scale):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, sel, axis=1)
        if route_norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return sel, w * route_scale
    want = was(x, w, bias, TOPK, route_norm, 2.5)
    for got in (moe_ops.route(x, w, bias, TOPK, route_norm, 2.5),
                moe_ops.route(x, w, bias, TOPK, route_norm, 2.5, "sigmoid")):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    # and an op built without the attribute routes by sigmoid
    out, _ = _moe(np.asarray(x[:6]), scoring="sigmoid")
    assert np.abs(out - _moe(np.asarray(x[:6]))[0]).max() > 1e-3


def test_a_tied_head_reads_the_embedding_as_it_lies():
    rs = np.random.RandomState(8)
    x = rs.randn(2, 3, 16).astype(np.float32)
    for dtype in ("float32", "bfloat16"):
        emb = jnp.asarray(rs.randn(24, 16), dtype)

        def build():
            xv = layers.data("x", shape=[2, 3, 16], dtype="float32",
                             append_batch_size=False)
            return [layers.linear(xv, 24, "emb.w", dtype, transpose_w=True)]
        (got,), _ = _run(build, {"x": x}, {"emb.w": emb})
        want = x.astype(np.float64) @ np.asarray(emb, np.float64).T
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the whole model through a session ----------------------------------------

T = 40


@pytest.fixture(scope="module")
def model_scope():
    """A scope with the model's weights at their own initial values, every
    one: the comparisons below tell a wrong state on the weights the
    startup program draws, as the benchmark's check has to."""
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 7
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        loss, logits = moe_lm(toks, lbls, **SIZES)
        ptpu.Executor().run(startup)
    initial = {n: np.asarray(scope.find_var(n)) for n in scope.var_names()
               if n.startswith("moe_lm.l0.mamba.")}
    return scope, main, loss, logits, initial


def _session(scope, flash=False, **kw):
    ptpu.config.set_flags(flash_attention=flash)
    args = dict(slots=3, cache_len=64, prompt_buckets=(16, 32), block_size=8,
                num_blocks=24)
    args.update(kw)
    return GenerationSession(moe_lm_session(**args, **SIZES), scope=scope)


def _state(sess, slot=None):
    """The state pools of every state-space layer, as arrays (of one slot's
    rows where ``slot`` is given)."""
    out = {}
    for name, _, _ in sess.spec.cache_vars:
        if name.rsplit(".", 1)[1] in ("ssm", "conv", "at"):
            value = np.asarray(sess.scope.find_var(name))
            out[name.split(".", 1)[1]] = value if slot is None \
                else value[slot]
    return out


def _step_with_logits(sess):
    """One decode step as the benchmark's check runs it: the decode program
    once with its logits fetched, then the step itself on the same feeds.
    -> ({slot: token}, logits [slots, V])."""
    prepared = sess.step_prepare()
    name = bench_lm.logits_var(sess.spec.decode_program,
                               sess.spec.decode_fetch)
    logits = sess.exe.run(sess.spec.decode_program, feed=prepared[2],
                          fetch_list=[name, sess.spec.decode_fetch],
                          scope=sess.scope)[0]
    return sess.step_run(prepared), np.asarray(logits, np.float32)


def _alone(scope, prompt, steps, **kw):
    """(tokens, logits of every decode step, the slot's final state) of one
    request in a session of its own, every step run once."""
    sess = _session(scope, **kw)
    slot, first = sess.admit(prompt)
    name = bench_lm.logits_var(sess.spec.decode_program,
                               sess.spec.decode_fetch)
    toks, logits = [first], []
    for _ in range(steps):
        prepared = sess.step_prepare()
        sess._decode_fetches.append(name)
        flight = sess.step_launch(prepared)
        sess._decode_fetches.pop()
        logits.append(np.asarray(flight.outs[-1], np.float32)[slot])
        toks.append(sess.step_collect(flight)[slot])
    state = _state(sess, slot)
    sess.close()
    return toks, np.stack(logits), state


def test_the_model_holds_the_parameters_the_equations_name(model_scope):
    scope = model_scope[0]
    names = {n for n in scope.var_names() if n.startswith("moe_lm.")}
    want = set(ref.weight_names(CFG).values()) | {
        "moe_lm.l%d.moe.expert_bias" % i for i in range(4)}
    assert names == want
    assert "moe_lm.lm_head.w" not in names      # the head is the embedding
    w_in = scope.find_var("moe_lm.l0.mamba.in.w")
    assert w_in.shape == (16, 2 * H * P + 2 * N + H)
    assert scope.find_var("moe_lm.l0.mamba.conv.w").shape == (K, LANES)
    assert scope.find_var("moe_lm.l2.attn.q.w").shape == (16, 16)
    assert scope.find_var("moe_lm.l2.attn.k.w").shape == (16, 8)
    assert scope.find_var("moe_lm.l1.moe.shared.gate.w").shape == (16, 16)
    assert scope.find_var("moe_lm.l1.moe.experts.gate.w").shape == (8, 16, 8)


def test_initial_values_follow_the_published_rule(model_scope):
    """``A`` uniform in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1] through
    the inverse softplus, ``D`` ones: a state that carries over hundreds
    of rows, which Normal(0, 0.02) would not give."""
    initial = model_scope[4]
    a = np.exp(initial["moe_lm.l0.mamba.a_log"])
    dt = np.log1p(np.exp(initial["moe_lm.l0.mamba.dt_bias"]))
    assert ((a >= 1) & (a <= 16)).all() and a.std() > 1
    assert ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
    assert dt.max() / dt.min() > 3
    assert (initial["moe_lm.l0.mamba.d"] == 1).all()
    assert (initial["moe_lm.l0.mamba.norm.w"] == 1).all()
    assert (initial["moe_lm.l0.mamba.conv.b"] == 0).all()
    # the convolution's taps: uniform within 1 / sqrt(K), whatever
    # initializer_range is (std 0.5 / sqrt(3) = 0.29)
    taps = initial["moe_lm.l0.mamba.conv.w"]
    assert np.abs(taps).max() <= 0.5 and 0.25 < taps.std() < 0.33
    # the slowest of these eight heads still holds a quarter of a row 100
    # rows later (of 128 heads the slowest holds 1e-3 x 1 a row: e^-0.2
    # after 200)
    assert np.exp(-(a * dt).min() * 100) > 0.25
    # a second layer draws its own
    other = np.asarray(model_scope[0].find_var("moe_lm.l1.mamba.a_log"))
    assert np.abs(other - initial["moe_lm.l0.mamba.a_log"]).max() > 0.1


def _state_share_of_y(w):
    """Root mean square of ``S C`` over that of ``y = S C + D x`` in layer
    0's mixer, over the later half of T rows of unit inputs."""
    a = jnp.asarray(np.random.RandomState(2).standard_normal((T, 16)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, _, act, dt = ref.mamba_inputs(a, w, "l0.mamba.", CFG)
        xs = act[:, :H * P].reshape(T, H, P)
        y, _ = ref.mamba_scan(xs, dt, -jnp.exp(w["l0.mamba.a_log"]),
                              act[:, H * P:H * P + N], act[:, H * P + N:],
                              w["l0.mamba.d"])
    y, skip = np.asarray(y)[T // 2:], np.asarray(
        w["l0.mamba.d"][:, None] * xs)[T // 2:]
    return float(np.sqrt(((y - skip) ** 2).mean() / (y ** 2).mean()))


def test_the_state_carries_a_share_of_y_at_the_initial_values(model_scope):
    """``y = S C + D x``: at the initial values the state's term is over a
    tenth of ``y`` here, with a state of 16 numbers a lane (0.41 at the
    published width, where ``B C`` sums 128: my CPU run, PR 33), so a
    comparison of logits sees it. Taps of Normal(0, 0.02) leave x, B and C
    near 0.03 and the state a thousandth of ``y``."""
    w = ref.gather_weights(model_scope[0].find_var, CFG)
    assert _state_share_of_y(w) > 0.1
    taps = np.random.RandomState(3).standard_normal((K, LANES))
    small = dict(w, **{"l0.mamba.conv_w": jnp.asarray(0.02 * taps,
                                                      jnp.float32)})
    assert _state_share_of_y(small) < 0.005


def _control(name):
    """``tools/ssm_precision_control.py``'s control: a function that swaps
    one function of ``ops/ssm_ops.py`` for a faulty one."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "ssm_precision_control.py")
    spec = importlib.util.spec_from_file_location("ssm_control", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.CONTROLS[name]


@pytest.mark.parametrize("fault", ["none", "zeroed", "stale", "crossed"])
def test_the_benchmarks_check_tells_a_wrong_state(model_scope, flash_off,
                                                  monkeypatch, fault):
    """The check of ``benchmarks/harness/serve.py`` at a small size, on the
    model's own initial values: two prompts prefilled, CHECK_STEPS decode
    steps each run twice, the worst logit against the reference's forward
    as a share of the largest. A prefill that leaves no state, a step that
    never advances a row and a step that reads the neighbour's row each
    read over the harness's limit; the program as it is reads four orders
    under it."""
    from paddle_tpu.ops import ssm_ops
    for name in ("ssd_chunked", "ssm_step"):
        monkeypatch.setattr(ssm_ops, name, getattr(ssm_ops, name))
    _control(fault)()
    scope = model_scope[0]
    sess = _session(scope)
    rs = np.random.RandomState(12)
    prompts = [rs.randint(2, 96, n) for n in (14, 30)]
    slots, toks = [], []
    for p in prompts:
        slot, first = sess.admit(p)
        slots.append(slot)
        toks.append([first])
    got = [[] for _ in prompts]
    for _ in range(CHECK_STEPS):
        out, logits = _step_with_logits(sess)
        for i, slot in enumerate(slots):
            got[i].append(logits[slot])
            toks[i].append(out[slot])
    sess.close()
    w = ref.gather_weights(scope.find_var, CFG)
    worst = 0.0
    for p, mine, g in zip(prompts, toks, got):
        seq = np.concatenate([p, mine])
        want = np.asarray(ref.logits_at(
            w, jnp.asarray(seq),
            jnp.arange(len(p), len(p) + CHECK_STEPS), CFG))
        worst = max(worst, np.abs(np.stack(g) - want).max()
                    / np.abs(want).max())
    if fault == "none":
        assert worst < 1e-2 * LOGIT_RTOL, worst
    else:
        assert worst > 2 * LOGIT_RTOL, worst


def test_whole_sequence_forward_equals_the_reference(model_scope):
    scope, main, loss, logits, _ = model_scope
    rs = np.random.RandomState(0)
    toks = rs.randint(2, 96, (2, T)).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    with ptpu.scope_guard(scope):
        got_loss, got = ptpu.Executor().run(
            main, feed={"toks": toks, "lbls": lbls},
            fetch_list=[loss, logits])
    w = ref.gather_weights(scope.find_var, CFG)
    for b in range(2):
        want = np.asarray(ref.logits_at(w, jnp.asarray(toks[b]),
                                        jnp.arange(T), CFG))
        np.testing.assert_allclose(np.asarray(got)[b], want,
                                   atol=2e-4 * np.abs(want).max())
    want_loss = np.mean([float(ref.loss(w, jnp.asarray(toks[b]),
                                        jnp.asarray(lbls[b]), CFG))
                         for b in range(2)])
    assert abs(float(np.asarray(got_loss)) - want_loss) < 1e-4


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_equals_the_references_forward(model_scope,
                                                           flash):
    """A prompt of 21 rows in a bucket of 32 (three chunks of 8 and a
    padded tail) and 14 decode steps, every step run twice as the
    benchmark's check runs it: rows 0..34 cross four chunks and lie in
    five blocks of the attention layer's cache. Everything is float32 and
    the products are at the highest precision, so what is left is the
    order of the sums: the chunked scan against the row-by-row one, the
    paged softmax against the dense one. 2e-4 of the largest logit; a
    wrong state, chunk edge or block is of the order of the logits."""
    scope = model_scope[0]
    sess = _session(scope, flash=flash)
    rs = np.random.RandomState(5)
    prompt = rs.randint(2, 96, 21)
    other = rs.randint(2, 96, 9)
    slot, first = sess.admit(prompt)
    sess.admit(other)
    toks, got = [first], []
    for _ in range(14):
        out, logits = _step_with_logits(sess)
        got.append(logits[slot])
        toks.append(out[slot])
    assert int(sess.lengths[slot]) == 35
    assert len(sess.tables[slot]) == 5
    assert all(v.tolist() == [35, 23, 0] or v.ndim > 1
               for k, v in _state(sess).items())
    w = ref.gather_weights(scope.find_var, CFG)
    seq = np.concatenate([prompt, toks])
    want = np.asarray(ref.logits_at(
        w, jnp.asarray(seq), jnp.arange(20, 35), CFG))
    assert first == int(want[0].argmax())
    scale = np.abs(want).max()
    assert scale > 0.5
    np.testing.assert_allclose(np.stack(got), want[1:], atol=2e-4 * scale)
    assert toks[1:] == want[1:].argmax(-1).tolist()
    assert len(set(toks)) > 3       # not an echo of one token
    sess.close()


def test_a_step_run_twice_is_a_step_run_once(model_scope, flash_off):
    """Every decode step twice on the same feeds (the decode program, then
    the step itself) against a session that runs each once: the same
    logits both times, the same tokens, and state rows equal to the last
    bit."""
    scope = model_scope[0]
    prompt = np.random.RandomState(6).randint(2, 96, 13)
    toks1, logits1, state1 = _alone(scope, prompt, 6)
    sess = _session(scope)
    slot, first = sess.admit(prompt)
    toks2, logits2 = [first], []
    for _ in range(6):
        out, logits = _step_with_logits(sess)
        # what the step itself computed, the row holding p + 1 tokens
        logits2.append(logits[slot])
        toks2.append(out[slot])
    assert toks1 == toks2
    np.testing.assert_array_equal(np.stack(logits2), logits1)
    for name, value in _state(sess, slot).items():
        np.testing.assert_array_equal(value, state1[name], err_msg=name)
    sess.close()


def test_a_starved_slot_leaves_no_trace_and_is_stepped_again(model_scope,
                                                             flash_off):
    """Three blocks of 8 rows: request A (8 rows) and B (9 rows) fill them,
    so A's next row finds no block. The step advances B alone: A's state
    rows do not move, and once B retires A is stepped at the same position
    and goes on as if alone."""
    scope = model_scope[0]
    rs = np.random.RandomState(7)
    a, b = rs.randint(2, 96, 8), rs.randint(2, 96, 9)
    want, want_logits, want_state = _alone(scope, a, 4)
    sess = _session(scope, num_blocks=3)
    slot_a, first = sess.admit(a)
    slot_b, _ = sess.admit(b)
    sess.check_pool_invariant()
    before = _state(sess, slot_a)
    out = sess.step()
    assert sorted(out) == [slot_b] and sess._starved == {slot_a}
    sess.check_pool_invariant()
    for name, value in _state(sess, slot_a).items():
        np.testing.assert_array_equal(value, before[name], err_msg=name)
    assert all(v == 8 for k, v in _state(sess, slot_a).items()
               if k.endswith(".at"))
    sess.retire(slot_b)
    sess.check_pool_invariant()
    toks, logits = [first], []
    for _ in range(4):
        out, lg = _step_with_logits(sess)
        toks.append(out[slot_a])
        logits.append(lg[slot_a])
    assert toks == want
    np.testing.assert_array_equal(np.stack(logits), want_logits)
    for name, value in _state(sess, slot_a).items():
        np.testing.assert_array_equal(value, want_state[name], err_msg=name)
    sess.retire(slot_a)
    sess.check_pool_invariant()
    sess.close()


def test_a_retired_slots_row_starts_the_next_request_from_nothing(
        model_scope, flash_off):
    scope = model_scope[0]
    rs = np.random.RandomState(8)
    a, b = rs.randint(2, 96, 25), rs.randint(2, 96, 11)
    want, want_logits, _ = _alone(scope, b, 5)
    sess = _session(scope)
    slot, _ = sess.admit(a)
    for _ in range(7):
        sess.step()
    sess.retire(slot)
    assert sess.kinds[1].pool.used_count() == 0
    slot, first = sess.admit(b)
    assert slot == 0 and sess.kinds[1].tables[0] == [0]
    toks, logits = [first], []
    for _ in range(5):
        out, lg = _step_with_logits(sess)
        toks.append(out[slot])
        logits.append(lg[slot])
    assert toks == want
    np.testing.assert_array_equal(np.stack(logits), want_logits)
    sess.close()


def test_a_request_admitted_into_a_running_batch_gives_its_own_tokens(
        model_scope, flash_off):
    scope = model_scope[0]
    rs = np.random.RandomState(9)
    a, b = rs.randint(2, 96, 19), rs.randint(2, 96, 7)
    want_a, _, _ = _alone(scope, a, 9)
    want_b, _, state_b = _alone(scope, b, 5)
    sess = _session(scope)
    slot_a, first = sess.admit(a)
    toks_a, toks_b = [first], []
    for step in range(9):
        if step == 4:
            slot_b, first_b = sess.admit(b)
            toks_b.append(first_b)
        out = sess.step()
        toks_a.append(out[slot_a])
        if step >= 4:
            toks_b.append(out[slot_b])
    assert toks_a == want_a and toks_b == want_b
    # batch rows are independent: B's rows are what they are alone, bit
    # for bit, in another slot
    for name, value in _state(sess, slot_b).items():
        np.testing.assert_array_equal(value, state_b[name], err_msg=name)
    sess.close()


def test_the_state_kind_is_named_by_the_spec(model_scope, flash_off):
    scope = model_scope[0]
    sess = _session(scope)
    spec = sess.spec
    assert [(k.name, k.num_blocks, k.layers, k.prefill_table,
             k.decode_table) for k in spec.cache_kinds] == [
        ("full", 24, 1, "gen.ptab", "gen.dtab"),
        ("state", 3, 3, "gen.ptab.state", "gen.dtab.state")]
    # three variables a state-space layer, a K and a V the attention layer
    assert [n.split(".", 1)[1] for n, _, _ in spec.cache_vars] == [
        "l0.ssm", "l0.conv", "l0.at", "l1.ssm", "l1.conv", "l1.at",
        "l2.k", "l2.v", "l3.ssm", "l3.conv", "l3.at"]
    shapes = {n.split(".", 1)[1]: (s, d) for n, s, d in spec.cache_vars}
    assert shapes["l0.ssm"] == ((3, H, P, N), "float32")
    assert shapes["l0.conv"] == ((3, K, LANES), "float32")
    assert shapes["l0.at"] == ((3,), "int32")
    assert shapes["l2.k"] == ((24, 8, 8), "float32")
    # a table feed of one entry a slot
    block = spec.decode_program.global_block()
    assert tuple(block.var("gen.dtab.state").shape) == (3, 1)
    assert tuple(spec.prefill_programs[16].global_block()
                 .var("gen.ptab.state").shape) == (1,)
    row = 4 * (H * P * N + K * LANES) + 4
    stats = sess.pool_stats()
    assert stats["bytes_per_block"] == 8 * 8 * 4 * 2
    assert stats["kinds"]["state"] == {
        "blocks_in_use": 0, "num_blocks": 3, "block_size": 1,
        "bytes_per_block": 3 * row}
    slot, _ = sess.admit(np.arange(2, 12))
    assert sess.pool_stats()["kinds"]["state"]["blocks_in_use"] == 1
    assert sess.pool_stats()["kinds"]["full"]["blocks_in_use"] == 2
    assert sess.storable(64) and sess.admit_ok(40)
    sess.check_pool_invariant()
    sess.retire(slot)
    sess.check_pool_invariant()
    sess.close()


def test_the_state_pools_gauge_goes_by_the_kinds_name(model_scope,
                                                      flash_off):
    def gauges():
        return {l["pool"]: float(p)
                for n, _, _, _, ch in metrics.REGISTRY.snapshot()
                if n == BLOCKS_IN_USE.name for l, p in ch
                if l["pool"].startswith("state.")}
    scope = model_scope[0]
    before = set(gauges())
    sess = _session(scope)
    label = sess.kinds[1].pool._label
    assert label.startswith("state.p") and set(gauges()) - before == {label}
    sess.admit(np.arange(2, 12))
    sess.admit(np.arange(3, 9))
    assert gauges()[label] == 2.0
    sess.retire(0)
    assert gauges()[label] == 1.0
    sess.close()
    assert set(gauges()) == before


def _counter(name):
    return sum(float(p) for n, _, _, _, ch in metrics.REGISTRY.snapshot()
               if n == name for _, p in ch)


def test_the_state_rows_counter_adds_up_by_hand(model_scope, flash_off):
    scope = model_scope[0]
    name = "paddle_generation_state_rows_updated_total"
    sess = _session(scope, num_blocks=3)
    rs = np.random.RandomState(7)
    sess.admit(rs.randint(2, 96, 8))
    sess.admit(rs.randint(2, 96, 9))
    before = _counter(name)
    assert sorted(sess.step()) == [1]       # slot 0 is starved
    assert _counter(name) - before == 3 * 1
    sess.retire(1)
    sess.step()
    sess.admit(rs.randint(2, 96, 5))
    sess.step()
    assert _counter(name) - before == 3 * (1 + 1 + 2)
    sess.close()


@pytest.mark.parametrize("what", ["prefix_cache", "speculate_k"])
def test_a_state_spec_refuses_what_a_row_rewritten_whole_cannot_serve(what):
    model = MoeLM(**SIZES)
    kw = dict(max_len=64, slots=2, cache_len=64, prompt_buckets=(16,),
              block_size=8, num_blocks=16, prefix_cache=False,
              decode_policy=None, kind_blocks={"state": 2})
    if what == "prefix_cache":
        kw["prefix_cache"] = True
    else:
        kw["decode_policy"] = DecodePolicy(kind="greedy", speculate_k=2)
    with pytest.raises(ValueError, match="state kind.*rewritten whole"):
        lm_session(model, **kw)


def test_a_session_refuses_a_state_spec_with_a_prefix_index(model_scope):
    spec = moe_lm_session(slots=2, cache_len=64, prompt_buckets=(16,),
                          block_size=8, num_blocks=16, **SIZES)
    spec.prefix_cache = True
    with pytest.raises(ValueError, match="state kind.*rewritten whole"):
        GenerationSession(spec, scope=model_scope[0])


def test_a_model_of_state_space_layers_alone_is_refused():
    with pytest.raises(ValueError, match="no paged kind"):
        MoeLM(**dict(SIZES, layer_types=["mamba"] * 4))
    with pytest.raises(ValueError, match="layer_types holds"):
        MoeLM(**dict(SIZES, layer_types=["mamba", "conv"] * 2))


def test_the_scheduler_serves_the_model_a_step_ahead(model_scope, flash_off):
    scope = model_scope[0]
    sess = _session(scope)
    prompts = [np.arange(2, 9), np.arange(5, 26), np.arange(30, 41),
               np.arange(7, 20)]
    budgets = [6, 9, 4, 7]
    want = [_alone(scope, p, n - 1)[0] for p, n in zip(prompts, budgets)]
    ahead0 = _counter("paddle_generation_decode_steps_ahead_total")
    assert sess.lookahead
    sched = GenerationScheduler(sess, deadline_ms=0)
    # four requests on three slots: the last takes a retired slot's row
    futures = [sched.submit(p, max_new_tokens=n, eos_id=-1)
               for p, n in zip(prompts, budgets)]
    outs = [np.asarray(f.result(timeout=120)).tolist() for f in futures]
    sched.close()
    assert outs == want
    assert _counter("paddle_generation_decode_steps_ahead_total") > ahead0
    sess.check_pool_invariant()
    assert all(k.pool.used_count() == 0 for k in sess.kinds)
    sess.close()


def test_state_bind_lies_inside_the_admission_and_marks_the_return(
        model_scope, flash_off):
    scope = model_scope[0]
    sess = _session(scope)
    sess.generate(np.arange(2, 8), max_new_tokens=2)       # compile
    tracing.start(clear=True)
    try:
        sched = GenerationScheduler(sess, deadline_ms=0)
        out = sched.submit(np.arange(2, 12), max_new_tokens=4,
                           eos_id=-1).result(timeout=120)
        tid = sched._thread.ident
        sched.close()
    finally:
        tracing.stop()
    events = [e for e in tracing.events()
              if e["ph"] == "X" and e["tid"] == tid]
    tracing.clear()
    assert len(out) == 4
    binds = [e for e in events if e["name"] == "session:state_bind"]
    assert [b["args"]["bound"] for b in binds] == [True, False]
    admit, = [e for e in events if e["name"] == "scheduler:admit"]
    assert admit["ts"] - 0.5 <= binds[0]["ts"] and \
        binds[0]["ts"] + binds[0]["dur"] <= admit["ts"] + admit["dur"] + 0.5
    assert binds[1]["ts"] > admit["ts"] + admit["dur"]
    sess.close()


@pytest.mark.chaos
def test_token_replay_failover_continues_identically(model_scope, flash_off):
    """Session 0 breaks after the request's third token: the request is
    prefilled again on session 1 from prompt + journal, which rebuilds the
    state rows from nothing, and goes on as if nothing had happened."""
    scope = model_scope[0]
    prompt = np.random.RandomState(12).randint(2, 96, 14)
    want, _, _ = _alone(scope, prompt, 9)
    s_a, s_b = _session(scope), _session(scope)
    sched = GenerationScheduler([s_a, s_b], breaker_failures=1,
                                breaker_cooldown_ms=10000, replay_attempts=2)
    seen = []

    def on_token(tok):
        seen.append(tok)
        if len(seen) == 3:
            faults.arm("generation_step_fail", at=0, times=None)
    try:
        fut = sched.submit(prompt, max_new_tokens=10, eos_id=-1,
                           on_token=on_token)
        got = [int(t) for t in fut.result(timeout=120)]
    finally:
        faults.disarm()
        sched.drain()
    assert got == want
    assert s_b.prefill_log and s_b.prefill_log[-1][2] > len(prompt)
    for s in (s_a, s_b):
        s.check_pool_invariant()
        s.close()
