"""The ``nemotron_h``-shaped model (``models/moe_lm.py`` with a block of one
sublayer: layers of a Mamba-2 mixer, an expert layer or an attention alone),
its two-matrix relu² experts at a width that is no whole lane tiles
(``ops/moe_ops.py``, ``ops/pallas_moe.py``) and its mixer with several
groups of B and C (``ops/ssm_ops.py``), through a session against the plain
reference of ``benchmarks/reference/nemotron_h.py``. CPU, small sizes."""

import importlib.util
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.moe_lm import MoeLM, moe_lm, moe_lm_session
from paddle_tpu.observability import metrics
from paddle_tpu.ops import kernel_path, moe_ops, pallas_moe, ssm_ops
from paddle_tpu.serving import GenerationScheduler, GenerationSession

from benchmarks.architectures import nemotron_h as arch
from benchmarks.harness import lm as bench_lm
from benchmarks.reference import nemotron_h as ref

_spec = importlib.util.spec_from_file_location(
    "nemotron_controls", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "nemotron_controls.py"))
controls = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(controls)

pytestmark = [pytest.mark.generation, pytest.mark.paged]

# the catalog's keys at a small size: the published pattern's first run and
# an expert layer behind the attention layer (every letter, an expert layer
# both before and after a cache), chunks of 8 rows, 8 heads of 4 lanes in 4
# groups of B and C over a state of 16, 8 two-matrix experts of which a
# share can be held, a scaling factor that is not 1. ``initializer_range``
# 0.3 at 16 lanes gives a projection's output the spread the published 0.02
# gives at 2,688 (0.3 x 4 = 1.2; 0.02 x 52 = 1.04)
CFG = dict(
    attention_bias=False, mlp_bias=False, use_bias=False,
    mamba_proj_bias=False, use_conv_bias=True, mamba_hidden_act="silu",
    mlp_hidden_act="relu2", tie_word_embeddings=False, n_group=1,
    topk_group=1, n_shared_experts=1, norm_topk_prob=True,
    layer_norm_epsilon=1e-5, initializer_range=0.3, torch_dtype="float32",
    max_position_embeddings=4096, hidden_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, mamba_num_heads=8, mamba_head_dim=4,
    ssm_state_size=16, n_groups=4, conv_kernel=4, chunk_size=8,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=32,
    n_routed_experts_published=8, n_routed_experts=8, expert_offset=0,
    num_experts_per_tok=3, routed_scaling_factor=2.5, num_hidden_layers=6,
    hybrid_override_pattern="MEM*EM", vocab_size=96)
SIZES = arch.sizes(CFG)
H, P, N, G, K = 8, 4, 16, 4, 4
LANES = H * P + 2 * G * N
T = 40


def _run(build, feed, sets=None):
    """Build a program with ``build() -> fetch vars``, run its startup, set
    ``sets`` {name: array} and run it on ``feed``; -> outputs."""
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
    return [np.asarray(o) for o in outs]


@pytest.fixture()
def flash_off():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


# -- groups of B and C -------------------------------------------------------

def _scan_inputs(seed, groups, t=24):
    rs = np.random.RandomState(seed)
    x = rs.randn(t, H, P).astype(np.float32)
    dt = np.log1p(np.exp(rs.uniform(-4, -1, (t, H)))).astype(np.float32)
    a = -rs.uniform(1, 16, H).astype(np.float32)
    b, c = (rs.randn(t, groups, N).astype(np.float32) for _ in "bc")
    return x, dt, a, b, c


@pytest.mark.parametrize("groups", [2, 4, 8])
def test_groups_that_repeat_one_b_and_c_scan_as_one_group(groups):
    """G groups whose B and C are all the one group's give what the one
    group gives, in the chunked scan and in the one-step update: a head
    reads its own group and nothing of a neighbour's. Equal to the float32
    sums' order (the same einsums over a leading group axis): 1e-6."""
    x, dt, a, b, c = _scan_inputs(2, 1)
    rep_b, rep_c = (np.repeat(v, groups, axis=1) for v in (b, c))
    y1, s1 = ssm_ops.ssd_chunked(x, dt, a, b[:, 0], c[:, 0], 8)
    yg, sg = ssm_ops.ssd_chunked(x, dt, a, rep_b, rep_c, 8)
    np.testing.assert_allclose(yg, y1, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(sg, s1, rtol=1e-6, atol=1e-6)
    pool = np.random.RandomState(3).randn(24, H, P, N).astype(np.float32)
    fresh = np.arange(24) % 3 != 0
    n1, o1 = ssm_ops.ssm_step(pool, dt, a, x, b[:, 0], c[:, 0], fresh)
    ng, og = ssm_ops.ssm_step(pool, dt, a, x, rep_b, rep_c, fresh)
    np.testing.assert_array_equal(ng, n1)
    np.testing.assert_allclose(og, o1, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ng)[~fresh], pool[~fresh])


def test_groups_of_their_own_scan_as_the_sequential_recurrence():
    """Four groups with B and C of their own against the reference's row
    by row recurrence, head ``h`` reading group ``h // 2``: 24 rows cross
    three chunks. Float32 at the highest precision: 2e-5 of values of order
    one is the chunked sums' order; a head on a neighbour's group is of
    the order of the values."""
    x, dt, a, b, c = _scan_inputs(4, G)
    y, last = ssm_ops.ssd_chunked(x, dt, a, b, c, 8)
    want_y, want_last = ref.mamba_scan(
        jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a), jnp.asarray(b),
        jnp.asarray(c), jnp.zeros(H))
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(last, want_last, rtol=2e-5, atol=2e-5)
    crossed = ssm_ops.ssd_chunked(x, dt, a, b[:, ::-1], c[:, ::-1], 8)[0]
    assert np.abs(np.asarray(crossed) - np.asarray(want_y)).max() > 0.1


def test_a_long_chunk_against_a_float64_recurrence():
    """How far the chunked scan is from the truth where it is worst: a
    chunk of 128 rows under steps of dt A near -1.4 (the published ranges'
    far end: dt 0.09, A 16). The cumulative sum reaches -180, whose float32
    neighbours lie 1.5e-5 apart, and a decay taken as ``exp(cs_i - cs_j)``
    carries that into every factor: 1.8e-6 of the largest output against a
    float64 recurrence, where a sequential float32 one reads 1.2e-7. The
    decays added up as segment sums read 1.2e-7 too and were tried (PR 46):
    on the chip the scan then took 5.6 ms for 1.45 at 2,048 rows, its
    distance from the reference's recurrence there did not move (3.9e-4 of
    values to 13.7: the chip's own exp and sums set it) and neither did the
    router's choices at near ties, so the form stayed (PERF.md section 6).
    5e-6 holds the form to what it reads; a wrong mask or chunk edge is the
    order of the values."""
    t, q = 256, 128
    rs = np.random.RandomState(12)
    x = rs.randn(t, H, P) * 0.5
    dt = 0.09 * np.exp(rs.randn(t, H) * 0.2)
    a = -np.linspace(9.0, 16.0, H)
    b, c = (rs.randn(t, 2, N) * 0.5 for _ in "bc")
    state = np.zeros((H, P, N))
    want = np.zeros((t, H, P))
    for i in range(t):                      # the recurrence in float64
        bh, ch = (np.repeat(v[i], H // 2, axis=0) for v in (b, c))
        state = np.exp(dt[i] * a)[:, None, None] * state + \
            (dt[i][:, None] * x[i])[:, :, None] * bh[:, None, :]
        want[i] = np.einsum("hpn,hn->hp", state, ch)
    y, last = ssm_ops.ssd_chunked(*(np.float32(v) for v in (x, dt, a, b, c)),
                                  q)
    scale = np.abs(want).max()
    assert float(np.abs(dt * a).sum(0).max()) > 2 * 150     # two chunks' sum
    assert np.abs(np.asarray(y) - want).max() < 5e-6 * scale
    assert np.abs(np.asarray(last) - state).max() < 5e-6 * np.abs(state).max()


def test_the_references_step_decay_is_exp_to_two_roundings():
    """The reference multiplies one decay a row into its state, so it
    takes each ``exp(dt A)`` by hand (range reduction and a series:
    ``reference/nemotron_h.py::step_decay`` says what the chip's ``exp``
    cost it): within 1.5e-7 of float64's over every step a model can
    take, exact at 0, and no lower in the mean than 1e-9."""
    rs = np.random.RandomState(3)
    z = -np.exp(rs.uniform(np.log(1e-7), np.log(80.0), 1 << 16))
    z = np.float32(np.concatenate([z, [0.0, -0.34657, -0.34658, -87.0]]))
    got = np.asarray(jax.jit(ref.step_decay)(z), np.float64)
    want = np.exp(np.float64(z))
    rel = (got - want) / want
    assert np.abs(rel).max() < 1.5e-7
    assert abs(rel.mean()) < 1e-9
    assert got[-4] == 1.0


def _mixer_weights(seed=3, d=16):
    rs = np.random.RandomState(seed)
    w = {"in.w": rs.randn(d, 2 * H * P + 2 * G * N + H) * 0.3,
         "conv.w": rs.randn(K, LANES) * 0.5, "conv.b": rs.randn(LANES) * 0.1,
         "dt_bias": rs.uniform(-4, -1, H),
         "a_log": np.log(rs.uniform(1, 16, H)), "d": rs.randn(H),
         "norm.w": 1 + 0.1 * rs.randn(H * P),
         "out.w": rs.randn(H * P, d) * 0.3}
    w = {k: v.astype(np.float32) for k, v in w.items()}
    theirs = {"m." + (k.replace(".", "_") if k.startswith("conv")
                      else k.replace(".w", "")): jnp.asarray(v)
              for k, v in w.items()}
    return {"mix." + k: v for k, v in w.items()}, theirs


def test_the_grouped_mixer_equals_the_references():
    """The whole mixer op over two sequences of 24 rows, four groups: the
    split of ``in_proj`` into [z, x, B x4, C x4, dt], the convolution over
    all 160 lanes and the gated norm within each group's 8 lanes, against
    the reference's. 2e-5 of outputs of order one (float32 sums in another
    order)."""
    ours, theirs = _mixer_weights()
    x = np.random.RandomState(8).randn(2, 24, 16).astype(np.float32)

    def build():
        xv = layers.data("x", shape=[2, 24, 16], dtype="float32",
                         append_batch_size=False)
        return [layers.mamba2_mixer(xv, prefix="mix", **SIZES["mamba"])]
    got, = _run(build, {"x": x}, ours)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref._mamba(jnp.asarray(x[i]), theirs,
                                               "m.", CFG)) for i in (0, 1)])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- two-matrix relu2 experts -------------------------------------------------

D, F, E, TOPK = 16, 24, 8, 3


def _expert_weights(seed=5):
    rs = np.random.RandomState(seed)
    return {"router": rs.randn(D, E), "up": rs.randn(E, F, D) * 0.3,
            "down": rs.randn(E, F, D) * 0.3}


def _moe(x, offset=0, held=None):
    full = _expert_weights()
    n_held = held or E
    sets = {"m.router.w": full["router"].astype(np.float32)}
    for part in ("up", "down"):
        sets["m.experts.%s.w" % part] = \
            full[part][offset:offset + n_held].astype(np.float32)

    def build():
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        return layers.moe_ffn(xv, E, TOPK, F, "m", expert_offset=offset,
                              experts_held=held, act="relu2",
                              route_scale=2.5)
    out, counts = _run(build, {"x": x}, sets)
    return out, counts


def _layer_weights():
    w = _expert_weights()
    rs = np.random.RandomState(11)
    weights = {"l.router": w["router"], "l.bias": np.zeros(E),
               "l.experts.up": w["up"], "l.experts.down": w["down"],
               "l.shared.up": rs.randn(D, 2 * F) * 0.3,
               "l.shared.down": rs.randn(2 * F, D) * 0.3}
    return {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}


@pytest.mark.parametrize("share_rows", [None, 4],
                         ids=["one_pass", "passes_of_4"])
def test_the_two_shares_add_up_to_the_uncut_layer(share_rows, monkeypatch):
    """The tie of the share to the model: 8 two-matrix experts held 4 at a
    time, as the deployment's two chips hold [0, 64) and [64, 128), + the
    shared expert counted once = the reference's whole expert layer under
    the normalised sigmoid top-3 times 2.5; with the pairs in one pass and
    in passes of a few rows. Float32 at the highest precision: 2e-4 is the
    order of the float32 sums; a gate that was multiplied in, or a relu
    that was not squared, is of the order of the values."""
    if share_rows:
        monkeypatch.setattr(moe_ops, "SHARE_ROWS", share_rows)
    x = np.random.RandomState(10).randn(12, D).astype(np.float32)
    parts, all_counts = zip(*[_moe(x, offset=o, held=4) for o in (0, 4)])
    weights = _layer_weights()
    cfg = dict(num_experts_per_tok=TOPK, norm_topk_prob=True,
               routed_scaling_factor=2.5)
    xj = jnp.asarray(x)
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref._relu2_mlp(xj, weights["l.shared.up"],
                                           weights["l.shared.down"]))
        whole = np.asarray(ref._experts(xj, weights, "l.", cfg))
        np.testing.assert_allclose(sum(parts) + shared, whole,
                                   rtol=2e-4, atol=2e-5)
        assert np.concatenate(all_counts).sum() == 12 * TOPK
        # the holder of every expert gives the same in one piece
        np.testing.assert_allclose(_moe(x)[0] + shared, whole,
                                   rtol=2e-4, atol=2e-5)
        # and the reference, given one share, gives that share
        cfg["expert_offset"] = 4
        weights.update({"l.experts." + n: weights["l.experts." + n][4:]
                        for n in ("up", "down")})
        np.testing.assert_allclose(
            parts[1], np.asarray(ref.routed_experts(xj, weights, "l.", cfg)),
            rtol=2e-4, atol=2e-5)


def _sorted_rows(seed, rows, d, counts):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(rows, d), jnp.float32),
            jnp.asarray(counts, jnp.int32))


def _plain_relu2(xs, wu, wd, counts):
    """``_expert_rows``' plain form by hand: ``exact_ragged_dot`` over the
    up matrix laid ``[d, f]``, the relu squared, ``exact_ragged_dot``."""
    inner = jnp.square(jax.nn.relu(moe_ops.exact_ragged_dot(
        xs, jnp.swapaxes(wu, 1, 2), counts)))
    return moe_ops.exact_ragged_dot(inner, wd, counts)


@pytest.mark.parametrize("f,d", [(192, 128), (48, 128), (256, 256)],
                         ids=["a_tile_and_a_half", "sublane_tiles_alone",
                              "whole_lane_tiles"])
def test_the_kernels_take_a_width_that_is_no_whole_lane_tile(f, d):
    """``pallas_moe`` interpreted at expert widths of 192 = 1.5 x 128 (as
    1856 = 14.5 x 128), 48 and 256, two matrices with relu² behind the
    first, the up matrix held ``[f, d]``, against ``exact_ragged_dot``:
    the same three bfloat16 pieces against the same bfloat16 weights with
    float32 sums. 1e-5 of values of order 10 is the sums' order; rows
    past the last group are nobody's and are left out."""
    held, counts = 4, [5, 0, 70, 9]
    rs = np.random.RandomState(f)
    wu = jnp.asarray(rs.randn(held, f, d) * 0.3, jnp.bfloat16)
    wd = jnp.asarray(rs.randn(held, f, d) * 0.3, jnp.bfloat16)
    xs, c = _sorted_rows(1, 96, d, counts)
    assert pallas_moe.admits(96, wu, "relu2")
    got = pallas_moe.expert_ffn(xs, None, wu, wd, c, True, act="relu2")
    want = _plain_relu2(xs, wu, wd, c)
    n = sum(counts)
    assert float(jnp.abs(want[:n]).max()) > 1.0
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype,path", [("bfloat16", "interpret"),
                                        ("float32", "xla")])
def test_the_two_matrix_op_equals_the_plain_form(dtype, path):
    """``moe_ffn``'s rows through ``_expert_rows`` with ``act`` ``relu2``:
    bfloat16-held weights of width 192 take the kernels (interpreted off
    the chip), float32-held ones ``exact_ragged_dot``; both equal the plain
    form by hand, and the path is counted."""
    held, counts = 4, [7, 30, 0, 11]
    rs = np.random.RandomState(9)
    wu = jnp.asarray(rs.randn(held, 192, 128) * 0.3, dtype)
    wd = jnp.asarray(rs.randn(held, 192, 128) * 0.3, dtype)
    xs, c = _sorted_rows(2, 64, 128, counts)
    before = dict(kernel_path.counts().get("moe_grouped_matmul", {}))
    got = moe_ops._expert_rows(xs, None, wu, wd, c, "relu2")
    after = kernel_path.counts()["moe_grouped_matmul"]
    assert {p: n - before.get(p, 0) for p, n in after.items()
            if n != before.get(p, 0)} == {path: 1}
    want = _plain_relu2(xs, wu, wd, c)
    np.testing.assert_allclose(np.asarray(got)[:48], np.asarray(want)[:48],
                               rtol=1e-5, atol=1e-4)
    assert not np.asarray(got)[48:].any()


@pytest.mark.parametrize("shape,act,admitted", [
    ((64, 1856, 2688), "relu2", True),  # the configuration's held experts
    ((128, 1856, 2688), "relu2", True),     # both shares on one chip
    ((64, 2688, 1856), None, False),    # held [d, f]: XLA would relay it
    ((64, 1860, 2688), "relu2", False),     # no whole sublane tiles
    ((64, 4096, 2688), "relu2", True),  # whole lane tiles, held [f, d]
    ((128, 2048, 1024), None, True),    # trinity-mini-l5, as it was
], ids=["published", "uncut", "held_d_f", "ragged_sublanes",
        "lane_tiles_held_f_d", "swiglu_as_it_was"])
def test_the_gate_admits_the_published_width(shape, act, admitted):
    """``admits`` at ``[64, 2688, 1856]``: a decode step's 768 pairs and a
    2,048-row pass over the held experts, both taken; what it refuses.
    ONE knob: ``act`` ``relu2`` is the two-matrix form and says how its
    first stack is held."""
    w = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    for pairs in (768, 2048):
        assert pallas_moe.admits(pairs, w, act) is admitted
    assert not pallas_moe.admits(
        768, jax.ShapeDtypeStruct(shape, jnp.float32), act)


def test_the_kernel_refuses_a_form_nobody_checks():
    """``grouped_matmul`` has two forms of a first product, SwiGLU over two
    ``[G, d, f]`` stacks and relu² over one ``[G, f, d]``: another ``act``,
    or relu² over two stacks, is refused and not compiled."""
    w = jnp.zeros((2, 128, 128), jnp.bfloat16)
    xs = jnp.zeros((64, 128), jnp.float32)
    items = pallas_moe.work_items(jnp.asarray([3, 4]), 1, 64)
    for ws, act in (((w, w), "relu2"), ((w,), "gelu")):
        with pytest.raises(ValueError, match="relu2"):
            pallas_moe.grouped_matmul(xs, ws, items, 64, True, act)


# -- the whole model ----------------------------------------------------------

@pytest.fixture(scope="module")
def model_scope():
    """A scope with the model's weights at their own initial values."""
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 7
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        loss, logits = moe_lm(toks, lbls, **SIZES)
        ptpu.Executor().run(startup)
    return scope, main, loss, logits


def _session(scope, flash=False, sizes=SIZES, **kw):
    ptpu.config.set_flags(flash_attention=flash)
    args = dict(slots=3, cache_len=64, prompt_buckets=(16, 32), block_size=8,
                num_blocks=24)
    args.update(kw)
    return GenerationSession(moe_lm_session(**args, **sizes), scope=scope)


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


def test_the_model_holds_the_parameters_the_equations_name(model_scope):
    """One norm a layer and its sublayer's parameters, nothing else: no
    gate matrix anywhere, the up matrices of the routed experts held as
    the down matrices lie, an untied head, and as many parameters as the
    architecture module counts."""
    scope = model_scope[0]
    names = {n: np.shape(scope.find_var(n)) for n in scope.var_names()
             if n.startswith("moe_lm.")}
    assert set(names) == set(ref.weight_names(CFG).values())
    assert not [n for n in names if ".gate." in n]
    assert names["moe_lm.l1.moe.experts.up.w"] == (8, 24, 16)
    assert names["moe_lm.l1.moe.experts.down.w"] == (8, 24, 16)
    assert names["moe_lm.l1.moe.shared.up.w"] == (16, 32)
    assert names["moe_lm.l0.mamba.in.w"] == (16, 2 * 32 + 2 * 4 * 16 + 8)
    assert names["moe_lm.l0.mamba.conv.w"] == (4, LANES)
    assert names["moe_lm.l3.attn.q.w"] == (16, 4 * 8)
    assert names["moe_lm.lm_head.w"] == (16, 96)
    assert sum(int(np.prod(s)) for s in names.values()) == \
        arch.parameters_held(CFG)


def test_the_architecture_modules_startup_centres_the_second_matrices(
        model_scope):
    """What relu(.)^2 hands a down matrix is nonnegative in every lane for
    every token, so a matrix whose rows add up to a vector adds that vector
    to every token alike. That is an initial value of the benchmark's
    seeded stand-ins, so ``benchmarks/architectures/nemotron_h.py``'s
    startup program takes the mean over the rows out of the routed and
    the shared experts' down matrices (float32 here: to rounding), every
    other matrix as drawn and the deviation the drawn one; **the layers
    draw Normal(0, std)** for whoever else builds a relu² feed-forward."""
    def rows(scope, name):
        return np.asarray(scope.find_var(name), np.float32)
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.unique_name.guard():
        ptpu.Executor().run(arch.serve_startup(CFG, 6))
    assert set(n for n in scope.var_names() if n.startswith("moe_lm.")) == \
        set(ref.weight_names(CFG).values())        # no temporary kept
    for name, std in (("moe_lm.l1.moe.experts.down.w", 0.3),
                      ("moe_lm.l4.moe.shared.down.w", 0.02)):
        w = rows(scope, name)
        assert np.abs(w.sum(-2)).max() < 1e-5, name
        assert 0.85 * std < w.std() < 1.1 * std, name
        # the layers' own draw, the same seed or another: not centred
        assert np.abs(rows(model_scope[0], name).sum(-2)).max() > 0.05, name
    for name in ("moe_lm.l1.moe.experts.up.w", "moe_lm.l1.moe.shared.up.w",
                 "moe_lm.l0.mamba.out.w", "moe_lm.l3.attn.o.w"):
        assert np.abs(rows(scope, name).sum(-2)).max() > 0.05, name


def test_whole_sequence_forward_equals_the_reference(model_scope):
    """The training-shaped forward (whole sequences, no cache) against the
    reference, logits and loss. Float32 at the highest precision: 2e-4 of
    the largest logit is the order of the sums (the chunked scan against
    the row by row one); one bfloat16 pass reads fifty times that."""
    scope, main, loss, logits = model_scope
    rs = np.random.RandomState(1)
    toks = rs.randint(2, 96, (2, T))
    lbls = np.roll(toks, -1, axis=1)
    with ptpu.scope_guard(scope):
        got_loss, got = ptpu.Executor().run(
            main, feed={"toks": toks, "lbls": lbls},
            fetch_list=[loss, logits])
    w = ref.gather_weights(scope.find_var, CFG)
    for i in (0, 1):
        want = np.asarray(ref.logits_at(w, jnp.asarray(toks[i]),
                                        jnp.arange(T), CFG))
        scale = np.abs(want).max()
        assert scale > 0.5
        np.testing.assert_allclose(np.asarray(got)[i], want,
                                   atol=2e-4 * scale)
    want_loss = np.mean([float(ref.loss(w, jnp.asarray(toks[i]),
                                        jnp.asarray(lbls[i]), CFG))
                         for i in (0, 1)])
    np.testing.assert_allclose(float(np.asarray(got_loss)), want_loss,
                               rtol=1e-4)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_equals_the_references_forward(model_scope,
                                                           flash):
    """A prompt of 21 rows in a bucket of 32 (three chunks of 8 and a
    padded tail) and 14 decode steps, every step run twice as the
    benchmark's check runs it: rows 0..34 cross four chunks and lie in
    five blocks of the attention layer's cache; an expert layer stands
    before the first cache and behind the last. **Logits, not tokens.**
    Everything is float32 and the products are at the highest precision, so
    what is left is the order of the sums: the chunked scan against the row
    by row one, the paged softmax against the dense one. 2e-4 of the
    largest logit; a bfloat16 pass reads 1e-2, a wrong state, group, chunk
    edge or block the order of the logits."""
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


def test_a_bfloat16_pass_would_fail_the_comparison(model_scope):
    """What the tolerance above is worth: the reference's own logits with
    every weight and activation rounded to bfloat16 before each sublayer
    differ from the float32 ones by far more than 2e-4 of the largest."""
    scope = model_scope[0]
    w = ref.gather_weights(scope.find_var, CFG)
    toks = jnp.asarray(np.random.RandomState(5).randint(2, 96, 35))
    want = np.asarray(ref.logits_at(w, toks, jnp.arange(20, 35), CFG))
    rounded = {k: jnp.asarray(v, jnp.bfloat16).astype(jnp.float32)
               for k, v in w.items()}
    with jax.default_matmul_precision("bfloat16"):
        low = np.asarray(ref._head(ref.hidden(rounded, toks, CFG)[20:35],
                                   rounded["head"]))
    assert np.abs(low - want).max() > 20 * 2e-4 * np.abs(want).max()


# -- a router's choice at a near tie, told from a fault ------------------------

def test_the_reference_takes_the_choices_it_is_told(model_scope):
    """``forced`` {layer: [T, k]}: a row of expert ids is chosen as told,
    a row of -1 by the scores. Told its own choices the reference gives
    its own logits to the bit; told another expert in one row of one
    layer it gives others from that row on and the rows before as they
    were (the sequence is causal)."""
    scope = model_scope[0]
    w = ref.gather_weights(scope.find_var, CFG)
    toks = jnp.asarray(np.random.RandomState(8).randint(2, 96, 20))
    pos = jnp.arange(20)
    want, seen = ref.logits_and_router_inputs(w, toks, pos, CFG)
    assert sorted(seen) == [1, 4] and seen[1].shape == (20, 16)
    own = {}
    for layer, x in seen.items():
        sel, _, gap = controls.choices(x, w["l%d.router" % layer],
                                       w["l%d.bias" % layer], 3)
        assert gap.min() > 1e-4         # no near tie in this draw
        own[layer] = jnp.asarray(sel)
    np.testing.assert_array_equal(
        np.asarray(ref.logits_at(w, toks, pos, CFG, own)), np.asarray(want))
    free = {1: jnp.full((20, 3), -1, jnp.int32)}
    np.testing.assert_array_equal(
        np.asarray(ref.logits_at(w, toks, pos, CFG, free)), np.asarray(want))
    other = np.asarray(own[4]).copy()
    other[11, 0] = next(e for e in range(8) if e not in other[11])
    told = np.asarray(ref.logits_at(w, toks, pos, CFG,
                                    {4: jnp.asarray(other)}))
    np.testing.assert_array_equal(told[:11], np.asarray(want)[:11])
    assert np.abs(told[11] - np.asarray(want)[11]).max() > \
        1e-2 * np.abs(want).max()


@pytest.mark.parametrize("fault", [None, "choice", "expert"])
def test_the_witness_tells_a_choice_from_a_fault(model_scope, monkeypatch,
                                                 fault):
    """``tools/nemotron_controls.py --witness 1`` on this file's session:
    the check's prompts with every expert layer's input fetched beside the
    logits, the prefills' and the decode steps'. As it is, the program's
    choices are the reference's and both readings are the sums' order.
    Where the reference's own float path falls the other way in one decode
    row (it takes its 4th expert for its 3rd there, unless told) the
    harness's reading is the order of the logits and **the reference told
    the program's choices agrees again**; with an expert's result wrong
    in the program (a fault) being told cures nothing."""
    scope = model_scope[0]
    if fault == "choice":
        real = ref.route

        def route(m, w, p, cfg, forced=None):
            if p != "l4." or forced is None:
                return real(m, w, p, cfg, forced)
            s = jax.nn.sigmoid(m @ w[p + "router"].astype(jnp.float32))
            _, four = jax.lax.top_k(s, 4)
            own = jnp.where(jnp.arange(m.shape[0])[:, None] == 25,
                            four[:, jnp.asarray([0, 1, 3])], four[:, :3])
            told = jnp.all(forced >= 0, axis=-1, keepdims=True)
            return real(m, w, p, cfg, jnp.where(told, forced, own))
        monkeypatch.setattr(ref, "route", route)
    elif fault == "expert":
        real_rows = moe_ops._expert_rows
        monkeypatch.setattr(moe_ops, "_expert_rows",
                            lambda *a: real_rows(*a) * 1.5)
    sess = _session(scope)
    cfg = dict(CFG, architecture="nemotron_h")
    dep = types.SimpleNamespace(session=sess, spec=sess.spec, cfg=cfg,
                                arch=arch, buckets=(16, 32))
    report = controls.witness(dep, seed=3)
    sess.close()
    assert [r["bucket"] for r in report] == [16, 32]
    assert [r["prompt_len"] for r in report] == [14, 22]
    for r in report:
        assert r["smallest_gap_in_a_decode_row"] > 0
    if fault is None:
        for r in report:
            assert r["err_own"] < 2e-4 and r["err_forced"] < 2e-4
            assert r["rows_that_differ"] == 0
    elif fault == "choice":
        first, second = report      # row 25 is the second's 4th decode row
        assert first["err_own"] < 2e-4 and first["err_forced"] < 2e-4
        assert second["err_own"] > 1e-2 and second["err_forced"] < 2e-4
        by_step = second["err_by_decode_step_own"]
        assert max(by_step[:3]) < 2e-4 < 1e-2 < by_step[3]
    else:
        for r in report:
            assert r["err_own"] > 1e-2 and r["err_forced"] > 1e-2


def test_the_rehearsals_size_streams_its_experts_through_the_kernels(
        flash_off):
    """The architecture module's tiny configuration (bfloat16-held
    weights, experts of width 192, two groups): prefill then decode through
    the cache against the reference, the held experts' matmuls in
    ``pallas_moe``'s kernels (interpreted), two calls an expert layer. The
    products are exact, so the tolerance is the float32 one."""
    cfg = arch.tiny(dict(CFG, deployment={}))
    cfg["initializer_range"] = 0.1
    sizes = arch.sizes(cfg)
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 7
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[8], dtype="int64")
        moe_lm(toks, toks, **sizes)
        ptpu.Executor().run(startup)
    before = dict(kernel_path.counts().get("moe_grouped_matmul", {}))
    sess = _session(scope, sizes=sizes, prompt_buckets=(16,))
    after = kernel_path.counts()["moe_grouped_matmul"]
    assert set(p for p, n in after.items() if n != before.get(p, 0)) == \
        {"interpret"}
    prompt = np.random.RandomState(4).randint(2, 128, 13)
    slot, first = sess.admit(prompt)
    toks, got = [first], []
    for _ in range(5):
        out, logits = _step_with_logits(sess)
        got.append(logits[slot])
        toks.append(out[slot])
    w = ref.gather_weights(scope.find_var, cfg)
    want = np.asarray(ref.logits_at(
        w, jnp.asarray(np.concatenate([prompt, toks])), jnp.arange(12, 18),
        cfg))
    scale = np.abs(want).max()
    assert first == int(want[0].argmax()) and scale > 0.5
    np.testing.assert_allclose(np.stack(got), want[1:], atol=2e-4 * scale)
    sess.close()


def test_an_expert_layer_is_a_fourth_type_of_a_one_sublayer_block():
    """``"experts"`` is a layer type of a block of one sublayer only; such
    a block has neither leading dense layers nor norms behind a sublayer;
    the pairs a row routes count the expert layers, and the layer caches
    are the mixers' and the attention layers' alone, by site."""
    model = MoeLM(**SIZES)
    assert model.layer_types == ("mamba", "experts", "mamba",
                                 "full_attention", "experts", "mamba")
    assert model.pairs_per_row == 3 * 2
    assert model.site == {0: 0, 2: 1, 3: 2, 5: 3}
    assert [k for _, k in model.cache_layers] == [1, 1, 0, 1]
    assert model.kinds == (("full", None), ("state", None))
    with pytest.raises(ValueError, match="one sublayer"):
        MoeLM(**dict(SIZES, block=None))
    with pytest.raises(ValueError, match="no leading dense"):
        MoeLM(**dict(SIZES, num_dense_layers=1))
    with pytest.raises(ValueError, match="no leading dense"):
        MoeLM(**dict(SIZES, post_norms=True))


def test_the_session_counts_five_expert_layers_of_thirteen():
    """The configuration's own pattern, small widths: 13 layers, 5 expert
    layers, 8 layer caches (6 state rows and 2 paged sites); a step's
    routed pairs are slots x 6 x 5, not x 13."""
    cfg = dict(CFG, num_hidden_layers=13, num_experts_per_tok=6,
               hybrid_override_pattern="MEMEM*EMEMEM*")
    spec = moe_lm_session(slots=4, cache_len=32, prompt_buckets=(8,),
                          block_size=8, num_blocks=16, **arch.sizes(cfg))
    assert spec.routed_pairs == 4 * 6 * 5
    assert [(k.name, k.layers) for k in spec.cache_kinds] == \
        [("full", 2), ("state", 6)]
    assert len(spec.cache_vars) == 2 * 2 + 6 * 3
    c = arch.param_counts(cfg)
    assert (c["mixer_layers"], c["expert_layers"],
            c["attention_layers"]) == (6, 5, 2)


def test_a_layers_operations_go_under_its_sublayers_name(model_scope):
    """Every op of a one-sublayer layer carries the ``name_scope`` of its
    kind, so that a device trace groups by ``mamba2_mixer``, ``moe_ffn``
    and ``attention``; the embedding, the final norm and the head carry
    none."""
    spec = moe_lm_session(slots=2, cache_len=32, prompt_buckets=(8,),
                          block_size=8, num_blocks=8, **SIZES)
    for program in (spec.decode_program, spec.prefill_programs[8]):
        scopes = [(op.type, op.attrs.get("op_namescope"))
                  for op in program.global_block().ops]
        by_scope = {}
        for op, scope in scopes:
            by_scope.setdefault(scope, set()).add(op)
        assert set(by_scope) == {None, "mamba2_mixer", "moe_ffn",
                                 "attention"}
        assert "moe_ffn" in by_scope["moe_ffn"]
        assert {"relu", "square"} <= by_scope["moe_ffn"]
        assert by_scope["mamba2_mixer"] & {"mamba2_mixer",
                                          "mamba2_mixer_decode"}
        assert "moe_ffn" not in by_scope[None]
        assert sum(op == "rms_norm" and scope is None
                   for op, scope in scopes) == 1


def _counter(name):
    return sum(float(p) for n, _, _, _, ch in metrics.REGISTRY.snapshot()
               if n == name for _, p in ch)


def test_the_scheduler_serves_the_model_and_its_counters_add_up(
        model_scope, flash_off):
    """Three requests through the scheduler: the tokens are the session's
    own, and the routing and state counters count two expert layers and
    three mixer layers a step as the other models' do."""
    scope = model_scope[0]
    rs = np.random.RandomState(12)
    prompts = [rs.randint(2, 96, n) for n in (9, 14, 5)]
    sess = _session(scope)
    want = []
    for p in prompts:
        slot, first = sess.admit(p)
        toks = [first]
        for _ in range(5):
            toks.append(sess.step()[slot])
        want.append(toks)
        sess.retire(slot)
    sess.close()
    names = ("paddle_generation_routed_pairs_total",
             "paddle_generation_expert_assignments_total",
             "paddle_generation_moe_layer_steps_total",
             "paddle_generation_state_rows_updated_total",
             "paddle_generation_decode_steps_total")
    before = {n: _counter(n) for n in names}
    sched = GenerationScheduler(_session(scope), max_queue=8, deadline_ms=0)
    futures = [sched.submit(p, max_new_tokens=6, eos_id=-1) for p in prompts]
    got = [np.asarray(f.result(timeout=120)).tolist() for f in futures]
    sched.drain(timeout=60)
    assert got == want
    delta = {n: _counter(n) - before[n] for n in names}
    steps = delta["paddle_generation_decode_steps_total"]
    assert steps >= 5
    # every slot of the session routes in both expert layers, held or idle
    assert delta["paddle_generation_routed_pairs_total"] == steps * 3 * 3 * 2
    # the holder of every expert computes every routed pair
    assert delta["paddle_generation_expert_assignments_total"] == \
        delta["paddle_generation_routed_pairs_total"]
    assert delta["paddle_generation_moe_layer_steps_total"] == steps * 2
    # a step advances the rows of its advancing slots in three mixer layers
    assert delta["paddle_generation_state_rows_updated_total"] == 3 * 5 * 3
