"""Layers that borrow (a cross layer walking another layer's pool, a gated
memory unit reading another layer's scan output) and the whole
``phi4flash``-shaped model (``models/moe_lm.py``) through a session against
the plain reference of ``benchmarks/reference/phi4flash.py``. CPU, small
sizes; ``tests/test_phi4flash_ops.py`` holds the mixer's and the
attention's ops to their equations."""

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
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention_ops, ssm_ops
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.serving.decoding import DecodePolicy
from paddle_tpu.serving.paged_cache import CacheKind, LayerCache

from benchmarks.architectures import phi4flash as arch
from benchmarks.harness import lm as bench_lm
from benchmarks.harness.serve import CHECK_STEPS, LOGIT_RTOL
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
SIZES = arch.sizes(CFG)
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


@pytest.fixture()
def flash_off():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


def test_the_layer_plan_is_the_published_one():
    plan = arch.layer_plan(dict(num_hidden_layers=32, mb_per_layer=2))
    assert plan[:16] == ["mamba", "sliding_attention"] * 8
    assert plan[16:18] == ["mamba", "full_attention"]
    assert plan[18:] == ["gmu", "cross_attention"] * 7
    assert CFG["layer_types"] == (
        ["mamba", "sliding_attention"] * 3 + ["mamba", "full_attention"]
        + ["gmu", "cross_attention"] * 2)


# -- the whole model through a session ----------------------------------------

T = 40


@pytest.fixture(scope="module")
def model_scope():
    """A scope with the model's weights at their own initial values, every
    one: the comparisons below tell a wrong state, memory or pool on the
    weights the startup program draws, as the benchmark's check has to."""
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 7
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        loss, logits = moe_lm(toks, lbls, **SIZES)
        ptpu.Executor().run(startup)
    return scope, main, loss, logits


def _session(scope, flash=False, **kw):
    ptpu.config.set_flags(flash_attention=flash)
    args = dict(slots=3, cache_len=64, prompt_buckets=(32,), block_size=4,
                num_blocks=48, window_num_blocks=36)
    args.update(kw)
    return GenerationSession(moe_lm_session(**args, **SIZES), scope=scope)


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


def _reference_logits(scope, seq, positions):
    w = ref.gather_weights(scope.find_var, CFG)
    return np.asarray(ref.logits_at(w, jnp.asarray(seq, jnp.int32),
                                    jnp.asarray(positions), CFG))


def test_the_model_holds_the_parameters_the_equations_name(model_scope):
    scope = model_scope[0]
    names = {n for n in scope.var_names() if n.startswith("moe_lm.")}
    assert names == set(ref.weight_names(CFG).values())
    assert "moe_lm.lm_head.w" not in names      # the head is the embedding
    assert scope.find_var("moe_lm.l0.mamba.in.w").shape == (D, 2 * DI)
    assert scope.find_var("moe_lm.l0.mamba.a_log").shape == (N, DI)
    assert scope.find_var("moe_lm.l1.attn.qkv.w").shape == (D, 2 * D)
    assert scope.find_var("moe_lm.l9.attn.q.w").shape == (D, D)
    assert scope.find_var("moe_lm.l8.gmu.in.w").shape == (D, DI)
    assert scope.find_var("moe_lm.l1.attn.subln.w").shape == (8,)
    assert scope.find_var("moe_lm.l1.norm_in.b").shape == (D,)
    total = sum(int(np.prod(scope.find_var(n).shape)) for n in names)
    assert total == arch.parameters_held(CFG)


def test_initial_values_follow_the_published_rule(model_scope):
    scope = model_scope[0]
    a = np.exp(np.asarray(scope.find_var("moe_lm.l0.mamba.a_log")))
    np.testing.assert_allclose(a, np.tile(np.arange(1, N + 1)[:, None],
                                          (1, DI)), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(
        scope.find_var("moe_lm.l0.mamba.dt_bias"))))
    assert ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
    assert (np.asarray(scope.find_var("moe_lm.l0.mamba.d")) == 1).all()
    taps = np.asarray(scope.find_var("moe_lm.l0.mamba.conv.w"))
    assert np.abs(taps).max() <= 0.5 and 0.2 < taps.std() < 0.35
    step = np.asarray(scope.find_var("moe_lm.l0.mamba.dt.w"))
    assert np.abs(step).max() <= R ** -0.5
    # B and C start twice as wide as the rest of x_proj
    wide = np.asarray(scope.find_var("moe_lm.l0.mamba.x_bc.w")).std()
    assert 1.6 < wide / np.asarray(
        scope.find_var("moe_lm.l0.mamba.x_dt.w")).std() < 2.4
    assert np.asarray(scope.find_var("moe_lm.l1.attn.qkv.b")).std() > 0.05
    assert np.asarray(scope.find_var("moe_lm.l1.attn.lambda_q1")).std() > .05


def test_whole_sequence_logits_are_the_references(model_scope):
    scope, main, _, logits = model_scope
    toks = np.random.RandomState(0).randint(2, 96, (2, T)).astype(np.int64)
    with ptpu.scope_guard(scope):
        got = np.asarray(ptpu.Executor().run(
            main, feed={"toks": toks, "lbls": toks}, fetch_list=[logits])[0])
    for b in range(2):
        want = _reference_logits(scope, toks[b], np.arange(T))
        np.testing.assert_allclose(got[b], want, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_is_the_references_forward(model_scope, flash):
    """Two slots of different lengths, admitted one after the other and
    stepped together, each step run twice as the benchmark's check runs
    it: the 13-row prompt crosses the window of 8 in its prefill and a
    block's edge of 4 in its decode, the 27-row one three windows; every
    cross layer reads layer 7's rows of the step. The prefill's token is
    the reference's best and every decode step's logits are its forward
    over the same tokens."""
    scope = model_scope[0]
    sess = _session(scope, flash=flash)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(2, 96, n) for n in (13, 27)]
    slots, toks = [], []
    for p in prompts:
        slot, first = sess.admit(p)
        slots.append(slot)
        toks.append([first])
    got = [[] for _ in prompts]
    for _ in range(10):
        out, logits = _step_with_logits(sess)
        for i, slot in enumerate(slots):
            got[i].append(logits[slot])
            toks[i].append(out[slot])
    sess.check_pool_invariant()
    # the window kind has trimmed what lies behind the window, the full
    # kind holds every row, the state kind a row a slot
    full, window, state = sess.kinds
    assert [len(full.tables[s]) for s in slots] == [6, 10]
    assert window.first[slots[1]] > 0
    assert [state.tables[s] for s in slots] == [[0], [1]]
    sess.close()
    for i, p in enumerate(prompts):
        n = len(p)
        want = _reference_logits(scope, np.concatenate([p, toks[i]]),
                                 np.arange(n - 1, n + 10))
        assert want[0].argmax() == toks[i][0]
        np.testing.assert_allclose(np.stack(got[i]), want[1:], rtol=1e-4,
                                   atol=3e-5)


def test_a_prefill_runs_the_layers_that_own_nothing_on_its_last_row(
        model_scope):
    """The prefill program cuts the prompt's last row out before layer 8,
    the first after the last layer that owns a cache: its cross layers
    walk the pool with one query and its gated memory units read one row
    of ``m``. The token it gives is the every-row forward's (the
    whole-sequence program, which runs all rows through all layers)."""
    scope, main, _, logits = model_scope
    model = MoeLM(**SIZES)
    assert (model.cross_from, model.tail_from) == (6, 8)
    spec = moe_lm_session(slots=2, cache_len=64, prompt_buckets=(32,),
                          block_size=4, num_blocks=32, window_num_blocks=24,
                          **SIZES)
    ops = spec.prefill_programs[32].global_block().ops
    walks = [op for op in ops
             if op.type == "multihead_attention_decode_paged"]
    assert len(walks) == 2
    for op in walks:
        q = spec.prefill_programs[32].global_block().var(
            op.inputs["Q"][0])
        assert tuple(q.shape) == (1, 1, 2 * D)
    linears = [op for op in ops if op.type == "linear"
               and op.inputs["W"][0].startswith("moe_lm.l8.")]
    block = spec.prefill_programs[32].global_block()
    assert linears and all(
        tuple(block.var(op.inputs["X"][0]).shape)[:2] == (1, 1)
        for op in linears)
    sess = GenerationSession(spec, scope=scope)
    prompt = np.random.RandomState(3).randint(2, 96, 29)
    _, first = sess.admit(prompt)
    sess.close()
    toks = np.zeros((1, T), np.int64)
    toks[0, :29] = prompt
    with ptpu.scope_guard(scope):
        every = np.asarray(ptpu.Executor().run(
            main, feed={"toks": toks, "lbls": toks}, fetch_list=[logits])[0])
    assert every[0, 28].argmax() == first


def _alone(scope, prompt, steps, **kw):
    sess = _session(scope, **kw)
    slot, first = sess.admit(prompt)
    toks, logits = [first], []
    for _ in range(steps):
        out, lg = _step_with_logits(sess)
        toks.append(out[slot])
        logits.append(lg[slot])
    sess.close()
    return toks, np.stack(logits)


def test_a_cross_layer_reads_its_sources_pool_after_a_trim_and_a_rebind(
        model_scope, flash_off):
    """Slot 0 serves a long request until the window kind has trimmed
    blocks and is retired; the next request is bound to the same slot, to
    blocks the first one held, and gives the tokens and logits it gives in
    a session of its own: the cross layers walk the full kind's table of
    the new tenancy, whatever the window kind freed."""
    scope = model_scope[0]
    rs = np.random.RandomState(8)
    a, b = rs.randint(2, 96, 25), rs.randint(2, 96, 11)
    want, want_logits = _alone(scope, b, 6)
    # ten blocks of the full kind: the second request cannot but take
    # blocks the first one wrote
    sess = _session(scope, num_blocks=10)
    slot, _ = sess.admit(a)
    freed = _counter("paddle_generation_kv_window_blocks_freed_total")
    for _ in range(7):
        sess.step()
    assert _counter("paddle_generation_kv_window_blocks_freed_total") > freed
    held = list(sess.kinds[0].tables[slot])
    sess.retire(slot)
    assert all(k.pool.used_count() == 0 for k in sess.kinds)
    slot, first = sess.admit(b)
    assert slot == 0
    toks, logits = [first], []
    for _ in range(6):
        out, lg = _step_with_logits(sess)
        toks.append(out[slot])
        logits.append(lg[slot])
    assert set(sess.kinds[0].tables[0]) & set(held)
    assert toks == want
    np.testing.assert_allclose(np.stack(logits), want_logits, rtol=1e-5,
                               atol=1e-6)
    sess.close()


def test_a_request_admitted_into_a_running_batch_gives_its_own_tokens(
        model_scope, flash_off):
    scope = model_scope[0]
    rs = np.random.RandomState(9)
    a, b = rs.randint(2, 96, 14), rs.randint(2, 96, 9)
    want, _ = _alone(scope, b, 5)
    sess = _session(scope)
    sess.admit(a)
    for _ in range(3):
        sess.step()
    slot, first = sess.admit(b)
    toks = [first]
    for _ in range(5):
        toks.append(sess.step()[slot])
    assert toks == want
    sess.close()


def _control(name):
    """``tools/phi4flash_controls.py``'s witness: a function that makes one
    thing of the program wrong."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "phi4flash_controls.py")
    spec = importlib.util.spec_from_file_location("phi4flash_controls", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.CONTROLS[name]


@pytest.mark.parametrize("fault", ["none", "memory", "pool", "lambda",
                                   "state"])
def test_the_benchmarks_check_tells_each_witness(model_scope, flash_off,
                                                 monkeypatch, fault):
    """The check of ``benchmarks/harness/serve.py`` at a small size, on the
    model's own initial values: two prompts prefilled, CHECK_STEPS decode
    steps each run twice, the worst logit against the reference's forward
    as a share of the largest. Gated memory units on zeros, cross layers on
    an empty pool, lambda 0 and a prefill that leaves no state each read
    over the harness's limit; the program as it is reads four orders under
    it."""
    monkeypatch.setattr(MoeLM, "_gmu", MoeLM._gmu)
    monkeypatch.setattr(MoeLM, "_attention", MoeLM._attention)
    monkeypatch.setattr(attention_ops, "diff_lambda",
                        attention_ops.diff_lambda)
    monkeypatch.setattr(ssm_ops, "s6_scan", ssm_ops.s6_scan)
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
    worst = 0.0
    for i, p in enumerate(prompts):
        n = len(p)
        want = _reference_logits(scope, np.concatenate([p, toks[i]]),
                                 np.arange(n - 1, n + CHECK_STEPS))
        err = np.abs(np.stack(got[i]) - want[1:]).max()
        gap = want[0].max() - want[0][toks[i][0]]
        worst = max(worst, max(err, gap) / np.abs(want).max())
    if fault == "none":
        assert worst < LOGIT_RTOL * 1e-3
    else:
        assert worst > LOGIT_RTOL, (fault, worst)


def _state_share_of_y(scope, layer=0):
    """The share of ``y = S C + D x`` that the state's term carries in one
    Mamba layer, by root mean square over the later half of a sequence of
    normal inputs."""
    w = ref.gather_weights(scope.find_var, CFG)
    p = "l%d.mamba." % layer
    a = jnp.asarray(np.random.RandomState(5).randn(T, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, _, x, dt, b, c = ref.mamba_inputs(a, w, p)
        y, _ = ref.mamba_scan(x, dt, -jnp.exp(w[p + "a_log"]), b, c)
    y, skip = np.asarray(y)[T // 2:], np.asarray(w[p + "d"] * x)[T // 2:]
    return float(np.sqrt((y ** 2).mean() / ((y + skip) ** 2).mean()))


def test_the_state_carries_a_share_of_y_at_the_initial_values(model_scope):
    """``y = S C + D x``: at the initial values the state's term is a real
    part of ``y``, so a comparison of logits sees a wrong state (the
    ``state`` witness above reads over the limit because of it)."""
    assert _state_share_of_y(model_scope[0]) > 0.05


# -- the books ----------------------------------------------------------------

def _counter(name, **labels):
    return sum(float(p) for n, _, _, _, ch in metrics.REGISTRY.snapshot()
               if n == name for lab, p in ch
               if all(dict(lab).get(k) == v for k, v in labels.items()))


def test_the_borrowed_walks_are_counted_at_the_launch(model_scope,
                                                      flash_off):
    """Two cross layers walk the full kind's pool: a step's borrowed rows
    are twice the contexts of the slots it advanced, beside the owning
    layer's own ``_context_tokens_total`` of the scheduler; the window
    kind's and the state kind's counters go by their own layers."""
    scope = model_scope[0]
    sess = _session(scope)
    rs = np.random.RandomState(7)
    sess.admit(rs.randint(2, 96, 8))
    sess.admit(rs.randint(2, 96, 13))
    names = ("paddle_generation_borrowed_context_tokens_total",
             "paddle_generation_window_context_tokens_total",
             "paddle_generation_state_rows_updated_total")
    before = [_counter(n) for n in names]
    blocks = _counter("paddle_generation_paged_blocks_total", kind="full")
    sess.step()
    sess.step()
    got = [_counter(n) - b for n, b in zip(names, before)]
    # contexts 9, 14 then 10, 15; three window layers of at most 8 rows;
    # four Mamba layers, two slots, two steps
    assert got == [2 * (9 + 14 + 10 + 15), 3 * 4 * 8, 4 * 2 * 2]
    # the full kind's walks: the owner's and the two borrowers'
    assert (_counter("paddle_generation_paged_blocks_total", kind="full")
            - blocks) % 3 == 0
    sess.close()


def test_a_kind_counts_its_borrowers_walks():
    kind = LayerCache(CacheKind("full", None, 64, 1, "p", "d", borrowers=7),
                      4, 2, 16, 4 * 2 * 8 * 4)
    name = "paddle_generation_borrowed_context_tokens_total"
    before = _counter(name)
    kind.count_step(np.asarray([5, 11]))
    assert _counter(name) - before == 7 * 16
    assert CacheKind("full", None, 64, 1, "p", "d").borrowers == 0
    plain = LayerCache(CacheKind("full", None, 64, 1, "p", "d"), 4, 2, 16,
                       4 * 2 * 8 * 4)
    plain.count_step(np.asarray([5, 11]))
    assert _counter(name) - before == 7 * 16


def test_the_spec_names_its_kinds_and_who_borrows():
    spec = moe_lm_session(slots=2, cache_len=64, prompt_buckets=(16,),
                          block_size=4, num_blocks=32, window_num_blocks=24,
                          **SIZES)
    assert [(k.name, k.window, k.num_blocks, k.layers, k.borrowers)
            for k in spec.cache_kinds] == [
        ("full", None, 32, 1, 2), ("window", 8, 24, 3, 0),
        ("state", None, 2, 4, 0)]
    # 1 + 3 paged layers of K and V, 4 state layers of (ssm, conv, at)
    assert len(spec.cache_vars) == 2 * 4 + 3 * 4
    model = MoeLM(**SIZES)
    assert model.site == {0: 0, 1: 1, 2: 2, 3: 3, 4: 4, 5: 5, 6: 6, 7: 7}
    assert 8 not in model.site and 11 not in model.site


def test_the_layers_operations_carry_their_decoders_scope():
    spec = moe_lm_session(slots=2, cache_len=64, prompt_buckets=(16,),
                          block_size=4, num_blocks=32, window_num_blocks=24,
                          **SIZES)
    scopes = {}
    for op in spec.decode_program.global_block().ops:
        scope = op.attrs.get("op_namescope")
        if scope:
            scopes.setdefault(scope, set()).add(op.type)
    assert set(scopes) == {
        "self_decoder/mamba1_mixer", "self_decoder/diff_attention",
        "cross_decoder/mamba1_mixer", "cross_decoder/diff_attention",
        "cross_decoder/gmu", "cross_decoder/cross_attention"}
    assert "mamba1_mixer_decode" in scopes["self_decoder/mamba1_mixer"]
    assert "kv_cache_append_paged" in scopes["cross_decoder/diff_attention"]
    walking = scopes["cross_decoder/cross_attention"]
    assert "multihead_attention_decode_paged" in walking
    assert "kv_cache_append_paged" not in walking
    assert scopes["cross_decoder/gmu"] >= {"linear", "silu",
                                           "elementwise_mul"}


# -- what is refused ----------------------------------------------------------

@pytest.mark.parametrize("what", ["prefix_cache", "speculate_k"])
def test_the_spec_refuses_what_its_kinds_cannot_serve(what):
    model = MoeLM(**SIZES)
    kw = dict(max_len=64, slots=2, cache_len=64, prompt_buckets=(16,),
              block_size=4, num_blocks=32, prefix_cache=False,
              decode_policy=None, kind_blocks={"window": 24, "state": 2})
    if what == "prefix_cache":
        kw["prefix_cache"] = True
    else:
        kw["decode_policy"] = DecodePolicy(kind="greedy", speculate_k=2)
    with pytest.raises(ValueError, match="state kind.*rewritten whole"):
        lm_session(model, **kw)


@pytest.mark.parametrize("other,message", [
    (dict(kv_from=None), "reads layer kv_from = None"),
    (dict(kv_from=6), "reads layer kv_from = 6"),
    (dict(kv_from=9), "reads layer kv_from = 9"),
    (dict(memory_from=None), "reads layer memory_from = None"),
    (dict(memory_from=7), "reads layer memory_from = 7"),
    (dict(memory_from=10), "reads layer memory_from = 10"),
    (dict(layer_types=["mamba", "full_attention", "conv", "gmu"]),
     "layer_types holds .'conv'.*'cross_attention'.*'gmu'"),
    (dict(layer_types=["mamba", "full_attention"] * 2, num_dense_layers=4),
     "kv_from is 7 and no layer is 'cross_attention'"),
    (dict(layer_types=CFG["layer_types"][:10] + ["mamba", "cross_attention"]),
     "a layer that owns a cache follows one that borrows"),
    (dict(differential=True, qk_norm=True), "differential attention is"),
    (dict(norm="batch"), "norm is 'rms' or 'layer'"),
], ids=["no_kv_from", "kv_from_a_mixer", "kv_from_a_later_layer",
        "no_memory_from", "memory_from_an_attention", "memory_from_later",
        "an_unknown_type", "a_source_nobody_reads",
        "an_owner_behind_a_borrower", "a_norm_on_the_pairs",
        "an_unknown_norm"])
def test_the_model_refuses_a_plan_it_cannot_build(other, message):
    with pytest.raises(ValueError, match=message):
        MoeLM(**dict(SIZES, **other))


def test_a_memory_is_a_mamba1_scans_output():
    sizes = dict(SIZES, mamba=dict(num_heads=4, head_dim=16, state_dim=4,
                                   conv_width=4, chunk=8))
    with pytest.raises(ValueError, match="a Mamba-1 scan's output"):
        MoeLM(**sizes)


def test_the_architecture_module_refuses_another_plan():
    for other in (dict(mb_per_layer=4), dict(kv_from=5),
                  dict(attention_bias=False), dict(num_hidden_layers=10),
                  dict(layer_types=CFG["layer_types"][::-1])):
        with pytest.raises(ValueError, match="the phi4flash module builds"):
            arch.sizes(dict(CFG, **other))


# -- served -------------------------------------------------------------------

def test_the_scheduler_serves_the_model_a_step_ahead(model_scope, flash_off):
    scope = model_scope[0]
    prompts = [np.arange(5, 26), np.arange(30, 41)]
    want = [_alone(scope, p, 6)[0] for p in prompts]
    sess = _session(scope)
    sched = GenerationScheduler(sess, max_queue=8, deadline_ms=0)
    try:
        futures = [sched.submit(p, max_new_tokens=7, eos_id=-1)
                   for p in prompts]
        got = [np.asarray(f.result(timeout=120)).tolist() for f in futures]
    finally:
        sched.drain(timeout=60)
        sess.close()
    assert got == want
