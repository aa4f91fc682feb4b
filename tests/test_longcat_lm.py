"""The ``longcat_flash``-shaped model (``models/moe_lm.py`` with a block of
two halves and the expert layer laid across them, ``ops/moe_ops.py`` with
identity experts under a router wider than its experts) against the plain
reference of ``benchmarks/reference/longcat_flash.py``: the router and the
expert layer alone, the shares adding up, the whole model over whole
sequences and through a session's eight paged latent pools, the counters,
the harness's own check against four wrong programs, and the three older
models' programs, which must be op for op what they were. CPU, small sizes.

Tolerances. The programs here hold float32 weights and multiply at the
highest precision, the reference is float32 at ``highest``: what is left is
the order of float32 sums (a blocked softmax against a whole one, sorted
pairs against a dense mask). Logits are of order 1 to 6, so ``ATOL`` 5e-5
is some tens of float32 ulps of the largest; the one-pass bfloat16 control
(``test_a_one_pass_bfloat16_product_fails``) reads a thousand times that.
"""

import importlib.util
import json
import math
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.moe_lm import MoeLM, moe_lm, moe_lm_session
from paddle_tpu.observability import metrics
from paddle_tpu.ops import moe_ops
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.serving.paged_cache import BLOCKS_IN_USE

from benchmarks import architectures
from benchmarks.architectures import longcat_flash as arch
from benchmarks.harness import lm as bench_lm, serve
from benchmarks.reference import longcat_flash as ref

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "longcat_controls", os.path.join(os.path.dirname(HERE), "tools",
                                     "longcat_controls.py"))
_controls = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_controls)
CONTROLS = _controls.CONTROLS

pytestmark = [pytest.mark.generation, pytest.mark.paged]

ATOL = 5e-5


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


# -- the router and the expert layer alone ------------------------------------

D, F, E, Z, K = 16, 8, 16, 12, 12
LAYER_CFG = dict(moe_topk=K, routed_scaling_factor=6,
                 n_routed_experts_published=E, zero_expert_num=Z)


def _layer_weights(seed=5):
    """A router whose outputs lean on one direction ``v``: the real experts
    against it, the identity experts with it, so that a row ``+4v`` picks
    its twelve among the identity outputs and ``-4v`` none of them."""
    rs = np.random.RandomState(seed)
    v = rs.randn(D)
    v /= np.linalg.norm(v)
    router = 0.3 * rs.randn(D, E + Z)
    router[:, :E] -= v[:, None]
    router[:, E:] += v[:, None]
    return {"v": v, "router": router.astype(np.float32),
            "gate": (rs.randn(E, D, F) * 0.3).astype(np.float32),
            "up": (rs.randn(E, D, F) * 0.3).astype(np.float32),
            "down": (rs.randn(E, F, D) * 0.3).astype(np.float32)}


def _rows(n, seed=10):
    """n rows: the first all identity, the second none, the rest mixed."""
    x = np.random.RandomState(seed).randn(n, D).astype(np.float32)
    v = _layer_weights()["v"]
    x[0], x[1] = 4 * v, -4 * v
    return x


def _moe(x, offset=0, held=None, bias=None):
    """The op on x [n, D] with the seeded weights: -> (out, counts, zero)."""
    full = _layer_weights()
    n_held = held or E
    sets = {"m.router.w": full["router"]}
    if bias is not None:
        sets["m.expert_bias"] = np.asarray(bias, np.float32)
    for part in ("gate", "up", "down"):
        sets["m.experts.%s.w" % part] = full[part][offset:offset + n_held]

    def build():
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        return layers.moe_ffn(xv, E, K, F, "m", route_norm=False,
                              route_scale=6.0, expert_offset=offset,
                              experts_held=held, scoring="softmax_bias",
                              zero_experts=Z)
    return _run(build, {"x": x}, sets)


def _ref_weights(bias=None, experts=slice(0, E)):
    full = _layer_weights()
    w = {"l.router": full["router"],
         "l.expert_bias": np.zeros(E + Z) if bias is None else bias}
    w.update({"l.experts." + p: full[p][experts]
              for p in ("gate", "up", "down")})
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def _shortcut(x, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.shortcut(jnp.asarray(x), _ref_weights(**kw),
                                       "l.", LAYER_CFG))


def test_route_with_identity_outputs_is_the_references():
    """Selections and weights of the program's router against the
    reference's, under a bias that moves the choice and not the weights;
    the chosen scores are not renormalised: they sum to under 6."""
    x = _rows(9)
    bias = np.random.RandomState(3).randn(E + Z).astype(np.float32) * 0.05
    full = _layer_weights()
    sel, w = moe_ops.route(jnp.asarray(x), jnp.asarray(full["router"]),
                           jnp.asarray(bias), K, False, 6.0,
                           scoring="softmax_bias")
    with jax.default_matmul_precision("highest"):
        rsel, rw = ref.route(jnp.asarray(x), _ref_weights(bias=bias), "l.",
                             LAYER_CFG)
    np.testing.assert_array_equal(np.sort(sel, 1), np.sort(rsel, 1))
    order, rorder = np.argsort(sel, 1), np.argsort(rsel, 1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1),
                               np.take_along_axis(np.asarray(rw), rorder, 1),
                               rtol=1e-6)
    assert (np.asarray(w).sum(1) < 6.0).all()
    # the bias moved a choice somewhere
    free = moe_ops.route(jnp.asarray(x), jnp.asarray(full["router"]),
                         jnp.zeros(E + Z), K, False, 6.0,
                         scoring="softmax_bias")[0]
    assert (np.sort(free, 1) != np.sort(sel, 1)).any()
    # the weights are the softmax's own, whatever the bias chose
    p = jax.nn.softmax(jnp.asarray(x) @ jnp.asarray(full["router"]), -1)
    np.testing.assert_allclose(
        w, 6 * np.take_along_axis(np.asarray(p), np.asarray(sel), 1),
        rtol=1e-5)
    with pytest.raises(ValueError, match="softmax_bias"):
        moe_ops.route(jnp.asarray(x), jnp.asarray(full["router"]),
                      jnp.asarray(bias), K, False, 6.0, scoring="tanh")


def test_the_expert_layer_with_identity_experts_is_the_references():
    """A whole holder: every real pair computed, every identity pair
    ``w x``. The row whose twelve are all identity is ``sum(w) u`` to the
    last bit (no sorted pass adds as much as a rounding to it); the row
    with none has no identity part."""
    x = _rows(9)
    out, counts, zero = _moe(x)
    np.testing.assert_allclose(out, _shortcut(x), rtol=2e-4, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        sel, w = ref.route(jnp.asarray(x), _ref_weights(), "l.", LAYER_CFG)
    sel, w = np.asarray(sel), np.asarray(w)
    assert (sel[0] >= E).all() and (sel[1] < E).all()
    np.testing.assert_array_equal(
        out[0], np.asarray(jnp.sum(jnp.asarray(w[0])) * jnp.asarray(x[0])))
    assert int(zero[0]) == int((sel >= E).sum()) and zero.shape == (1,)
    np.testing.assert_array_equal(
        counts, np.bincount(sel[sel < E], minlength=E))
    assert counts.sum() + zero[0] == 9 * K
    # no identity part in row 1: the real experts' sum alone
    only_real = _shortcut(x[1:2])
    np.testing.assert_allclose(out[1], only_real[0], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("share_rows", [None, 5],
                         ids=["one_pass", "passes_of_5"])
def test_the_shares_add_up_to_the_uncut_reference_layer(share_rows,
                                                        monkeypatch):
    """Four holders of 4 of 16 experts, each with the identity part (what
    every chip computes alike for its own rows), summed with the identity
    part counted once, are the uncut reference's layer; and the pairs add
    up: a holder's counts, the identity pairs and the pairs held elsewhere
    are rows x 12."""
    if share_rows:
        monkeypatch.setattr(moe_ops, "SHARE_ROWS", share_rows)
    x = _rows(14)
    parts, counts, zeros = zip(*[_moe(x, offset=o, held=4)
                                 for o in range(0, E, 4)])
    with jax.default_matmul_precision("highest"):
        sel, w = ref.route(jnp.asarray(x), _ref_weights(), "l.", LAYER_CFG)
    identity = np.sum(np.where(np.asarray(sel) >= E, np.asarray(w), 0.0),
                      axis=1, keepdims=True) * x
    np.testing.assert_allclose(sum(parts) - 3 * identity, _shortcut(x),
                               rtol=2e-4, atol=2e-5)
    assert len({int(z[0]) for z in zeros}) == 1
    held = [int(c.sum()) for c in counts]
    for j in range(4):
        elsewhere = sum(held) - held[j]
        assert held[j] + int(zeros[j][0]) + elsewhere == 14 * K
    # and the reference, given one share, gives that share with the
    # identity part
    cfg = dict(LAYER_CFG, expert_offset=8)
    with jax.default_matmul_precision("highest"):
        one = np.asarray(ref.shortcut(
            jnp.asarray(x), _ref_weights(experts=slice(8, 12)), "l.", cfg))
    np.testing.assert_allclose(parts[2], one, rtol=2e-4, atol=2e-5)


def test_identity_pairs_are_never_sorted_into_a_pass():
    """A bias sends every token's twelve to the identity experts: a holder
    of a share has no pair to sort into a pass (its loop runs none), no
    held expert is counted, and the result is the identity part alone, to
    the last bit."""
    x = _rows(20)
    bias = np.zeros(E + Z, np.float32)
    bias[E:] = 10.0
    out, counts, zero = _moe(x, offset=4, held=4, bias=bias)
    np.testing.assert_array_equal(counts, [0, 0, 0, 0])
    assert int(zero[0]) == 20 * K
    with jax.default_matmul_precision("highest"):
        sel, w = ref.route(jnp.asarray(x), _ref_weights(bias=bias), "l.",
                           LAYER_CFG)
    assert (np.asarray(sel) >= E).all()
    np.testing.assert_array_equal(
        out, np.asarray(jnp.sum(w, axis=1, keepdims=True) * jnp.asarray(x)))
    np.testing.assert_allclose(out, _shortcut(x, bias=bias), rtol=1e-6)


def test_a_router_narrower_than_its_outputs_is_refused():
    full = _layer_weights()

    def build():
        xv = layers.data("x", shape=[3, D], dtype="float32",
                         append_batch_size=False)
        return layers.moe_ffn(xv, E, K, F, "m", zero_experts=Z)
    with pytest.raises(ValueError, match="routes over 28 experts"):
        _run(build, {"x": _rows(3)}, {"m.router.w": full["router"][:, :E]})


# -- the whole model -----------------------------------------------------------

CFG = dict(source=next(iter(arch.PUBLISHED)), architecture="longcat_flash",
           attention_method="MLA", attention_bias=False,
           zero_expert_type="identity", mla_scale_q_lora=True,
           mla_scale_kv_lora=True, routed_scaling_factor=6, rope_theta=1e7,
           rms_norm_eps=1e-5, torch_dtype="float32", initializer_range=0.02,
           expert_offset=4, hidden_size=32, num_attention_heads=4,
           q_lora_rank=16, kv_lora_rank=12, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, ffn_hidden_size=48,
           expert_ffn_hidden_size=16, n_routed_experts_published=16,
           n_routed_experts=4, zero_expert_num=8, moe_topk=3, num_layers=4,
           vocab_size=50, max_position_embeddings=64,
           deployment={"serving": dict(slots=3, cache_len=32, block_size=4,
                                       num_blocks=24, kv_dtype="float32")})
SIZES = arch.sizes(CFG)
T = 30


def _randomise(scope, seed=21):
    """Matmul weights of order 0.3, so that logits are of order one and
    every branch of a layer weighs in them."""
    rs = np.random.RandomState(seed)
    for n in scope.var_names():
        if n.startswith("moe_lm.") and "norm" not in n \
                and "expert_bias" not in n:
            cur = np.asarray(scope.find_var(n))
            scope.set_var(n, jnp.asarray(
                0.3 * rs.standard_normal(cur.shape), cur.dtype))


@pytest.fixture(scope="module")
def model_scope():
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 7
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        loss, logits = moe_lm(toks, lbls, **SIZES)
        ptpu.Executor().run(startup)
    _randomise(scope)
    return scope, main, loss, logits


def _session(scope, flash=False, sizes=SIZES, **kw):
    ptpu.config.set_flags(flash_attention=flash)
    args = dict(slots=3, cache_len=32, prompt_buckets=(8, 16), block_size=4,
                num_blocks=24)
    args.update(kw)
    return GenerationSession(moe_lm_session(**args, **sizes), scope=scope)


def _whole_sequence(model_scope, seq):
    scope, main, _, logits = model_scope
    with ptpu.scope_guard(scope):
        return np.asarray(ptpu.Executor().run(
            main, feed={"toks": seq[None], "lbls": seq[None]},
            fetch_list=[logits])[0])[0]


def _reference(scope, seq, cfg=CFG):
    w = ref.gather_weights(scope.find_var, cfg)
    return np.asarray(ref.logits_at(w, jnp.asarray(seq),
                                    jnp.arange(len(seq)), cfg))


def test_the_model_holds_the_parameters_the_equations_name(model_scope):
    scope = model_scope[0]
    shapes = {n: tuple(np.shape(scope.find_var(n)))
              for n in scope.var_names() if n.startswith("moe_lm.l1.")}
    half = {"norm_in.w": (32,), "norm_pre_mlp.w": (32,),
            "attn.q_a.w": (32, 16), "attn.q_a_norm.w": (16,),
            "attn.q_b.w": (16, 4 * 12), "attn.kv_a.w": (32, 12 + 4),
            "attn.kv_a_norm.w": (12,), "attn.kv_b.w": (12, 4 * 16),
            "attn.o.w": (4 * 8, 32), "mlp.gate.w": (32, 48),
            "mlp.up.w": (32, 48), "mlp.down.w": (48, 32)}
    want = {"moe_lm.l1.h%d.%s" % (j, k): v for j in (0, 1)
            for k, v in half.items()}
    want.update({"moe_lm.l1.moe.router.w": (32, 16 + 8),
                 "moe_lm.l1.moe.expert_bias": (16 + 8,),
                 "moe_lm.l1.moe.experts.gate.w": (4, 32, 16),
                 "moe_lm.l1.moe.experts.up.w": (4, 32, 16),
                 "moe_lm.l1.moe.experts.down.w": (4, 16, 32)})
    assert shapes == want
    assert set(ref.weight_names(CFG).values()) == {
        n for n in scope.var_names() if n.startswith("moe_lm.")}


def test_whole_sequence_logits_are_the_references(model_scope):
    rs = np.random.RandomState(22)
    for _ in range(2):
        seq = rs.randint(2, 50, T)
        want = _reference(model_scope[0], seq)
        assert np.abs(want).max() > 1.0
        np.testing.assert_allclose(_whole_sequence(model_scope, seq), want,
                                   atol=ATOL)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_equals_the_references_forward(model_scope,
                                                           flash):
    """Two sequences prefilled through the expanded path and decoded
    through the absorbed path and the eight latent pools, each against the
    reference's full forward. Logits, not tokens. A prompt of 13 crosses a
    bucket's edge (8 | 16) and three blocks' (4, 8, 12); decoding crosses
    five more."""
    scope = model_scope[0]
    rs = np.random.RandomState(23)
    sess = _session(scope, flash=flash)
    try:
        assert len(sess.spec.cache_vars) == \
            sess.spec.cache_kinds[0].layers == 8
        name = bench_lm.logits_var(sess.spec.decode_program,
                                   sess.spec.decode_fetch)
        for n0 in (13, 5):
            seq = rs.randint(2, 50, T)
            want = _reference(scope, seq)
            slot, first = sess.admit(seq[:n0])
            assert first == int(want[n0 - 1].argmax())
            assert sess.prefill_log[-1][0] == (16 if n0 == 13 else 8)
            for i in range(n0, T):
                prepared = sess.step_prepare()
                prepared[2]["gen.dtok"][slot, 0] = seq[i]
                got = np.asarray(sess.exe.run(
                    sess.spec.decode_program, feed=prepared[2],
                    fetch_list=[name, sess.spec.decode_fetch],
                    scope=scope)[0])
                sess.lengths[slot] += 1
                np.testing.assert_allclose(got[slot], want[i], atol=ATOL)
                sess.check_pool_invariant()
            assert sess.pool.used_count() == -(-T // 4)
            sess.retire(slot)
            assert sess.pool.used_count() == 0
    finally:
        sess.close()
        ptpu.config.set_flags(flash_attention=True)


def _wrong(control, monkeypatch):
    """One of ``tools/longcat_controls.py``'s wrong programs (what the
    chip runs at the published widths), for this test's life."""
    for owner, name, wrong in CONTROLS[control](CFG):
        monkeypatch.setattr(owner, name, wrong)


def test_a_one_pass_bfloat16_product_fails(model_scope, monkeypatch):
    """The control of the tolerance: the same whole-sequence comparison
    with every projection in one bfloat16 pass reads far over ``ATOL``,
    and over the harness's own limit as a share of the largest logit."""
    _wrong("bf16", monkeypatch)
    seq = np.random.RandomState(22).randint(2, 50, T)
    want = _reference(model_scope[0], seq)
    scope, _, _, _ = model_scope
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.scope_guard(scope), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        _, logits = moe_lm(toks, lbls, **SIZES)
        got = np.asarray(ptpu.Executor().run(
            main, feed={"toks": seq[None], "lbls": seq[None]},
            fetch_list=[logits])[0])[0]
    err = np.abs(got - want).max()
    assert err > 1000 * ATOL
    assert err / np.abs(want).max() > serve.LOGIT_RTOL


# -- the session's books and counters -----------------------------------------

def test_the_spec_counts_sites_not_layers():
    """Eight pools for four layers, one latent kind and one block table;
    the routed pairs a step from the block's spec; the counts' fetch one
    column wider than the held experts."""
    spec = moe_lm_session(slots=3, cache_len=32, prompt_buckets=(8,),
                          block_size=4, num_blocks=24, cache_ns="kv", **SIZES)
    assert spec.cache_vars == tuple(
        ("kv.l%d.c" % i, (24, 4, 128), "float32") for i in range(8))
    kind, = spec.cache_kinds
    assert (kind.name, kind.window, kind.num_blocks, kind.layers) == \
        ("latent", None, 24, 8)
    assert spec.zero_experts == 8
    assert spec.routed_pairs == 3 * 3 * 4       # slots x top-k x layers
    block = spec.decode_program.global_block()
    ops = [op.type for op in block.ops]
    assert ops.count("mla_attention_decode_paged") == 8
    assert ops.count("kv_cache_append_paged") == 8
    assert ops.count("moe_ffn") == 4
    pools = [op.inputs["Cache"][0] for op in block.ops
             if op.type == "mla_attention_decode_paged"]
    assert pools == ["kv.l%d.c" % i for i in range(8)]
    assert block.var(spec.stats_fetch).shape == (4, 4 + 1)
    pre = [op.type for op in spec.prefill_programs[8].global_block().ops]
    assert pre.count("mla_attention") == pre.count("kv_cache_write_paged") == 8
    copies = [op for op in spec.copy_program.global_block().ops
              if op.type == "kv_block_copy"]
    assert [op.inputs["Cache"] for op in copies] == \
        [["kv.l%d.c" % i] for i in range(8)]
    # the branch is told from the dense halves by its scope: the expert
    # op, its counts and the sum that joins it, in every layer
    scoped = [op.type for op in block.ops
              if op.attrs.get("op_namescope") == "scmoe_shortcut"]
    assert scoped == ["moe_ffn", "concat", "elementwise_add"] * 4
    m = MoeLM(**SIZES)
    assert (m.pairs_per_row, len(m.cache_layers)) == (3 * 4, 8)
    with pytest.raises(ValueError, match="a block of halves"):
        MoeLM(**dict(SIZES, block=dict(halves=2, experts_read=1,
                                       experts_join=0)))
    with pytest.raises(ValueError, match="a block of halves"):
        MoeLM(**dict(SIZES, num_dense_layers=1))


def test_the_branch_is_named_in_the_lowered_step():
    """``scmoe_shortcut`` and ``zero_expert_combine`` reach the lowered
    step's ``op_name`` metadata, which is what a device trace shows: the
    first through ``name_scope`` and the executor's per-op scope, the
    second inside the op."""
    from paddle_tpu.core import executor as core_executor
    from paddle_tpu.core.registry import ExecContext
    main = ptpu.Program()
    with ptpu.program_guard(main, ptpu.Program()):
        xv = layers.data("x", shape=[2, 4], dtype="float32",
                         append_batch_size=False)
        with ptpu.name_scope("scmoe_shortcut"):
            y = layers.elementwise_add(xv, xv)
        z = layers.elementwise_add(y, xv)
    ops = main.global_block().ops
    assert [op.attrs.get("op_namescope") for op in ops] == \
        ["scmoe_shortcut", None]

    def step(x):
        env = {"x": x}
        core_executor.run_block(main.global_block(), env,
                                core_executor._TraceState(set()))
        return env[z.name]
    text = jax.jit(step).lower(jnp.ones((2, 4))).as_text(debug_info=True)
    assert "scmoe_shortcut/elementwise_add" in text

    def combine(x, r, b, g, u, d):
        op = SimpleNamespace(attrs=dict(
            num_experts=E, top_k=K, zero_experts=Z, route_norm=False,
            route_scale=6.0, scoring="softmax_bias"))
        return moe_ops._moe_ffn(ExecContext(op, {
            "X": [x], "RouterW": [r], "ExpertBias": [b], "WGate": [g],
            "WUp": [u], "WDown": [d]}))["Out"]
    full = _layer_weights()
    text = jax.jit(combine).lower(
        jnp.asarray(_rows(4)), jnp.asarray(full["router"]),
        jnp.zeros(E + Z), *[jnp.asarray(full[p])
                            for p in ("gate", "up", "down")]
    ).as_text(debug_info=True)
    assert "zero_expert_combine" in text


def _counter(name):
    for n, _, _, _, children in metrics.REGISTRY.snapshot():
        if n == name:
            return sum(float(p) for _, p in children)
    return 0.0


COUNTERS = ("paddle_generation_routed_pairs_total",
            "paddle_generation_expert_assignments_total",
            "paddle_generation_zero_expert_pairs_total",
            "paddle_generation_experts_touched_total",
            "paddle_generation_expert_max_load_total",
            "paddle_generation_latent_rows_attended_total",
            "paddle_generation_moe_layer_steps_total")


def test_the_counters_add_up_by_hand(model_scope, flash_off):
    """Four decode steps of a 3-slot session with four expert layers that
    hold 4 of 16 experts under a 24-wide router and eight latent sites; the
    routing of each step is read back from the step's own fetch and
    counted by hand. The identity pairs are counted from the same fetch as
    the held experts' (its last column)."""
    scope = model_scope[0]
    sess = _session(scope)
    sess.admit(np.arange(2, 9))          # 7 rows
    sess.admit(np.arange(3, 15))         # 12 rows
    before = {c: _counter(c) for c in COUNTERS}
    by_hand = dict.fromkeys(COUNTERS, 0)
    for _ in range(4):
        prepared = sess.step_prepare()
        stats = np.asarray(sess.exe.run(
            sess.spec.decode_program, feed=prepared[2],
            fetch_list=[sess.spec.stats_fetch], scope=scope)[0])
        assert stats.shape == (4, 5)
        counts, zero = stats[:, :4], stats[:, 4]
        # every slot's row is routed, advancing or not: 3 x 3 pairs a layer
        assert (counts.sum(1) + zero <= 3 * 3).all()
        out = sess.step_run(prepared)
        assert sorted(out) == [0, 1]
        by_hand[COUNTERS[0]] += 4 * 3 * 3        # layers x slots x top-k
        by_hand[COUNTERS[1]] += int(counts.sum())
        by_hand[COUNTERS[2]] += int(zero.sum())
        by_hand[COUNTERS[3]] += int((counts > 0).sum())
        by_hand[COUNTERS[4]] += int(counts.max(1).sum())
        by_hand[COUNTERS[5]] += 8 * sum(int(sess.lengths[s]) for s in out)
        by_hand[COUNTERS[6]] += 4
    assert {c: _counter(c) - before[c] for c in COUNTERS} == by_hand
    assert by_hand[COUNTERS[5]] == 8 * sum(
        (7 + i) + (12 + i) for i in range(1, 5))
    assert 0 < by_hand[COUNTERS[2]] < by_hand[COUNTERS[0]]
    assert by_hand[COUNTERS[1]] + by_hand[COUNTERS[2]] < by_hand[COUNTERS[0]]
    sess.close()


def test_one_gauge_and_one_table_serve_the_eight_pools(model_scope,
                                                       flash_off):
    """The eight pools share block ids: one block table, one books, one
    gauge child ``latent.p<n>`` whose blocks each lie in all eight."""
    def gauges():
        return {l["pool"]: float(p)
                for n, _, _, _, ch in metrics.REGISTRY.snapshot()
                if n == BLOCKS_IN_USE.name for l, p in ch
                if l["pool"].startswith("latent.")}
    scope = model_scope[0]
    before = set(gauges())
    sess = _session(scope)
    sess.admit(np.arange(2, 12))         # 10 rows: 3 blocks of 4
    assert sess.pool._label.startswith("latent.p")
    assert set(gauges()) - before == {sess.pool._label}
    assert gauges()[sess.pool._label] == 3.0
    assert sess.pool_stats()["bytes_per_block"] == 4 * 128 * 4 * 8
    sess.close()
    assert set(gauges()) == before


def test_the_scheduler_serves_the_model_a_step_ahead(model_scope, flash_off):
    scope = model_scope[0]
    sess = _session(scope)
    want = sess.generate(np.arange(2, 9), max_new_tokens=6, eos_id=-1)
    zero0 = _counter("paddle_generation_zero_expert_pairs_total")
    ahead0 = _counter("paddle_generation_decode_steps_ahead_total")
    sched = GenerationScheduler(sess, deadline_ms=0)
    futures = [sched.submit(np.arange(2, 9), max_new_tokens=6, eos_id=-1),
               sched.submit(np.arange(5, 16), max_new_tokens=9, eos_id=-1)]
    outs = [np.asarray(f.result(timeout=120)) for f in futures]
    sched.close()
    np.testing.assert_array_equal(outs[0], np.asarray(want))
    assert len(outs[1]) == 9
    assert _counter("paddle_generation_decode_steps_ahead_total") > ahead0
    assert _counter("paddle_generation_zero_expert_pairs_total") > zero0
    sess.check_pool_invariant()
    sess.close()


# -- the harness's own check, and four wrong programs --------------------------

def _harness_check(scope, sizes=SIZES, seed=5):
    """``benchmarks/harness/serve.py``'s comparison (a prompt a bucket
    prefilled, 8 decode steps through the cache, logits against the
    reference's full forward) on a session over ``scope``."""
    sess = _session(scope, sizes=sizes, cache_len=40, num_blocks=30,
                    prompt_buckets=(16, 24))
    try:
        dep = SimpleNamespace(cfg=CFG, arch=arch, session=sess,
                              spec=sess.spec, buckets=(16, 24))
        return serve.Deployment._check_against_reference(dep, seed)
    finally:
        sess.close()


def test_the_harness_check_passes_the_program(model_scope, flash_off):
    report = _harness_check(model_scope[0])
    assert report["worst_rel_err"] < 1e-4 < serve.LOGIT_RTOL
    assert report["worst_first_token_rel_gap"] == 0.0
    assert [r["bucket"] for r in report["per_bucket"]] == [16, 24]


@pytest.mark.parametrize("wrong", ["identity", "shortcut", "late_read",
                                   "held", "bf16"])
def test_the_harness_check_sees_a_wrong_program(model_scope, flash_off,
                                                monkeypatch, wrong):
    """Each part of what is new, taken out or moved (the identity part
    zeroed, the shortcut never added, ``s`` read at the second half's
    norm, the held experts' part zeroed), reads over the check's limit
    through the check's own comparison, as does the precision below the
    configuration's."""
    _wrong(wrong, monkeypatch)
    report = _harness_check(model_scope[0], sizes=arch.sizes(CFG))
    assert report["worst_rel_err"] > serve.LOGIT_RTOL


# -- the three older models ------------------------------------------------------

def _listing(program):
    def plain(v):
        if isinstance(v, dict):
            return {str(k): plain(x) for k, x in sorted(v.items())}
        if isinstance(v, (list, tuple)):
            return [plain(x) for x in v]
        if isinstance(v, (int, float, str, bool)) or v is None:
            return v
        return repr(v)
    return [[op.type, plain(op.attrs), sorted(op.inputs), sorted(op.outputs)]
            for op in program.global_block().ops]


OLDER = ["trinity-mini-l5", "kimi-k2.7-code-l6", "granite-4.0-h-small-l10"]


@pytest.mark.parametrize("config", OLDER + [
    "cerebras-gpt-1.3b", "longcat-flash-chat-l4", "evabyte-6.5b-l8"])
def test_the_older_models_programs_are_op_for_op_what_they_were(config):
    """Op types, attrs and slot names of a served configuration's decode,
    prefill and copy programs at the rehearsal's sizes against lists taken
    from a parent commit. The three older models' from 129d118
    (``tests/data/moe_lm_programs_at_pr38.json``): the block's new data is
    absent where a model does not use it. The other three's, and every
    copy program, from 057a117 (``lm_programs_at_pr44.json``), before the
    session walked its kinds of layer cache in one loop."""
    was = {}
    for name in ("moe_lm_programs_at_pr38.json", "lm_programs_at_pr44.json"):
        with open(os.path.join(HERE, "data", name)) as f:
            for architecture, programs in json.load(f).items():
                was.setdefault(architecture, {}).update(programs)
    cfg = bench_lm.load_config(config)
    module = architectures.load(cfg)
    tiny = module.tiny(cfg)
    with ptpu.unique_name.guard():
        spec = module.serve_spec(tiny, dict(tiny["deployment"]["serving"]),
                                 (8, 16))
    now = {"decode": _listing(spec.decode_program),
           "copy": _listing(spec.copy_program)}
    now.update({"prefill_%d" % b: _listing(p)
                for b, p in spec.prefill_programs.items()})
    want = was[cfg["architecture"]]
    assert sorted(now) == sorted(want)
    for name in want:
        assert json.loads(json.dumps(now[name])) == want[name], name
    if config in OLDER:
        assert spec.zero_experts == 0
        assert not any("op_namescope" in op.attrs
                       for op in spec.decode_program.global_block().ops)


def test_the_latent_scale_factors_are_the_square_roots():
    s = arch.sizes(dict(CFG, hidden_size=6144, q_lora_rank=1536,
                        kv_lora_rank=512))["latent"]
    assert s["q_scale"] == 2.0 and s["kv_scale"] == math.sqrt(12)
    assert ref.latent_scales(CFG) == (math.sqrt(2), math.sqrt(32 / 12))
    off = arch.sizes(dict(CFG, mla_scale_q_lora=False,
                          mla_scale_kv_lora=False))["latent"]
    assert off["q_scale"] is None and off["kv_scale"] is None
