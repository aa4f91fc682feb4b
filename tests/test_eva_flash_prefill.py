"""The whole-sequence form of EVA attention through the flash forward
(``ops/eva_ops.py::flash_windowed_attention``: the windows as the kernel's
batch, the earlier windows' summaries in XLA, one merge) against the XLA
form (``windowed_attention``), and which of the two ``eva_attention`` takes.
CPU, the kernel interpreted, at the smallest sizes it tiles: windows of 128
and 256 rows, chunks of 16, 2 heads of 128."""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.core.registry import ExecContext, get_op_def
from paddle_tpu.models.moe_lm import moe_lm, moe_lm_session
from paddle_tpu.ops import kernel_path
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.serving import GenerationSession

from benchmarks.architectures import evabyte as arch
from benchmarks.harness import common, lm as bench_lm
from benchmarks.reference import evabyte as ref

pytestmark = [pytest.mark.generation, pytest.mark.paged]

C, HEADS, HD = 16, 2, 128


def _op(op_type, attrs, **inputs):
    """An op called as the executor calls it."""
    op = SimpleNamespace(attrs=attrs, type=op_type)
    return get_op_def(op_type).compute(
        ExecContext(op, {slot: [v] for slot, v in inputs.items()}))


def _flash_paths(before):
    """The paths ``flash_attention`` counted since a ``counts()`` reading."""
    return common.kernel_paths_since(before).get("flash_attention", {})


def _rows(seed, b, t, dtype):
    """Rotated queries and keys, values, and the summaries of their whole
    chunks as ``eva_summaries`` makes them: all in ``dtype``."""
    rs = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rs.standard_normal((b, t, HEADS * HD)) * 0.8,
                           dtype) for _ in range(3))
    mu, phi = (jnp.asarray(rs.standard_normal(HEADS * HD) * HD ** -0.5, dtype)
               for _ in range(2))
    s = _op("eva_summaries", {"num_heads": HEADS, "chunk": C}, K=k, V=v,
            Mu=mu, Phi=phi)
    return dict(Q=q, K=k, V=v, KBar=s["KBar"], VBar=s["VBar"])


def _attend(window, flash, **rows):
    """``eva_attention`` traced with ``flash_attention`` set so, and the
    paths ``flash_attention`` counted meanwhile."""
    prev = ptpu.config.get_flag("flash_attention")
    before = kernel_path.counts()
    ptpu.config.set_flags(flash_attention=flash)
    try:
        out = _op("eva_attention",
                  {"num_heads": HEADS, "window": window, "chunk": C},
                  **rows)["Out"]
    finally:
        ptpu.config.set_flags(flash_attention=prev)
    return np.asarray(out), _flash_paths(before)


# T in windows; 2.3: a ragged T that the op pads to three windows
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("batch,windows", [
    (1, 1), (1, 2), (1, 3), (1, 2.3), (2, 2), (2, 2.3)])
@pytest.mark.parametrize("window", [128, 256])
def test_the_flash_form_is_the_xla_form(window, batch, windows, dtype):
    """Float32 operands: equal to float32 rounding. Bfloat16 ones: as close
    to the float32 result on the same operands as the XLA form is (the
    flash form rounds ``p`` before the normaliser, the XLA form after)."""
    t = int(window * windows)
    rows = _rows(window + t + batch, batch, t, dtype)
    got, paths = _attend(window, True, **rows)
    assert paths == {"interpret": 1}
    want, paths = _attend(window, False, **rows)
    assert paths == {}
    assert got.dtype == np.float32 and got.shape == (batch, t, HEADS * HD)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-6)
        return
    exact, _ = _attend(window, False, **{
        slot: x.astype(jnp.float32) for slot, x in rows.items()})
    ours, theirs = np.abs(got - exact).max(), np.abs(want - exact).max()
    assert 0 < ours < 1.25 * theirs < 1e-2, (ours, theirs)


@pytest.mark.parametrize("window", [32, 64])
def test_a_window_the_kernel_cannot_tile_takes_the_xla_form(window):
    rows = _rows(window, 1, 3 * window, jnp.float32)
    got, paths = _attend(window, True, **rows)
    assert paths == {"xla": 1}          # armed, not taken: no kernel ran
    want, _ = _attend(window, False, **rows)
    np.testing.assert_array_equal(got, want)


def test_with_flash_attention_off_nothing_calls_the_kernel(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the kernel was called")
    monkeypatch.setattr(pa, "flash_attention_stats", refuse)
    monkeypatch.setattr(pa, "_forward", refuse)
    rows = _rows(3, 1, 256, jnp.bfloat16)
    _, paths = _attend(128, False, **rows)
    assert paths == {}
    with pytest.raises(AssertionError, match="the kernel was called"):
        _attend(128, True, **rows)


def test_a_call_counts_one_flash_attention():
    rows = _rows(4, 1, 384, jnp.bfloat16)       # three windows, one call
    for calls in (1, 2, 3):
        before = kernel_path.counts()
        for _ in range(calls):
            _attend(128, True, **rows)
        assert _flash_paths(before) == {"interpret": calls}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_forward_with_its_statistics(dtype):
    """``flash_attention_stats``: the float32 result, not rounded to the
    operands' dtype, and every row's log-sum-exp; None for a length of no
    whole lane tiles."""
    rs = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.standard_normal((3, 256, HD)), dtype)
               for _ in range(3))
    o, lse = pa.flash_attention_stats(q, k, v, causal=True)
    assert o.dtype == lse.dtype == jnp.float32
    assert o.shape == (3, 256, HD) and lse.shape == (3, 1, 256)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * HD ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool))[None], s, -jnp.inf)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(lse)[:, 0],
                               jax.nn.logsumexp(s, -1), atol=tol)
    want = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1),
                      v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(o), want, atol=tol)
    if dtype == jnp.bfloat16:           # the sums themselves, unrounded
        assert np.abs(np.asarray(o) - np.asarray(
            o.astype(jnp.bfloat16).astype(jnp.float32))).max() > 0
    for t in (100, 64):
        assert pa.flash_attention_stats(q[:, :t], k[:, :t], v[:, :t],
                                        causal=True) is None


# -- the model: two layers of two heads of 128, windows of 128 rows ---------

V, T, LAYERS = 64, 300, 2               # T: two windows and a part of a third
CFG = dict(
    attention_class="eva", hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, rope_scaling=None, num_chunks=None,
    hidden_size=HEADS * HD, num_attention_heads=HEADS,
    num_key_value_heads=HEADS, intermediate_size=96, window_size=128,
    chunk_size=C, num_hidden_layers=LAYERS, vocab_size=V, num_pred_heads=2,
    rope_theta=100000, rms_norm_eps=1e-5, torch_dtype="float32",
    init_std=0.1, norm_add_unit_offset=True)
SIZES = arch.sizes(CFG)
TOL = 2e-5


@pytest.fixture
def flash_on():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=True)
    yield
    ptpu.config.set_flags(flash_attention=prev)


@pytest.fixture(scope="module")
def model():
    """(scope, whole-sequence program, its logits' name), and the paths
    ``flash_attention`` counted while the program was built."""
    scope = ptpu.Scope()
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = 11
    before = kernel_path.counts()
    with ptpu.scope_guard(scope), ptpu.unique_name.guard(), \
            ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        _, logits = moe_lm(toks, lbls, **SIZES)
        ptpu.Executor().run(startup)
    return (scope, main, logits.name), _flash_paths(before) == {}


def _reference(scope, seq, positions):
    w = ref.gather_weights(scope.find_var, CFG)
    out = ref.all_heads_at(w, jnp.asarray(seq), jnp.asarray(positions), CFG)
    return np.asarray(out).reshape(len(positions), -1)


def test_whole_sequences_through_the_flash_form_agree_with_the_reference(
        model, flash_on):
    """The program's ``eva_attention`` ops take the flash form (one traced
    call a layer; building the program traces none: the op says its
    output's shape) and the logits are the reference's."""
    (scope, main, name), built_untraced = model
    assert built_untraced
    seq = np.random.RandomState(0).randint(2, V, T).astype(np.int64)
    before = kernel_path.counts()
    with ptpu.scope_guard(scope):
        got = np.asarray(ptpu.Executor().run(
            main, feed={"toks": seq[None], "lbls": seq[None]},
            fetch_list=[name])[0])[0]
    assert _flash_paths(before) == {"interpret": LAYERS}
    want = _reference(scope, seq, np.arange(T))
    assert np.abs(got - want).max() < TOL * np.abs(want).max()


def test_a_prefill_through_the_flash_form_and_decode_agree_with_the_reference(
        model, flash_on):
    """A prompt of 250 in a bucket of 256 (two windows of the kernel's
    batch), then 8 steps across the window's edge at 256, over the two
    pools the prefill wrote."""
    (scope, _, _), _ = model
    spec = moe_lm_session(slots=2, cache_len=384, prompt_buckets=(256,),
                          block_size=C, num_blocks=2 * 128 // C,
                          chunk_num_blocks=2 * 2, **SIZES)
    sess = GenerationSession(spec, scope=scope)
    seq = np.random.RandomState(1).randint(2, V, 250).astype(np.int64)
    before = kernel_path.counts()
    slot, first = sess.admit(seq)
    assert _flash_paths(before) == {"interpret": LAYERS}
    name = bench_lm.logits_var(spec.decode_program, spec.decode_fetch)
    toks, rows = [first], []
    for _ in range(8):
        prepared = sess.step_prepare()
        rows.append(np.asarray(sess.exe.run(
            spec.decode_program, feed=prepared[2], fetch_list=[name],
            scope=sess.scope)[0])[slot])
        toks.append(sess.step_run(prepared)[slot])
    sess.retire(slot)
    sess.close()
    full = np.concatenate([seq, toks])
    want = _reference(scope, full, np.arange(249, 258))[:, :V]
    assert toks[0] == int(np.argmax(want[0]))
    assert np.abs(np.stack(rows) - want[1:]).max() < TOL * np.abs(want).max()
