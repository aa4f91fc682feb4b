"""Executor semantics: scope persistence, jit-cache reuse, rng state
threading, fetch, program isolation (reference test_executor /
framework tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.framework import RNG_STATE_VAR


def test_persistable_state_survives_runs():
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        counter = main.global_block().create_var(
            name="counter", shape=[1], dtype="float32", persistable=True,
            stop_gradient=True)
        svar = startup.global_block().create_var(
            name="counter", shape=[1], dtype="float32", persistable=True)
        ptpu.initializer.Constant(0.0)(svar, startup.global_block())
        main.global_block().append_op(
            "increment", inputs={"X": ["counter"]},
            outputs={"Out": ["counter"]}, attrs={"step": 1.0},
            infer_shape=False)
    exe = ptpu.Executor()
    exe.run(startup)
    for i in range(5):
        exe.run(main)
    val = np.asarray(ptpu.global_scope().find_var("counter"))
    np.testing.assert_allclose(val, [5.0])


def test_rng_state_advances():
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        d = layers.data("x", shape=[100])
        out = layers.dropout(d, dropout_prob=0.5)
    exe = ptpu.Executor()
    x = np.ones((1, 100), dtype="float32")
    a, = exe.run(main, feed={"x": x}, fetch_list=[out])
    b, = exe.run(main, feed={"x": x}, fetch_list=[out])
    assert not np.array_equal(a, b), "dropout masks must differ across runs"
    assert ptpu.global_scope().has_var(RNG_STATE_VAR)


def test_rng_seed_reproducible():
    def run_once():
        main, startup = ptpu.Program(), ptpu.Program()
        main.random_seed = 42
        with ptpu.program_guard(main, startup):
            d = layers.data("x", shape=[50])
            out = layers.dropout(d, dropout_prob=0.5)
        with ptpu.scope_guard(ptpu.Scope()):
            exe = ptpu.Executor()
            a, = exe.run(main, feed={"x": np.ones((1, 50), "float32")},
                         fetch_list=[out])
        return a
    np.testing.assert_array_equal(run_once(), run_once())


def test_fetch_multiple_and_feed_shapes_respecialize():
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.scale(x, scale=2.0)
        z = layers.scale(y, scale=3.0)
    exe = ptpu.Executor()
    for bs in (2, 8, 3):
        xv = np.ones((bs, 4), dtype="float32")
        yv, zv = exe.run(main, feed={"x": xv}, fetch_list=[y, z])
        assert yv.shape == (bs, 4)
        np.testing.assert_allclose(zv, 6 * xv)


def test_two_programs_share_scope_params():
    """Train program and test program (is_test views) share parameters via
    the scope — the reference's train/test program pattern."""
    main, startup = ptpu.Program(), ptpu.Program()
    test_prog = ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        h = layers.fc(x, 3, param_attr=ptpu.ParamAttr(name="w"),
                      bias_attr=False)
    with ptpu.program_guard(test_prog, startup):
        x2 = layers.data("x", shape=[4])
        h2 = layers.fc(x2, 3, param_attr=ptpu.ParamAttr(name="w"),
                       bias_attr=False)
    exe = ptpu.Executor()
    exe.run(startup)
    xv = np.random.RandomState(0).randn(2, 4).astype("float32")
    a, = exe.run(main, feed={"x": xv}, fetch_list=[h])
    b, = exe.run(test_prog, feed={"x": xv}, fetch_list=[h2])
    np.testing.assert_allclose(a, b, rtol=1e-6)


@pytest.fixture
def check_nan_inf():
    ptpu.config.set_flags(check_nan_inf=True)
    yield
    ptpu.config.set_flags(check_nan_inf=False)


def test_nan_guard_raises_with_offending_op_key(check_nan_inf):
    """FLAGS_check_nan_inf parity (reference framework/executor.cc:
    120-128): a non-finite op output fails the step with the
    ``op#i:type:var`` key of the producer."""
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.log(x)       # log(-1) -> NaN
        z = layers.scale(y, scale=2.0)
    exe = ptpu.Executor()
    with pytest.raises(FloatingPointError) as ei:
        exe.run(main, feed={"x": -np.ones((2, 4), "float32")},
                fetch_list=[z])
    msg = str(ei.value)
    assert "NaN/Inf detected" in msg
    assert ":log:" in msg and "op#" in msg
    assert y.name in msg
    # downstream consumers of the NaN are flagged too (per-op scan)
    assert ":scale:" in msg


def test_nan_guard_passes_finite_program(check_nan_inf):
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.log(x)
    exe = ptpu.Executor()
    out, = exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                   fetch_list=[y])
    np.testing.assert_allclose(out, 0.0, atol=1e-6)


def test_nan_guard_off_lets_nan_through():
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.log(x)
    exe = ptpu.Executor()
    out, = exe.run(main, feed={"x": -np.ones((2, 4), "float32")},
                   fetch_list=[y])
    assert np.isnan(out).all()


def test_uninitialized_param_raises():
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        h = layers.fc(x, 3)
    exe = ptpu.Executor()
    try:
        exe.run(main, feed={"x": np.ones((1, 4), "float32")},
                fetch_list=[h])
    except RuntimeError as e:
        assert "startup" in str(e)
    else:
        raise AssertionError("expected RuntimeError for missing init")


# -- the optimizer update's gradient is fenced from the op that made it ----

def _fenced():
    """{op type: updates traced with their gradient fenced} so far in this
    process (a worker runs several test files: take deltas)."""
    return {labels[0]: child.value for labels, child in
            executor_mod._FENCED_UPDATES.children().items()}


def _two_layer_step(opt):
    """x -> fc(8) -> fc(3) -> mean square: two weight matrices (rank 2)
    and two biases (rank 1) under ``opt``."""
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.unique_name.guard(), ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.fc(layers.fc(x, 8, act="tanh"), 3)
        loss = layers.reduce_mean(layers.square(y))
        opt.minimize(loss, startup_program=startup)
    return main, startup, loss


DENSE_OPTIMIZERS = [
    ("sgd", lambda: ptpu.optimizer.SGD(learning_rate=0.3)),
    ("momentum", lambda: ptpu.optimizer.Momentum(learning_rate=0.1)),
    ("adagrad", lambda: ptpu.optimizer.Adagrad(learning_rate=0.5)),
    ("adam", lambda: ptpu.optimizer.Adam(learning_rate=0.1)),
    ("adamax", lambda: ptpu.optimizer.Adamax(learning_rate=0.1)),
    ("decayed_adagrad",
     lambda: ptpu.optimizer.DecayedAdagrad(learning_rate=0.5)),
    ("adadelta", lambda: ptpu.optimizer.AdaDelta(learning_rate=1.0)),
    ("rmsprop", lambda: ptpu.optimizer.RMSProp(learning_rate=0.05)),
    ("ftrl", lambda: ptpu.optimizer.Ftrl(learning_rate=0.5)),
]


@pytest.mark.parametrize("op_type,make", DENSE_OPTIMIZERS,
                         ids=[n for n, _ in DENSE_OPTIMIZERS])
def test_update_is_traced_with_its_gradient_fenced(op_type, make,
                                                   monkeypatch):
    """Every dense optimizer op (told by its Param / Grad / ParamOut
    slots): the traced step holds one ``optimization_barrier`` per weight
    matrix and none for a bias, the counter moves by as many, and the
    step's parameters and accumulators equal, bit for bit, those of the
    same step traced with the fence patched out."""
    main, startup, loss = _two_layer_step(make())
    updates = [op for op in main.global_block().ops if op.type == op_type]
    assert len(updates) == 4
    scope = ptpu.Scope()
    ptpu.Executor().run(startup, scope=scope)
    start = {n: np.asarray(scope.find_var(n)) for n in scope.var_names()}
    feed = {"x": np.random.RandomState(0).randn(5, 4).astype("float32")}

    fn, args = ptpu.Executor().as_jax_function(main, feed, [loss],
                                               scope=scope)
    before = _fenced().get(op_type, 0)
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    assert jaxpr.count("optimization_barrier") == 2
    assert _fenced()[op_type] - before == 2

    def one_step():
        sc = ptpu.Scope()
        for n, v in start.items():
            sc.set_var(n, jnp.asarray(v))
        out, = ptpu.Executor().run(main, feed=feed, fetch_list=[loss],
                                   scope=sc)
        return out, {n: np.asarray(sc.find_var(n)) for n in sc.var_names()}

    loss_fenced, fenced = one_step()
    monkeypatch.setattr(executor_mod, "_fence_update_grad",
                        lambda op, values: None)
    before = _fenced()
    loss_plain, plain = one_step()
    assert _fenced() == before
    assert np.array_equal(loss_fenced, loss_plain)
    written = {n for op in updates for n in op.output_names()}
    assert written <= set(fenced) and fenced.keys() == plain.keys()
    for n in fenced:
        assert np.array_equal(fenced[n], plain[n]), n
    assert any(not np.array_equal(fenced[n], start[n]) for n in written)


def test_a_sparse_rows_update_is_not_fenced():
    """A ``Rows`` gradient goes through a merge and a scatter, not a
    matmul's epilogue: its update is traced as it was."""
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.unique_name.guard(), ptpu.program_guard(main, startup):
        ids = layers.data("ids", shape=[1], dtype="int64")
        emb = layers.embedding(ids, size=[11, 6], is_sparse=True)
        loss = layers.reduce_mean(layers.square(emb))
        ptpu.optimizer.Adam(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    assert any("Rows" in op.inputs for op in main.global_block().ops
               if op.type == "adam")
    scope = ptpu.Scope()
    ptpu.Executor().run(startup, scope=scope)
    fn, args = ptpu.Executor().as_jax_function(
        main, {"ids": np.array([[1], [3], [3]], "int64")}, [loss],
        scope=scope)
    before = _fenced()
    assert "optimization_barrier" not in str(jax.make_jaxpr(fn)(*args))
    assert _fenced() == before


def test_a_decode_program_holds_no_fence():
    """The causal LM's Adam step fences every weight matrix; its decode
    step, a program with no optimizer op, holds no barrier and moves no
    counter."""
    from paddle_tpu.models.transformer import (transformer_lm,
                                               transformer_lm_session)
    from paddle_tpu.serving import GenerationSession
    sizes = dict(d_model=16, num_heads=2, d_ff=32, num_layers=2)
    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[12], dtype="int64")
            lbls = layers.data("lbls", shape=[12], dtype="int64")
            loss, _ = transformer_lm(toks, lbls, vocab_size=29, **sizes)
            ptpu.optimizer.Adam(learning_rate=1e-3).minimize(
                loss, startup_program=startup)
    with ptpu.unique_name.guard():
        spec = transformer_lm_session(29, max_len=12, slots=3, cache_len=16,
                                      prompt_buckets=(4,), bos_id=0,
                                      eos_id=1, **sizes)
    scope = ptpu.Scope()
    exe = ptpu.Executor()
    exe.run(startup, scope=scope)
    block = main.global_block()
    matrices = [op for op in block.ops if op.type == "adam"
                and len(block.var(op.inputs["Param"][0]).shape) >= 2]
    assert 0 < len(matrices) < sum(op.type == "adam" for op in block.ops)
    ids = np.zeros((2, 12), "int64")
    fn, args = exe.as_jax_function(main, {"toks": ids, "lbls": ids}, [loss],
                                   scope=scope)
    assert str(jax.make_jaxpr(fn)(*args)).count(
        "optimization_barrier") == len(matrices)
    GenerationSession(spec, scope=scope)  # its cache variables
    before = _fenced()
    feed = {"gen.dtok": np.zeros((3, 1), "int64"),
            "gen.dpos": np.zeros((3,), "int32"),
            "gen.dtab": np.zeros((3, spec.max_blocks), "int32")}
    assert set(feed) == set(spec.decode_feeds)
    text = exe.lower(spec.decode_program, feed, [spec.decode_fetch],
                     scope=scope).as_text()
    assert "optimization_barrier" not in text
    assert _fenced() == before


@pytest.mark.parametrize("data,barriers", [(4, 0), (1, 2)],
                         ids=["data4", "data1"])
def test_a_step_sharded_over_a_data_axis_is_not_fenced(data, barriers):
    """Under a strategy whose data axis spans chips the gradient all-reduce
    already stands between the product and the update: the step is traced
    as it was. A mesh with one data shard keeps the fence."""
    from paddle_tpu import parallel
    main, startup, loss = _two_layer_step(
        ptpu.optimizer.Adam(learning_rate=0.1))
    strategy = parallel.DistStrategy(
        parallel.make_mesh({"data": data, "model": 2}))
    scope = ptpu.Scope()
    exe = ptpu.Executor(strategy=strategy)
    exe.run(startup, scope=scope)
    before = _fenced().get("adam", 0)
    text = exe.lower(main, {"x": np.zeros((8, 4), "float32")}, [loss],
                     scope=scope).as_text()
    assert text.count("optimization_barrier") == barriers
    assert _fenced().get("adam", 0) - before == barriers
