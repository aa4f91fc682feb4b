"""SPMD data-parallel tests on the 8-device CPU mesh (SURVEY §2.3: replaces
MultiGradientMachine ring all-reduce / pserver sync / parallel_do)."""

import pytest
import numpy as np

import jax

import paddle_tpu as ptpu
from paddle_tpu import layers, parallel


def _build_mlp():
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[8])
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.fc(x, 16, act="relu",
                      param_attr=ptpu.ParamAttr(name="w1"))
        logits = layers.fc(h, 4, param_attr=ptpu.ParamAttr(name="w2"))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        opt = ptpu.optimizer.SGD(learning_rate=0.1)
        opt.minimize(loss, startup_program=startup)
    return main, startup, loss


def _data(n=64):
    rs = np.random.RandomState(0)
    xv = rs.randn(n, 8).astype("float32")
    yv = (xv[:, 0] > 0).astype("int64").reshape(-1, 1)
    return xv, yv


def test_eight_device_mesh_available():
    assert len(jax.devices()) == 8


def test_data_parallel_matches_single_device():
    xv, yv = _data()

    # single-device reference
    main, startup, loss = _build_mlp()
    exe = ptpu.Executor()
    with ptpu.scope_guard(ptpu.Scope()):
        exe.run(startup)
        single = [float(exe.run(main, feed={"x": xv, "y": yv},
                                fetch_list=[loss])[0]) for _ in range(5)]
        w1_single = np.asarray(ptpu.global_scope().find_var("w1"))

    # 8-way data parallel — same program, same init (seeded), same feeds
    strat = parallel.DataParallel(n_devices=8)
    exe_p = ptpu.Executor(strategy=strat)
    with ptpu.scope_guard(ptpu.Scope()):
        exe_p.run(startup)
        par = [float(exe_p.run(main, feed={"x": xv, "y": yv},
                               fetch_list=[loss])[0]) for _ in range(5)]
        w1_par = np.asarray(ptpu.global_scope().find_var("w1"))

    np.testing.assert_allclose(single, par, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(w1_single, w1_par, rtol=2e-3, atol=2e-5)


def test_data_parallel_feed_is_sharded():
    strat = parallel.DataParallel(n_devices=8)
    xv, _ = _data(16)
    arr = strat.shard_feed("x", xv)
    assert len(arr.sharding.device_set) == 8
    # 16 rows / 8 devices = 2 rows per shard
    shard = list(arr.addressable_shards)[0]
    assert shard.data.shape == (2, 8)


def test_model_parallel_param_rule():
    mesh = parallel.make_mesh({"data": 4, "model": 2})
    strat = parallel.DistStrategy(
        mesh, data_axis="data",
        param_rules=[(r"^w2", parallel.P(None, "model"))])
    main, startup, loss = _build_mlp()
    exe = ptpu.Executor(strategy=strat)
    with ptpu.scope_guard(ptpu.Scope()):
        exe.run(startup)
        xv, yv = _data(32)
        out1 = float(exe.run(main, feed={"x": xv, "y": yv},
                             fetch_list=[loss])[0])
        out2 = float(exe.run(main, feed={"x": xv, "y": yv},
                             fetch_list=[loss])[0])
        assert out2 < out1 * 1.01  # trains under dp+tp sharding

    # same loss as single device on the first step
    exe_s = ptpu.Executor()
    with ptpu.scope_guard(ptpu.Scope()):
        exe_s.run(startup)
        ref = float(exe_s.run(main, feed={"x": xv, "y": yv},
                              fetch_list=[loss])[0])
    np.testing.assert_allclose(out1, ref, rtol=2e-4)


class TestTransformerUnderMesh:
    """The pivot model under SPMD (VERDICT r4 demand 3): dp×tp
    transformer train step == single-device step, Megatron-style tp
    rules actually shard the qkv/out/ffn weights, and the flash kernel
    runs under the mesh via shard_map."""

    B, T, V, D, H = 8, 16, 64, 32, 4

    def _build_lm(self):
        from paddle_tpu.models.transformer import transformer_lm
        main, startup = ptpu.Program(), ptpu.Program()
        main.random_seed = startup.random_seed = 11
        with ptpu.program_guard(main, startup):
            tok = layers.data("tok", shape=[self.T], dtype="int64")
            lbl = layers.data("lbl", shape=[self.T], dtype="int64")
            loss, _ = transformer_lm(tok, lbl, self.V, d_model=self.D,
                                     num_heads=self.H, d_ff=self.D * 2,
                                     num_layers=2)
            ptpu.optimizer.Adam(1e-3).minimize(loss,
                                               startup_program=startup)
        return main, startup, loss

    def _feed(self):
        rs = np.random.RandomState(5)
        tok = rs.randint(2, self.V, (self.B, self.T)).astype("int64")
        lbl = np.roll(tok, -1, axis=1)
        return {"tok": tok, "lbl": lbl}

    def _run_steps(self, strat, flash, n=2):
        ptpu.config.set_flags(flash_attention=flash)
        try:
            with ptpu.scope_guard(ptpu.Scope()), \
                    ptpu.unique_name.guard():
                main, startup, loss = self._build_lm()
                exe = ptpu.Executor(strategy=strat)
                exe.run(startup)
                feed = self._feed()
                losses = [float(exe.run(main, feed=feed,
                                        fetch_list=[loss])[0])
                          for _ in range(n)]
                scope_vars = dict(ptpu.global_scope().items())
                qkv = next(k for k in scope_vars
                           if k.endswith(".qkv_q.w"))
                mom = next((k for k in scope_vars
                            if ".qkv_q.w_moment1" in k), None)
                return losses, (scope_vars[qkv],
                                scope_vars[mom] if mom else None)
        finally:
            ptpu.config.set_flags(flash_attention=False)

    def test_dp_tp_matches_single_device(self):
        from paddle_tpu.models.transformer import transformer_tp_rules
        single, _ = self._run_steps(None, flash=False)
        mesh = parallel.make_mesh({"data": 4, "model": 2})
        strat = parallel.DistStrategy(
            mesh, data_axis="data",
            param_rules=transformer_tp_rules("model"))
        sharded, (wq, mom) = self._run_steps(strat, flash=False)
        np.testing.assert_allclose(single, sharded, rtol=2e-3,
                                   atol=2e-4)
        # the qkv weight is really column-sharded over 'model', and
        # its Adam moment INHERITS the sharding (unanchored rules)
        assert np.asarray(wq).shape == (self.D, self.D)
        assert wq.addressable_shards[0].data.shape == (self.D,
                                                       self.D // 2)
        assert mom is not None
        assert mom.addressable_shards[0].data.shape == (self.D,
                                                        self.D // 2)

    def test_flash_under_mesh_matches_dense(self):
        """flash_attention=True under dp×tp runs the Pallas kernel
        per-shard (shard_map; interpret mode on CPU) and reproduces
        the dense path."""
        from paddle_tpu.models.transformer import transformer_tp_rules
        mesh = parallel.make_mesh({"data": 4, "model": 2})
        strat = parallel.DistStrategy(
            mesh, data_axis="data",
            param_rules=transformer_tp_rules("model"))
        dense, _ = self._run_steps(strat, flash=False)
        flash, _ = self._run_steps(strat, flash=True)
        np.testing.assert_allclose(dense, flash, rtol=5e-3, atol=5e-4)

    def test_flash_segment_mask_under_mesh(self):
        """Packed-segment/padding masks ride the kernel under SPMD:
        attention with KeyLength on a sharded batch == unsharded."""
        ptpu.config.set_flags(flash_attention=True)
        try:
            def run(strat):
                with ptpu.scope_guard(ptpu.Scope()), \
                        ptpu.unique_name.guard():
                    main, startup = ptpu.Program(), ptpu.Program()
                    main.random_seed = startup.random_seed = 3
                    with ptpu.program_guard(main, startup):
                        x = layers.data("x", shape=[16, 32])
                        ln = layers.data("len", shape=[],
                                         dtype="int64")
                        from paddle_tpu.layers.attention import \
                            multi_head_attention
                        out = layers.mean(multi_head_attention(
                            x, x, x, 32, 4, causal=True,
                            key_length=ln))
                    exe = ptpu.Executor(strategy=strat)
                    exe.run(startup)
                    rs = np.random.RandomState(2)
                    feed = {"x": rs.randn(8, 16, 32).astype("float32"),
                            "len": np.array([16, 12, 8, 4] * 2,
                                            "int64")}
                    return np.asarray(exe.run(main, feed=feed,
                                              fetch_list=[out])[0])
            ref = run(None)
            got = run(parallel.DataParallel(n_devices=8))
            np.testing.assert_allclose(ref, got, rtol=2e-4, atol=1e-5)
        finally:
            ptpu.config.set_flags(flash_attention=False)


class TestRingAttentionUnderMesh:
    """Ring (sequence-parallel) attention on the shared dp×tp mesh:
    T sharded over an axis, forward AND gradients match dense."""

    def _qkv(self, b=2, t=16, h=2, d=8, seed=0):
        rs = np.random.RandomState(seed)
        return [rs.randn(b, t, h, d).astype("float32") * 0.5
                for _ in range(3)]

    def test_forward_matches_dense_on_4dev_axis(self):
        q, k, v = self._qkv()
        mesh = parallel.make_mesh({"data": 4, "model": 2})
        for causal in (False, True):
            ref = parallel.dense_attention(q, k, v, causal=causal)
            out = parallel.ring_attention(q, k, v, mesh,
                                          axis_name="data",
                                          causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5)

    def test_grads_match_dense(self):
        q, k, v = self._qkv(seed=4)
        mesh = parallel.make_mesh({"data": 4, "model": 2})

        def loss_ring(q, k, v):
            o = parallel.ring_attention(q, k, v, mesh,
                                        axis_name="data", causal=True)
            return (o * o).sum()

        def loss_dense(q, k, v):
            o = parallel.dense_attention(q, k, v, causal=True)
            return (o * o).sum()

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)

    def test_sharded_inputs_stay_sharded(self):
        """Feeding T-sharded device arrays: output keeps the T
        sharding (no gather to host-size arrays mid-graph)."""
        from jax.sharding import NamedSharding
        q, k, v = self._qkv(t=32, seed=7)
        mesh = parallel.make_mesh({"data": 4, "model": 2})
        spec = parallel.P(None, "data", None, None)
        sh = NamedSharding(mesh, spec)
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        out = parallel.ring_attention(qs, ks, vs, mesh,
                                      axis_name="data", causal=True)
        # jax versions differ on whether trailing Nones are kept in the
        # spec repr; compare sharding equivalence, not spec identity
        assert out.sharding.is_equivalent_to(sh, out.ndim), out.sharding
        ref = parallel.dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_batch_norm_stats_are_global():
    """Cross-replica BN: sharded batch must produce identical running stats
    to single-device (SPMD global-view semantics = synced BN)."""
    def build():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            x = layers.data("x", shape=[3, 4, 4])
            bn = layers.batch_norm(x, name="bn0")
            loss = layers.mean(bn)
        return main, startup, loss

    rs = np.random.RandomState(1)
    xv = rs.randn(16, 3, 4, 4).astype("float32")

    main, startup, loss = build()
    exe = ptpu.Executor()
    with ptpu.scope_guard(ptpu.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": xv})
        mean_single = np.asarray(
            ptpu.global_scope().find_var("batch_norm_0.mean")
            if ptpu.global_scope().has_var("batch_norm_0.mean") else
            next(v for k, v in ptpu.global_scope().items()
                 if k.endswith(".mean")))

    exe_p = ptpu.Executor(strategy=parallel.DataParallel(n_devices=8))
    with ptpu.scope_guard(ptpu.Scope()):
        exe_p.run(startup)
        exe_p.run(main, feed={"x": xv})
        mean_par = np.asarray(
            next(v for k, v in ptpu.global_scope().items()
                 if k.endswith(".mean")))
    np.testing.assert_allclose(mean_single, mean_par, rtol=1e-4,
                               atol=1e-6)


# -- PR 38: the strategy says how its step is compiled ---------------------

def test_compiler_options_are_empty_off_the_tpu_and_off_the_data_axis():
    """``compiler_options()`` answers by what it can see: nothing for a
    data axis of 1, for no data axis, beside a model axis, and on CPU
    devices whatever the axes (an option the CPU compiler does not know
    is an error there)."""
    from types import SimpleNamespace
    from jax.sharding import PartitionSpec as P
    assert parallel.DataParallel(n_devices=1).compiler_options() == {}
    assert parallel.DataParallel(n_devices=4).compiler_options() == {}
    model_only = parallel.DistStrategy(
        parallel.make_mesh({"model": 4}), param_rules=[("w", P(None, "model"))])
    assert model_only.data_shards() == 1
    assert model_only.compiler_options() == {}

    def on_tpus(axes):
        s = parallel.DistStrategy(parallel.make_mesh(axes))
        s.mesh = SimpleNamespace(
            axis_names=s.mesh.axis_names,
            devices=np.full(s.mesh.devices.shape,
                            SimpleNamespace(platform="tpu"), dtype=object))
        return s
    assert on_tpus({"data": 4}).compiler_options() == \
        parallel._OVERLAPPED_ALL_REDUCE
    # a model axis beside the data axis: compiled as ever (PERF.md, PR 38)
    assert on_tpus({"data": 2, "model": 2}).compiler_options() == {}
    assert on_tpus({"data": 1, "model": 4}).compiler_options() == {}
    assert on_tpus({"model": 4}).compiler_options() == {}
    # a caller's copy: editing it does not edit the next step's
    on_tpus({"data": 4}).compiler_options().clear()
    assert parallel._OVERLAPPED_ALL_REDUCE


def _gauge(role):
    from paddle_tpu.core import executor
    return executor._ASYNC_COLLECTIVES.labels(role=role).value


def test_executor_hands_jit_the_strategys_options_and_no_other(monkeypatch):
    """With no strategy, or one with nothing to say (CPU devices), the
    executor's ``jit`` call has no ``compiler_options`` and the step is not
    compiled ahead of time; with options (here one the CPU compiler knows)
    a fed step is compiled under them, ahead of time, its module's text
    read once for the gauge, and a step that is fed nothing (startup) is
    compiled as ever. The results are the same step's."""
    calls = []
    real_jit = jax.jit

    def spy(fn, **kwargs):
        calls.append((fn.__name__, kwargs.get("compiler_options")))
        return real_jit(fn, **kwargs)
    monkeypatch.setattr(jax, "jit", spy)
    xv, yv = _data()
    main, startup, loss = _build_mlp()
    main.name = "pr38_step"

    def losses(strategy):
        exe = ptpu.Executor(strategy=strategy)
        with ptpu.scope_guard(ptpu.Scope()):
            exe.run(startup)
            out = [float(exe.run(main, feed={"x": xv, "y": yv},
                                 fetch_list=[loss])[0]) for _ in range(3)]
        return out, {e.role: e for e in exe._cache.values()}

    plain, entries = losses(parallel.DataParallel(n_devices=4))
    assert [c for c in calls if c[1] is not None] == []
    assert entries["pr38_step"].options == {}
    assert entries["pr38_step"].aot is None
    assert _gauge("pr38_step") == 0     # no text was read

    del calls[:]
    known = parallel.DataParallel(n_devices=4)
    known.compiler_options = lambda: {"xla_embed_ir_in_executable": False}
    under, entries = losses(known)
    assert ("pr38_step", {"xla_embed_ir_in_executable": False}) in calls
    assert [c for c in calls if c[0] != "pr38_step" and c[1]] == []
    step = entries["pr38_step"]
    assert step.options and step.aot is not None and not step.aot_failed
    assert not entries[startup.name or "program"].options
    assert under == plain
    # the CPU's module holds its all-reduce in no asynchronous form
    assert _gauge("pr38_step") == 0


def test_a_step_that_does_not_compile_under_its_options_raises():
    """A failed compile under a strategy's options is the caller's to see:
    the jit call path would compile the same module under the same
    options, so there is nothing to fall back to (and no text to read)."""
    xv, yv = _data()
    main, startup, loss = _build_mlp()
    strategy = parallel.DataParallel(n_devices=4)
    strategy.compiler_options = lambda: {"xla_no_such_option_pr38": True}
    exe = ptpu.Executor(strategy=strategy)
    with ptpu.scope_guard(ptpu.Scope()):
        exe.run(startup)        # fed nothing: compiled with no option
        with pytest.raises(Exception, match="xla_no_such_option_pr38"):
            exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[loss])
    (step,) = [e for e in exe._cache.values() if e.options]
    assert step.aot is None and not step.aot_failed


def test_async_collectives_counts_pairs_not_merged_back():
    """The TPU's asynchronous form alone: a pair merged back into a plain
    all-reduce and the generic ``async-start`` (prefetches, slices) are
    not counted."""
    text = """
ENTRY %main {
  %async-collective-start.3 = (bf16[8,8]{1,0}, u32[]) fusion(%p), kind=kCustom, calls=%f.1
  %fusion.9 = bf16[8,8]{1,0} fusion(%q), kind=kOutput, calls=%f.2
  %async-collective-done.3 = bf16[8,8]{1,0} fusion(%g), kind=kCustom, calls=%f.3
  %all-reduce.7 = f32[8]{0} all-reduce(%r), channel_id=2, frontend_attributes={async_collective_name="all-reduce-start.1"}
  %slice-start.4 = ((f32[8,8]{1,0}), f32[2,8]{1,0}, s32[]) async-start(%u), calls=%s.1
  ROOT %async-collective-start = (f32[2]{0}, u32[]) fusion(%t), kind=kCustom, calls=%f.4
}"""
    assert parallel.async_collectives(text) == 2
    assert parallel.async_collectives("ENTRY %m {\n  %a = f32[] add(%x, %y)\n}") == 0
