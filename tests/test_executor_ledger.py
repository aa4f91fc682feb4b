"""Set-up gets owners (PR 36): a compiled step goes by its program's role
(the HLO module is ``jit_<role>``), the compile ledger books every second
of JAX's compile-side work to a role and a stage, and the executor's spans
have their counters. Everything here runs on the CPU: none of its times is
a device number."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.monitoring

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.serving.decoding.policy import DecodePolicy
from paddle_tpu.models.transformer import (transformer_lm,
                                           transformer_lm_session)
from paddle_tpu.observability import compile_ledger, metrics, tracing
from paddle_tpu.serving import GenerationSession

pytestmark = [pytest.mark.generation, pytest.mark.paged]

V, MAXLEN = 29, 24
KW = dict(d_model=16, num_heads=2, d_ff=32, num_layers=2)
TRACE, LOWER, BACKEND = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")


@pytest.fixture(autouse=True)
def _no_flash():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


def _counters(prefix="paddle_"):
    """{name{k=v,...}: value} of the registry's counters and gauges."""
    out = {}
    for name, kind, _, _, children in metrics.REGISTRY.snapshot():
        if kind != "histogram" and name.startswith(prefix):
            for labels, payload in children:
                out[name + "".join("{%s=%s}" % kv
                                   for kv in sorted(labels.items()))] = \
                    float(payload)
    return out


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def _ledger(delta, family="paddle_compile_seconds_total", **labels):
    """Sum of a delta's children of ``family`` that hold ``labels``."""
    return sum(v for k, v in delta.items()
               if k.startswith(family + "{")
               and all("{%s=%s}" % kv in k for kv in labels.items()))


class _Listener:
    """An independent reading of jax.monitoring, process-wide as the
    ledger is: the raw spans of the three compile-side events, each with
    its thread, and the cache's hits."""

    def __init__(self):
        self.spans, self.hits = [], 0

    def _on_span(self, event, start, end, **kw):
        if event in (TRACE, LOWER, BACKEND):
            self.spans.append((event, start, end, kw.get("fun_name"),
                               threading.get_ident()))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self._on_span)
        jax.monitoring.unregister_event_listener(self._on_event)

    def seconds(self, event):
        return sum(e - s for ev, s, e, *_ in self.spans if ev == event)

    def top_level_seconds(self):
        """Seconds of the spans that lie inside no other of their thread:
        what the process spent compile-side, no second twice."""
        return sum(e - s for i, (_, s, e, _, tid) in enumerate(self.spans)
                   if not any(s2 <= s and e <= e2 and j != i
                              and tid2 == tid and (s2, e2) != (s, e)
                              for j, (_, s2, e2, _, tid2) in
                              enumerate(self.spans)))


def _lm_scope():
    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[1, MAXLEN], dtype="int64",
                               append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, MAXLEN], dtype="int64",
                               append_batch_size=False)
            transformer_lm(toks, lbls, vocab_size=V, is_test=True, **KW)
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope):
        ptpu.Executor().run(startup)
    return scope, startup


def _session(scope, **kw):
    spec = transformer_lm_session(
        V, max_len=MAXLEN, slots=3, cache_len=MAXLEN,
        prompt_buckets=(4, 8), bos_id=0, eos_id=1, paged=True,
        block_size=4, num_blocks=24, prefix_cache=False, **KW, **kw)
    return GenerationSession(spec, scope=scope)


def _train_program():
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        pred = layers.fc(layers.fc(x, 16, act="tanh"), 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        ptpu.optimizer.Adam(1e-3).minimize(loss, startup_program=startup)
    feed = {"x": np.ones((4, 8), np.float32),
            "y": np.ones((4, 1), np.float32)}
    return main, startup, loss, feed


@pytest.fixture(scope="module")
def served():
    """A tiny paged session's whole first life (both prefill buckets and
    the decode step compiled and run), read three ways: the registry's
    delta, an independent listener's raw spans, the executor's entries.
    The programs are built before the first reading: shape inference is
    booked apart (``test_shape_inference_is_booked_where_it_happens``)."""
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    scope, startup = _lm_scope()
    sess = _session(scope)
    c0 = _counters()
    with _Listener() as heard:
        for n in (3, 7):
            sess.generate(list(range(2, 2 + n)), max_new_tokens=3)
        # something compiled outside any first call, on this thread
        jax.jit(lambda a: jax.lax.add(a, a))(np.ones(3, np.float32))
    first = _delta(_counters(), c0)
    c1 = _counters()
    sess.generate(list(range(2, 5)), max_new_tokens=3)
    second = _delta(_counters(), c1)
    yield {"sess": sess, "startup": startup, "first": first,
           "second": second, "heard": heard}
    sess.close()
    ptpu.config.set_flags(flash_attention=prev)


# -- a compiled step has a name: its role -------------------------------------

def _compiled_module_name(exe, program, scope):
    """The name of the HLO module the executor's entry for ``program``
    compiles to: the entry's own feed signature, zeros fed."""
    key = next(k for k in exe._cache if k[0] == program._uid)
    feed = {name: np.zeros(shape, dtype) for name, shape, dtype in key[2]}
    text = exe.lower(program, feed=feed, fetch_list=list(key[3]),
                     scope=scope).compile().as_text()
    return text.split(None, 2)[1].rstrip(",")


@pytest.mark.parametrize("role", ["prefill_4", "prefill_8", "decode"])
def test_session_programs_compile_to_modules_named_by_role(served, role):
    sess = served["sess"]
    program = sess.spec.decode_program if role == "decode" else \
        sess.spec.prefill_programs[int(role.split("_")[1])]
    assert program.name == role
    assert _compiled_module_name(sess.exe, program, sess.scope) == \
        "jit_" + role
    entry = next(e for k, e in sess.exe._cache.items()
                 if k[0] == program._uid)
    assert entry.role == role and entry.fn.__name__ == role


def test_startup_and_training_programs_compile_to_modules_named_by_role():
    main, startup, loss, feed = _train_program()
    assert (main.name, startup.name) == ("train", "startup")
    exe = ptpu.Executor()
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    scope = ptpu.global_scope()
    assert _compiled_module_name(exe, startup, scope) == "jit_startup"
    assert _compiled_module_name(exe, main, scope) == "jit_train"


def test_a_program_nobody_named_goes_by_the_default_role():
    main = ptpu.Program()
    with ptpu.program_guard(main, ptpu.Program()):
        y = layers.scale(layers.data("x", shape=[4]), scale=2.0)
    assert main.name is None
    c0 = _counters("paddle_executor_runs_total")
    exe = ptpu.Executor()
    exe.run(main, feed={"x": np.ones((2, 4), "float32")}, fetch_list=[y])
    assert _delta(_counters("paddle_executor_runs_total"), c0) == {
        "paddle_executor_runs_total{role=program}": 1.0}
    assert _compiled_module_name(exe, main, ptpu.global_scope()) == \
        "jit_" + executor_mod.DEFAULT_ROLE


def test_a_named_program_keeps_its_name():
    main = ptpu.Program(name="score")
    with ptpu.program_guard(main, ptpu.Program(name="weights")) as _:
        x = layers.data("x", shape=[4])
        loss = layers.mean(layers.fc(x, 1))
        ptpu.optimizer.SGD(0.1).minimize(loss)
    assert main.name == "score"
    assert ptpu.default_startup_program().name == "startup"


def test_the_copy_verify_and_draft_programs_go_by_their_roles():
    spec = transformer_lm_session(
        V, max_len=MAXLEN, slots=2, cache_len=MAXLEN, prompt_buckets=(4,),
        bos_id=0, eos_id=1, paged=True, block_size=4, num_blocks=16,
        prefix_cache=False,
        decode_policy=DecodePolicy(kind="greedy", speculate_k=2), **KW)
    assert spec.copy_program.name == "copy"
    assert spec.verify_program.name == "verify"
    draft = spec.draft_spec
    assert draft.decode_program.name == "draft_decode"
    assert [p.name for p in draft.prefill_programs.values()] == [
        "draft_prefill_4"]
    assert draft.copy_program.name == "draft_copy"
    assert spec.decode_program.name == "decode"


def test_two_entries_of_one_program_keep_one_role(served):
    """The check fetches the logits beside the token: a second entry of
    the decode program, the same role, another key."""
    sess = served["sess"]
    spec = sess.spec
    block = spec.decode_program.global_block()
    extra = next(n for op in reversed(block.ops) for n in op.input_names()
                 if n != spec.decode_fetch and block.var_or_none(n)
                 is not None and not block.var(n).persistable)
    key = next(k for k in sess.exe._cache if k[0] ==
               spec.decode_program._uid)
    feed = {name: np.zeros(shape, dtype) for name, shape, dtype in key[2]}
    c0 = _counters()
    sess.exe.run(spec.decode_program, feed=feed,
                 fetch_list=[extra, spec.decode_fetch], scope=sess.scope)
    got = _delta(_counters(), c0)
    entries = [e for k, e in sess.exe._cache.items()
               if k[0] == spec.decode_program._uid]
    assert len(entries) == 2
    assert {e.role for e in entries} == {"decode"}
    assert len({e.key_id for e in entries}) == 2
    assert got["paddle_executor_first_calls_total{role=decode}"] == 1.0
    assert _ledger(got, role="decode", stage="lower") > 0


# -- the ledger ---------------------------------------------------------------

def test_first_calls_book_their_compiles_to_their_roles(served):
    first = served["first"]
    for role in ("prefill_4", "prefill_8", "decode"):
        for stage in ("trace", "lower"):
            assert _ledger(first, role=role, stage=stage) > 0, (role, stage)
        # compiled, or read from JAX's cache where a run has one armed
        assert _ledger(first, role=role, stage="compile") + \
            _ledger(first, role=role, stage="cache_read") > 0
        assert _ledger(first, "paddle_compile_events_total", role=role,
                       stage="lower") == 1.0
        assert first["paddle_executor_first_calls_total{role=%s}" % role] \
            == 1.0
    assert set(k.split("stage=")[1].rstrip("}") for k in first
               if k.startswith("paddle_compile_seconds_total")) <= \
        set(compile_ledger.STAGES)


def test_ledger_totals_equal_an_independent_listeners(served):
    """Over all roles: each stage's events are the listener's spans; the
    seconds of lowering and of the backend (spans that hold no other) are
    the listener's sums; and all the ledger's seconds are the seconds of
    the listener's top-level spans: nothing twice, nothing dropped."""
    first, heard = served["first"], served["heard"]
    events = "paddle_compile_events_total"
    n = {ev: sum(1 for e, *_ in heard.spans if e == ev)
         for ev in (TRACE, LOWER, BACKEND)}
    assert n[TRACE] > n[LOWER] >= 4         # jits nest inside a step's trace
    assert _ledger(first, events, stage="trace") == n[TRACE]
    assert _ledger(first, events, stage="lower") == n[LOWER]
    assert _ledger(first, events, stage="compile") + \
        _ledger(first, events, stage="cache_read") == n[BACKEND]
    assert _ledger(first, events, stage="cache_read") == heard.hits
    assert _ledger(first, stage="compile") + \
        _ledger(first, stage="cache_read") == \
        pytest.approx(heard.seconds(BACKEND), rel=1e-6)
    # a sum over the trace events would count the nested ones twice
    assert _ledger(first, stage="trace") < heard.seconds(TRACE)
    assert _ledger(first) == pytest.approx(heard.top_level_seconds(),
                                           rel=1e-6)
    # the steps' own trace events name their roles themselves
    assert {"prefill_4", "prefill_8", "decode"} <= {
        name for ev, _, _, name, _ in heard.spans if ev == TRACE}


def test_a_bare_jit_outside_the_executor_lands_under_other(served):
    c0 = _counters("paddle_compile_")
    jax.jit(lambda a: jax.lax.mul(a, a))(np.ones(5, np.float32))
    got = _delta(_counters("paddle_compile_"), c0)
    assert {k.split("role=")[1].split("}")[0] for k in got} == {"other"}
    for stage in ("trace", "lower"):
        assert got["paddle_compile_events_total{role=other}{stage=%s}"
                   % stage] == 1.0
    assert _ledger(served["first"], role="other", stage="lower") > 0


def test_a_second_run_of_an_entry_adds_nothing_to_the_ledger(served):
    second = served["second"]
    assert not [k for k in second if k.startswith("paddle_compile_")]
    assert not [k for k in second if "first_call" in k]
    # one prefill, its first token, two more decode steps
    assert second["paddle_executor_runs_total{role=prefill_4}"] == 1.0
    assert second["paddle_executor_runs_total{role=decode}"] == 2.0
    main, startup, loss, feed = _train_program()
    exe = ptpu.Executor()
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    c0 = _counters()
    exe.run(main, feed=feed, fetch_list=[loss])
    got = _delta(_counters(), c0)
    assert not [k for k in got if k.startswith("paddle_compile_")]
    assert got["paddle_executor_runs_total{role=train}"] == 1.0
    assert "paddle_executor_first_calls_total{role=train}" not in got


def test_shape_inference_is_booked_where_it_happens():
    """Building a program traces every op once under ``jax.eval_shape``:
    those seconds are on the build's own counter and not on the ledger."""
    c0 = _counters()
    with _Listener() as heard:
        main = ptpu.Program()
        with ptpu.program_guard(main, ptpu.Program()):
            x = layers.data("x", shape=[8], dtype="float32")
            layers.fc(layers.fc(x, 16, act="tanh"), 4, act="softmax")
    got = _delta(_counters(), c0)
    assert got["paddle_program_infer_shape_ops_total"] >= 4
    assert got["paddle_program_infer_shape_seconds_total"] > 0
    assert heard.seconds(TRACE) > 0         # JAX did trace
    assert _ledger(got, stage="trace") == 0.0
    assert heard.top_level_seconds() <= \
        got["paddle_program_infer_shape_seconds_total"]


def test_the_aot_path_feeds_the_same_counters():
    """Under ``telemetry`` the first call compiles ahead of time
    (``_aot_compile``): its seconds are on the ledger under the role, and
    the per-key gauges of before PR 36 are gone."""
    main = ptpu.Program(name="scored")
    with ptpu.program_guard(main, ptpu.Program()):
        y = layers.scale(layers.data("x", shape=[4]), scale=3.0)
    prev = ptpu.config.get_flag("telemetry")
    ptpu.config.set_flags(telemetry=True)
    try:
        c0 = _counters()
        exe = ptpu.Executor()
        exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                fetch_list=[y])
        got = _delta(_counters(), c0)
    finally:
        ptpu.config.set_flags(telemetry=prev)
        tracing.clear()
    entry, = exe._cache.values()
    assert entry.aot is not None
    for stage in ("trace", "lower"):
        assert _ledger(got, role="scored", stage=stage) > 0
    assert _ledger(got, "paddle_compile_events_total", role="scored",
                   stage="lower") == 1.0        # compiled once, not twice
    assert got["paddle_executor_step_flops{key=%s}" % entry.key_id] >= 0
    families = metrics.REGISTRY.families()
    assert "paddle_executor_trace_seconds" not in families
    assert "paddle_executor_compile_seconds" not in families


# -- the four spans on counters -----------------------------------------------

def test_phase_counters_sum_to_the_spans_seconds():
    """The counters read the clock around the spans: per phase they hold
    the ring's spans of the steady runs, and over all phases every span of
    every run, the first (booked whole under ``first_call``) included."""
    main, startup, loss, feed = _train_program()
    exe = ptpu.Executor()
    exe.run(startup)
    runs = 6
    tid = threading.get_ident()
    c0 = _counters("paddle_executor_")
    tracing.start(clear=True)
    try:
        for _ in range(runs):
            exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        tracing.stop()
    spans = [e for e in tracing.events()
             if e["ph"] == "X" and e["tid"] == tid
             and e["name"].startswith("executor:")]
    tracing.clear()
    got = _delta(_counters("paddle_executor_"), c0)

    def ring_ms(name, skip_first=0):
        mine = [e["dur"] for e in spans if e["name"] == "executor:" + name]
        return sum(mine[skip_first:]) / 1e3

    def counted(phase):
        return got.get(
            "paddle_executor_host_ms_total{phase=%s}{role=train}" % phase,
            0.0)

    assert got["paddle_executor_runs_total{role=train}"] == runs
    assert got["paddle_executor_first_calls_total{role=train}"] == 1.0
    entry, = (e for k, e in exe._cache.items() if k[0] == main._uid)
    assert [e["args"] for e in spans
            if e["name"] == "executor:first_call"] == [
        {"role": "train", "key": entry.key_id}]
    assert len([s for s in spans if s["name"] == "executor:call"]) == \
        runs - 1
    # the clock is read just outside a span: a counter holds its spans
    # and at most a little more (half a millisecond a run, so that a
    # collection or another worker's turn between the two does not fail it)
    slack = 0.5 * runs                  # ms
    for phase in ("call", "fetch"):
        assert ring_ms(phase) <= counted(phase) <= ring_ms(phase) + slack
    steady_of = {}
    for phase in ("prepare", "writeback"):
        steady = steady_of[phase] = ring_ms(phase, skip_first=1)
        assert steady <= counted(phase) <= steady + slack
    first = ring_ms("first_call") + ring_ms("prepare") - steady_of[
        "prepare"] + ring_ms("writeback") - steady_of["writeback"]
    assert first <= counted("first_call") <= first + slack
    total = sum(counted(p) for p in executor_mod._PHASES)
    assert total == pytest.approx(sum(e["dur"] for e in spans) / 1e3,
                                  abs=5 * slack)


def test_call_and_prepare_spans_carry_the_role():
    main, startup, loss, feed = _train_program()
    exe = ptpu.Executor()
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    tracing.start(clear=True)
    try:
        exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        tracing.stop()
    args = {e["name"]: e.get("args", {}) for e in tracing.events()
            if e["ph"] == "X"}
    tracing.clear()
    key = next(e.key_id for k, e in exe._cache.items()
               if k[0] == main._uid)
    assert args["executor:prepare"] == {"role": "train"}
    assert args["executor:call"] == {"role": "train", "key": key}


def test_a_runs_bookkeeping_costs_under_two_microseconds():
    """What every ``Executor.run`` pays for its counters: five clock
    readings and one booking. This thread's CPU time, so that the other
    test workers on the machine's cores do not count (1.0 us on a quiet
    machine; a training step is 269 ms, a decode turn 2.2-4.0)."""
    entry = executor_mod._CacheEntry(None, (), (), False, "k0",
                                     role="cost_probe")
    clock = time.perf_counter

    def per_run_us(n=100_000):
        t0 = time.thread_time()
        for _ in range(n):
            a = clock()
            b = clock()
            c = clock()
            d = clock()
            e = clock()
            entry.book(False, a, b, c, d, e)
        return (time.thread_time() - t0) / n * 1e6
    # the least of five: what else the core does only adds
    assert min(per_run_us() for _ in range(5)) < 2.0
    assert metrics.REGISTRY.counter(
        "paddle_executor_runs_total", labelnames=("role",)).labels(
            role="cost_probe").value == 500_000


def test_the_import_says_how_long_it_took():
    gauge = metrics.REGISTRY.families()["paddle_process_import_seconds"]
    assert gauge.kind == "gauge" and 0 < gauge.value < 600
