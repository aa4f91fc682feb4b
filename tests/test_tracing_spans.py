"""Program spans on the profiler's clock, and the decode round cut into
phases: ``tracing.span`` lands in a ``jax.profiler`` trace under its name
with its arguments and costs next to nothing outside one; a tiny paged
``GenerationScheduler`` run produces the span tree of the dispatcher's
clock (``GenerationScheduler`` docstring) and counters that agree with it.
Everything here runs on the CPU: none of its times is a device number."""

import glob
import json
import os
import statistics
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.transformer import (transformer_lm,
                                           transformer_lm_session)
from paddle_tpu.observability import metrics, tracing
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.utils import stat

pytestmark = [pytest.mark.generation, pytest.mark.paged]

V, MAXLEN = 29, 24
KW = dict(d_model=16, num_heads=2, d_ff=32, num_layers=2)


# -- the span primitive -----------------------------------------------------

SPAN_MAKERS = {
    "plain": lambda: tracing.span("probe:plain"),
    "args": lambda: tracing.span("probe:args", round=3, slot=1),
    "armed": lambda: tracing.span("probe:armed", round=4),
    "timer": lambda: stat.timer("probe:timer"),
}


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session over one span of each maker (``armed`` with
    the ring recording): ``{name: (duration_ns, args)}`` as the xplane's
    host plane holds them, and the ring's events."""
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for key, make in SPAN_MAKERS.items():
            if key == "armed":
                tracing.start(clear=True)
            try:
                with make():
                    time.sleep(0.002)
            finally:
                if key == "armed":
                    tracing.stop()
    finally:
        jax.profiler.stop_trace()
    ring = tracing.events()
    tracing.clear()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("probe:"):
                    found[e.name] = (float(e.duration_ns), dict(e.stats))
    return found, ring


@pytest.mark.parametrize("key,args", [
    ("plain", {}), ("args", {"round": 3, "slot": 1}),
    ("armed", {"round": 4}), ("timer", {})])
def test_span_lands_in_the_profilers_host_plane(profiled, key, args):
    found, _ = profiled
    dur_ns, got = found["probe:" + key]
    assert got == args
    assert 2e6 <= dur_ns < 200e6      # it slept 2 ms inside


def test_ring_event_and_annotation_agree_on_duration(profiled):
    found, ring = profiled
    assert [e["name"] for e in ring] == ["probe:armed"]   # armed only
    assert ring[0]["args"] == {"round": 4}
    ring_ns = ring[0]["dur"] * 1e3
    # the ring's clock is read inside the annotation's
    assert 0 <= found["probe:armed"][0] - ring_ns < 200e3


def test_span_outside_a_trace_costs_under_two_microseconds():
    """This thread's CPU time, so that the other test workers on the
    machine's cores do not count (0.7 us on a quiet machine)."""
    assert not tracing.active()

    def per_span_us(n=100_000):
        t0 = time.thread_time()
        for i in range(n):
            with tracing.span("scheduler:deliver", round=i):
                pass
        return (time.thread_time() - t0) / n * 1e6
    assert statistics.median(per_span_us() for _ in range(5)) < 2.0


def test_chrome_export_carries_one_clock_anchor(tmp_path):
    tracing.start(clear=True)
    try:
        with tracing.span("probe:anchored"):
            pass
    finally:
        tracing.stop()
    before = tracing.now_us(), time.time_ns()
    doc = json.load(open(tracing.emit_chrome_trace(
        str(tmp_path / "trace.json"))))
    after = tracing.now_us(), time.time_ns()
    tracing.clear()
    assert set(doc["metadata"]) == {"clock_anchor"}
    anchor = doc["metadata"]["clock_anchor"]
    assert set(anchor) == {"perf_counter_ns", "time_ns", "ts_us"}
    # one reading of both clocks, on the clock the events are stamped in
    assert before[0] <= anchor["ts_us"] <= after[0]
    assert before[1] <= anchor["time_ns"] <= after[1]
    ev = next(e for e in doc["traceEvents"]
              if e.get("name") == "probe:anchored")
    assert ev["ts"] <= anchor["ts_us"]


# -- the decode round -------------------------------------------------------

@pytest.fixture(autouse=True)
def _no_flash():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


def _lm_scope(seed=7):
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
    rs = np.random.RandomState(seed)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        scope.set_var(n, rs.standard_normal(cur.shape).astype(cur.dtype))
    return scope


def _session(scope, prefix_cache=False, slots=3):
    spec = transformer_lm_session(
        V, max_len=MAXLEN, slots=slots, cache_len=MAXLEN,
        prompt_buckets=(4, 8), bos_id=0, eos_id=1, paged=True,
        block_size=4, num_blocks=24, prefix_cache=prefix_cache, **KW)
    return GenerationSession(spec, scope=scope)


def _counters():
    out = {}
    for name, kind, _, _, children in metrics.REGISTRY.snapshot():
        for labels, payload in children:
            key = name + "".join("{%s=%s}" % kv
                                 for kv in sorted(labels.items()))
            if kind == "counter":
                out[key] = float(payload)
            elif kind == "histogram":
                out[key + ":count"] = float(payload[1])
                out[key + ":sum"] = float(payload[2])
    return out


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


PHASES = ("deliver", "admit", "prepare", "dispatch", "other")
# (prompt length, new tokens)
REQUESTS = [(5, 6), (3, 4), (7, 5)]


@pytest.fixture(scope="module")
def decode_run():
    """A paged scheduler's whole life under the armed ring: the ring's
    events on the dispatcher's thread, the counters' deltas, the requests'
    results."""
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    sess = _session(_lm_scope())
    # compile outside the measured life, as a deployment's warm-up does
    for n in (3, 7):
        sess.generate(list(range(2, 2 + n)), max_new_tokens=2)
    c0 = _counters()
    tracing.start(clear=True)
    try:
        sched = GenerationScheduler(sess, deadline_ms=0)
        rs = np.random.RandomState(3)
        futures = []
        for n_prompt, n_new in REQUESTS:
            futures.append(sched.submit(
                rs.randint(2, V, n_prompt), max_new_tokens=n_new,
                eos_id=-1))
        results = [f.result(timeout=120) for f in futures]
        time.sleep(0.12)        # two idle waits with nothing to serve
        tid = sched._thread.ident
        sched.close()
    finally:
        tracing.stop()
    events = [e for e in tracing.events()
              if e["ph"] == "X" and e["tid"] == tid]
    tracing.clear()
    counters = _delta(_counters(), c0)
    sess.close()
    ptpu.config.set_flags(flash_attention=prev)
    return {"events": events, "counters": counters, "results": results}


def _named(run, name):
    return [e for e in run["events"] if e["name"] == name]


def _inside(inner, outer, slack_us=0.5):
    return outer["ts"] - slack_us <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + slack_us


def test_requests_got_what_they_asked_for(decode_run):
    assert [len(r) for r in decode_run["results"]] == \
        [n_new for _, n_new in REQUESTS]


@pytest.mark.parametrize("child,parent", [
    ("scheduler:deliver", "scheduler:host_turn"),
    ("scheduler:admit", "scheduler:host_turn"),
    ("session:step_prepare", "scheduler:host_turn"),
    ("session:step_dispatch", "scheduler:host_turn"),
    ("session:prefill_call", "scheduler:admit"),
    ("scheduler:first_token", "scheduler:host_turn"),
    ("session:prefill_wait", "scheduler:first_token"),
])
def test_span_nests_in_its_parent_of_the_same_round(decode_run, child,
                                                    parent):
    """Nesting by containment on the dispatcher's thread."""
    children, parents = _named(decode_run, child), _named(decode_run, parent)
    assert children
    for c in children:
        inside = [p for p in parents if _inside(c, p)]
        assert len(inside) == 1, (c, len(inside))
        assert c["args"]["round"] == inside[0]["args"]["round"]


@pytest.mark.parametrize("call,holds", [
    ("session:step_dispatch",
     {"executor:prepare": 1, "executor:call": 1, "executor:writeback": 1,
      "executor:fetch": 0}),
    ("session:prefill_call",
     {"executor:prepare": 1, "executor:call": 1, "executor:writeback": 1,
      "executor:fetch": 0}),
])
def test_device_call_holds_the_executors_spans(decode_run, call, holds):
    """A decode call is dispatched and not fetched (``session:step_wait``
    is the fetch), and so is a prefill (``session:prefill_wait``): a
    decode step queued ahead of it is collected between the two."""
    calls = _named(decode_run, call)
    assert calls
    for c in calls:
        got = {name: sum(_inside(e, c) for e in _named(decode_run, name))
               for name in holds}
        assert got == holds, c


@pytest.mark.parametrize("name", ["session:step_wait",
                                  "scheduler:idle_wait"])
def test_waits_lie_outside_every_host_turn(decode_run, name):
    waits = _named(decode_run, name)
    assert waits
    for w in waits:
        for t in _named(decode_run, "scheduler:host_turn"):
            overlap = min(w["ts"] + w["dur"], t["ts"] + t["dur"]) - \
                max(w["ts"], t["ts"])
            assert overlap < 0.5, (w, t)


def test_one_round_per_host_turn_and_a_turn_before_every_step(decode_run):
    turns = sorted(_named(decode_run, "scheduler:host_turn"),
                   key=lambda e: e["ts"])
    rounds = [t["args"]["round"] for t in turns]
    assert rounds == list(range(rounds[0], rounds[0] + len(rounds)))
    steps = decode_run["counters"]["paddle_generation_decode_steps_total"]
    waits = _named(decode_run, "session:step_wait")
    dispatches = _named(decode_run, "session:step_dispatch")
    assert len(waits) == len(dispatches) == steps
    # every decode call closes the turn it was dispatched in: the turn
    # ends behind it with nothing else of the dispatcher's begun between
    # (by order, not by a stopwatch: with a prefill and a step on the
    # queue the host's threads are busy, and the dispatcher may wait
    # milliseconds for a core between the call's return and the next line)
    by_round = {t["args"]["round"]: t for t in turns}
    for d in dispatches:
        turn = by_round[d["args"]["round"]]
        end, turn_end = d["ts"] + d["dur"], turn["ts"] + turn["dur"]
        assert end <= turn_end + 0.5
        assert not [e["name"] for e in decode_run["events"]
                    if end <= e["ts"] < turn_end - 0.5]


def test_step_phases_sum_to_the_decode_step_histogram(decode_run):
    c = decode_run["counters"]
    steps = c["paddle_generation_decode_steps_total"]
    assert steps == c["paddle_request_decode_step_ms:count"] > 0
    parts = (c["paddle_generation_host_ms_total{phase=prepare}"] +
             c["paddle_generation_host_ms_total{phase=dispatch}"] +
             c["paddle_generation_device_wait_ms_total"])
    # the same clock readings: exact but for float rounding, far inside
    # the 0.05 ms a round that the contract allows
    assert abs(parts - c["paddle_request_decode_step_ms:sum"]) < 0.05


def test_phases_waits_and_idle_fill_the_dispatchers_wall_time(decode_run):
    c = decode_run["counters"]
    host_ms = sum(c["paddle_generation_host_ms_total{phase=%s}" % p]
                  for p in PHASES)
    turns_ms = sum(e["dur"] for e in _named(
        decode_run, "scheduler:host_turn")) / 1e3
    # the five phases are the host turns (spans and counters are read
    # from neighbouring clock readings: a few microseconds a turn)
    assert host_ms == pytest.approx(turns_ms, rel=0.01, abs=0.2)
    idle_ms = sum(e["dur"] for e in _named(
        decode_run, "scheduler:idle_wait")) / 1e3
    assert idle_ms > 100.0
    ev = decode_run["events"]
    wall_ms = (max(e["ts"] + e["dur"] for e in ev) -
               min(e["ts"] for e in ev)) / 1e3
    total = host_ms + c["paddle_generation_device_wait_ms_total"] + idle_ms
    assert total == pytest.approx(wall_ms, rel=0.01)


def test_context_tokens_are_the_lengths_attended(decode_run):
    """By hand: a request with p prompt tokens gets its first token from
    the prefill; its j-th decode step attends the p + j - 1 cached rows
    and the new token."""
    want = sum(p + j for p, n_new in REQUESTS for j in range(1, n_new))
    c = decode_run["counters"]
    assert c["paddle_generation_context_tokens_total"] == want
    assert c["paddle_generation_tokens_total"] == \
        sum(n_new for _, n_new in REQUESTS)


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["no_prefix_cache", "prefix_cache_hit"])
def test_prefill_counters_count_what_was_really_prefilled(prefix_cache):
    """The same 6-token prompt twice (blocks of 4, buckets 4 and 8).
    Without the prefix cache both prefills run all 6 tokens in the bucket
    of 8. With it the second shares the first 5 (the last prompt token is
    always re-run) or the first whole block of 4, and prefills the rest in
    the bucket of 4."""
    sess = _session(_lm_scope(), prefix_cache=prefix_cache)
    c0 = _counters()
    with GenerationScheduler(sess, deadline_ms=0) as sched:
        for _ in range(2):
            sched.submit([2, 3, 4, 5, 6, 7], max_new_tokens=2,
                         eos_id=-1).result(timeout=120)
    c = _delta(_counters(), c0)
    log = sess.prefill_log[-2:]
    sess.close()
    hit = log[1][1]
    assert (4 <= hit <= 5) if prefix_cache else hit == 0
    assert c["paddle_generation_prompt_tokens_total"] == 6 + 6 - hit
    assert c["paddle_generation_prefill_padded_tokens_total"] == \
        (8 + 4 if prefix_cache else 8 + 8)


def test_dispatcher_spans_stay_on_the_dispatchers_thread(decode_run):
    assert threading.get_ident() not in {e["tid"]
                                         for e in decode_run["events"]}
    names = {e["name"] for e in decode_run["events"]}
    assert {"scheduler:host_turn", "scheduler:deliver", "scheduler:admit",
            "scheduler:idle_wait", "session:step_prepare",
            "session:step_dispatch", "session:step_wait",
            "session:prefill_call", "session:prefill_wait",
            "scheduler:first_token", "executor:prepare", "executor:call",
            "executor:writeback"} <= names
