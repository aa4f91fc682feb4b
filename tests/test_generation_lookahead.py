"""The dispatcher works one decode step ahead (``GenerationScheduler``,
"One step ahead"): step n+1 is on the device's queue before step n's
tokens are fetched, fed by them on the device. What that must not change:
the tokens of any request, the pool's books, the replay contract, the
dispatcher's clock. What it must do: engage where the session allows it
and nowhere else. Everything here runs on the CPU."""

import time

import numpy as np
import pytest

import paddle_tpu as ptpu
from paddle_tpu.models.transformer import transformer_lm_session
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.serving.decoding import DecodePolicy, DFAConstraint

from test_tracing_spans import KW, MAXLEN, V, _counters, _delta, _lm_scope

pytestmark = [pytest.mark.generation, pytest.mark.paged]

BOS, EOS = 0, 1
STEPS = "paddle_generation_decode_steps_total"
AHEAD = "paddle_generation_decode_steps_ahead_total"
TOKENS = "paddle_generation_tokens_total"
SAMPLED = DecodePolicy(kind="sample", temperature=1.0)


@pytest.fixture(autouse=True)
def _no_flash():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


@pytest.fixture(scope="module")
def lm_scope():
    return _lm_scope()


def _session(scope, policy=None, slots=3, prefix_cache=False):
    spec = transformer_lm_session(
        V, max_len=MAXLEN, slots=slots, cache_len=MAXLEN,
        prompt_buckets=(4, 8, 16), bos_id=BOS, eos_id=EOS, paged=True,
        block_size=4, num_blocks=24, prefix_cache=prefix_cache,
        decode_policy=policy, **KW)
    return GenerationSession(spec, scope=scope)


def _place_all(sched):
    while True:
        item = sched._next_item(block=False)
        if item is None or not sched._place(item):
            return


def _drive(sched, futures, limit=200):
    """The dispatcher's loop by hand, on this thread: every step of it is
    then exactly where the test says it is."""
    for _ in range(limit):
        if all(f.done() for f in futures) and not sched._busy():
            return
        _place_all(sched)
        sched._step_all()
    raise AssertionError("the requests did not finish")


def _requests(seed=11, n=7):
    """(prompt, new tokens, seed) of requests that differ in both
    lengths, so that slots are admitted and retired mid-stream."""
    rs = np.random.RandomState(seed)
    return [(rs.randint(2, V, int(rs.randint(2, 9))),
             int(rs.randint(2, 11)), 1000 + i) for i in range(n)]


# -- the same tokens -------------------------------------------------------

@pytest.mark.parametrize("policy", [None, SAMPLED],
                         ids=["greedy", "sampled"])
def test_streams_through_the_scheduler_equal_generates(lm_scope, policy):
    """``generate`` launches and collects each step before the next (the
    order every session had before); the scheduler runs the same requests
    three at a time, one step ahead, with some ended early by an EOS that
    it sees a step late."""
    sess = _session(lm_scope, policy)
    want = []
    for i, (prompt, n_new, seed) in enumerate(_requests()):
        free = sess.generate(prompt, max_new_tokens=n_new, eos_id=-1,
                             seed=seed)
        # every other request ends on the value of its third token
        eos = free[2] if i % 2 and len(free) > 3 else -1
        want.append((eos, sess.generate(prompt, max_new_tokens=n_new,
                                        eos_id=eos, seed=seed)))
    assert any(eos != -1 and len(out) < n_new
               for (eos, out), (_, n_new, _) in zip(want, _requests()))
    c0 = _counters()
    with GenerationScheduler(sess, deadline_ms=0) as sched:
        futures = [sched.submit(prompt, max_new_tokens=n_new, eos_id=eos,
                                seed=seed)
                   for (prompt, n_new, seed), (eos, _)
                   in zip(_requests(), want)]
        got = [[int(t) for t in f.result(timeout=120)] for f in futures]
    c = _delta(_counters(), c0)
    assert got == [out for _, out in want]
    # it did work ahead, and not on every step: the step after a prefill
    # has nothing uncollected before it
    assert 0 < c[AHEAD] < c[STEPS]
    sess.check_pool_invariant()
    assert sess.pool.used_count() == 0 and not sess._flights
    sess.close()


def test_token_feed_on_the_device_takes_the_hosts_token_for_new_slots(
        lm_scope):
    """The feed of a step prepared with one uncollected: the uncollected
    step's tokens, but the host's for a slot admitted since."""
    sess = _session(lm_scope)
    a, _ = sess.admit([BOS, 5, 7])
    flight = sess.step_launch(sess.step_prepare())
    b, first_b = sess.admit([BOS, 9])
    prepared = sess.step_prepare()
    feed = prepared[2][sess.spec.decode_feeds[0]]
    assert not isinstance(feed, np.ndarray)          # built on the device
    tok_a = sess.step_collect(flight)[a]
    assert np.asarray(feed).reshape(-1)[[a, b]].tolist() == [tok_a,
                                                             first_b]
    out = sess.step_run(prepared)
    assert sorted(out) == sorted([a, b])
    # with nothing uncollected the feed is the host's own array
    assert isinstance(sess.step_prepare()[2][sess.spec.decode_feeds[0]],
                      np.ndarray)
    sess.close()


def test_a_device_feed_is_not_fetched_by_the_step_that_takes_it(
        lm_scope, monkeypatch):
    """``Executor.run`` must not read a device array it is fed (it did,
    for the dtype: the dispatch of step n+1 then waited for step n, and
    the first chip run of the lookahead read an 11 ms host turn)."""
    import jax
    from paddle_tpu.core import executor

    class Numpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(value, *args, **kwargs):
            assert not isinstance(value, jax.Array), "fed array fetched"
            return np.asarray(value, *args, **kwargs)
    sess = _session(lm_scope)
    slot, _ = sess.admit([BOS, 5, 7])
    flight = sess.step_launch(sess.step_prepare())
    prepared = sess.step_prepare()
    monkeypatch.setattr(executor, "np", Numpy())
    ahead = sess.step_launch(prepared)
    monkeypatch.undo()
    assert slot in sess.step_collect(flight)
    assert slot in sess.step_collect(ahead)
    sess.close()


# -- what ends a request by count, and what by value ------------------------

def test_a_budget_that_ends_in_flight_launches_no_further_step(lm_scope):
    """A (3 tokens) and B (6): the step launched while A's last one is
    uncollected holds A's slot, and no step runs for a request whose
    tokens are all launched."""
    sess = _session(lm_scope)
    holds, prepare = [], sess.step_prepare
    sess.step_prepare = lambda hold=(): (holds.append(list(hold)),
                                         prepare(hold))[1]
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False)
    c0 = _counters()
    fa = sched.submit([BOS, 5, 7], max_new_tokens=3, eos_id=-1)
    fb = sched.submit([BOS, 9], max_new_tokens=6, eos_id=-1)
    _drive(sched, [fa, fb])
    c = _delta(_counters(), c0)
    assert len(fa.result(1)) == 3 and len(fb.result(1)) == 6
    # B's five decode steps, of which A shared two; none beyond
    assert c[STEPS] == len(holds) == 5
    assert c[TOKENS] == 3 + 6
    assert holds == [[], [], [0], [], []]
    # the last step launched had B's slot alone to hold: nothing to step
    assert c[AHEAD] == 4
    sess.check_pool_invariant()
    assert sess.pool.used_count() == 0
    sched.close()
    sess.close()


def test_a_slot_at_cache_capacity_sits_the_next_step_out(lm_scope):
    """The implicit budget (as much as fits): the lengths already hold
    the step launched ahead, and the request still gets every token the
    cache has room for."""
    sess = _session(lm_scope)
    want = sess.generate([BOS, 5, 7], eos_id=-1)
    assert len(want) == MAXLEN - 3 + 1
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False)
    f = sched.submit([BOS, 5, 7], eos_id=-1)
    _drive(sched, [f])
    assert [int(t) for t in f.result(1)] == want
    sched.close()
    sess.close()


def test_an_eos_seen_one_step_late_discards_exactly_one_result(lm_scope):
    sess = _session(lm_scope, SAMPLED)
    for seed in range(5, 50):       # a stream whose third token is new
        free = sess.generate([BOS, 5, 7], max_new_tokens=8, eos_id=-1,
                             seed=seed)
        eos = free[2]
        if eos not in free[:2]:
            break
    assert eos not in free[:2]
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False)
    c0 = _counters()
    f = sched.submit([BOS, 5, 7], max_new_tokens=8, eos_id=eos, seed=seed)
    _drive(sched, [f])
    c = _delta(_counters(), c0)
    assert [int(t) for t in f.result(1)] == free[:2]
    # the prefill's token and two steps' were handed over (the EOS is
    # one); a third step had been launched before the EOS was read
    assert c[TOKENS] == 3 and c[STEPS] == 3
    sess.check_pool_invariant()
    assert sess.pool.used_count() == 0 and not sess._flights
    assert sched._inflight == [None]
    sched.close()
    sess.close()


# -- failure, and everything that acted between two steps ---------------------

def test_a_step_that_fails_with_another_queued_behind_it_replays(lm_scope):
    """The failure surfaces at the collect of step 3, when step 4 is
    already on the queue: both are dropped, and the journals (delivered
    tokens only) replay on the other session to the same streams."""
    ref = _session(lm_scope, SAMPLED)
    reqs = _requests(seed=23, n=3)
    want = [ref.generate(p, max_new_tokens=n_new + 4, eos_id=-1, seed=seed)
            for p, n_new, seed in reqs]
    ref.close()
    bad, good = _session(lm_scope, SAMPLED), _session(lm_scope, SAMPLED)
    calls, collect = [], bad.step_collect

    def failing(flight):
        calls.append(len(bad._flights))
        if len(calls) == 3:
            raise RuntimeError("injected: the device lost step 3")
        return collect(flight)
    bad.step_collect = failing
    sched = GenerationScheduler([bad, good], deadline_ms=0,
                                replay_attempts=2, autostart=False)
    futures = [sched.submit(p, max_new_tokens=n_new + 4, eos_id=-1,
                            seed=seed) for p, n_new, seed in reqs]
    _drive(sched, futures)
    assert [[int(t) for t in f.result(1)] for f in futures] == want
    # two steps were uncollected when the third collect was called
    assert calls[2] == 2 and len(calls) == 3
    for sess in (bad, good):
        sess.check_pool_invariant()
        assert sess.pool.used_count() == 0 and not sess._flights
    sched.close()
    bad.close()
    good.close()


@pytest.mark.parametrize("how", ["swap_weights", "drain", "close"])
def test_what_is_in_flight_is_collected_first(lm_scope, how):
    sess = _session(lm_scope)
    want = sess.generate([BOS, 5, 7], max_new_tokens=6, eos_id=-1)
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False)
    f = sched.submit([BOS, 5, 7], max_new_tokens=6, eos_id=-1)
    _place_all(sched)
    sched._step_all()
    item = next(iter(sched._active.values()))
    assert sched._inflight[0] is not None and len(item.tokens) == 1
    if how == "swap_weights":
        name = sorted(n for n in lm_scope.var_names()
                      if n not in sess._claimed)[0]
        sched.swap_weights({name: np.asarray(lm_scope.find_var(name))})
        assert len(item.tokens) == 2
    elif how == "drain":
        sched.drain()
        assert [int(t) for t in f.result(1)] == want
    else:
        sched.close()
        assert len(item.tokens) == 2
    assert sched._inflight == [None] and not sess._flights
    assert item.tokens == want[:len(item.tokens)]
    sched.close()
    sess.close()


# -- where no step ahead is taken --------------------------------------------

def _constrained(scope):
    dfa = DFAConstraint({0: {5: 1, 7: 1}, 1: {6: 0, 8: 0}})
    return _session(scope, DecodePolicy(constraint=dfa)), {}


def _speculative(scope):
    return _session(scope, DecodePolicy(kind="greedy", speculate_k=2)), {}


def _step_bounded(scope):
    return _session(scope), {"step_timeout_ms": 60000.0}


@pytest.mark.parametrize("make", [_constrained, _speculative,
                                  _step_bounded])
def test_sessions_that_need_the_token_on_the_host_count_no_step_ahead(
        lm_scope, make):
    sess, kwargs = make(lm_scope)
    assert sess.lookahead == (make is _step_bounded)
    c0 = _counters()
    with GenerationScheduler(sess, deadline_ms=0, **kwargs) as sched:
        assert sched._depth(sess) == 0
        outs = [sched.submit([BOS, 5, 7], max_new_tokens=6, eos_id=-1)
                .result(timeout=120) for _ in range(2)]
    c = _delta(_counters(), c0)
    assert all(len(o) == 6 for o in outs)
    assert c[STEPS] > 0 and c.get(AHEAD, 0.0) == 0
    sess.close()


# -- the clock ---------------------------------------------------------------

def test_the_clock_stays_inside_the_dispatchers_wall_time(lm_scope):
    """Decode-step and prefill observations are disjoint stretches of the
    dispatcher's time (a step queued ahead of a prefill is collected and
    booked before the prefill's wait), and the five host phases are the
    host turns."""
    sess = _session(lm_scope)
    for prompt, n_new, _ in _requests():
        sess.generate(prompt, max_new_tokens=2, eos_id=-1)     # compile
    c0 = _counters()
    tracing.start(clear=True)
    try:
        sched = GenerationScheduler(sess, deadline_ms=0)
        futures = [sched.submit(p, max_new_tokens=n_new, eos_id=-1)
                   for p, n_new, _ in _requests()]
        for f in futures:
            f.result(timeout=120)
        tid = sched._thread.ident
        sched.close()
    finally:
        tracing.stop()
    events = [e for e in tracing.events()
              if e["ph"] == "X" and e["tid"] == tid]
    tracing.clear()
    c = _delta(_counters(), c0)
    sess.close()
    assert c[AHEAD] > 0
    wall_ms = (max(e["ts"] + e["dur"] for e in events) -
               min(e["ts"] for e in events)) / 1e3
    observed = c["paddle_request_decode_step_ms:sum"] + \
        c["paddle_request_prefill_ms:sum"]
    assert 0 < observed <= wall_ms
    assert c["paddle_request_decode_step_ms:count"] == c[STEPS]
    host_ms = sum(v for k, v in c.items()
                  if k.startswith("paddle_generation_host_ms_total{"))
    turns_ms = sum(e["dur"] for e in events
                   if e["name"] == "scheduler:host_turn") / 1e3
    assert host_ms == pytest.approx(turns_ms, rel=0.01, abs=0.2)
    # every wait lies outside every turn, the one for a step queued ahead
    # of a prefill too
    waits = [e for e in events if e["name"] == "session:step_wait"]
    assert len(waits) == c[STEPS]
    for w in waits:
        for t in (e for e in events if e["name"] == "scheduler:host_turn"):
            assert min(w["ts"] + w["dur"], t["ts"] + t["dur"]) - \
                max(w["ts"], t["ts"]) < 0.5
    # and a prefill waited for with a step queued ahead of it follows
    # that step's wait
    firsts = [e for e in events if e["name"] == "session:prefill_wait"]
    assert len(firsts) == len(_requests())


def test_parking_the_dispatcher_in_its_observer_leaves_one_step_queued(
        lm_scope):
    """What the benchmark's harness does at the end of a run: the
    observer blocks the dispatcher for good. The step launched ahead
    stays uncollected, and nothing else in the process waits for it."""
    import threading
    sess = _session(lm_scope)
    parked, forever = threading.Event(), threading.Event()

    def on_token(tok):
        if len(seen) == 2:
            parked.set()
            forever.wait(5.0)
        seen.append(tok)
    seen = []
    sched = GenerationScheduler(sess, deadline_ms=0)
    f = sched.submit([BOS, 5, 7], max_new_tokens=8, eos_id=-1,
                     on_token=on_token)
    assert parked.wait(60.0)
    time.sleep(0.05)
    assert sched._inflight[0] is not None and len(sess._flights) == 1
    forever.set()
    assert len(f.result(timeout=120)) == 8
    sched.close()
    sess.close()
