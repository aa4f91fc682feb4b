"""The dispatcher works one decode step ahead (``GenerationScheduler``,
"One step ahead"): step n+1 is on the device's queue before step n's
tokens are fetched, fed by them on the device, and before the first token
of a prefill launched ahead of it is, which it takes from the prefill's
own device array (the token is *owed*). What that must not change:
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
OWED = "paddle_generation_first_tokens_owed_total"
REQUESTS = "paddle_generation_requests_total"
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
    # it did work ahead, behind prefills too, and not on every step: a
    # step launched with nothing active before it has no predecessor
    assert 0 < c[AHEAD] < c[STEPS] and c[OWED] > 0
    sess.check_pool_invariant()
    assert sess.pool.used_count() == 0 and not sess._flights
    assert not sess._owed
    sess.close()


@pytest.mark.parametrize("policy", [None, SAMPLED],
                         ids=["greedy", "sampled"])
def test_streams_with_admissions_between_steps_equal_a_depth_0_schedulers(
        lm_scope, policy):
    """Seven requests through three slots, by hand: every retirement is
    followed by an admission whose first token is owed while the next
    step is launched. The same requests through a scheduler that works
    no step ahead (launch, wait, book, then step) give the same streams,
    and owe nothing."""
    got = {}
    for depth, kwargs in ((1, {}), (0, {"step_timeout_ms": 60000.0})):
        sess = _session(lm_scope, policy)
        sched = GenerationScheduler(sess, deadline_ms=0, autostart=False,
                                    **kwargs)
        assert sched._depth(sess) == depth
        c0 = _counters()
        futures = [sched.submit(prompt, max_new_tokens=n_new, eos_id=-1,
                                seed=seed)
                   for prompt, n_new, seed in _requests()]
        _drive(sched, futures)
        c = _delta(_counters(), c0)
        got[depth] = [[int(t) for t in f.result(1)] for f in futures]
        # every admission but those into an idle session had a step
        # launched behind its prefill before its token was fetched
        assert (0 < c[OWED] <= len(futures)) if depth \
            else c.get(OWED, 0.0) == 0
        assert c[REQUESTS] == len(futures)
        assert c[TOKENS] == sum(n_new for _, n_new, _ in _requests())
        sess.check_pool_invariant()
        assert sess.pool.used_count() == 0 and not sess._owed
        sched.close()
        sess.close()
    assert got[1] == got[0]
    assert [len(g) for g in got[1]] == [n for _, n, _ in _requests()]


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


class _NoFetch:
    """``numpy`` with an ``asarray`` that refuses a device array."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def asarray(value, *args, **kwargs):
        import jax
        assert not isinstance(value, jax.Array), "device array fetched"
        return np.asarray(value, *args, **kwargs)


def test_a_device_feed_is_not_fetched_by_the_step_that_takes_it(
        lm_scope, monkeypatch):
    """``Executor.run`` must not read a device array it is fed (it did,
    for the dtype: the dispatch of step n+1 then waited for step n, and
    the first chip run of the lookahead read an 11 ms host turn)."""
    from paddle_tpu.core import executor
    sess = _session(lm_scope)
    slot, _ = sess.admit([BOS, 5, 7])
    flight = sess.step_launch(sess.step_prepare())
    prepared = sess.step_prepare()
    monkeypatch.setattr(executor, "np", _NoFetch())
    ahead = sess.step_launch(prepared)
    monkeypatch.undo()
    assert slot in sess.step_collect(flight)
    assert slot in sess.step_collect(ahead)
    sess.close()


@pytest.mark.parametrize("admissions", [1, 2], ids=["one", "two"])
@pytest.mark.parametrize("uncollected", [True, False],
                         ids=["behind_a_step", "idle"])
def test_an_owed_first_token_is_fed_from_the_prefills_device_array(
        lm_scope, monkeypatch, admissions, uncollected):
    """Admissions entered with their first token owed, one or two of them
    between two steps: the next step is prepared and launched with no
    array fetched, by the session or by the executor, and its feed holds
    each prefill's token where the host's ``last_token`` knows none."""
    from paddle_tpu.core import executor
    from paddle_tpu.serving import generation
    prompts = [[BOS, 9], [BOS, 4, 8, 3]][:admissions]
    ref = _session(lm_scope)
    want = [ref.generate(p, max_new_tokens=3, eos_id=-1) for p in prompts]
    ref.close()
    sess = _session(lm_scope)
    a, _ = sess.admit([BOS, 5, 7])
    flight = sess.step_launch(sess.step_prepare()) if uncollected else None
    monkeypatch.setattr(executor, "np", _NoFetch())
    monkeypatch.setattr(generation, "np", _NoFetch())
    launched, slots = [], []
    for p in prompts:       # back to back, neither prefill waited for
        launched.append(sess.admit_launch(p))
        slots.append(sess.admit_enter(launched[-1]))
    assert sorted(sess._owed) == slots and sess.active[slots].all()
    prepared = sess.step_prepare()
    feed = prepared[2][sess.spec.decode_feeds[0]]
    assert not isinstance(feed, np.ndarray)          # built on the device
    ahead = sess.step_launch(prepared)
    monkeypatch.undo()
    assert (sess.last_token[slots] == 0).all()       # not on the host yet
    tok_a = sess.step_collect(flight)[a] if uncollected \
        else int(sess.last_token[a])
    firsts = [sess.admit_collect(one)[1] for one in launched]
    assert not sess._owed
    assert np.asarray(feed).reshape(-1)[[a] + slots].tolist() == \
        [tok_a] + firsts
    out = sess.step_collect(ahead)
    seconds = [out[s] for s in slots]
    third = sess.step()
    thirds = [third[s] for s in slots]
    assert [list(t) for t in zip(firsts, seconds, thirds)] == want
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


@pytest.mark.parametrize("ends", ["eos", "max_new"])
def test_a_request_that_its_first_token_ends_leaves_nothing_to_the_next(
        lm_scope, ends):
    """B decodes in slot 0 throughout. A is admitted into slot 1 and its
    first token, owed while the next step is launched, ends it: by value
    (the EOS) one step has run for it, whose result is discarded; by count
    (a budget of one) it sat that step out. C takes slot 1 next, with the
    step that A was or was not in still uncollected, and gets its own
    tokens, none of A's."""
    sess = _session(lm_scope, slots=2)
    pa, pb, pc = [BOS, 5, 7], [BOS, 9], [BOS, 4, 8, 3]
    first_a = sess.generate(pa, max_new_tokens=1, eos_id=-1)[0]
    want_b = sess.generate(pb, max_new_tokens=9, eos_id=-1)
    want_c = sess.generate(pc, max_new_tokens=4, eos_id=-1)
    holds, prepare = [], sess.step_prepare
    sess.step_prepare = lambda hold=(): (holds.append(list(hold)),
                                         prepare(hold))[1]
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False)
    c0 = _counters()
    fb = sched.submit(pb, max_new_tokens=9, eos_id=-1)
    _place_all(sched)
    sched._step_all()
    sched._step_all()                    # B has a step uncollected
    fa = sched.submit(pa, eos_id=first_a) if ends == "eos" \
        else sched.submit(pa, max_new_tokens=1, eos_id=-1)
    fc = sched.submit(pc, max_new_tokens=4, eos_id=-1)
    _place_all(sched)                    # A into slot 1; C waits for it
    item_a = sched._active[(0, 1)]
    assert sess.owes(1) and not item_a.tokens
    n_holds = len(holds)
    sched._step_all()
    # the step launched behind A's prefill: A in it, or held out of it
    assert holds[n_holds] == ([] if ends == "eos" else [1])
    assert fa.done() and (0, 1) not in sched._active
    assert item_a.ahead == (1 if ends == "eos" else 0)
    _drive(sched, [fb, fc])
    c = _delta(_counters(), c0)
    assert [int(t) for t in fa.result(1)] == \
        ([] if ends == "eos" else [first_a])
    assert [int(t) for t in fb.result(1)] == want_b
    assert [int(t) for t in fc.result(1)] == want_c
    assert sched._active == {} and c[REQUESTS] == 3
    # A's token (its EOS counts as decoded), B's and C's: the result of
    # the step A ran beyond its end is in no count
    assert c[TOKENS] == 1 + 9 + 4
    sess.check_pool_invariant()
    assert sess.pool.used_count() == 0 and not sess._flights
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


@pytest.mark.parametrize("sessions", [2, 1], ids=["two", "one_session"])
@pytest.mark.parametrize("fails", ["admit_fail", "fetch", "step"])
def test_a_prefill_that_fails_with_a_step_queued_behind_it_replays(
        lm_scope, fails, sessions):
    """A request joins two that are decoding on the first session, and
    its admission fails. At the launch (the ``generation_admit_fail``
    site) it fails alone: nothing of it is in any book. At the fetch of
    its first token the slot is in the books and a decode step that took
    the token on the device is queued behind the prefill: the session's
    failure, the step dropped with it, the prefix index gives back what
    it had published, the request replays as it came and the two others
    from their journals. Or the step queued ahead of the prefill fails at
    its collect: the prefill and the step behind it go with it. Every
    stream is what ``generate`` gives and the counts conserve, also where
    there is one session alone and every request replays into the slots
    it left."""
    from paddle_tpu.resilience import faults
    ref = _session(lm_scope, SAMPLED, prefix_cache=True)
    reqs = _requests(seed=23, n=3)
    want = [ref.generate(p, max_new_tokens=n_new + 4, eos_id=-1, seed=seed)
            for p, n_new, seed in reqs]
    ref.close()
    bad = _session(lm_scope, SAMPLED, prefix_cache=True)
    good = _session(lm_scope, SAMPLED, prefix_cache=True) \
        if sessions == 2 else bad
    seen, collect = [], bad.admit_collect

    def failing(launched):
        seen.append((bad.owes(launched.slot), len(bad._flights)))
        if fails == "fetch" and len(seen) == 3:
            launched.outs = [_Unfetchable()]
        return collect(launched)
    bad.admit_collect = failing
    step_collect = bad.step_collect

    def failing_step(flight):
        if fails == "step" and bad.owed() and "step" not in seen:
            seen.append("step")
            seen.append(len(bad._flights))
            raise RuntimeError("injected: the device lost the step")
        return step_collect(flight)
    bad.step_collect = failing_step
    sched = GenerationScheduler([bad, good][:sessions], deadline_ms=0,
                                replay_attempts=2, autostart=False)
    c0 = _counters()
    futures = [sched.submit(p, max_new_tokens=n_new + 4, eos_id=-1,
                            seed=seed) for p, n_new, seed in reqs[:2]]
    _place_all(sched)
    sched._step_all()
    sched._step_all()                    # two decoding, a step uncollected
    journals = sum(len(it.tokens) for it in sched._active.values())
    if fails == "admit_fail":
        faults.arm("generation_admit_fail", at=0, times=1)
    try:
        p, n_new, seed = reqs[2]
        futures.append(sched.submit(p, max_new_tokens=n_new + 4, eos_id=-1,
                                    seed=seed))
        _drive(sched, futures)
    finally:
        faults.disarm()
    c = _delta(_counters(), c0)
    assert [[int(t) for t in f.result(1)] for f in futures] == want
    if fails == "fetch":
        # the failing fetch found the slot entered and a step behind it
        # (the one ahead of the prefill had been collected: a token more
        # in each of the two journals)
        assert seen[2] == (True, 1)
        journals += 2
    elif fails == "step":
        # the failing collect had the prefill and a second step behind it
        assert seen[2:4] == ["step", 2]
    # the third request replayed alone and had no slot yet, or all three
    moved = 1 if fails == "admit_fail" else 3
    if sessions == 2:
        assert len(good.prefill_log) == moved
        # nothing of the failed prefill stays published where it failed
        assert bad.prefix.peek(reqs[2][0]) == 0
    assert good.prefix.peek(reqs[2][0]) == len(reqs[2][0])
    assert c[REQUESTS] == 3
    assert c[TOKENS] == sum(len(w) for w in want)
    assert c["paddle_generation_failover_total"] == moved
    # the owed request's journal was empty; the two others held the
    # tokens delivered before the failure
    assert c.get("paddle_generation_replayed_tokens_total", 0.0) == \
        (0 if fails == "admit_fail" else journals)
    retired = {k: v for k, v in c.items() if v and k.startswith(
        "paddle_generation_retired_total")}
    want_retired = {"paddle_generation_retired_total{reason=max_tokens}": 3.0}
    if fails != "admit_fail":
        want_retired["paddle_generation_retired_total{reason=failover}"] = 3.0
    assert retired == want_retired
    for sess in {bad, good}:
        sess.check_pool_invariant()
        assert not sess._flights and not sess.owed()
        assert not sess.active.any()
        # what is in use is what the prefix index keeps, and nothing of
        # the prefill that failed at its fetch
        assert sess.pool.used_count() == len(sess.prefix.pinned_blocks())
    assert sched._active == {} and sched._inflight == [None] * sessions
    sched.close()
    bad.close()
    if good is not bad:
        good.close()


def test_a_step_launched_for_an_earlier_tenancy_gives_the_same_request_nothing(
        lm_scope):
    """One session, so a request that leaves its slot with a step launched
    for it (preempted for want of a block, or failed at its first token)
    replays into the slot it left, and is ``_active`` there again when that
    step is collected. The step was launched for the admission before:
    its result goes to nobody, the request's count of launched steps is
    not touched, and its stream is ``generate``'s."""
    from paddle_tpu.serving.paged_cache import PoolExhausted
    sess = _session(lm_scope, SAMPLED, slots=2)
    pa, pb = [BOS, 5, 7], [BOS, 9, 3, 4]
    want_a = sess.generate(pa, max_new_tokens=8, eos_id=-1, seed=5)
    want_b = sess.generate(pb, max_new_tokens=8, eos_id=-1, seed=6)
    sched = GenerationScheduler(sess, deadline_ms=0, replay_attempts=2,
                                autostart=False)
    fa = sched.submit(pa, max_new_tokens=8, eos_id=-1, seed=5)
    fb = sched.submit(pb, max_new_tokens=8, eos_id=-1, seed=6)
    _place_all(sched)
    sched._step_all()
    sched._step_all()                    # both decoding, a step uncollected
    b = sched._active[(0, 1)]
    stale, first = sched._inflight[0], b.admission
    assert (1, b) in stale.mine and b.ahead == 1
    # what ``_deliver`` does to a request that the pool starved
    sess.retire(1)
    del sched._active[(0, 1)]
    sched._requeue_for_replay([b], PoolExhausted("injected"))
    had = list(b.tokens)
    _place_all(sched)                    # B again, into the slot it left
    assert sched._active[(0, 1)] is b and b.admission is not first
    assert b.ahead == 0 and sess.owes(1)
    sched._step_all()                    # launches B's step, collects stale
    assert sched._active[(0, 1)] is b and b.ahead == 1
    assert b.tokens == had + [want_b[len(had)]]      # its first token alone
    _drive(sched, [fa, fb])
    assert [int(t) for t in fa.result(1)] == want_a
    assert [int(t) for t in fb.result(1)] == want_b
    assert b.replays == 1
    sess.check_pool_invariant()
    assert sess.pool.used_count() == 0 and not sess._flights
    sched.close()
    sess.close()


class _Unfetchable:
    """Stands in for a prefill's device array whose fetch fails."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("injected: the prefill's token did not arrive")


@pytest.mark.parametrize("owed", [False, True], ids=["a_step", "a_token"])
@pytest.mark.parametrize("how", ["swap_weights", "drain", "close"])
def test_what_is_in_flight_is_collected_first(lm_scope, how, owed):
    """What is launched and uncollected when something acts between two
    steps: a decode step, or (nothing stepped since the admission) a
    prefill whose first token is owed."""
    sess = _session(lm_scope)
    want = sess.generate([BOS, 5, 7], max_new_tokens=6, eos_id=-1)
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False)
    f = sched.submit([BOS, 5, 7], max_new_tokens=6, eos_id=-1)
    _place_all(sched)
    item = next(iter(sched._active.values()))
    if owed:
        assert sess.owed() == [item.admission] and not item.tokens
    else:
        sched._step_all()
        assert sched._inflight[0] is not None and len(item.tokens) == 1
    had = len(item.tokens)
    if how == "swap_weights":
        name = sorted(n for n in lm_scope.var_names()
                      if n not in sess._claimed)[0]
        sched.swap_weights({name: np.asarray(lm_scope.find_var(name))})
        assert len(item.tokens) == had + 1
    elif how == "drain":
        sched.drain()
        assert [int(t) for t in f.result(1)] == want
    else:
        sched.close()
        assert len(item.tokens) == had + 1
    assert sched._inflight == [None] and not sess._flights
    assert not sess.owed()
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
    assert c.get(OWED, 0.0) == 0
    sess.close()


@pytest.mark.parametrize("make", [_constrained, _speculative,
                                  _step_bounded])
def test_sessions_that_need_the_token_on_the_host_keep_the_old_order(
        lm_scope, make):
    """Launch, wait, book, and only then a step: an admission into a
    session at depth 0 is never entered with its token owed, and no step
    is prepared between its two phases."""
    sess, kwargs = make(lm_scope)
    calls = []
    for name in ("admit_launch", "admit_enter", "admit_collect",
                 "step_prepare"):
        def logged(*args, _name=name, _call=getattr(sess, name), **kw):
            calls.append((_name, len(sess._owed)))
            return _call(*args, **kw)
        setattr(sess, name, logged)
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False,
                                **kwargs)
    fa = sched.submit([BOS, 5, 7], max_new_tokens=5, eos_id=-1)
    _place_all(sched)
    sched._step_all()
    fb = sched.submit([BOS, 5], max_new_tokens=3, eos_id=-1)
    _drive(sched, [fa, fb])
    assert len(fa.result(1)) == 5 and len(fb.result(1)) == 3
    names = [name for name, _ in calls]
    assert names.count("admit_launch") == 2
    for i, name in enumerate(names):
        if name == "admit_launch":
            # the scheduler's own call, then the one inside admit_collect
            assert names[i + 1] == "admit_collect"
    # the only entry is admit_collect's own, after its fetch
    assert all(owed == 0 for name, owed in calls if name != "admit_enter")
    sched.close()
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
    assert c[AHEAD] > 0 and c[OWED] > 0
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
    # each inside a first_token span, which a host turn holds: the wait
    # for an owed token is admit time of a turn, like every other
    for name, parent in (("session:prefill_wait", "scheduler:first_token"),
                         ("scheduler:first_token", "scheduler:host_turn")):
        for e in (e for e in events if e["name"] == name):
            assert sum(p["ts"] - 0.5 <= e["ts"] and e["ts"] + e["dur"] <=
                       p["ts"] + p["dur"] + 0.5 for p in events
                       if p["name"] == parent) == 1


def test_the_step_behind_a_prefill_counts_as_a_step_ahead(lm_scope):
    """One iteration of the dispatcher with a step uncollected and a
    request waiting: the prefill's call, the next step's dispatch, the
    uncollected step's wait, and only then the wait for the prefill's
    first token. The step launched behind the prefill counts as a step
    ahead, and the admission as one whose token was owed."""
    sess = _session(lm_scope)
    sess.generate([BOS, 9], max_new_tokens=2, eos_id=-1)       # compile
    sched = GenerationScheduler(sess, deadline_ms=0, autostart=False)
    fa = sched.submit([BOS, 5, 7], max_new_tokens=6, eos_id=-1)
    _place_all(sched)
    sched._step_all()
    fb = sched.submit([BOS, 9], max_new_tokens=4, eos_id=-1)
    c0 = _counters()
    tracing.start(clear=True)
    try:
        _place_all(sched)
        sched._step_all()
    finally:
        tracing.stop()
    order = [e["name"] for e in sorted(
        (e for e in tracing.events() if e["ph"] == "X"),
        key=lambda e: e["ts"]) if e["name"] in (
            "session:prefill_call", "session:step_dispatch",
            "session:step_wait", "session:prefill_wait")]
    tracing.clear()
    c = _delta(_counters(), c0)
    assert order == ["session:prefill_call", "session:step_dispatch",
                     "session:step_wait", "session:prefill_wait"]
    assert c[AHEAD] == 1 and c[OWED] == 1 and c[STEPS] == 1
    assert c["paddle_generation_prefills_total{bucket=4}"] == 1
    _drive(sched, [fa, fb])
    sched.close()
    sess.close()


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
