"""Autoregressive generation serving: KV-cache decode parity with the
O(L^2) re-encode reference, closed compile-shape contract, and the
continuous-batching scheduler. (The cache's ops and its single-query
Pallas kernel: tests/test_paged_cache.py.)"""

import time

import numpy as np
import pytest


import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.transformer import (transformer_lm,
                                           transformer_lm_generate,
                                           transformer_lm_session)
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (GenerationScheduler, GenerationSession,
                                ServingDeadlineError,
                                ServingOverloadError)

pytestmark = pytest.mark.generation

V, MAXLEN = 29, 12
KW = dict(d_model=16, num_heads=2, d_ff=32, num_layers=2)
BOS, EOS = 0, 1


@pytest.fixture(autouse=True)
def _no_flash():
    """Every test starts from the default (XLA gather) path; flash tests
    arm the flag themselves."""
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


def _lm_scope(seed=7):
    """A scope holding randomized LM weights plus the TRAIN program
    (whose per-position logits are the re-encode oracle). Seed 7 gives
    prompt-dependent, non-constant greedy sequences — the parity test
    is not satisfied by an attractor token."""
    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[1, MAXLEN], dtype="int64",
                               append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, MAXLEN], dtype="int64",
                               append_batch_size=False)
            _, logits = transformer_lm(toks, lbls, vocab_size=V,
                                       is_test=True, **KW)
    exe = ptpu.Executor()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope):
        exe.run(startup)
    rs = np.random.RandomState(seed)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        scope.set_var(n, rs.standard_normal(cur.shape)
                      .astype(cur.dtype))
    return scope, exe, main, logits


def _reencode_greedy(exe, main, logits, scope, prompt, eos=EOS):
    """Greedy continuation by re-encoding the FULL history through the
    train program each step — the O(L^2) oracle, driven from the host
    so it works for arbitrary prompts."""
    seq = list(prompt)
    out = []
    while len(seq) <= MAXLEN:
        buf = np.zeros((1, MAXLEN), np.int64)
        buf[0, :len(seq)] = seq
        lg, = exe.run(main, feed={"toks": buf, "lbls": buf},
                      fetch_list=[logits], scope=scope)
        nxt = int(np.argmax(lg[0, len(seq) - 1]))
        out.append(nxt)
        seq.append(nxt)
        if nxt == eos:
            break
    if out and out[-1] == eos:
        out = out[:-1]
    return out


def _session(scope, slots=3, cache_len=16, prompt_buckets=(4, 8)):
    spec = transformer_lm_session(V, max_len=MAXLEN, slots=slots,
                                  cache_len=cache_len,
                                  prompt_buckets=prompt_buckets,
                                  bos_id=BOS, eos_id=EOS, **KW)
    return GenerationSession(spec, scope=scope)


# -- greedy parity vs the O(L^2) reference ---------------------------------

class TestGreedyParity:
    def test_cached_decode_token_identical_to_beam1_reference(self):
        """ISSUE satellite: the reference transformer_lm_generate
        (beam_size=1 == greedy) and the KV-cached session produce
        token-for-token identical output from BOS."""
        with ptpu.unique_name.guard():
            main, startup = ptpu.Program(), ptpu.Program()
            with ptpu.program_guard(main, startup):
                anchor = layers.data("anchor", shape=[1], dtype="int32")
                ids, lengths, _ = transformer_lm_generate(
                    anchor, vocab_size=V, max_len=MAXLEN, beam_size=1,
                    bos_id=BOS, eos_id=EOS, **KW)
        exe = ptpu.Executor()
        scope = ptpu.Scope()
        with ptpu.scope_guard(scope):
            exe.run(startup)
        rs = np.random.RandomState(7)
        for n in sorted(scope.var_names()):
            cur = np.asarray(scope.find_var(n))
            scope.set_var(n, rs.standard_normal(cur.shape)
                          .astype(cur.dtype))
        ref_ids, ref_len = exe.run(
            main, feed={"anchor": np.zeros((1, 1), "int32")},
            fetch_list=[ids, lengths], scope=scope)
        want = [int(t) for t in ref_ids[0][:int(ref_len[0])]]

        sess = _session(scope)
        got = [int(t) for t in sess.generate([BOS],
                                             max_new_tokens=MAXLEN)]
        assert got == want

    @pytest.mark.parametrize("flash", [False, True])
    def test_cached_decode_matches_reencode_for_prompts(self, flash):
        """Every prompt, every step: cached decode == full re-encode
        (dense XLA decode AND the Pallas single-query kernel)."""
        ptpu.config.set_flags(flash_attention=flash)
        scope, exe, main, logits = _lm_scope()
        sess = _session(scope)
        seqs = []
        for prompt in ([BOS], [BOS, 5, 7], [2, 3, 4, 5, 6]):
            want = _reencode_greedy(exe, main, logits, scope, prompt)
            got = [int(t) for t in sess.generate(prompt)]
            assert got == want, prompt
            seqs.append(tuple(got))
        # the weights are chosen so outputs are prompt-dependent —
        # an attractor token cannot fake this parity
        assert len(set(seqs)) == len(seqs)

    def test_compile_once_per_shape_across_requests(self):
        """Acceptance: exactly one executor compile per
        (batch-bucket, cache-bucket) decode shape plus one per prompt
        bucket used — no per-step or per-length recompiles across a
        multi-request, mid-flight-admit run."""
        scope, exe, main, logits = _lm_scope()
        sess = _session(scope, prompt_buckets=(4, 8))
        sess.generate([BOS], max_new_tokens=4)            # bucket 4
        stats0 = sess.compile_stats()
        assert stats0 == {"entries": 2, "compiles": 2}
        # continuous batching with staggered depths + a second bucket
        s1, _ = sess.admit([2, 3])                        # bucket 4
        sess.step()
        s2, _ = sess.admit([2, 3, 4, 5, 6])               # bucket 8
        for _ in range(3):
            sess.step()
        sess.retire(s1)
        s3, _ = sess.admit([BOS])                         # mid-flight
        sess.step()
        sess.retire(s2)
        sess.retire(s3)
        stats1 = sess.compile_stats()
        # one NEW compile (the 8-bucket prefill); decode reused for
        # every step at every mix of depths
        assert stats1 == {"entries": 3, "compiles": 3}
        sess.generate([4, 5, 6, 7], max_new_tokens=5)
        assert sess.compile_stats() == stats1


# -- continuous batching ---------------------------------------------------

class TestContinuousBatching:
    def test_mid_flight_admit_and_retire_no_flush(self):
        """Acceptance: a sequence admitted while others are mid-decode
        and one retired mid-flight produce EXACTLY the tokens they
        produce when decoded alone — slot isolation, no batch flush."""
        scope, exe, main, logits = _lm_scope()
        solo = {}
        for p in ((BOS,), (2, 3), (4, 5, 6)):
            solo[p] = _reencode_greedy(exe, main, logits, scope,
                                       list(p))[:6]
        sess = _session(scope, slots=2, prompt_buckets=(4,))
        got = {}
        sA, tA = sess.admit([BOS])
        toksA = [tA]
        for _ in range(2):
            toksA.append(sess.step()[sA])          # A decodes alone
        sB, tB = sess.admit([2, 3])                # admit mid-decode
        toksB = [tB]
        for _ in range(3):
            step = sess.step()                     # A and B co-decode
            toksA.append(step[sA])
            toksB.append(step[sB])
        sess.retire(sA)                            # retire mid-flight
        got[(BOS,)] = toksA[:6]
        sC, tC = sess.admit([4, 5, 6])             # reuses A's slot
        assert sC == sA
        toksC = [tC]
        for _ in range(2):
            step = sess.step()                     # B keeps decoding
            toksB.append(step[sB])
            toksC.append(step[sC])
        got[(2, 3)] = toksB[:6]
        got[(4, 5, 6)] = toksC[:3]
        for p, toks in got.items():
            want = solo[p][:len(toks)]
            assert [int(t) for t in toks] == want, p

    def test_scheduler_interleaves_and_matches_solo(self):
        scope, exe, main, logits = _lm_scope()
        solo = {p: _reencode_greedy(exe, main, logits, scope,
                                    list(p))[:6]
                for p in ((BOS,), (2, 3), (4, 5, 6))}
        sess = _session(scope, slots=2, prompt_buckets=(4,))
        sched = GenerationScheduler(sess)
        try:
            futs = {p: sched.submit(list(p), max_new_tokens=6)
                    for p in solo}
            for p, f in futs.items():
                got = [int(t) for t in f.result(timeout=60)]
                assert got == solo[p][:len(got)], p
                assert len(got) >= min(6, len(solo[p]))
        finally:
            sched.close()

    def test_scheduler_drain_serves_accepted(self):
        scope, _, _, _ = _lm_scope()
        sess = _session(scope, slots=2, prompt_buckets=(4,))
        sched = GenerationScheduler(sess, autostart=False)
        futs = [sched.submit([BOS], max_new_tokens=3)
                for _ in range(4)]
        sched.start()
        sched.drain()
        for f in futs:
            assert len(f.result(timeout=1)) >= 1

    def test_scheduler_close_fails_queued(self):
        scope, _, _, _ = _lm_scope()
        sess = _session(scope, slots=1, prompt_buckets=(4,))
        sched = GenerationScheduler(sess, autostart=False)
        fut = sched.submit([BOS], max_new_tokens=2)
        sched.close()
        with pytest.raises(RuntimeError, match="closed"):
            fut.result(timeout=1)
        with pytest.raises(RuntimeError, match="closed"):
            sched.submit([BOS])


# -- deadlines / backpressure / failure ------------------------------------

class TestSchedulerResilience:
    def test_expired_deadline_never_reaches_a_slot(self):
        scope, _, _, _ = _lm_scope()
        sess = _session(scope, slots=1, prompt_buckets=(4,))
        sched = GenerationScheduler(sess, autostart=False)
        fut = sched.submit([BOS], deadline_ms=1)
        time.sleep(0.02)
        prefills = sess.compile_stats()["compiles"]
        sched.start()
        with pytest.raises(ServingDeadlineError):
            fut.result(timeout=5)
        assert sess.compile_stats()["compiles"] == prefills
        sched.close()

    def test_queued_deadline_expires_while_all_slots_busy(self):
        """A doomed queued request resolves AT its deadline even while
        every slot is held by a long generation — the slot-starved
        stretch must not suspend the deadline contract."""
        scope, _, _, _ = _lm_scope()
        sess = _session(scope, slots=1, prompt_buckets=(4,))
        sched = GenerationScheduler(sess)
        try:
            long_fut = sched.submit([BOS], max_new_tokens=11,
                                    eos_id=-1)
            doomed = sched.submit([BOS], deadline_ms=30, eos_id=-1)
            t0 = time.perf_counter()
            with pytest.raises(ServingDeadlineError):
                doomed.result(timeout=10)
            # resolved near its 30 ms budget, not after the ~long
            # generation ahead of it finished
            assert time.perf_counter() - t0 < 5.0
            assert len(long_fut.result(timeout=60)) == 11
        finally:
            sched.close()

    def test_placement_respects_token_budget_capacity(self):
        """A request routes to a session that can serve its FULL token
        budget — a smaller-cache session listed first must not grab it
        and silently retire it early with reason 'capacity'."""
        scope, _, _, _ = _lm_scope()
        tiny = GenerationSession(transformer_lm_session(
            V, max_len=6, slots=1, cache_len=6, prompt_buckets=(4,),
            bos_id=BOS, eos_id=EOS, **KW), scope=scope)
        big = GenerationSession(transformer_lm_session(
            V, max_len=MAXLEN, slots=1, cache_len=MAXLEN,
            prompt_buckets=(4,), bos_id=BOS, eos_id=EOS, **KW),
            scope=scope)
        sched = GenerationScheduler([tiny, big])
        try:
            got = sched.submit([BOS], max_new_tokens=10,
                               eos_id=-1).result(timeout=60)
            assert len(got) == 10
        finally:
            sched.close()

    def test_duplicate_cache_claim_rejected(self):
        """Two sessions sharing one spec on one scope would silently
        corrupt each other's KV state — construction refuses, and
        close() releases the claim."""
        scope, _, _, _ = _lm_scope()
        spec = transformer_lm_session(V, max_len=MAXLEN, slots=2,
                                      cache_len=16, prompt_buckets=(4,),
                                      bos_id=BOS, eos_id=EOS, **KW)
        sess = GenerationSession(spec, scope=scope)
        with pytest.raises(ValueError, match="already driven"):
            GenerationSession(spec, scope=scope)
        sess.close()
        sess2 = GenerationSession(spec, scope=scope)  # claim released
        assert sess2.generate([BOS], max_new_tokens=2)
        sess2.close()

    def test_negative_budget_rejected_synchronously(self):
        scope, _, _, _ = _lm_scope()
        sched = GenerationScheduler(
            _session(scope, slots=1, prompt_buckets=(4,)),
            autostart=False)
        with pytest.raises(ServingDeadlineError):
            sched.submit([BOS], deadline_ms=-5)
        sched.close()

    def test_full_queue_backpressure(self):
        scope, _, _, _ = _lm_scope()
        sched = GenerationScheduler(
            _session(scope, slots=1, prompt_buckets=(4,)),
            max_queue=1, autostart=False)
        sched.submit([BOS])
        with pytest.raises(ServingOverloadError):
            sched.submit([BOS], timeout=0.01)
        sched.close()

    def test_step_failure_opens_breaker_and_fails_requests(self):
        scope, _, _, _ = _lm_scope()
        sess = _session(scope, slots=2, prompt_buckets=(4,))
        sched = GenerationScheduler(sess, breaker_failures=1,
                                    breaker_cooldown_ms=60000.0)
        try:
            faults.arm("generation_step_fail", times=1)
            fut = sched.submit([BOS], max_new_tokens=6)
            with pytest.raises(faults.InjectedFault):
                fut.result(timeout=30)
            deadline = time.monotonic() + 5
            while sched.session_health() != ["open"] and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert sched.session_health() == ["open"]
            # quarantined: admission refuses rather than wedging
            from paddle_tpu.serving import ServingUnavailableError
            fut2 = sched.submit([BOS], max_new_tokens=2)
            with pytest.raises(ServingUnavailableError):
                fut2.result(timeout=30)
        finally:
            faults.disarm()
            sched.close()

    def test_swap_weights_between_steps(self):
        """The deploy-tier story composed with sessions: new values
        land on a step boundary; requests admitted after the swap
        decode with the new weights."""
        scope, exe, main, logits = _lm_scope(seed=7)
        scope2, exe2, main2, logits2 = _lm_scope(seed=11)
        want_old = _reencode_greedy(exe, main, logits, scope, [BOS])[:4]
        want_new = _reencode_greedy(exe2, main2, logits2, scope2,
                                    [BOS])[:4]
        sess = _session(scope, slots=2, prompt_buckets=(4,))
        sched = GenerationScheduler(sess)
        try:
            old = [int(t) for t in
                   sched.submit([BOS], max_new_tokens=4)
                   .result(timeout=60)]
            assert old == want_old[:len(old)]
            params = {n: np.asarray(scope2.find_var(n))
                      for n in scope2.var_names()}
            version = sched.swap_weights(params)
            assert version == 1
            new = [int(t) for t in
                   sched.submit([BOS], max_new_tokens=4)
                   .result(timeout=60)]
            assert new == want_new[:len(new)]
        finally:
            sched.close()

    def test_swap_rejects_bad_push(self):
        scope, _, _, _ = _lm_scope()
        sess = _session(scope, slots=1, prompt_buckets=(4,))
        sched = GenerationScheduler(sess, autostart=False)
        try:
            with pytest.raises(ValueError, match="unknown variable"):
                sched.swap_weights({"nope": np.zeros(3, "float32")})
            with pytest.raises(ValueError, match="signature mismatch"):
                sched.swap_weights(
                    {"tok_embedding": np.zeros((2, 2), "float32")})
            with pytest.raises(ValueError, match="cache variable"):
                name = sess.spec.cache_vars[0][0]
                shape = sess.spec.cache_vars[0][1]
                sched.swap_weights({name: np.zeros(shape, "float32")})
            assert sched.weights_version == 0
        finally:
            sched.close()


# -- off-by-default guarantee ----------------------------------------------

class TestDefaultOff:
    def test_flags_exist_with_defaults(self):
        assert ptpu.config.get_flag("generation_slots") == 4
        assert tuple(ptpu.config.get_flag(
            "generation_cache_buckets")) == (128,)
        assert tuple(ptpu.config.get_flag(
            "generation_prompt_buckets")) == (16,)

    def test_executor_step_consults_no_generation_flag(self, monkeypatch):
        """The default executor step (and therefore the serving fast
        path built on it) never reads a generation flag — generation
        costs nothing until a session is constructed."""
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            x = layers.data("x", shape=[4])
            out = layers.fc(x, 3)
        exe = ptpu.Executor()
        exe.run(startup)
        calls = []
        orig = ptpu.config.get_flag

        def counting(name):
            calls.append(name)
            return orig(name)

        monkeypatch.setattr(ptpu.config, "get_flag", counting)
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[out])
        assert not [c for c in calls if c.startswith("generation")]


# -- perf: cached decode beats the O(L^2) re-encode (slow) -----------------

@pytest.mark.slow
class TestDecodeBeatsReencode:
    def test_speedup_at_64_and_growing_with_length(self):
        """Acceptance: cached decode tokens/sec beats the re-encode
        baseline at generation length >= 64, and the speedup grows
        with length (O(L) vs O(L^2))."""
        # big enough that re-encode compute dominates dispatch overhead
        # on CPU (measured ~3x at 64, ~5.5x at 128 — margin over noise)
        kw = dict(d_model=256, num_heads=4, d_ff=1024, num_layers=2)
        vocab = 64
        results = {}
        for length in (64, 128):
            with ptpu.unique_name.guard():
                main, startup = ptpu.Program(), ptpu.Program()
                with ptpu.program_guard(main, startup):
                    anchor = layers.data("anchor", shape=[1],
                                         dtype="int32")
                    ids, _, _ = transformer_lm_generate(
                        anchor, vocab_size=vocab, max_len=length,
                        beam_size=1, bos_id=BOS, eos_id=EOS, **kw)
            exe = ptpu.Executor()
            scope = ptpu.Scope()
            with ptpu.scope_guard(scope):
                exe.run(startup)
            anchor_v = np.zeros((1, 1), "int32")
            exe.run(main, feed={"anchor": anchor_v},
                    fetch_list=[ids], scope=scope)       # warm compile
            t0 = time.perf_counter()
            exe.run(main, feed={"anchor": anchor_v},
                    fetch_list=[ids], scope=scope)
            reencode_tps = length / (time.perf_counter() - t0)

            spec = transformer_lm_session(
                vocab, max_len=length, slots=1, cache_len=length,
                prompt_buckets=(8,), bos_id=BOS, eos_id=EOS, **kw)
            sess = GenerationSession(spec, scope=scope)
            # disable EOS stopping so both paths decode full length
            sess.generate([BOS], max_new_tokens=length,
                          eos_id=-1)                     # warm compile
            t0 = time.perf_counter()
            toks = sess.generate([BOS], max_new_tokens=length,
                                 eos_id=-1)
            cached_tps = len(toks) / (time.perf_counter() - t0)
            results[length] = cached_tps / reencode_tps
        assert results[64] > 1.0, results
        assert results[128] > results[64], results
