"""Decode-policy subsystem (PR 17): counter-keyed on-device sampling,
speculative decoding with COW rollback, constrained output — and the
default-off guarantees that keep all-defaults serving byte-identical
greedy.

The determinism spine everywhere: every sampled token is keyed by
``decoding_key(request_seed, sequence_position)``, a pure function —
so a replayed journal (session rebuild, fleet failover) re-derives
the exact key for every position it regenerates, and the chaos tests
in test_generation_failover.py / test_fleet.py can demand
bit-identical output from SAMPLED runs."""

import os

import numpy as np
import pytest

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.transformer import (transformer_lm,
                                           transformer_lm_generate,
                                           transformer_lm_session)
from paddle_tpu.observability import metrics
from paddle_tpu.resilience import faults
from paddle_tpu.serving import GenerationScheduler, GenerationSession
from paddle_tpu.serving.decoding import (ConstraintDeadEnd,
                                         DecodePolicy, DFAConstraint,
                                         mint_seed)
from paddle_tpu.serving.decoding.policy import GREEDY_FINGERPRINT

pytestmark = pytest.mark.decoding

HERE = os.path.dirname(os.path.abspath(__file__))

V, MAXLEN = 29, 24
KW = dict(d_model=16, num_heads=2, d_ff=32, num_layers=2)
BOS, EOS = 0, 1


def _counter(name):
    for s in metrics.REGISTRY.dump().get(name, {}).get("samples", ()):
        return s["value"]
    return 0.0


def _lm_scope(seed=7):
    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[1, MAXLEN],
                               dtype="int64", append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, MAXLEN],
                               dtype="int64", append_batch_size=False)
            transformer_lm(toks, lbls, vocab_size=V, is_test=True,
                           **KW)
    exe = ptpu.Executor()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope):
        exe.run(startup)
    rs = np.random.RandomState(seed)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        scope.set_var(n, rs.standard_normal(cur.shape)
                      .astype(cur.dtype))
    return scope


@pytest.fixture(scope="module")
def lm_scope():
    return _lm_scope()


def _session(scope, policy, slots=2, paged=False, block_size=4,
             **over):
    kw = dict(KW)
    kw.update(over)
    spec = transformer_lm_session(
        V, max_len=MAXLEN, slots=slots, prompt_buckets=(4, 8, 16),
        bos_id=BOS, eos_id=EOS, paged=paged or None,
        block_size=block_size if paged else None,
        decode_policy=policy, **kw)
    return GenerationSession(spec, scope=scope)


# -- op level --------------------------------------------------------------

def _run_prog(build, feeds):
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.unique_name.guard(), ptpu.program_guard(main, startup):
        fetch = build()
    exe = ptpu.Executor()
    scope = ptpu.Scope()
    return exe.run(main, feed=feeds, fetch_list=list(fetch),
                   scope=scope)


class TestDecodingOps:
    def test_decoding_key_is_a_pure_counter_function(self):
        from paddle_tpu.ops.random_ops import decoding_key
        k1 = np.asarray(decoding_key(7, 3))
        k2 = np.asarray(decoding_key(7, 3))
        k3 = np.asarray(decoding_key(7, 4))
        k4 = np.asarray(decoding_key(8, 3))
        assert (k1 == k2).all()
        assert not (k1 == k3).all()
        assert not (k1 == k4).all()

    def _sample(self, logits, seeds, steps, mask=None, **attrs):
        def build():
            lg = layers.data("lg", shape=list(logits.shape),
                             dtype="float32", append_batch_size=False)
            sd = layers.data("sd", shape=[len(seeds)], dtype="int64",
                             append_batch_size=False)
            st = layers.data("st", shape=[len(steps)], dtype="int32",
                             append_batch_size=False)
            mk = None
            if mask is not None:
                mk = layers.data("mk", shape=list(mask.shape),
                                 dtype="float32",
                                 append_batch_size=False)
            return [layers.decode_sample(lg, sd, st, mask=mk, **attrs)]
        feeds = {"lg": logits.astype(np.float32),
                 "sd": np.asarray(seeds, np.int64),
                 "st": np.asarray(steps, np.int32)}
        if mask is not None:
            feeds["mk"] = mask.astype(np.float32)
        out, = _run_prog(build, feeds)
        return [int(t) for t in np.asarray(out)]

    def test_sample_deterministic_per_seed_and_step(self):
        rs = np.random.RandomState(0)
        lg = rs.standard_normal((4, V))
        a = self._sample(lg, [5, 5, 9, 9], [1, 2, 1, 2])
        b = self._sample(lg, [5, 5, 9, 9], [1, 2, 1, 2])
        assert a == b
        # the key is (seed, step): same logits row under a different
        # counter draws independently
        many_a = self._sample(np.repeat(lg[:1], 32, 0), [5] * 32,
                              list(range(32)))
        assert len(set(many_a)) > 1

    def test_top_k_one_collapses_to_argmax(self):
        rs = np.random.RandomState(1)
        lg = rs.standard_normal((3, V))
        got = self._sample(lg, [3, 4, 5], [0, 1, 2], top_k=1)
        assert got == [int(t) for t in lg.argmax(-1)]

    def test_tiny_top_p_collapses_to_argmax(self):
        rs = np.random.RandomState(2)
        lg = 5.0 * rs.standard_normal((3, V))
        got = self._sample(lg, [3, 4, 5], [0, 1, 2], top_p=1e-6)
        assert got == [int(t) for t in lg.argmax(-1)]

    def test_additive_mask_constrains_the_draw(self):
        rs = np.random.RandomState(3)
        lg = rs.standard_normal((6, V))
        mask = np.full((6, V), -1e30, np.float32)
        legal = [4, 11, 2, 27, 9, 16]
        for i, t in enumerate(legal):
            mask[i, t] = 0.0
        got = self._sample(lg, [7] * 6, list(range(6)), mask=mask)
        assert got == legal

    def _verify(self, logits, window, seed=0, hist=0, **attrs):
        W = len(window)

        def build():
            lg = layers.data("lg", shape=[1, W, V], dtype="float32",
                             append_batch_size=False)
            wd = layers.data("wd", shape=[W], dtype="int64",
                             append_batch_size=False)
            sd = layers.data("sd", shape=[1], dtype="int64",
                             append_batch_size=False)
            hs = layers.data("hs", shape=[1], dtype="int32",
                             append_batch_size=False)
            toks, accept = layers.decode_verify(lg, wd, sd, hs,
                                                **attrs)
            return [toks, accept]
        toks, accept = _run_prog(build, {
            "lg": logits.reshape(1, W, V).astype(np.float32),
            "wd": np.asarray(window, np.int64),
            "sd": np.asarray([seed], np.int64),
            "hs": np.asarray([hist], np.int32)})
        return [int(t) for t in np.asarray(toks)], int(
            np.asarray(accept).reshape(-1)[0])

    def test_verify_accepts_the_longest_matching_prefix(self):
        # target tokens (one-hot logits): [3, 7, 11]
        lg = np.zeros((3, V))
        lg[0, 3] = lg[1, 7] = lg[2, 11] = 10.0
        # window = [pending, d1, d2]; d1 == 3 matches, d2 != 7
        toks, accept = self._verify(lg, [99, 3, 5])
        assert toks == [3, 7, 11]
        assert accept == 1
        _, a_all = self._verify(lg, [99, 3, 7])
        assert a_all == 2
        _, a_none = self._verify(lg, [99, 4, 7])
        assert a_none == 0

    def test_verify_sampled_matches_row_sampling(self):
        """kind="sample" keys window row i with (seed, hist+1+i) —
        the SAME key the plain decode path would use at that
        position, which is the whole determinism argument for
        speculative sampling."""
        rs = np.random.RandomState(4)
        lg = rs.standard_normal((3, V))
        toks, _ = self._verify(lg, [0, 0, 0], seed=11, hist=5,
                               kind="sample")
        ref = TestDecodingOps._sample(
            self, lg, [11, 11, 11], [6, 7, 8])
        assert toks == ref


# -- policy + constraint objects -------------------------------------------

class TestDecodePolicy:
    def test_from_flags_is_none_at_defaults(self):
        assert DecodePolicy.from_flags() is None

    def test_from_flags_reads_the_knobs(self):
        ptpu.config.set_flags(decode_policy="sample",
                              decode_temperature=0.7, decode_top_k=5)
        try:
            pol = DecodePolicy.from_flags()
            assert pol.sampled and pol.temperature == 0.7
            assert pol.top_k == 5
        finally:
            ptpu.config.set_flags(decode_policy="greedy",
                                  decode_temperature=1.0,
                                  decode_top_k=0)
        assert DecodePolicy.from_flags() is None

    def test_speculative_greedy_is_the_greedy_fingerprint(self):
        # speculate_k/draft never change emitted tokens: members with
        # different drafts (or none) may legally share journals
        assert DecodePolicy(kind="greedy",
                            speculate_k=3).fingerprint() == \
            GREEDY_FINGERPRINT
        assert DecodePolicy().fingerprint() == GREEDY_FINGERPRINT

    def test_fingerprint_tracks_decision_knobs(self):
        a = DecodePolicy(kind="sample", temperature=0.9)
        b = DecodePolicy(kind="sample", temperature=0.9)
        c = DecodePolicy(kind="sample", temperature=0.8)
        assert a.fingerprint() == b.fingerprint()
        assert a.fingerprint() != c.fingerprint()
        d = DecodePolicy(constraint=DFAConstraint({0: {2: 0}}))
        assert d.fingerprint() != GREEDY_FINGERPRINT

    def test_invalid_combinations_rejected(self):
        with pytest.raises(ValueError):
            DecodePolicy(kind="beam")
        with pytest.raises(ValueError):
            DecodePolicy(kind="sample", temperature=0.0)
        with pytest.raises(ValueError):
            DecodePolicy(constraint=DFAConstraint({0: {2: 0}}),
                         speculate_k=2)
        with pytest.raises(ValueError):
            DecodePolicy(draft=dict(num_layers=1))

    def test_mint_seed_fits_int32(self):
        for _ in range(100):
            s = mint_seed()
            assert 0 <= s < 2 ** 31


class TestDFAConstraint:
    def test_mask_advance_dead(self):
        dfa = DFAConstraint({0: {2: 1, 3: 0}, 1: {4: 2}, 2: {}})
        tbl = dfa.mask_table(8)
        assert tbl.shape == (3, 8)
        assert tbl[0, 2] == 0.0 and tbl[0, 3] == 0.0
        assert tbl[0, 4] < -1e29
        s = dfa.advance(dfa.start, 2)
        assert not dfa.dead(s)
        assert dfa.dead(dfa.advance(s, 4))
        with pytest.raises(ValueError):
            dfa.advance(dfa.start, 7)
        assert dfa.advance_many(dfa.start, [2, 4]) == \
            dfa.advance(dfa.advance(dfa.start, 2), 4)

    def test_digest_stable_and_shape_sensitive(self):
        a = DFAConstraint({0: {2: 1}, 1: {3: 1}})
        b = DFAConstraint({0: {2: 1}, 1: {3: 1}})
        c = DFAConstraint({0: {2: 1}, 1: {4: 1}})
        assert a.digest() == b.digest()
        assert a.digest() != c.digest()


# -- reference-path parity (satellite 1) -----------------------------------

class TestSampledReferenceParity:
    @pytest.mark.slow  # two full generate-program compiles (~10 s);
    # the shared key schedule itself is tier-1-covered by the
    # decode_sample op tests + the sampled-session determinism tests
    def test_cached_sampled_session_matches_reference_stream(self):
        """transformer_lm_generate(decode="sample") and the cached
        sampled session share one threefry schedule: from a [bos]
        prompt with one seed they emit the identical stream —
        stochastic decode gets the same oracle greedy always had."""
        seed = 20260807
        temp, top_k = 0.9, 6
        with ptpu.unique_name.guard():
            main, startup = ptpu.Program(), ptpu.Program()
            with ptpu.program_guard(main, startup):
                anchor = layers.data("anchor", shape=[1],
                                     dtype="int32")
                ids, lengths, _ = transformer_lm_generate(
                    anchor, vocab_size=V, max_len=MAXLEN,
                    bos_id=BOS, eos_id=EOS, decode="sample",
                    sample_seed=seed, temperature=temp, top_k=top_k,
                    **KW)
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

        pol = DecodePolicy(kind="sample", temperature=temp,
                           top_k=top_k)
        sess = _session(scope, pol)
        try:
            got = [int(t) for t in
                   sess.generate([BOS], max_new_tokens=MAXLEN,
                                 seed=seed)]
        finally:
            sess.close()
        assert got == want
        # and the stream is genuinely stochastic: another seed differs
        sess = _session(scope, pol)
        try:
            other = [int(t) for t in
                     sess.generate([BOS], max_new_tokens=MAXLEN,
                                   seed=seed + 1)]
        finally:
            sess.close()
        assert other != got


# -- sampled sessions ------------------------------------------------------

class TestSampledSession:
    def test_generate_deterministic_per_seed(self, lm_scope):
        pol = DecodePolicy(kind="sample", temperature=1.0)
        sess = _session(lm_scope, pol)
        try:
            a = sess.generate([BOS, 5, 7], max_new_tokens=10,
                              eos_id=-1, seed=1234)
            b = sess.generate([BOS, 5, 7], max_new_tokens=10,
                              eos_id=-1, seed=1234)
            c = sess.generate([BOS, 5, 7], max_new_tokens=10,
                              eos_id=-1, seed=99)
        finally:
            sess.close()
        assert a == b
        assert a != c

    def test_mid_journal_replay_is_bit_identical(self, lm_scope):
        """Admit prompt + a PREFIX of a sampled generation (exactly
        what session rebuild and fleet failover do) and continue: the
        counter keys line up so the continuation reproduces the rest
        of the stream token-for-token."""
        pol = DecodePolicy(kind="sample", temperature=0.9)
        sess = _session(lm_scope, pol)
        try:
            full = sess.generate([BOS, 5, 7], max_new_tokens=10,
                                 eos_id=-1, seed=4321)
            cut = 4
            hist = [BOS, 5, 7] + full[:cut]
            slot, first = sess.admit(np.asarray(hist, np.int64),
                                     seed=4321)
            cont = [int(first)]
            while len(cont) < len(full) - cut:
                cont.append(int(sess.step()[slot]))
            sess.retire(slot)
        finally:
            sess.close()
        assert cont == full[cut:]

    def test_scheduler_mints_and_reuses_seeds(self, lm_scope):
        pol = DecodePolicy(kind="sample", temperature=1.0)
        sched = GenerationScheduler(_session(lm_scope, pol),
                                    autostart=False)
        assert sched.policy_fingerprint().startswith("sample:")
        f1 = sched.submit([BOS, 5, 7], max_new_tokens=8, eos_id=-1,
                          seed=777)
        f2 = sched.submit([BOS, 5, 7], max_new_tokens=8, eos_id=-1,
                          seed=777)
        f3 = sched.submit([BOS, 5, 7], max_new_tokens=8, eos_id=-1)
        sched.drain()
        assert list(f1.result(1)) == list(f2.result(1))
        assert f3.result(1) is not None

    def test_mixed_fingerprint_sessions_rejected(self, lm_scope):
        a = _session(lm_scope, DecodePolicy(kind="sample",
                                            temperature=0.9))
        b = _session(lm_scope, None)
        try:
            with pytest.raises(ValueError, match="decode policy"):
                GenerationScheduler([a, b], autostart=False)
        finally:
            a.close()
            b.close()


# -- speculative decoding --------------------------------------------------

class TestSpeculativeDecoding:
    def _pair(self, scope, policy, baseline_policy, prompt,
              max_new=12, seed=0):
        s1 = _session(scope, policy, paged=True)
        try:
            out = s1.generate(prompt, max_new_tokens=max_new,
                              eos_id=-1, seed=seed)
            s1.check_pool_invariant()
        finally:
            s1.close()
        s2 = _session(scope, baseline_policy, paged=True)
        try:
            base = s2.generate(prompt, max_new_tokens=max_new,
                               eos_id=-1, seed=seed)
        finally:
            s2.close()
        return out, base

    def test_greedy_speculative_matches_plain(self, lm_scope):
        out, base = self._pair(
            lm_scope, DecodePolicy(kind="greedy", speculate_k=3),
            None, [BOS, 5, 7])
        assert out == base

    @pytest.mark.slow  # second speculative session pair (~7 s); the
    # greedy parity test above exercises the same verify/draft path
    # in tier-1, and the sampled keys are op-tested directly
    def test_sampled_speculative_matches_plain_sampled(self,
                                                       lm_scope):
        """The determinism-preserving property: verify re-decides
        every window position with the TARGET's logits under the
        target's counter keys, so the draft can only change HOW FAST
        tokens land, never which tokens."""
        out, base = self._pair(
            lm_scope,
            DecodePolicy(kind="sample", temperature=0.8,
                         speculate_k=3),
            DecodePolicy(kind="sample", temperature=0.8),
            [BOS, 5, 7], seed=42)
        assert out == base

    def test_perfect_draft_accepts_everything(self, lm_scope):
        """A draft configured identical to the target must agree on
        every proposal — accept == k each full round, and the
        multi-token emission path (lists from step_run) is exercised
        end to end."""
        d0 = _counter("paddle_generation_speculative_drafted_total")
        a0 = _counter("paddle_generation_speculative_accepted_total")
        out, base = self._pair(
            lm_scope,
            DecodePolicy(kind="greedy", speculate_k=3,
                         draft=dict(num_layers=KW["num_layers"])),
            None, [BOS, 5, 7])
        assert out == base
        drafted = _counter(
            "paddle_generation_speculative_drafted_total") - d0
        accepted = _counter(
            "paddle_generation_speculative_accepted_total") - a0
        assert drafted > 0
        assert accepted == drafted

    def test_draft_mismatch_fault_forces_rollback(self, lm_scope):
        """decode_draft_mismatch forces a zero-accept round: every
        draft block rolls back through the COW machinery and the
        output still matches plain decode (worst-case draft)."""
        r0 = _counter(
            "paddle_generation_kv_spec_rollback_blocks_total")
        faults.arm("decode_draft_mismatch", at=0, times=2)
        try:
            out, base = self._pair(
                lm_scope,
                DecodePolicy(kind="greedy", speculate_k=3,
                             draft=dict(
                                 num_layers=KW["num_layers"])),
                None, [BOS, 5, 7])
        finally:
            faults.disarm("decode_draft_mismatch")
        assert out == base
        assert _counter(
            "paddle_generation_kv_spec_rollback_blocks_total") > r0

    def test_the_drafts_pool_holds_what_its_live_slots_need(
            self, lm_scope):
        """The draft is a session over a block pool like its target, with
        a whole table for every slot and no prefix index. Under forced
        full rejections (slot 0) beside full acceptances (slot 1) its books
        balance after every round: each block in use is in one live
        slot's table, each table covers its slot's length (a rollback is
        a truncation of ``lengths``, the blocks stay), and the draft
        stands where its target does."""
        sess = _session(lm_scope,
                        DecodePolicy(kind="greedy", speculate_k=3,
                                     draft=dict(
                                         num_layers=KW["num_layers"])),
                        paged=True)
        draft, spec = sess.draft, sess.draft.spec
        assert spec.num_blocks == spec.slots * spec.max_blocks
        assert draft.prefix is None and draft.draft is None
        assert all(shape[:2] == (spec.num_blocks, 4)
                   for _, shape, _ in spec.cache_vars)
        faults.arm("decode_draft_mismatch", at=0, times=None)
        try:
            slots = [sess.admit(p)[0] for p in ([BOS, 5, 7],
                                                [2, 3, 4, 5, 6])]
            for _ in range(4):
                sess.step()
                draft.check_pool_invariant()
                held = [b for s in slots for b in draft.tables[s]]
                assert draft.pool.used_count() == len(held) \
                    == len(set(held))
                for s in slots:
                    assert draft.lengths[s] == sess.lengths[s]
                    assert len(draft.tables[s]) * 4 >= draft.lengths[s]
            # slot 0 gained one token a round; slot 1, whose draft is
            # its target, all four
            assert sess.lengths[slots[0]] == 3 + 4
            assert sess.lengths[slots[1]] == 5 + 4 * 4
        finally:
            faults.disarm("decode_draft_mismatch")
            sess.close()

    def test_retiring_every_slot_empties_the_drafts_pool(self, lm_scope):
        sess = _session(lm_scope,
                        DecodePolicy(kind="greedy", speculate_k=3),
                        paged=True)
        draft = sess.draft
        try:
            slots = [sess.admit(p)[0] for p in ([BOS, 5, 7], [2, 3])]
            for _ in range(3):
                sess.step()
            assert draft.pool.used_count() > 0
            sess.retire(slots[0])
            draft.check_pool_invariant()
            assert draft.tables[slots[0]] == [] and draft.tables[slots[1]]
            sess.retire(slots[1])
            assert draft.pool.used_count() == 0
            assert sess.pool.used_count() == 0
            draft.check_pool_invariant()
        finally:
            sess.close()

    def test_speculative_rejects_step_timeout(self, lm_scope):
        sess = _session(lm_scope,
                        DecodePolicy(kind="greedy", speculate_k=2),
                        paged=True)
        try:
            with pytest.raises(ValueError, match="step_timeout"):
                GenerationScheduler(sess, step_timeout_ms=500,
                                    autostart=False)
        finally:
            sess.close()

    def test_unknown_draft_override_rejected(self, lm_scope):
        with pytest.raises(ValueError, match="draft"):
            transformer_lm_session(
                V, max_len=MAXLEN, slots=2, prompt_buckets=(4, 8),
                paged=True, block_size=4,
                decode_policy=DecodePolicy(
                    kind="greedy", speculate_k=2,
                    draft=dict(nonsense=3)), **KW)


# -- constrained decoding --------------------------------------------------

class TestConstrainedDecoding:
    def test_output_follows_the_dfa(self, lm_scope):
        dfa = DFAConstraint({0: {5: 1}, 1: {6: 2}, 2: {EOS: 2}})
        sched = GenerationScheduler(
            _session(lm_scope, DecodePolicy(constraint=dfa)),
            autostart=False)
        f = sched.submit([BOS, 5, 7], max_new_tokens=8)
        sched.drain()
        assert [int(t) for t in f.result(1)] == [5, 6]

    def test_dead_end_is_a_typed_client_error(self, lm_scope):
        dfa = DFAConstraint({0: {5: 1}, 1: {6: 3}, 3: {}})
        sched = GenerationScheduler(
            _session(lm_scope, DecodePolicy(constraint=dfa)),
            autostart=False)
        f = sched.submit([BOS, 5, 7], max_new_tokens=8)
        sched.drain()
        with pytest.raises(ConstraintDeadEnd):
            f.result(1)

    def test_dead_end_fault_site(self, lm_scope):
        """decode_constraint_dead_end forces the verdict on a live
        DFA: the request resolves with the typed error — never a
        hang, never a replay."""
        dfa = DFAConstraint({0: {5: 1}, 1: {6: 2}, 2: {EOS: 2}})
        faults.arm("decode_constraint_dead_end", at=0, times=1)
        try:
            sched = GenerationScheduler(
                _session(lm_scope, DecodePolicy(constraint=dfa)),
                autostart=False)
            f = sched.submit([BOS, 5, 7], max_new_tokens=8)
            sched.drain()
            with pytest.raises(ConstraintDeadEnd):
                f.result(1)
        finally:
            faults.disarm("decode_constraint_dead_end")

    def test_sampled_constrained_composes(self, lm_scope):
        dfa = DFAConstraint({0: {5: 1, 7: 1}, 1: {6: 0, 8: 0}})
        pol = DecodePolicy(kind="sample", temperature=1.0,
                           constraint=dfa)
        sess = _session(lm_scope, pol)
        try:
            out = sess.generate([BOS, 5, 7], max_new_tokens=8,
                                eos_id=-1, seed=5)
            again = sess.generate([BOS, 5, 7], max_new_tokens=8,
                                  eos_id=-1, seed=5)
        finally:
            sess.close()
        assert out == again
        legal = {0: {5, 7}, 1: {6, 8}}
        state = 0
        for t in out:
            assert t in legal[state], (t, state, out)
            state = dfa.advance(state, t)


# -- default-off + hygiene -------------------------------------------------

class TestDefaultOff:
    def test_default_spec_constructs_no_policy_machinery(self,
                                                         lm_scope):
        spec = transformer_lm_session(
            V, max_len=MAXLEN, slots=2, prompt_buckets=(4, 8),
            bos_id=BOS, eos_id=EOS, **KW)
        assert spec.policy is None
        assert spec.verify_program is None
        assert spec.draft_spec is None
        assert not any("gen.pseed" in n or "gen.dseed" in n or
                       "gen.pmask" in n or "gen.dmask" in n
                       for n in tuple(spec.prefill_feeds) +
                       tuple(spec.decode_feeds))
        sess = GenerationSession(spec, scope=lm_scope)
        try:
            assert sess.policy is None and sess.draft is None
            assert not sess.sampled and not sess.constrained
        finally:
            sess.close()

    def test_no_jax_prngkey_in_serving(self):
        """Grep-lint (satellite 2): ALL decode randomness flows
        through ops/random_ops.decoding_key — serving/ never touches
        jax.random, so there is no stateful key to lose in a crash."""
        serving = os.path.join(os.path.dirname(HERE), "paddle_tpu",
                               "serving")
        hits = []
        for dirpath, _, files in os.walk(serving):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path) as fh:
                    if "PRNGKey" in fh.read():
                        hits.append(path)
        assert not hits, hits
