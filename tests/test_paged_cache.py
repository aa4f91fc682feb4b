"""Paged KV cache with prefix reuse: block-pool allocator accounting,
paged op/kernel correctness, greedy token parity with the re-encode oracle,
copy-on-write divergence isolation, shared-prefix suffix-only prefill,
pool-exhaustion capacity retirement, PR-9 failover over the paged
pool, and the fixed-budget concurrency win."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.models.transformer import (transformer_lm,
                                           transformer_lm_session)
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (BlockPool, GenerationScheduler,
                                GenerationSession, PoolExhausted,
                                PrefixIndex)

pytestmark = [pytest.mark.generation, pytest.mark.paged]

V, MAXLEN = 29, 12
KW = dict(d_model=16, num_heads=2, d_ff=32, num_layers=2)
BOS, EOS = 0, 1


@pytest.fixture(autouse=True)
def _no_flash():
    prev = ptpu.config.get_flag("flash_attention")
    ptpu.config.set_flags(flash_attention=False)
    yield
    ptpu.config.set_flags(flash_attention=prev)


def _lm_scope(seed=7, max_len=MAXLEN):
    """Randomized LM weights + the train program whose per-position
    logits are the re-encode oracle (the test_generation idiom)."""
    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[1, max_len],
                               dtype="int64", append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, max_len],
                               dtype="int64", append_batch_size=False)
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


def _reencode_greedy(exe, main, logits, scope, prompt, eos=EOS,
                     max_len=MAXLEN):
    seq = list(prompt)
    out = []
    while len(seq) <= max_len:
        buf = np.zeros((1, max_len), np.int64)
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


def _paged_session(scope, slots=3, cache_len=16, prompt_buckets=(4, 8),
                   block_size=4, num_blocks=None, prefix_cache=True):
    spec = transformer_lm_session(
        V, max_len=MAXLEN, slots=slots, cache_len=cache_len,
        prompt_buckets=prompt_buckets, bos_id=BOS, eos_id=EOS,
        paged=True, block_size=block_size, num_blocks=num_blocks,
        prefix_cache=prefix_cache, **KW)
    return GenerationSession(spec, scope=scope)


# -- block-pool allocator --------------------------------------------------

class TestBlockPool:
    def test_alloc_refcount_free_cycle(self):
        pool = BlockPool(4, 8)
        a = pool.alloc()
        b = pool.alloc()
        assert pool.used_count() == 2 and pool.free_count() == 2
        pool.incref(a)
        assert not pool.decref(a)      # still referenced
        assert pool.decref(a)          # now freed
        assert pool.free_count() == 3
        assert pool.decref(b)
        assert pool.free_count() == 4
        pool.check_invariant([])

    def test_exhaustion_raises(self):
        pool = BlockPool(2, 4)
        pool.alloc()
        pool.alloc()
        with pytest.raises(PoolExhausted):
            pool.alloc()

    def test_double_free_is_loud(self):
        pool = BlockPool(2, 4)
        a = pool.alloc()
        pool.decref(a)
        with pytest.raises(RuntimeError):
            pool.decref(a)

    def test_invariant_catches_leak(self):
        pool = BlockPool(3, 4)
        a = pool.alloc()
        # a table that lost the reference: the invariant must fail
        with pytest.raises(AssertionError):
            pool.check_invariant([[]])
        pool.check_invariant([[a]])    # balanced books pass


class TestPrefixIndex:
    def test_full_chunk_chain_match(self):
        pool = BlockPool(8, 4)
        idx = PrefixIndex(pool)
        toks = np.arange(10, 20)       # 10 tokens, bs 4
        table = [pool.alloc(), pool.alloc(), pool.alloc()]
        idx.register(toks, table)
        # full chunks + exact tail prefix
        m, blocks = idx.match(toks)
        assert m == 10 and blocks == table
        # diverging second chunk: only the first block matches
        other = np.concatenate([toks[:4], [99, 98, 97, 96]])
        m, blocks = idx.match(other)
        assert m == 4 and blocks == table[:1]
        # same tokens after a DIFFERENT first chunk: chain hash
        # refuses (context is part of a block's identity)
        shifted = np.concatenate([[5, 5, 5, 5], toks[4:8]])
        m, blocks = idx.match(shifted)
        assert m == 0 and blocks == []

    def test_partial_tail_longest_common_prefix(self):
        pool = BlockPool(8, 4)
        idx = PrefixIndex(pool)
        toks = np.asarray([1, 2, 3, 4, 7, 8, 9])   # tail (7, 8, 9)
        table = [pool.alloc(), pool.alloc()]
        idx.register(toks, table)
        m, blocks = idx.match(np.asarray([1, 2, 3, 4, 7, 8, 5, 5]))
        assert m == 6 and blocks == table        # 4 full + 2 of tail
        m, blocks = idx.match(np.asarray([1, 2, 3, 4, 5]))
        assert m == 4 and blocks == table[:1]    # tail diverges at 0

    def test_eviction_frees_only_pin_only_blocks(self):
        pool = BlockPool(2, 4)
        idx = PrefixIndex(pool)
        toks = np.arange(8)
        table = [pool.alloc(), pool.alloc()]
        idx.register(toks, table)      # both pinned, refcount 2
        assert idx.evictable_count() == 0
        assert not idx.evict_one()     # live references: nothing evictable
        pool.decref(table[0])          # sequence releases block 0
        assert idx.evictable_count() == 1
        assert idx.evict_one()
        assert pool.free_count() == 1
        pool.check_invariant([[table[1]]], idx)


# -- paged device ops ------------------------------------------------------

class TestPagedOps:
    def _run(self, build, feeds, cache_shape):
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            build(main)
        scope = ptpu.Scope()
        scope.set_var("pool", jnp.zeros(cache_shape, jnp.float32))
        ptpu.Executor().run(main, feed=feeds, fetch_list=[],
                            scope=scope)
        return np.asarray(scope.find_var("pool"))

    def test_write_paged_scatters_through_table_and_drops_padding(self):
        NB, BS, D = 5, 4, 3
        rs = np.random.RandomState(0)
        newv = rs.randn(1, 6, D).astype("float32")

        def build(main):
            block = main.global_block()
            block.create_var(name="pool", shape=(NB, BS, D),
                             persistable=True, stop_gradient=True)
            new = layers.data("new", shape=[1, 6, D],
                              append_batch_size=False)
            tab = layers.data("tab", shape=[3], dtype="int32",
                              append_batch_size=False)
            hist = layers.data("hist", shape=[1], dtype="int32",
                               append_batch_size=False)
            ln = layers.data("ln", shape=[1], dtype="int32",
                             append_batch_size=False)
            block.append_op(type="kv_cache_write_paged",
                            inputs={"Cache": ["pool"],
                                    "New": [new.name],
                                    "Table": [tab.name],
                                    "Hist": [hist.name],
                                    "Len": [ln.name]},
                            outputs={"Out": ["pool"]})

        # hist=2: rows land at logical positions 2..5 through table
        # [3, 1, NB]; only Len=4 of the 6 window rows are real
        table = np.asarray([3, 1, NB], np.int32)
        got = self._run(build, {"new": newv, "tab": table,
                                "hist": np.asarray([2], np.int32),
                                "ln": np.asarray([4], np.int32)},
                        (NB, BS, D))
        want = np.zeros((NB, BS, D), "float32")
        for i in range(4):                       # rows 0..3 of window
            pos = 2 + i
            want[table[pos // BS], pos % BS] = newv[0, i]
        np.testing.assert_allclose(got, want)

    def test_append_paged_dead_entry_drops_write(self):
        NB, BS, D, S = 4, 4, 3, 3
        rs = np.random.RandomState(1)
        onev = rs.randn(S, 1, D).astype("float32")

        def build(main):
            block = main.global_block()
            block.create_var(name="pool", shape=(NB, BS, D),
                             persistable=True, stop_gradient=True)
            one = layers.data("one", shape=[S, 1, D],
                              append_batch_size=False)
            pos = layers.data("pos", shape=[S], dtype="int32",
                              append_batch_size=False)
            tab = layers.data("tab", shape=[S, 2], dtype="int32",
                              append_batch_size=False)
            block.append_op(type="kv_cache_append_paged",
                            inputs={"Cache": ["pool"],
                                    "New": [one.name],
                                    "Pos": [pos.name],
                                    "Table": [tab.name]},
                            outputs={"Out": ["pool"]})

        posv = np.asarray([5, 2, 1], np.int32)
        tabv = np.asarray([[0, 2], [1, 0], [NB, NB]], np.int32)
        got = self._run(build, {"one": onev, "pos": posv, "tab": tabv},
                        (NB, BS, D))
        want = np.zeros((NB, BS, D), "float32")
        want[2, 1] = onev[0, 0]       # slot 0: pos 5 -> block 2 row 1
        want[1, 2] = onev[1, 0]       # slot 1: pos 2 -> block 1 row 2
        # slot 2: dead table entry (NB) -> write dropped entirely
        np.testing.assert_allclose(got, want)

    def test_block_copy(self):
        NB, BS, D = 4, 4, 3
        rs = np.random.RandomState(2)
        init = rs.randn(NB, BS, D).astype("float32")

        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            block = main.global_block()
            block.create_var(name="pool", shape=(NB, BS, D),
                             persistable=True, stop_gradient=True)
            src = layers.data("src", shape=[1], dtype="int32",
                              append_batch_size=False)
            dst = layers.data("dst", shape=[1], dtype="int32",
                              append_batch_size=False)
            block.append_op(type="kv_block_copy",
                            inputs={"Cache": ["pool"],
                                    "Src": [src.name],
                                    "Dst": [dst.name]},
                            outputs={"Out": ["pool"]})
        scope = ptpu.Scope()
        scope.set_var("pool", jnp.asarray(init))
        ptpu.Executor().run(
            main, feed={"src": np.asarray([1], np.int32),
                        "dst": np.asarray([3], np.int32)},
            fetch_list=[], scope=scope)
        got = np.asarray(scope.find_var("pool"))
        want = init.copy()
        want[3] = init[1]
        np.testing.assert_allclose(got, want)


# toy pool geometry of the kernel cases: 2 heads of 8, blocks of 4 rows,
# table rows of 7 blocks, and a page budget cut so that a compute block
# holds _P pages (the real budget would hold a whole toy table row)
_H, _HD, _NB, _BS, _MB, _P = 2, 8, 40, 4, 7, 2
_DEAD = (_NB, _NB + 1000)       # both mark a dead table entry


def _kernel_cases():
    """(id, lengths per slot, slots with a dead table row, share)."""
    full = _MB * _BS
    edge = [1, _BS, _BS + 1, _P * _BS, _P * _BS + 1, full]
    cases = [("len%d" % n, [n, 2 * _P * _BS + 3, n], (), False)
             for n in edge]
    cases += [
        # the host's inactive slot (length 1) and a starved one (its
        # length, no blocks), first, last and between live slots
        ("inactive", [1, 9, full, 1, 13, full - 1], (0, 3, 5), False),
        ("all_inactive", [1, 1, 5], (0, 1, 2), False),
        # prefix cache: two slots name the same physical blocks
        ("shared", [11, 11, 6], (), True),
        # 7 blocks a row, 2 a compute block: the last holds one page
        ("odd_max_blocks", [full, full - _BS, full - _BS + 1], (), False),
    ]
    return [pytest.param(*c[1:], id=c[0]) for c in cases]


def _block_cases():
    """(id, pages a block, lengths, dead slots, the walk's keywords) of the
    blocks' two paths: blocks of ``_BS`` rows, ``P`` pages a block."""
    P, R = 3, 3 * _BS           # pages and rows of a block
    cases = [
        # one full block a slot and no partial one: each hands its
        # successor a full first block
        ("a_block", P, [R, R, R], (), {}),
        ("a_block_and_a_page", P, [R + 1, R + _BS, R + 1], (), {}),
        ("two_blocks", P, [2 * R, 2 * R - _BS + 1, 2 * R], (), {}),
        ("three_blocks_and_one", P, [3 * R, R, 3 * R + 2, 1], (), {}),
        ("one_page", P, [1, _BS, 2], (), {}),
        ("none", P, [R, 2 * R], (0, 1), {}),
        # a starved slot between two live ones: the first block of the
        # slot behind it, a full one, is started by that slot itself
        ("starved_between", P, [2 * R, 7, R + 2, 2 * R], (1,), {}),
        ("dead_first_and_last", P, [5, R, R + 1, 9], (0, 3), {}),
        # the window's edge in the first page of a full block (rows 13-22
        # of 23: pages 3, 4, 5), of a partial one, and behind two blocks
        ("window_edge_in_a_full_block", P, [23, 24, 21], (),
         {"window": 10}),
        ("window_of_two_blocks", P, [2 * R + 9, 50, 7], (),
         {"window": 2 * R - 2}),
        # an aligned window's walk and a plain one with their statistics:
        # two and three full blocks, and the rows just past them
        ("aligned_two_blocks", P, [4 * R, 2 * R, 4 * R + 1], (),
         {"window": 2 * R, "aligned": True, "stats": True}),
        ("aligned_three_blocks", P, [6 * R, 3 * R, 3 * R + 2], (),
         {"window": 3 * R, "aligned": True, "stats": True}),
        ("stats_two_and_three_blocks", P, [2 * R, 3 * R, 3 * R + 5, 0],
         (3,), {"stats": True}),
        # four query heads on two KV heads
        ("grouped_queries", P, [R, 2 * R + 1, 3, 2 * R], (),
         {"num_kv_heads": 2}),
        ("grouped_queries_window", P, [23, 2 * R + 9, R], (),
         {"num_kv_heads": 2, "window": 10}),
        # a latent pool (the value is the head of the key's row), blocks of
        # 13 pages: one, one and a page, two, less than one
        ("latent_13_pages", 13, [13 * _BS, 13 * _BS + 1, 26 * _BS, 3], (),
         {"num_kv_heads": 1, "v_width": 16, "scale": 0.2}),
        ("latent_starved", 13, [26 * _BS, 9, 13 * _BS], (1,),
         {"num_kv_heads": 1, "v_width": 16, "scale": 0.2}),
    ]
    return [pytest.param(*c[1:], id=c[0]) for c in cases]


def _strict_interpreter():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.InterpretParams(uninitialized_memory="nan",
                                 out_of_bounds_reads="raise",
                                 detect_races=True)


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("interpret", [True, _strict_interpreter],
                             ids=["interpreter", "strict"])
    @pytest.mark.parametrize("pool,query,tol", [
        ("float32", "float32", 1e-5), ("bfloat16", "float32", 1e-5),
        # equal dtypes go to the MXU as they are: bf16's own rounding
        ("bfloat16", "bfloat16", 2e-2)],
        ids=["f32_pool", "bf16_pool", "bf16_pool_bf16_query"])
    @pytest.mark.parametrize("lengths,dead,share", _kernel_cases())
    def test_live_page_walk_matches_dense_gather(
            self, monkeypatch, lengths, dead, share, pool, query, tol,
            interpret):
        """The Pallas kernel walks each slot's live pages through
        hand-issued copies; unreferenced pool blocks are NaN-poisoned so
        a stray fetch (wrong block, a dead page feeding compute) fails
        loudly instead of averaging in. The strict interpreter also
        poisons VMEM that no copy filled, raises on a read outside the
        pool and checks that no copy is in flight when its buffer is
        read."""
        from paddle_tpu.ops import pallas_attention as pa
        if interpret is not True:
            interpret = interpret()
        D = _H * _HD
        monkeypatch.setattr(
            pa, "_PAGED_BUFFER_BYTES",
            4 * _P * _BS * D * jnp.dtype(pool).itemsize)
        assert pa._paged_block_pages(_BS, D, pool, _MB) == _P
        rs = np.random.RandomState(0)
        S = len(lengths)
        lengths = np.asarray(lengths, np.int32)
        tables = np.full((S, _MB), _DEAD[0], np.int32)
        tables[:, 1::2] = _DEAD[1]
        pool_k = np.full((_NB, _BS, D), np.nan, "float32")
        pool_v = np.full((_NB, _BS, D), np.nan, "float32")
        free = iter(rs.permutation(_NB))        # scattered, unordered
        for s in range(S):
            if s in dead:
                continue
            for j in range(-(-int(lengths[s]) // _BS)):
                if share and s == 1:
                    tables[1, j] = tables[0, j]
                    continue
                b = next(free)
                tables[s, j] = b
                pool_k[b] = rs.randn(_BS, D)
                pool_v[b] = rs.randn(_BS, D)
        q = jnp.asarray(rs.randn(S, 1, D), query)
        out = np.asarray(pa.decode_attention_paged(
            q, jnp.asarray(pool_k, pool), jnp.asarray(pool_v, pool),
            jnp.asarray(lengths), jnp.asarray(tables), _H,
            interpret=interpret), np.float32)
        assert np.isfinite(out).all()
        assert (out[list(dead)] == 0).all()
        # reference on pools with the NaNs zeroed (the dense gather
        # touches masked rows; the kernel must match its live math)
        ref = pa._decode_paged_reference(
            q, jnp.asarray(np.nan_to_num(pool_k), pool),
            jnp.asarray(np.nan_to_num(pool_v), pool),
            jnp.asarray(lengths), jnp.asarray(tables), _H)
        np.testing.assert_allclose(out, np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("interpret", [True, _strict_interpreter],
                             ids=["interpreter", "strict"])
    @pytest.mark.parametrize("pool", ["float32", "bfloat16"])
    @pytest.mark.parametrize("pages,lengths,dead,kw", _block_cases())
    def test_full_blocks_and_partial_ones_walk_by_their_own_paths(
            self, monkeypatch, pages, lengths, dead, kw, pool, interpret):
        """A block all of whose ``pages`` pages are live is awaited once a
        pool, through the buffer half's own descriptor; a walk's last,
        partial block waits page by page; a slot hands its successor's
        first block over and that slot awaits it by either. The
        lengths put both paths and their seams side by side, under a
        window, an aligned window, with the statistics, on a latent pool
        and under grouped queries. Every block no table names, the pages
        behind a window and the strict interpreter's unfilled VMEM hold
        NaN: a page awaited but never started, or started and never
        awaited (its bytes left on the semaphore for the next block's
        wait), shows as a NaN or as a race."""
        from paddle_tpu.ops import pallas_attention as pa
        if interpret is not True:
            interpret = interpret()
        H, HD, BS = 4, 8, _BS
        nkv = kw.get("num_kv_heads", H)
        width = 24 if kw.get("v_width") else nkv * HD
        mb = max(-(-max(lengths) // BS), pages) + 1
        monkeypatch.setattr(
            pa, "_PAGED_BUFFER_BYTES",
            (2 if kw.get("v_width") else 4) * pages * BS * width
            * jnp.dtype(pool).itemsize)
        rs = np.random.RandomState(1)
        S = len(lengths)
        lens = np.asarray(lengths, np.int32)
        nb = S * mb + 3
        tables = np.full((S, mb), nb + 7, np.int32)
        pools = [np.full((nb, BS, width), np.nan, "float32")
                 for _ in range(1 if kw.get("v_width") else 2)]
        free = iter(rs.permutation(nb))
        window = kw.get("window")
        for s in range(S):
            if s in dead:
                continue
            edge = 0 if window is None else int(pa.window_edge(
                lens[s], window, kw.get("aligned", False)))
            for j in range(edge // BS, -(-int(lens[s]) // BS)):
                tables[s, j] = b = next(free)
                for held in pools:
                    held[b] = rs.randn(BS, width)
        # a walk to be merged runs on a query of the pools' own dtype (the
        # reference rounds its weights to it): bfloat16's own rounding
        query = pool if kw.get("stats") else "float32"
        tol = 2e-2 if query == "bfloat16" else 1e-5
        q = jnp.asarray(rs.randn(S, 1, H * width // nkv), query)
        args = (jnp.asarray(lens), jnp.asarray(tables), H)
        got = pa.decode_attention_paged(
            q, *[jnp.asarray(x, pool) for x in pools],
            *([None] if len(pools) == 1 else []), *args,
            interpret=interpret, **kw)
        want = pa._decode_paged_reference(
            q, *[jnp.asarray(np.nan_to_num(x), pool) for x in pools],
            *([None] if len(pools) == 1 else []), *args, **kw)
        live = [s for s in range(S) if s not in dead]
        for g, w in zip(*([got, want] if kw.get("stats")
                          else [[got], [want]])):
            g = np.asarray(g, np.float32)
            assert np.isfinite(g).all()
            np.testing.assert_allclose(
                g[live], np.asarray(w, np.float32)[live], atol=tol, rtol=tol)
        out = np.asarray(got[0] if kw.get("stats") else got)
        assert (out[list(dead)] == 0).all()

    @pytest.mark.parametrize("kw", [
        {}, {"window": 10}, {"window": 18, "aligned": True, "stats": True},
        {"num_kv_heads": 1, "v_width": 16, "scale": 0.2}],
        ids=["plain", "window", "aligned_stats", "latent"])
    @pytest.mark.parametrize("hostile", [
        "negative_entries", "entries_past_the_pool", "length_past_the_table",
        "negative_length"])
    def test_hostile_books_stay_inside_the_pool_and_the_buffers(
            self, monkeypatch, hostile, kw):
        """The kernel's copies run with the compiler's bounds checks off,
        so its own arithmetic is all that keeps an index in range. Books no
        session writes (negative table entries and entries past the pool in
        a live slot's later pages, lengths past their table rows, a negative
        one) beside honest slots: the strict interpreter raises on a
        read outside the pool, the table or a buffer, every result is
        finite, the honest slots' and the one with clipped entries are the
        reference's, which clips the same."""
        from paddle_tpu.ops import pallas_attention as pa
        H, HD, BS, P, mb = 4, 8, _BS, 3, 8
        nkv = kw.get("num_kv_heads", H)
        width = 24 if kw.get("v_width") else nkv * HD
        monkeypatch.setattr(
            pa, "_PAGED_BUFFER_BYTES",
            (2 if kw.get("v_width") else 4) * P * BS * width * 4)
        rs = np.random.RandomState(2)
        nb = 3 * mb
        lens = np.asarray([2 * P * BS + 3, 2 * P * BS + 1, P * BS], np.int32)
        tables = rs.permutation(nb).reshape(3, mb).astype(np.int32)
        tables[0, -(-int(lens[0]) // BS):] = nb + 7
        tables[2, P:] = nb + 7
        # behind the slot's first page, which says whether it lives
        later = slice(int(pa.paged_walk(
            lens[1], BS, mb, kw.get("window"), kw.get("aligned", False),
            np)[0]) + 1, None, 2)
        if hostile == "negative_entries":
            tables[1, later] = [-1, -nb - 1, -(2 ** 31), -7][
                :len(tables[1, later])]
        elif hostile == "entries_past_the_pool":
            tables[1, later] = [nb, nb + 1, 2 ** 31 - 1, 4 * nb][
                :len(tables[1, later])]
        elif hostile == "length_past_the_table":
            # the last slot's too: behind its row the table ends
            lens[1:] = mb * BS + 3 * P * BS + 1
        else:
            lens[1] = -5
        pools = [rs.randn(nb, BS, width).astype("float32")
                 for _ in range(1 if kw.get("v_width") else 2)]
        q = jnp.asarray(rs.randn(3, 1, H * width // nkv), "float32")
        args = (*([None] if len(pools) == 1 else []), jnp.asarray(lens),
                jnp.asarray(tables), H)
        got = pa.decode_attention_paged(
            q, *map(jnp.asarray, pools), *args,
            interpret=_strict_interpreter(), **kw)
        want = pa._decode_paged_reference(
            q, *map(jnp.asarray, pools), *args, **kw)
        # a slot with no rows: the kernel writes zeros, the reference
        # attends clamped rows nobody reads. A length past the table: the
        # walk ends with the row's last entry, and the mask, which trusts
        # the length, lets the unfetched pages' stale rows in: nothing
        # foreign is read, and the slot's result is nobody's
        live = {"negative_length": [0, 2], "length_past_the_table": [0]}.get(
            hostile, [0, 1, 2])
        for g, w in zip(*([got, want] if kw.get("stats")
                          else [[got], [want]])):
            g = np.asarray(g)
            assert np.isfinite(g).all()
            np.testing.assert_allclose(g[live], np.asarray(w)[live],
                                       atol=1e-5, rtol=1e-5)
        if hostile == "negative_length":
            assert (np.asarray(got[0] if kw.get("stats") else got)[1]
                    == 0).all()

    @pytest.mark.parametrize("dtype,max_blocks,pages", [
        ("bfloat16", 128, 8), ("float32", 128, 4), ("bfloat16", 5, 5)])
    def test_pages_per_compute_block_follow_from_static_shapes(
            self, dtype, max_blocks, pages):
        """Blocks of 16 x 2048 (the benchmark's serving geometry): 2 MB
        of page buffers hold 8 bf16 pages or 4 f32 ones, K and V double
        buffered, and never more than a table row has."""
        from paddle_tpu.ops.pallas_attention import _paged_block_pages
        assert _paged_block_pages(16, 2048, dtype, max_blocks) == pages

    @pytest.mark.parametrize("cache_len,lengths", [
        (64, [1, 17, 64]),          # one compute block holds the cache
        # several compute blocks: the running maximum, sum and accumulator
        # carried across them; lengths on both sides of a block's edge
        (1024, [513, 1024]),
        (100, [3, 100, 52])],       # a cache that is no whole compute block
        ids=["one_block", "carry_across_blocks", "ragged"])
    def test_contiguous_cache_under_an_identity_table(self, monkeypatch,
                                                      cache_len, lengths):
        """A contiguous [S, C, H*D] cache cut into blocks of 4 rows under
        the table that names them in order: the kernel against
        ``_decode_reference`` on the cache itself."""
        from paddle_tpu.ops import pallas_attention as pa
        H, HD, BS = 2, 8, 4
        D, S, MB = H * HD, len(lengths), cache_len // BS
        # 16 pages (64 rows) a compute block
        monkeypatch.setattr(pa, "_PAGED_BUFFER_BYTES", 4 * 16 * BS * D * 4)
        assert pa._paged_block_pages(BS, D, "float32", MB) == 16
        rs = np.random.RandomState(2)
        k = rs.randn(S, cache_len, D).astype("float32")
        v = rs.randn(S, cache_len, D).astype("float32")
        q = rs.randn(S, 1, D).astype("float32")
        lens = np.asarray(lengths, np.int32)
        tables = np.arange(S * MB, dtype=np.int32).reshape(S, MB)
        out = pa.decode_attention_paged(
            jnp.asarray(q), jnp.asarray(k.reshape(S * MB, BS, D)),
            jnp.asarray(v.reshape(S * MB, BS, D)), jnp.asarray(lens),
            jnp.asarray(tables), H, interpret=True)

        def heads(x):       # [S, C, H*D] -> [S*H, C, D]
            return jnp.asarray(x.reshape(S, -1, H, HD).transpose(
                0, 2, 1, 3).reshape(S * H, -1, HD))
        ref = pa._decode_reference(heads(q), heads(k), heads(v),
                                   jnp.asarray(np.repeat(lens, H)))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref).reshape(S, 1, D),
            atol=1e-5, rtol=1e-5)

    def test_dense_gather_reference_equals_contiguous_reference(self):
        """_decode_paged_reference over a scattered pool ==
        _decode_reference over the hand-gathered contiguous cache."""
        from paddle_tpu.ops.pallas_attention import (
            _decode_paged_reference, _decode_reference)
        rs = np.random.RandomState(3)
        S, H, HD, NB, BS, MB = 2, 2, 4, 6, 4, 3
        D = H * HD
        C = MB * BS
        pool_k = rs.randn(NB, BS, D).astype("float32")
        pool_v = rs.randn(NB, BS, D).astype("float32")
        tables = np.asarray([[4, 1, 5], [2, 0, 3]], np.int32)
        lengths = np.asarray([7, 12], np.int32)
        q = rs.randn(S, 1, D).astype("float32")
        out = _decode_paged_reference(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(lengths), jnp.asarray(tables), H)
        k = pool_k[tables].reshape(S, C, D)
        v = pool_v[tables].reshape(S, C, D)
        qh = q.reshape(S, H, HD)
        kh = k.reshape(S, C, H, HD).transpose(0, 2, 1, 3)
        vh = v.reshape(S, C, H, HD).transpose(0, 2, 1, 3)
        ref = _decode_reference(
            jnp.asarray(qh.reshape(S * H, 1, HD)),
            jnp.asarray(kh.reshape(S * H, C, HD)),
            jnp.asarray(vh.reshape(S * H, C, HD)),
            jnp.asarray(np.repeat(lengths, H))).reshape(S, 1, D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)


# -- greedy parity with the oracle -----------------------------------------

class TestPagedParity:
    @pytest.mark.parametrize("flash", [False, True])
    def test_token_identical_to_dense_and_oracle(self, flash):
        """Acceptance: greedy output token-identical to the re-encode
        oracle in ALL paths (XLA gather and Pallas), over ragged prompt
        lengths crossing block boundaries (block_size 4; prompts of
        1/3/4/5/7 tokens end before, at, and past block edges)."""
        ptpu.config.set_flags(flash_attention=flash)
        scope, exe, main, logits = _lm_scope()
        paged = _paged_session(scope)      # prefix sharing armed
        prompts = ([BOS], [BOS, 5, 7], [2, 3, 4, 5], [2, 3, 4, 5, 6],
                   [2, 3, 4, 5, 6, 7, 8])
        seqs = []
        for prompt in prompts:
            want = _reencode_greedy(exe, main, logits, scope, prompt)
            got_p = [int(t) for t in paged.generate(prompt)]
            assert got_p == want, prompt
            seqs.append(tuple(want))
        assert len(set(seqs)) > 1          # prompt-dependent outputs
        paged.check_pool_invariant()
        paged.close()

    def test_compile_shape_set_stays_closed(self):
        """One compile per prompt bucket + one decode + one block-copy
        program — however many admissions, prefix hits, and COWs
        flow."""
        scope, exe, main, logits = _lm_scope()
        sess = _paged_session(scope, prompt_buckets=(4, 8))
        sess.generate([BOS], max_new_tokens=4)
        sess.generate([2, 3, 4, 5, 6], max_new_tokens=5)   # bucket 8
        sess.generate([2, 3, 4, 5, 6], max_new_tokens=5)   # prefix hit
        stats = sess.compile_stats()
        sess.generate([4, 5, 6, 7], max_new_tokens=5)
        s1, _ = sess.admit([2, 3])
        sess.step()
        sess.retire(s1)
        assert sess.compile_stats() == stats
        # <= 2 prefill buckets + 1 decode + 1 copy program
        assert stats["compiles"] <= 4
        sess.close()


# -- prefix reuse ----------------------------------------------------------

class TestPrefixReuse:
    def test_shared_prefix_prefills_once(self):
        """Acceptance: a shared-prefix batch prefills the common
        prefix exactly once — proven by the per-admission prefill log
        (bucket, hist, window): later admissions re-prefill ONLY the
        unshared suffix, and the full-prompt bucket is never used
        again."""
        scope, exe, main, logits = _lm_scope()
        sess = _paged_session(scope, slots=3,
                              prompt_buckets=(4, 8, 12),
                              num_blocks=24)
        system = [2, 3, 4, 5, 6, 7, 8, 9]          # two full blocks
        users = ([10], [11], [12])
        for u in users:
            want = _reencode_greedy(exe, main, logits, scope,
                                    system + u)
            got = [int(t) for t in sess.generate(system + u,
                                                 max_new_tokens=3)]
            assert got == want[:len(got)], u
        log = sess.prefill_log
        assert log[0][1] == 0                      # full first prefill
        # every later admission: hist covers the shared system
        # prompt, window is the 1-2 unshared tokens in the SMALL
        # bucket — the 9-token bucket is never compiled again
        for bucket, hist, window in log[1:]:
            assert hist >= 8, log
            assert window <= 2, log
            assert bucket == 4, log
        stats = sess.prefix_stats()
        assert stats["hits"] == len(users) - 1
        assert stats["misses"] == 1                # the first admission
        assert stats["shared_tokens"] >= 8 * (len(users) - 1)
        sess.check_pool_invariant()
        sess.close()

    def test_prefix_survives_retire_and_serves_next_admission(self):
        """Retired sequences free their exclusive blocks; prompt
        blocks pinned by the index stay cached, so a later identical
        prompt re-prefills only its tail."""
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, num_blocks=16)
        prompt = [2, 3, 4, 5, 6, 7]
        sess.generate(prompt, max_new_tokens=4)
        used_after_retire = sess.pool.used_count()
        assert used_after_retire > 0        # prompt blocks cached
        sess.generate(prompt, max_new_tokens=4)
        _, hist, window = sess.prefill_log[-1]
        assert hist >= 4 and window <= 2
        sess.check_pool_invariant()
        sess.close()

    def test_pool_pressure_evicts_cold_prefix_blocks(self):
        """A full pool reclaims pin-only (no live sequence) prefix
        entries LRU instead of refusing admission."""
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=2, num_blocks=4)
        sess.generate([2, 3, 4, 5, 6], max_new_tokens=3)
        assert sess.pool.used_count() > 0   # cached prompt blocks
        # a different prompt needing most of the pool: must evict,
        # not die
        sess.generate([10, 11, 12, 13, 14], max_new_tokens=3)
        sess.check_pool_invariant()
        sess.close()


# -- copy-on-write ---------------------------------------------------------

class TestCopyOnWrite:
    def test_divergence_isolation_under_sharing(self):
        """Acceptance satellite: two sequences admitted from the SAME
        prompt share its blocks; both then decode concurrently and
        MUST NOT see each other's writes — each matches its solo
        run token for token (COW gives the writer a private copy)."""
        scope, exe, main, logits = _lm_scope()
        solo = _reencode_greedy(exe, main, logits, scope, [2, 3, 4, 5, 6])
        sess = _paged_session(scope, slots=2, num_blocks=20)
        from paddle_tpu.serving.paged_cache import BLOCK_COWS
        cows0 = BLOCK_COWS._default().value
        sA, tA = sess.admit([2, 3, 4, 5, 6])
        toksA = [tA]
        toksA.append(sess.step()[sA])          # A decodes alone first
        sB, tB = sess.admit([2, 3, 4, 5, 6])   # shares A's blocks
        toksB = [tB]
        for _ in range(4):
            step = sess.step()
            toksA.append(step[sA])
            toksB.append(step[sB])
        assert [int(t) for t in toksA[:6]] == solo[:6]
        assert [int(t) for t in toksB[:5]] == solo[:5]
        # sharing + diverging really exercised the COW path
        assert BLOCK_COWS._default().value > cows0
        stats = sess.prefix_stats()
        assert stats["shared_tokens"] >= 4
        sess.retire(sA)
        sess.retire(sB)
        sess.check_pool_invariant()
        sess.close()

    def test_cow_write_does_not_corrupt_cached_prefix(self):
        """After a sharer diverges (COW + decode writes), the ORIGINAL
        cached prompt blocks still serve a third admission with the
        same prompt correctly."""
        scope, exe, main, logits = _lm_scope()
        want = _reencode_greedy(exe, main, logits, scope, [2, 3, 4, 5, 6])
        sess = _paged_session(scope, slots=2, num_blocks=20)
        sess.generate([2, 3, 4, 5, 6], max_new_tokens=6)
        sess.generate([2, 3, 4, 5, 6], max_new_tokens=6)  # shares+COWs
        got = [int(t) for t in sess.generate([2, 3, 4, 5, 6],
                                             max_new_tokens=6)]
        assert got == want[:len(got)]
        sess.check_pool_invariant()
        sess.close()


# -- pool accounting / capacity --------------------------------------------

class TestPoolAccounting:
    def test_retire_returns_every_block(self):
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=3, prefix_cache=False)
        slots = [sess.admit([2, 3, 4, 5, 6])[0],
                 sess.admit([7, 8])[0]]
        for _ in range(3):
            sess.step()
        assert sess.pool.used_count() > 0
        for s in slots:
            sess.retire(s)
        sess.check_pool_invariant()
        # no prefix index: every reference was the sequences' own
        assert sess.pool.used_count() == 0
        sess.close()

    def test_close_releases_prefix_pins_too(self):
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=2, prefix_cache=True)
        sess.generate([2, 3, 4, 5, 6], max_new_tokens=3)
        assert sess.pool.used_count() > 0   # pinned prompt blocks
        pool = sess.pool
        sess.close()                        # asserts zero leaked inside
        assert pool.used_count() == 0

    def test_failed_admission_rolls_back_references(self):
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=2, num_blocks=16)
        before = sess.pool.used_count()
        with pytest.raises(ValueError):
            sess.admit([2] * 20)            # exceeds cache capacity
        assert sess.pool.used_count() == before
        sess.check_pool_invariant()
        sess.close()

    def test_pool_exhaustion_finishes_sequence_at_capacity(self):
        """A sequence that cannot get a growth block is excluded from
        the step (its write drops on device) and a scheduler finishes
        it at its current length — the 'capacity' contract via pool
        bytes."""
        scope, _, _, _ = _lm_scope()
        # 2 slots x long budgets over a 3-block pool: one sequence
        # must starve while the other keeps every block busy
        sess = _paged_session(scope, slots=2, num_blocks=3,
                              prefix_cache=False)
        sched = GenerationScheduler(sess)
        try:
            futs = [sched.submit([2, 3], max_new_tokens=8, eos_id=-1),
                    sched.submit([4, 5], max_new_tokens=8, eos_id=-1)]
            outs = [f.result(timeout=60) for f in futs]
        finally:
            sched.drain()
        # both resolve (no exception), at least one was cut short by
        # pool capacity, and nothing leaked
        assert all(len(o) >= 1 for o in outs)
        assert any(len(o) < 8 for o in outs), [len(o) for o in outs]
        sess.check_pool_invariant()
        assert sess.pool.used_count() == 0
        sess.close()

    def test_pool_preemption_replays_explicit_budget_in_full(self):
        """With replay armed, pool starvation is PREEMPTION, not
        truncation: the starved request re-queues with its journal
        and resumes once blocks free — the explicit token budget is
        delivered in full, bit-identical to an uncontended run."""
        scope, _, _, _ = _lm_scope()
        solo_sess = _paged_session(scope, slots=2, num_blocks=8,
                                   prefix_cache=False)
        solos = {p: [int(t) for t in solo_sess.generate(
            list(p), max_new_tokens=8, eos_id=-1)]
            for p in ((2, 3), (4, 5))}
        solo_sess.close()
        sess = _paged_session(scope, slots=2, num_blocks=3,
                              prefix_cache=False)
        sched = GenerationScheduler(sess, replay_attempts=4)
        try:
            futs = {p: sched.submit(list(p), max_new_tokens=8,
                                    eos_id=-1)
                    for p in solos}
            for p, f in futs.items():
                got = [int(t) for t in f.result(timeout=120)]
                assert got == solos[p], (p, got)     # full 8 tokens
        finally:
            sched.drain()
        sess.check_pool_invariant()
        assert sess.pool.used_count() == 0
        sess.close()

    def test_admit_ok_accepts_history_needing_whole_pool(self):
        """The COW margin must not make a history that needs exactly
        the full pool permanently unadmittable (it would park
        forever): on an idle pool admit_ok says yes."""
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=1, num_blocks=2,
                              prefix_cache=True)
        assert sess.admit_ok(8)        # 2 blocks = the whole pool
        sess.close()

    def test_admit_ok_gates_scheduler_placement(self):
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=2, num_blocks=2,
                              prefix_cache=False)
        # 2 blocks busy -> a 5-token admission (2 blocks) must report
        # not-ok instead of raising inside the dispatcher
        s0, _ = sess.admit([2, 3, 4, 5, 6])
        assert not sess.admit_ok(5)
        sess.retire(s0)
        assert sess.admit_ok(5)
        sess.check_pool_invariant()
        sess.close()


# -- PR-9 failover over the paged pool -------------------------------------

@pytest.mark.chaos
class TestPagedFailover:
    def test_replay_bit_identical_with_suffix_only_reprefill(self):
        """Acceptance satellite: a session fault mid-decode over the
        paged pool replays onto the healthy session BIT-identically,
        and because the healthy session already serves the shared
        prompt, the replay re-prefills only its unshared suffix
        (journal hist > 0). Both pools balance afterwards."""
        scope, _, _, _ = _lm_scope()
        prompt = [2, 3, 4, 5, 6, 7, 8, 9]      # two full blocks
        s_a = _paged_session(scope, slots=2, num_blocks=24)
        s_b = _paged_session(scope, slots=2, num_blocks=24)
        # fault-free baseline from its own session set
        base_sess = _paged_session(scope, slots=2, num_blocks=24)
        baseline = [int(t) for t in base_sess.generate(
            prompt, max_new_tokens=6, eos_id=-1)]
        base_sess.close()
        # warm the healthy session's prefix cache with the prompt
        s_b.generate(prompt, max_new_tokens=1, eos_id=-1)
        warm_log = len(s_b.prefill_log)
        sched = GenerationScheduler(
            [s_a, s_b], breaker_failures=1, breaker_cooldown_ms=10000,
            replay_attempts=2)
        try:
            # persistent step fault on session 0: the request admits
            # there (lowest index), fails, and must replay onto 1. Armed
            # at its first token, which is fetched behind the first
            # step's launch: a step failing before that would find no
            # token in the journal, and nothing of it to share
            def arm(_token):
                if not faults.armed("generation_step_fail"):
                    faults.arm("generation_step_fail", at=0, times=None)
            fut = sched.submit(prompt, max_new_tokens=6, eos_id=-1,
                               on_token=arm)
            got = [int(t) for t in fut.result(timeout=120)]
        finally:
            faults.disarm()
            sched.drain()
        assert got == baseline                  # bit-identical replay
        # the replay admission on the healthy session shared the
        # prompt prefix: its journal prefill carried hist > 0
        replay_log = s_b.prefill_log[warm_log:]
        assert replay_log, "replay never reached the healthy session"
        assert all(hist >= 8 for _, hist, _ in replay_log), replay_log
        s_a.check_pool_invariant()
        s_b.check_pool_invariant()
        s_a.close()
        s_b.close()


@pytest.mark.chaos
class TestPagedWedge:
    def test_leaked_step_worker_cannot_corrupt_pool_books(self):
        """A step wedged past generation_step_timeout_ms leaks its
        worker thread; on the paged layout that worker must never
        touch the allocator (step_prepare runs host-side bookkeeping
        on the dispatcher BEFORE the bounded call), so the pool books
        balance even while the leaked worker finishes long after the
        dispatcher retired the slots and replayed the requests."""
        import time as _time
        scope, _, _, _ = _lm_scope()
        s_a = _paged_session(scope, slots=2, num_blocks=24)
        s_b = _paged_session(scope, slots=2, num_blocks=24)
        baseline_sess = _paged_session(scope, slots=2, num_blocks=24)
        prompts = ([2, 3, 4], [5, 6])
        want = [[int(t) for t in baseline_sess.generate(
            list(p), max_new_tokens=5, eos_id=-1)] for p in prompts]
        baseline_sess.close()
        for s in (s_a, s_b):          # warm: a cold compile would
            s.generate([BOS], max_new_tokens=2, eos_id=-1)  # trip the
        sched = GenerationScheduler(                        # timeout
            [s_a, s_b], replay_attempts=4, breaker_failures=3,
            breaker_cooldown_ms=60000.0, step_timeout_ms=400.0)
        try:
            faults.arm("generation_session_wedge", at=0, times=1,
                       action="callback",
                       callback=lambda: _time.sleep(1.5))
            futs = [sched.submit(list(p), max_new_tokens=5, eos_id=-1)
                    for p in prompts]
            got = [[int(t) for t in f.result(timeout=120)]
                   for f in futs]
            assert got == want        # replayed onto the healthy one
            assert sched.session_health()[0] == "open"
            _time.sleep(1.8)          # let the leaked worker finish
            s_a.check_pool_invariant()
            s_b.check_pool_invariant()
        finally:
            faults.disarm()
            sched.drain()
        s_a.check_pool_invariant()
        s_b.check_pool_invariant()
        s_a.close()                   # close asserts zero leaked
        s_b.close()


@pytest.mark.chaos
class TestPagedRebuild:
    def test_rebuild_warms_every_bucket_despite_prefix_cache(self):
        """The background rebuild of a paged session detaches the
        prefix index during warmup — otherwise a later bucket's warm
        prompt matches an earlier one's cached prefix and the large
        prefill program never compiles (a live-traffic stall after
        hand-over). The rebuilt session must carry compiles for EVERY
        bucket plus decode plus the COW program, an unpolluted index,
        and balanced pool books."""
        import time as _time
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=2, prompt_buckets=(4, 8),
                              num_blocks=24)
        sched = GenerationScheduler(
            sess, replay_attempts=10, breaker_failures=1,
            breaker_cooldown_ms=30.0, rebuild_limit=2)
        try:
            # initial failure + two failed cooldown trials = rebuild
            # trigger; then the "device" heals and the rebuilt
            # session serves (the dense-rebuild test's recipe)
            faults.arm("generation_step_fail", at=0, times=3)
            got = sched.submit([2, 3, 4], max_new_tokens=4,
                               eos_id=-1).result(timeout=120)
            assert len(got) == 4
            deadline = _time.monotonic() + 30
            while sched.sessions[0] is sess and \
                    _time.monotonic() < deadline:
                _time.sleep(0.05)
            rebuilt = sched.sessions[0]
            assert rebuilt is not sess, "rebuild never handed over"
            stats = rebuilt.compile_stats()
            # 2 prompt buckets + 1 decode + 1 block-copy, all warmed
            # BEFORE traffic (the live request above reuses them)
            assert stats["entries"] >= 4, stats
            # warm prompts must not stay pinned in the prefix index;
            # only the live request's own registration may remain
            live_entries = rebuilt.prefix_stats()["entries"]
            assert live_entries <= 2, live_entries
            rebuilt.check_pool_invariant()
        finally:
            faults.disarm()
            sched.close()

class TestConcurrencyAtFixedBudget:
    def test_paged_sustains_2x_dense_sequences(self):
        """Acceptance: at the budget of 3 whole rows of ``cache_len``
        (what 3 sequences would pin, each given its worst case), the pool
        holds >= 2x as many concurrent sequences on a
        mixed-length workload, token-identical throughout."""
        scope, exe, main, logits = _lm_scope()
        # 3 rows x cache_len 16 = 48 rows of budget: 3 sequences a row each
        admitted_d = 3
        # the SAME 48 rows (12 blocks x 4), but 8 decode lanes
        paged = _paged_session(scope, slots=8, cache_len=16,
                               block_size=4, num_blocks=12,
                               prefix_cache=False)
        rs = np.random.RandomState(0)
        prompts = [list(rs.randint(2, V, int(n)))
                   for n in (1, 2, 3, 1, 2, 3, 2, 1)]   # mixed, short
        # admits while blocks last
        admitted_p, slots_p = 0, []
        for p in prompts:
            if not (paged.free_slots() and paged.admit_ok(len(p))):
                break
            slots_p.append(paged.admit(p)[0])
            admitted_p += 1
        assert paged.pool.num_blocks * 4 == admitted_d * 16
        assert admitted_p >= 2 * admitted_d, (admitted_p, admitted_d)
        # all paged sequences decode together, matching their solos
        toks = {s: [] for s in slots_p}
        for _ in range(2):
            step = paged.step()
            for s in slots_p:
                toks[s].append(step[s])
        for i, s in enumerate(slots_p):
            want = _reencode_greedy(exe, main, logits, scope,
                                    prompts[i], eos=-1)[1:3]
            assert [int(t) for t in toks[s]] == want, prompts[i]
        for s in list(paged.active_slots()):
            paged.retire(s)
        paged.check_pool_invariant()
        paged.close()


# -- one layout --------------------------------------------------------------

class TestOneLayout:
    def test_flags_exist_with_defaults(self):
        assert ptpu.config.get_flag("generation_paged_kv") is True
        assert ptpu.config.get_flag("generation_block_size") == 16
        assert ptpu.config.get_flag("generation_pool_blocks") == 0
        assert ptpu.config.get_flag("generation_prefix_cache") is False

    def test_default_spec_is_a_pool_with_a_table_for_every_slot(self):
        """No cache arguments: blocks of 16, ``slots x ceil(cache_len /
        16)`` of them, no prefix index; the session reports the pool."""
        spec = transformer_lm_session(V, max_len=MAXLEN, slots=2,
                                      cache_len=40,
                                      prompt_buckets=(4,), **KW)
        assert spec.paged is True
        assert (spec.block_size, spec.max_blocks, spec.num_blocks) == \
            (16, 3, 6)
        assert not spec.prefix_cache and spec.copy_program is not None
        assert all(shape == (6, 16, KW["d_model"])
                   for _, shape, _ in spec.cache_vars)
        assert spec.prefill_feeds == ("gen.ptok", "gen.plen", "gen.ppos",
                                      "gen.phist", "gen.ppix", "gen.ptab")
        assert spec.decode_feeds == ("gen.dtok", "gen.dpos", "gen.dtab")
        sess = GenerationSession(spec, scope=_lm_scope()[0])
        assert sess.prefix is None
        assert sess.pool_stats() == {
            "blocks_in_use": 0, "num_blocks": 6, "block_size": 16,
            "bytes_per_block": 16 * KW["d_model"] * 4 * 2
            * KW["num_layers"]}
        sess.close()

    def test_the_dense_layout_is_refused_by_name(self):
        with pytest.raises(ValueError, match="PR 29"):
            transformer_lm_session(V, max_len=MAXLEN, slots=2,
                                   prompt_buckets=(4,), paged=False, **KW)
        spec = transformer_lm_session(V, max_len=MAXLEN, slots=2,
                                      prompt_buckets=(4,), paged=True, **KW)
        assert spec.paged is True

    def test_the_flag_is_a_constant(self):
        with pytest.raises(ValueError, match="PR 29"):
            ptpu.config.set_flags(generation_paged_kv=False)
        assert ptpu.config.get_flag("generation_paged_kv") is True
        ptpu.config.set_flags(generation_paged_kv=True)     # harness/lm.py
        assert ptpu.config.get_flag("generation_paged_kv") is True
        assert len(ptpu.config._flags) == 64

    def test_the_deleted_path_is_not_left_behind(self):
        from paddle_tpu.core import registry
        from paddle_tpu.ops import pallas_attention
        for op in ("kv_cache_write_slot", "kv_cache_append",
                   "multihead_attention_decode"):
            with pytest.raises(NotImplementedError, match=op):
                registry.get_op_def(op)
        registry.get_op_def("kv_cache_append_paged")
        assert not hasattr(pallas_attention, "decode_attention")
        assert hasattr(pallas_attention, "_decode_reference")

    def test_hot_path_consults_no_cache_flag(self, monkeypatch):
        """A session's admit/step never read a cache flag: they are read
        once, where the spec is built."""
        scope, _, _, _ = _lm_scope()
        sess = _paged_session(scope, slots=2, prompt_buckets=(4,))
        sess.generate([BOS], max_new_tokens=2)       # warm compiles
        calls = []
        orig = ptpu.config.get_flag

        def counting(name):
            calls.append(name)
            return orig(name)

        monkeypatch.setattr(ptpu.config, "get_flag", counting)
        slot, _ = sess.admit([BOS])
        sess.step()
        sess.retire(slot)
        assert not [c for c in calls
                    if c.startswith("generation_paged")
                    or c in ("generation_block_size",
                             "generation_pool_blocks",
                             "generation_prefix_cache")], calls
        sess.close()

    def test_rebuild_factory_preserves_paged_geometry(self):
        spec = transformer_lm_session(
            V, max_len=MAXLEN, slots=2, cache_len=16,
            prompt_buckets=(4,), paged=True, block_size=4,
            num_blocks=10, prefix_cache=True, **KW)
        fresh = spec.rebuild()
        assert fresh.paged and fresh.block_size == 4
        assert fresh.num_blocks == 10 and fresh.prefix_cache
        # fresh cache namespace: no name collides with the original
        assert not ({n for n, _, _ in fresh.cache_vars}
                    & {n for n, _, _ in spec.cache_vars})
