"""The session's one loop over its kinds of layer cache, held to what the
session did before it had one (PR 45): the six served configurations at the
rehearsal's sizes, driven through one fixed script, feed the same programs
the same feeds, keep the same books and count the same rows as at the parent
commit (057a117, ``tests/data/session_script_at_pr44.json``: recorded there
with ``_drive``, not made again since); and an admission that runs a later
kind's pool dry gives every earlier kind its blocks back. CPU, small sizes.
"""

import contextlib
import json
import os

import numpy as np
import pytest

import paddle_tpu as ptpu
from paddle_tpu.observability import metrics
from paddle_tpu.serving import GenerationSession
from paddle_tpu.serving.paged_cache import PoolExhausted

from benchmarks import architectures
from benchmarks.harness import lm as bench_lm

HERE = os.path.dirname(os.path.abspath(__file__))

pytestmark = [pytest.mark.generation, pytest.mark.paged]

CONFIGS = ["cerebras-gpt-1.3b", "trinity-mini-l5", "kimi-k2.7-code-l6",
           "granite-4.0-h-small-l10", "longcat-flash-chat-l4",
           "evabyte-6.5b-l8"]
SEVERAL_KINDS = ["trinity-mini-l5", "granite-4.0-h-small-l10",
                 "evabyte-6.5b-l8"]
BUCKETS = (8, 16, 32)

# what a step keeps of its kinds, and what a window gives back
COUNTERS = ("paddle_generation_window_context_tokens_total",
            "paddle_generation_eva_window_rows_total",
            "paddle_generation_eva_chunk_rows_total",
            "paddle_generation_eva_chunks_written_total",
            "paddle_generation_latent_rows_attended_total",
            "paddle_generation_state_rows_updated_total",
            "paddle_generation_kv_window_blocks_freed_total")


def _counters():
    snap = {n: children for n, _, _, _, children in
            metrics.REGISTRY.snapshot()}
    return {n: sum(float(v) for _, v in snap.get(n, ())) for n in COUNTERS}


def _blocks_in_use(sess):
    """The gauge ``_kv_blocks_in_use`` of each of the session's pools, by
    the pool's kind."""
    gauge = {l["pool"]: float(v) for n, _, _, _, children in
             metrics.REGISTRY.snapshot()
             if n == "paddle_generation_kv_blocks_in_use"
             for l, v in children}
    return {k.kind.name: gauge[k.pool._label] for k in sess.kinds}


@contextlib.contextmanager
def _session(config, buckets=BUCKETS, **geometry):
    """A configuration's tiny session over weights of its own start-up;
    ``geometry`` changes its serving geometry."""
    cfg = bench_lm.load_config(config)
    module = architectures.load(cfg)
    tiny = module.tiny(cfg)
    geometry = dict(tiny["deployment"]["serving"], **geometry)
    with bench_lm.flags(generation_kv_dtype=geometry["kv_dtype"],
                        **tiny["flags"]), \
            ptpu.scope_guard(ptpu.Scope()):
        with ptpu.unique_name.guard():
            ptpu.Executor().run(module.serve_startup(tiny, 0))
        with ptpu.unique_name.guard():
            spec = module.serve_spec(tiny, geometry, buckets)
        sess = GenerationSession(spec)
        try:
            yield sess
        finally:
            sess.close()


def _drive(sess):
    """Two admissions, three steps, a retire, an admission, two steps and
    the close: every executor call's program and feeds (a feed's name,
    shape, dtype and, but for the decode step's tokens, which are the
    model's, its values), and after each action the pools' books, the
    counters since the start and the gauges. The first prompt ends two
    rows before the 32nd position, so that a window of 8 or of 32 rows has
    an edge crossed in the steps."""
    spec = sess.spec
    calls = []
    run = sess.exe.run

    def recording(program, feed=None, **kw):
        calls.append([program.name, [
            [name, list(np.shape(v)), str(np.asarray(v).dtype),
             None if name == "gen.dtok" else np.asarray(v).tolist()]
            for name, v in sorted(feed.items())]])
        return run(program, feed=feed, **kw)
    sess.exe.run = recording
    before = _counters()
    log = []

    def note(action):
        now = _counters()
        log.append({"action": action, "calls": list(calls),
                    "pool_stats": sess.pool_stats(),
                    "lengths": sess.lengths.tolist(),
                    "counters": {n: now[n] - before[n] for n in COUNTERS},
                    "blocks_in_use": _blocks_in_use(sess)})
        del calls[:]
        if sess.pool is not None:
            sess.check_pool_invariant()

    def admit(n):
        slot, _ = sess.admit(2 + np.arange(n) % 5)
        note("admit %d -> slot %d" % (n, slot))
        return slot

    def step(times):
        for _ in range(times):
            out = sess.step()
            note("step -> slots %s" % sorted(out))

    first = admit(30)
    admit(7)
    step(3)
    sess.retire(first)
    note("retire %d" % first)
    admit(13)
    step(2)
    kinds = [k.kind for k in sess.kinds]
    pools = [k.pool for k in sess.kinds]
    sess.close()
    log.append({"action": "close", "calls": list(calls),
                "free": [p.free_count() for p in pools]})
    return {"cache_vars": [[name, list(shape), str(dtype)]
                           for name, shape, dtype in spec.cache_vars],
            "prefill_feeds": list(spec.prefill_feeds),
            "decode_feeds": list(spec.decode_feeds),
            "copy_feeds": list(spec.copy_feeds),
            "kinds": [[k.name, k.window, k.num_blocks, k.layers, k.aligned,
                       k.chunk] for k in kinds],
            "script": log}


@pytest.mark.parametrize("config", CONFIGS)
def test_a_scripted_session_feeds_and_counts_what_it_did_at_the_parent(config):
    with open(os.path.join(HERE, "data", "session_script_at_pr44.json")) as f:
        want = json.load(f)[config]
    with _session(config) as sess:
        got = json.loads(json.dumps(_drive(sess)))
    assert sorted(got) == sorted(want)
    for part in want:
        if part != "script":
            assert got[part] == want[part], part
    assert len(got["script"]) == len(want["script"])
    for now, was in zip(got["script"], want["script"]):
        for part in was:
            assert now[part] == was[part], (was["action"], part)


@pytest.mark.parametrize("config,geometry", [
    (config, {}) for config in SEVERAL_KINDS] + [
    ("trinity-mini-l5", {"window_num_blocks": 4})],
    ids=SEVERAL_KINDS + ["trinity-mini-l5-too-small"])
def test_a_later_kind_run_dry_in_an_admission_returns_the_earlier_kinds_blocks(
        config, geometry):
    """``admit_launch`` takes a sequence's blocks kind by kind. With the
    last kind's pool run dry (too small for the prompt's 8 blocks, and dry
    half-way; else taken from under it: of a state kind, the slot's row)
    the admission fails, and what the kinds before it had handed out is
    back, with what the last kind had."""
    prompt = 2 + np.arange(30) % 5
    with _session(config, buckets=(8, 32), **geometry) as sess:
        sess.admit(prompt[:7])
        used = [k.pool.used_count() for k in sess.kinds]
        last = sess.kinds[-1]
        held = []
        if last.pool.free_count() >= last.blocks_for(prompt.size):
            held = [last.pool.take(b) for b in list(last.pool._free)]
        # a state kind's row is taken, not its pool found empty
        with pytest.raises(RuntimeError if last.state else PoolExhausted):
            sess.admit_launch(prompt)
        for block in held:
            last.pool.decref(block)
        assert [k.pool.used_count() for k in sess.kinds] == used
        sess.check_pool_invariant()
        assert sess.free_slots() == [1, 2, 3]


def _walk_blocks():
    """``paddle_generation_paged_blocks_total`` as {(kind, fill): value}."""
    return {(l["kind"], l["fill"]): float(v)
            for n, _, _, _, children in metrics.REGISTRY.snapshot()
            if n == "paddle_generation_paged_blocks_total"
            for l, v in children}


# (configuration, buckets, what a block holds in a layer, prompts, steps,
# blocks by hand). A walk's block is two pages here (the kernel's budget is
# cut to four times a layer's block). By hand, for a step's
# slot of L rows (the new one included): the pages from the one that holds the
# first row its query sees to the one that holds row L - 1, two a full block,
# an odd one left a partial block; times the kind's layers.
_WALKS = [
    # blocks of 4 rows; one full layer: after the first step 4, 11 and 25
    # rows are 1, 3 and 7 pages, 0 + 1 + 3 full blocks and 3 partial ones;
    # after the second 5, 12 and 26 rows are 2, 3 and 7 pages, 1 + 1 + 3 and
    # 2. Four window layers of 8 rows: rows 0-3, 3-10 and 17-24 lie in 1, 3
    # and 3 pages (0 + 1 + 1 full, 3 partial), then rows 0-4, 4-11 and 18-25
    # in 2, 2 and 3 (1 + 1 + 1 full, 1 partial)
    ("trinity-mini-l5", (8, 16, 32), 1024, (3, 10, 24), 2,
     {("full", "full"): 9, ("full", "partial"): 5,
      ("window", "full"): 4 * 5, ("window", "partial"): 4 * 4}),
    # blocks of 8 rows, three latent layers: 4, 16 and 51 rows are 1, 2 and
    # 7 pages (0 + 1 + 3 full, 2 partial), then 5, 17 and 52 rows are 1, 3
    # and 7 (0 + 1 + 3 full, 3 partial)
    ("kimi-k2.7-code-l6", (8, 16, 64), 4096, (3, 15, 50), 2,
     {("latent", "full"): 3 * 8, ("latent", "partial"): 3 * 5}),
    # blocks of 4 rows, three layers with an aligned window of 32 rows and
    # a summary every 4: of 4, 41 and 101 rows the window walks rows 0-3,
    # 32-40 and 96-100 in 1, 3 and 2 pages (0 + 1 + 1 full, 2 partial), the
    # chunk walk 0, 8 and 24 summaries in 0, 2 and 6 pages (0 + 1 + 3 full)
    ("evabyte-6.5b-l8", (8, 64, 128), 2048, (3, 40, 100), 1,
     {("window", "full"): 3 * 2, ("window", "partial"): 3 * 2,
      ("chunk", "full"): 3 * 4, ("chunk", "partial"): 0}),
]


@pytest.mark.parametrize("config,buckets,layer_bytes,prompts,steps,want",
                         _WALKS, ids=[w[0] for w in _WALKS])
def test_a_scripted_sessions_walks_count_their_full_and_partial_blocks(
        monkeypatch, config, buckets, layer_bytes, prompts, steps, want):
    """``paddle_generation_paged_blocks_total{kind, fill}`` over admissions
    whose lengths make 0, 1 and 3 full blocks a slot, against the count by
    hand, for a plain, a window, a latent, an aligned and a chunk kind; and
    a kind's ``walk_pages`` is what the kernel counts for its pools."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(pa, "_PAGED_BUFFER_BYTES", 2 * 2 * layer_bytes)
    with _session(config, buckets=buckets) as sess:
        assert {b // k.layers for b, k in zip(
            sess.spec.kind_block_bytes, sess.spec.cache_kinds)} \
            == {layer_bytes}
        pools = 1 if sess.kinds[0].kind.name == "latent" else 2
        assert [k.walk_pages for k in sess.kinds] == [2] * len(sess.kinds)
        assert {pa._paged_block_pages(shape[1], shape[2], dtype,
                                      sess.spec.max_blocks, 2 * pools)
                for _, shape, dtype in sess.spec.cache_vars} == {2}
        for n in prompts:
            sess.admit(2 + np.arange(n) % 5)
        before = _walk_blocks()
        for _ in range(steps):
            assert len(sess.step()) == len(prompts)
        after = _walk_blocks()
    got = {key: after[key] - before.get(key, 0.0) for key in after}
    assert {key: got.get(key, 0.0) for key in want} == want
    assert not any(v for key, v in got.items() if key not in want)


# the served geometries: (block rows, lanes, dtype, K and V or one pool,
# table row) and the pages of a block of the walk
@pytest.mark.parametrize("rows,lanes,dtype,pools,max_blocks,pages", [
    (16, 512, "float32", 2, 512, 16),       # trinity-mini, both its kinds
    (16, 256, "float32", 2, 256, 32),       # nemotron 3 nano
    (32, 640, "float32", 1, 256, 12),       # kimi k2
    (32, 640, "float32", 1, 64, 12),        # longcat flash, a site
    (16, 1024, "float32", 2, 256, 8),       # granite 4.0 h small
    (16, 2048, "bfloat16", 2, 128, 8),      # the GPT-2 block's LM
    (16, 4096, "bfloat16", 2, 768, 4)],     # evabyte, window and chunk pools
    ids=["trinity", "nemotron", "kimi", "longcat", "granite", "lm",
         "evabyte"])
def test_a_kinds_walk_pages_are_the_kernels_own(rows, lanes, dtype, pools,
                                                max_blocks, pages):
    """What the books count a block of a walk as, from what a block holds
    over the kind's layers, is what the kernel takes at once of pools of
    that shape."""
    from paddle_tpu.ops import pallas_attention as pa
    from paddle_tpu.serving.paged_cache import CacheKind, LayerCache
    layers = 3
    page = rows * lanes * np.dtype(dtype).itemsize
    kind = LayerCache(CacheKind("full", None, 64, layers, "p", "d"), rows, 2,
                      max_blocks, layers * pools * page)
    assert kind.walk_pages == pages == pa._paged_block_pages(
        rows, lanes, dtype, max_blocks, 2 * pools)
    kind.pool.close()
