"""Paged-KV-cache probe: block-pool memory + prefix reuse, headless.

Drives the shared-system-prompt workload the paged cache exists for —
N requests carrying a common prefix with distinct user suffixes —
through a prefix-cache-armed paged ``GenerationSession`` and a
``GenerationScheduler``, printing:

1. **prefix reuse** — hit rate, shared tokens, and the per-admission
   prefill log (bucket, hist, window) proving the common prefix
   prefilled EXACTLY once: every later admission re-prefills only its
   unshared suffix through the small prompt bucket.
2. **memory** — blocks in use vs the bytes of a worst-case cache row
   for every slot at the same moment, i.e. what the
   block pool actually buys per live token.
3. **closed shape set** — executor compile counters across the whole
   run (prompt buckets + one decode + one block-copy program, however
   many admissions, hits, and COWs flow), plus the pool-accounting
   invariant re-checked at the end.

Usage:
    JAX_PLATFORMS=cpu python tools/paged_cache_probe.py [--requests N]
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

VOCAB = 64
KW = dict(d_model=64, num_heads=2, d_ff=128, num_layers=2)
BOS, EOS = 0, 1
BLOCK_SIZE = 8


def build_scope(max_len):
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm_generate

    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            anchor = layers.data("anchor", shape=[1], dtype="int32")
            transformer_lm_generate(anchor, vocab_size=VOCAB,
                                    max_len=max_len, beam_size=1,
                                    bos_id=BOS, eos_id=EOS, **KW)
    exe = ptpu.Executor()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope):
        exe.run(startup)
    rs = np.random.RandomState(7)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        scope.set_var(n, rs.standard_normal(cur.shape).astype(cur.dtype))
    return scope


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8,
                    help="requests sharing the system prompt")
    args = ap.parse_args()

    from paddle_tpu.models.transformer import transformer_lm_session
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.generation import (GenerationScheduler,
                                               GenerationSession)

    max_len = 64
    slots = max(args.requests, 4)
    scope = build_scope(max_len)
    rs = np.random.RandomState(0)
    system = list(rs.randint(2, VOCAB, 14))

    print("== shared-system-prompt workload: %d requests, %d-token "
          "common prefix ==" % (args.requests, len(system)))
    spec = transformer_lm_session(
        VOCAB, max_len=max_len, slots=slots, cache_len=max_len,
        prompt_buckets=(8, 16), bos_id=BOS, eos_id=EOS,
        block_size=BLOCK_SIZE, prefix_cache=True, **KW)
    sess = GenerationSession(spec, scope=scope)
    sched = GenerationScheduler(sess)
    prompts = [system + [2 + i] for i in range(args.requests)]
    futs = [sched.submit(p, max_new_tokens=8, eos_id=-1)
            for p in prompts]
    outs = [f.result(timeout=300) for f in futs]
    assert all(len(o) == 8 for o in outs), [len(o) for o in outs]
    sched.drain()

    xstats = sess.prefix_stats()
    prompt_tokens = sum(len(p) for p in prompts)
    pstats = sess.pool_stats()
    row_bytes = pstats["bytes_per_block"] / BLOCK_SIZE
    full_prefills = sum(1 for _, hist, _ in sess.prefill_log
                        if hist == 0)
    print(json.dumps({
        "requests": args.requests,
        "prefix_hits": xstats["hits"],
        "prefix_misses": xstats["misses"],
        "prefix_hit_rate": round(
            xstats["shared_tokens"] / float(prompt_tokens), 3),
        "shared_tokens": xstats["shared_tokens"],
        "full_prefills": full_prefills,
        "suffix_only_prefills": len(sess.prefill_log) - full_prefills,
    }))
    assert full_prefills == 1, \
        "common prefix must prefill exactly once, got %d" % full_prefills
    print("prefill log (bucket, hist, window): %s"
          % sess.prefill_log[:args.requests])

    print("== memory: blocks in use vs a worst-case row a slot ==")
    # prompt blocks are still cached (index-pinned) post-drain
    print(json.dumps({
        "blocks_in_use": pstats["blocks_in_use"],
        "num_blocks": pstats["num_blocks"],
        "paged_cache_bytes": int(pstats["blocks_in_use"]
                                 * pstats["bytes_per_block"]),
        "row_a_slot_bytes": int(slots * max_len * row_bytes),
        "block_size": BLOCK_SIZE,
    }))

    stats = sess.compile_stats()
    print(json.dumps({
        "executor_compiles": stats["compiles"],
        "executor_cache_entries": stats["entries"],
        "closed_set": "2 prompt buckets + 1 decode + 1 block-copy",
    }))
    assert stats["compiles"] <= 4, stats
    sess.check_pool_invariant()
    sess.close()

    print("== paged-cache metric families ==")
    for line in metrics.REGISTRY.expose_text().splitlines():
        if ("prefix" in line or "kv_block" in line or "kv_pool" in line
                or "blocks_in_use" in line) and not line.startswith("#"):
            print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
