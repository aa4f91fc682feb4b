"""Generation serving probe: KV-cache decode + continuous batching,
headless.

Builds a transformer LM, randomizes its weights, then drives the
cached-decode stack end to end:

1. **Baseline** — the O(L^2) re-encode reference
   (``transformer_lm_generate``, beam_size=1) timed over the same
   generation lengths, so the report carries the honest speedup and
   its growth with length (the acceptance criterion: cached wins at
   length >= 64 and the gap widens).
2. **Session** — prefill + ``STEPS`` decode steps through a
   ``GenerationSession`` with mid-flight admits and retires (slot-level
   continuous batching: sequences at different depths share every
   decode step), printing per-step latency percentiles, decode
   tokens/sec, time-to-first-token, cache-slot occupancy, and the
   executor compile counters proving the closed shape set (one decode
   compile, one per prompt bucket — however many requests flow).
3. **Scheduler** — concurrent submits through ``GenerationScheduler``
   with the generation metric families printed at the end.

Usage:
    JAX_PLATFORMS=cpu python tools/generate_probe.py [--steps N]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

VOCAB = 64
# large enough that the re-encode baseline's per-step compute dominates
# Python dispatch on CPU — the speedup numbers then reflect the O(L^2)
# vs O(L) algorithmic gap, not interpreter overhead
KW = dict(d_model=256, num_heads=4, d_ff=1024, num_layers=2)
BOS, EOS = 0, 1
SLOTS = 4


def _pct(xs, p):
    return float(np.percentile(np.asarray(xs), p)) if len(xs) else 0.0


def build_scope(max_len):
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm_generate

    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            anchor = layers.data("anchor", shape=[1], dtype="int32")
            ids, lengths, _ = transformer_lm_generate(
                anchor, vocab_size=VOCAB, max_len=max_len, beam_size=1,
                bos_id=BOS, eos_id=EOS, **KW)
    exe = ptpu.Executor()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope):
        exe.run(startup)
    rs = np.random.RandomState(7)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        scope.set_var(n, rs.standard_normal(cur.shape).astype(cur.dtype))
    return scope, exe, main, ids


def bench_reencode(exe, main, ids, scope, length):
    feed = {"anchor": np.zeros((1, 1), "int32")}
    exe.run(main, feed=feed, fetch_list=[ids], scope=scope)  # compile
    t0 = time.perf_counter()
    exe.run(main, feed=feed, fetch_list=[ids], scope=scope)
    return length / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=64,
                    help="decode steps in the continuous-batching run")
    args = ap.parse_args()
    steps = args.steps
    max_len = max(2 * steps, steps + 16)

    import paddle_tpu as ptpu
    from paddle_tpu.models.transformer import transformer_lm_session
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.generation import (GenerationScheduler,
                                               GenerationSession)

    print("== baseline: O(L^2) re-encode reference ==")
    reencode_tps = {}
    for length in (steps, 2 * steps):
        scope_b, exe_b, main_b, ids_b = build_scope(length)
        reencode_tps[length] = bench_reencode(exe_b, main_b, ids_b,
                                              scope_b, length)
        print(json.dumps({"reencode_len": length,
                          "tokens_per_sec":
                              round(reencode_tps[length], 1)}))

    scope, _, _, _ = build_scope(max_len)
    spec = transformer_lm_session(
        VOCAB, max_len=max_len, slots=SLOTS, cache_len=max_len,
        prompt_buckets=(8, 16), bos_id=BOS, eos_id=EOS, **KW)
    sess = GenerationSession(spec, scope=scope)

    print("== session: prefill + %d decode steps, mid-flight "
          "admit/retire ==" % steps)
    rs = np.random.RandomState(0)
    t0 = time.perf_counter()
    slot0, _ = sess.admit([BOS])
    ttft_ms = (time.perf_counter() - t0) * 1e3
    sess.admit(list(rs.randint(2, VOCAB, 5)))
    sess.admit(list(rs.randint(2, VOCAB, 7)))
    step_ms, occupancies = [], []
    produced = 3
    for i in range(steps):
        if i == steps // 4:      # mid-flight admit into the free slot
            sess.admit(list(rs.randint(2, VOCAB, 12)))
            produced += 1        # prefill's first token
        if i == steps // 2:      # mid-flight retire + same-step admit
            sess.retire(slot0)
            sess.admit(list(rs.randint(2, VOCAB, 3)))
            produced += 1
        t0 = time.perf_counter()
        toks = sess.step()
        step_ms.append((time.perf_counter() - t0) * 1e3)
        produced += len(toks)
        occupancies.append(sess.occupancy())
    decode_tps = produced / (sum(step_ms) / 1e3)
    stats = sess.compile_stats()
    report = {
        "decode_steps": steps,
        "tokens_decoded": produced,
        "decode_tokens_per_sec": round(decode_tps, 1),
        "time_to_first_token_ms": round(ttft_ms, 2),
        "inter_token_ms_p50": round(_pct(step_ms, 50), 2),
        "inter_token_ms_p95": round(_pct(step_ms, 95), 2),
        "cache_slot_occupancy_mean": round(float(
            np.mean(occupancies)), 3),
        "cache_slot_occupancy_max": round(float(
            np.max(occupancies)), 3),
        "executor_compiles": stats["compiles"],
        "executor_cache_entries": stats["entries"],
        "batched_speedup_vs_reencode@%d" % steps: round(
            decode_tps / reencode_tps[steps], 2),
    }
    print(json.dumps(report))
    for s in sess.active_slots():
        sess.retire(s)

    print("== speedup vs re-encode, matched cache buckets "
          "(slots=1) ==")
    for length in (steps, 2 * steps):
        solo_spec = transformer_lm_session(
            VOCAB, max_len=length, slots=1, cache_len=length,
            prompt_buckets=(8,), bos_id=BOS, eos_id=EOS, **KW)
        solo = GenerationSession(solo_spec, scope=scope)
        solo.generate([BOS], max_new_tokens=length,
                      eos_id=-1)                      # warm compiles
        t0 = time.perf_counter()
        toks = solo.generate([BOS], max_new_tokens=length, eos_id=-1)
        solo_tps = len(toks) / (time.perf_counter() - t0)
        print(json.dumps({
            "length": length,
            "cached_tokens_per_sec": round(solo_tps, 1),
            "reencode_tokens_per_sec": round(reencode_tps[length], 1),
            "speedup": round(solo_tps / reencode_tps[length], 2)}))

    print("== scheduler: concurrent submits, slot-level continuous "
          "batching ==")
    sched = GenerationScheduler(sess)
    futs = [sched.submit(list(rs.randint(2, VOCAB,
                                         int(rs.randint(1, 8)))),
                         max_new_tokens=16, eos_id=-1)
            for _ in range(12)]
    done = sum(1 for f in futs if len(f.result(timeout=300)) > 0)
    sched.drain()
    stats2 = sess.compile_stats()
    print(json.dumps({"scheduler_requests": len(futs),
                      "completed": done,
                      "executor_compiles": stats2["compiles"],
                      "compiles_added_by_scheduler_run":
                          stats2["compiles"] - stats["compiles"]}))

    print("== generation metric families ==")
    for line in metrics.REGISTRY.expose_text().splitlines():
        if "generation" in line and not line.startswith("#"):
            print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
