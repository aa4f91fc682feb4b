"""Fleet engine-worker child process (test_fleet.py + the fleet
probe/bench drivers).

Runs one :class:`~paddle_tpu.serving.fleet.EngineWorker` serving a
tiny deterministic transformer LM through a GenerationScheduler, and
registers with the router whose control address arrives on argv.
EVERY worker built from the same ``--seed`` holds bit-identical
weights — that is what makes a replay journal re-driven on a peer
produce token-for-token the fault-free output (greedy determinism).

The parent imports :func:`build_scope` / :func:`make_scheduler` /
:func:`model_params` to build the same model in-process for the
bit-identical oracle and to write deploy pushes.

Usage:
    python fleet_worker_child.py --router HOST:PORT --member m0
        [--seed 7] [--kill-at-token N] [--fail-after-swap TAG]
        [--compile-cache DIR] [--heartbeat-ms MS] [--slots N]

``--kill-at-token N`` arms the ``fleet_member_kill`` fault site with
``action="kill"`` at streamed-token N: the worker SIGKILLs itself
mid-generation — the deterministic process-death chaos shape.
``--fail-after-swap TAG`` makes a swap landing TAG behave as a broken
weights push (persistent ``generation_step_fail`` until rollback).
Prints ``READY <member> <port>`` on stdout once registered.
"""

import argparse
import os
import sys

# always the CPU, and said so: this child is a protocol double for
# tests and CPU probes, never a chip member — a chip belongs to one
# process, and a fleet member on a chip needs a chip of its own
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

VOCAB = 64
MAX_LEN = 48
KW = dict(d_model=64, num_heads=2, d_ff=128, num_layers=2)
PROMPT_BUCKETS = (8, 16, 32)
BOS, EOS = 0, 1


def build_scope(seed=7):
    """A trained-looking LM scope, deterministic in ``seed`` — every
    fleet member built from one seed serves identical weights."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm

    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[1, MAX_LEN],
                               dtype="int64", append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, MAX_LEN],
                               dtype="int64", append_batch_size=False)
            transformer_lm(toks, lbls, vocab_size=VOCAB, is_test=True,
                           **KW)
    exe = ptpu.Executor()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope):
        exe.run(startup)
    rs = np.random.RandomState(seed)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        if np.issubdtype(cur.dtype, np.floating):
            scope.set_var(n, rs.standard_normal(cur.shape)
                          .astype(cur.dtype))
    return scope


def model_params(scope, factor=1.0):
    """The swappable float params of a freshly-built scope (cache
    variables don't exist yet; special ``@...@`` state excluded),
    optionally scaled — the deploy-push payload."""
    out = {}
    for n in sorted(scope.var_names()):
        if n.startswith("@") or n.startswith("kv_session"):
            # special executor state / session cache variables (a
            # scope a session already ran on carries them; a push
            # naming one is rejected by swap_weights)
            continue
        cur = np.asarray(scope.find_var(n))
        if np.issubdtype(cur.dtype, np.floating):
            out[n] = (cur * factor).astype(cur.dtype)
    return out


def make_scheduler(scope, slots=4, replay_attempts=2, warm=True,
                   decode_policy=None):
    from paddle_tpu.models.transformer import transformer_lm_session
    from paddle_tpu.serving.generation import (GenerationScheduler,
                                               GenerationSession)

    spec = transformer_lm_session(
        VOCAB, max_len=MAX_LEN, slots=slots, cache_len=MAX_LEN,
        prompt_buckets=PROMPT_BUCKETS, bos_id=BOS, eos_id=EOS,
        decode_policy=decode_policy, **KW)
    sess = GenerationSession(spec, scope=scope)
    if warm:
        sess.generate([BOS], max_new_tokens=2, eos_id=-1)
    return GenerationScheduler(sess, replay_attempts=replay_attempts)


def sampled_policy(temperature=4.0, top_k=0, top_p=1.0):
    """The one sampled policy the sampled-fleet chaos tests share —
    parent oracle and child members must agree on every knob, or the
    fingerprint gate (correctly) resets their journals. Temperature
    4.0 on purpose: the random-weight child LM has sharply peaked
    logits, and anything near 1.0 degenerates to argmax — a sampled
    chaos test that secretly replays greedy proves nothing."""
    from paddle_tpu.serving.decoding import DecodePolicy
    return DecodePolicy(kind="sample", temperature=temperature,
                        top_k=top_k, top_p=top_p)


def chaos_prompts(n, seed=0):
    """Prompt-dependent varied prompts (an attractor sequence can't
    fake bit-identity) — shared by tests, probe, and bench."""
    rs = np.random.RandomState(seed)
    return [[BOS] + [int(t) for t in
                     rs.randint(2, VOCAB, int(rs.randint(1, 7)))]
            for _ in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--router", required=True)
    ap.add_argument("--member", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--kill-at-token", type=int, default=None)
    ap.add_argument("--decode-policy", default=None,
                    choices=(None, "greedy", "sample"))
    ap.add_argument("--decode-temperature", type=float, default=4.0)
    ap.add_argument("--fail-after-swap", default=None)
    ap.add_argument("--compile-cache", default=None)
    ap.add_argument("--heartbeat-ms", type=float, default=None)
    ap.add_argument("--metrics-interval-ms", type=float, default=None)
    ap.add_argument("--version", default="v0")
    ap.add_argument("--model", default=None,
                    help="catalog model this worker starts resident "
                    "for (multi-model fleets)")
    args = ap.parse_args()

    import paddle_tpu as ptpu
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving.fleet import EngineWorker

    if args.compile_cache:
        # PR-7 persistent compile cache: a cold member deserializes
        # executables a warm one published — scale-up-to-first-token
        ptpu.config.set_flags(compile_cache_dir=args.compile_cache)

    policy = None
    if args.decode_policy == "sample":
        policy = sampled_policy(temperature=args.decode_temperature)
    scope = build_scope(args.seed)
    sched = make_scheduler(scope, slots=args.slots,
                           decode_policy=policy)

    if args.kill_at_token is not None:
        faults.arm("fleet_member_kill", at=args.kill_at_token,
                   times=1, action="kill")

    host, port = args.router.rsplit(":", 1)
    worker = EngineWorker(
        sched, member_id=args.member, router_addr=(host, int(port)),
        heartbeat_ms=args.heartbeat_ms, version=args.version,
        fail_after_swap_tag=args.fail_after_swap,
        metrics_interval_ms=args.metrics_interval_ms,
        model=args.model)
    print("READY %s %d" % (args.member, worker.addr[1]), flush=True)
    try:
        worker.serve_forever()
    finally:
        sched.close()


if __name__ == "__main__":
    main()
