#!/usr/bin/env python3
"""A serving cell's own programs, traced apart on the chip: each prefill
bucket and the decode step of the cell's deployment (its weights, pools
and slots, built as ``benchmarks/run.py`` builds them, check and warm-up
included), run by hand outside any traffic, with the device's operations
by time a call.

A traced run of a cell (``--trace 1``) books the last 3 s of its window,
whatever programs ran in them: two runs of one seed hold one prefill of the
largest bucket or five, and every operation's seconds move with that
(PERF.md section 7). This tool times a program a call, so a parent and a
change can be compared operation by operation: run it from each tree's root
in one chip call.

    python3 tools/serve_program_profile.py --workload evabyte-serve-offline

One JSON line a prefill bucket (a prompt 48 positions short of the bucket;
``ms_a_call`` by the host's clock around ``admit``, ``ops``: device ms a
call, largest first) and one for the decode step (every slot admitted at
about two thirds of the largest bucket, steps run one by one with nothing
ahead: ``ms_a_step`` holds the host's turn too, ``ops_ms_a_step`` does
not). Chip only; no cell runs it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

STEPS = 10      # decode steps a traced call


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=4244000201)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)
    from eva_probe import _profile
    from benchmarks.harness import common, lm, serve
    cell = copy.deepcopy(lm.load_json("workloads", args.workload + ".json"))
    cfg = lm.load_config(cell["config"])
    env = common.Env(T_PROCESS, args.workload + ".profile", cell["chips"],
                     False, drain=False)
    dep = serve.Deployment(cell, cfg, args.seed, env)
    sess, vocab = dep.session, dep.arch.vocab(cfg)
    rs = np.random.RandomState(args.seed % (2 ** 31))

    def prompt(n):
        return rs.randint(2, vocab, n).astype(np.int64)
    for bucket in sorted(dep.buckets, reverse=True):
        tokens = prompt(bucket - 48)

        def once():
            slot, first = sess.admit(tokens)
            sess.retire(slot)
            return first
        once()
        t0 = time.perf_counter()
        for _ in range(args.reps):
            once()
        ms = (time.perf_counter() - t0) / args.reps * 1e3
        print(json.dumps({
            "what": "prefill", "bucket": bucket, "ms_a_call": round(ms, 3),
            "ops": _profile(once, (), reps=args.reps, top=args.top)}),
            flush=True)
    longest = max(dep.buckets) * 2 // 3
    slots = [sess.admit(prompt(longest - 40 * i))[0]
             for i in range(sess.spec.slots)]

    def steps():
        out = None
        for _ in range(STEPS):
            out = sess.step()
        return out[slots[0]]
    steps()
    t0 = time.perf_counter()
    steps()
    ms = (time.perf_counter() - t0) / STEPS * 1e3
    ops = _profile(steps, (), reps=2, top=args.top)
    print(json.dumps({
        "what": "decode", "slots": len(slots), "ms_a_step": round(ms, 3),
        "ops_ms_a_step": [[n, round(v / STEPS, 4)] for n, v in ops]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
