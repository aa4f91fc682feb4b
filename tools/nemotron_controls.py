#!/usr/bin/env python3
"""The controls of ``nemotron-serve-offline``'s check (ISSUE 46), and the
witness that tells a router's choice at a near tie from a fault.

The check is the harness's own (``benchmarks/harness/serve.py::Deployment``:
a prompt a bucket prefilled, 8 decode steps through the state rows and the
paged keys and values, decode logits against the reference's full forward):

* ``--control none``: the configuration as it is;
* ``--control bf16``: every activation that meets a bfloat16 weight rounded
  to bfloat16 first (the first of its three pieces alone, in the dense
  products and in the experts' kernels): one bfloat16 pass in the program's
  place, the precision below the configuration's. The router's own product,
  the scan, the state and the caches stay float32.

``--witness 1`` then runs the same prompts once more **with the input of
every expert layer fetched beside the logits**, the prefills' rows and the
decode steps' (the program's ``moe_ffn`` ops' ``X``), and reads, a bucket:

* the harness's number again (``err_own``);
* the program's choice at every row (the top k of the router's scores of
  the program's own input, float64 on the host) beside the reference's
  choice at the same row, every row where the two differ with the gap
  between the k-th and the (k+1)-th score on either side and the largest
  difference of the two sides' scores there: a choice at a near tie has a
  gap no larger than that difference;
* the reference **handed the program's choices** (``forced``:
  ``reference/nemotron_h.py::route``) against the program's logits
  (``err_forced``). Where ``err_own`` is over the limit and ``err_forced``
  is the order of the float32 sums, every other number of the program is
  the reference's and the choice is all that differs; where it is not, the
  fault is in the program.

    python3 tools/nemotron_controls.py --control none --witness 1 \\
        --seed 4146000205

One process a control (each holds the chip's memory whole). Chip only, but
for ``--tiny 1``: the rehearsal's size on the CPU, which checks the flow.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bf16():
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops, pallas_moe

    def first_piece_alone(x):
        top = x.astype(jnp.bfloat16)
        return [top, jnp.zeros_like(top), jnp.zeros_like(top)]
    return [(moe_ops, "_pieces", first_piece_alone),
            (pallas_moe, "_pieces", first_piece_alone)]


# control -> () -> [(owner, attribute, replacement)], set for the process's
# life
CONTROLS = {"none": lambda: [], "bf16": _bf16}


def expert_inputs(program):
    """[(layer, name of the ``moe_ffn`` op's X)] of a program, in program
    order: the model's expert layers by their parameters' names."""
    found = []
    for op in program.global_block().ops:
        if op.type == "moe_ffn":
            layer = int(op.inputs["RouterW"][0].split(".l")[1].split(".")[0])
            found.append((layer, op.inputs["X"][0]))
    return found


def choices(x, router, bias, k):
    """x [n, d] -> (the top k by ``sigmoid(x router) + bias`` [n, k], the
    scores [n, E], the gap between the k-th and the (k+1)-th [n]), float64
    on the host."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(router, np.float64))))
    ranked = np.sort(s + np.asarray(bias, np.float64), axis=1)[:, ::-1]
    sel = np.argsort(-(s + np.asarray(bias, np.float64)), axis=1,
                     kind="stable")[:, :k]
    return sel.astype(np.int32), s, ranked[:, k - 1] - ranked[:, k]


def witness(dep, seed):
    """The check's prompts once more with every expert layer's input
    fetched: see the module's docstring. -> a report a bucket."""
    import jax
    import jax.numpy as jnp
    from benchmarks import architectures
    from benchmarks.harness import lm, serve
    sess, spec, cfg = dep.session, dep.spec, dep.cfg
    ref = architectures.reference(cfg)
    k = cfg["num_experts_per_tok"]
    vocab = dep.arch.vocab(cfg)
    # the harness's own draw (serve.py::_check_against_reference)
    rs = np.random.RandomState(seed + 7919)
    width = dep.buckets[-1]
    steps = serve.CHECK_STEPS
    lens = [min(b - 2, width - steps - 2) for b in dep.buckets]
    prompts = [rs.randint(2, vocab, n).astype(np.int64) for n in lens]

    # a prefill's expert inputs ride beside its token: the session's own
    # call with a longer fetch list
    run, taken = sess.exe.run, []

    def tapped(program, feed=None, fetch_list=None, **kw):
        taps = [name for _, name in expert_inputs(program)]
        outs = run(program, feed=feed, fetch_list=list(fetch_list) + taps,
                   **kw)
        taken.append([np.asarray(o, np.float32)
                      for o in outs[len(fetch_list):]])
        return outs[:len(fetch_list)]

    slots, toks, seen = [], [], []
    sess.exe.run = tapped
    try:
        for p in prompts:
            slot, first = sess.admit(p)
            slots.append(slot)
            toks.append([first])
            assert len(taken) == 1, "an admission is one prefill call"
            # [layers][1, bucket, d] -> [layers, rows of the prompt, d]
            seen.append([np.stack([x.reshape(-1, x.shape[-1])[:len(p)]
                                   for x in taken.pop()])])
    finally:
        sess.exe.run = run
    layers_, taps = zip(*expert_inputs(spec.decode_program))
    logits_name = lm.logits_var(spec.decode_program, spec.decode_fetch)
    got = [[] for _ in prompts]
    for _ in range(steps):
        prepared = sess.step_prepare()
        outs = sess.exe.run(
            spec.decode_program, feed=prepared[2],
            fetch_list=[logits_name, spec.decode_fetch] + list(taps),
            scope=sess.scope)
        logits = np.asarray(outs[0], np.float32)
        xs = [np.asarray(x, np.float32).reshape(logits.shape[0], -1)
              for x in outs[2:]]
        out = sess.step_run(prepared)
        for i, slot in enumerate(slots):
            got[i].append(logits[slot])
            toks[i].append(out[slot])
            seen[i].append(np.stack([x[slot] for x in xs])[:, None])
    for slot in slots:
        sess.retire(slot)

    weights = ref.gather_weights(sess.scope.find_var, cfg)
    routers = {layer: (np.asarray(weights["l%d.router" % layer], np.float32),
                       np.asarray(weights["l%d.bias" % layer], np.float32))
               for layer in layers_}
    ref_fn = jax.jit(lambda w, t, pos, forced:
                     ref.logits_and_router_inputs(w, t, pos, cfg, forced))
    report = []
    for i, (p, n) in enumerate(zip(prompts, lens)):
        rows = n + steps                    # the rows the program ran
        seq = np.zeros(width, np.int32)
        seq[:n] = p
        seq[n:n + steps + 1] = toks[i]
        pos = np.arange(n - 1, n + steps, dtype=np.int32)
        mine = np.concatenate(seen[i], axis=1)          # [layers, rows, d]
        free = {layer: jnp.full((width, k), -1, jnp.int32)
                for layer in layers_}
        want, theirs = ref_fn(weights, jnp.asarray(seq), jnp.asarray(pos),
                              free)
        want = np.asarray(want)
        forced, differ, gaps = {}, [], []
        for j, layer in enumerate(layers_):
            router, bias = routers[layer]
            sel_p, s_p, gap_p = choices(mine[j], router, bias, k)
            sel_r, s_r, gap_r = choices(np.asarray(theirs[layer])[:rows],
                                        router, bias, k)
            gaps.append(float(gap_p[n:].min()))
            told = np.full((width, k), -1, np.int32)
            told[:rows] = sel_p
            forced[layer] = jnp.asarray(told)
            for row in np.nonzero((np.sort(sel_p, 1)
                                   != np.sort(sel_r, 1)).any(1))[0]:
                differ.append({
                    "layer": int(layer), "row": int(row),
                    "decode_step": int(row - n) if row >= n else None,
                    "program_only": sorted(set(sel_p[row].tolist())
                                           - set(sel_r[row].tolist())),
                    "reference_only": sorted(set(sel_r[row].tolist())
                                             - set(sel_p[row].tolist())),
                    "gap_program": float(gap_p[row]),
                    "gap_reference": float(gap_r[row]),
                    "scores_differ_by": float(
                        np.abs(s_p[row] - s_r[row]).max())})
        told_logits = np.asarray(ref_fn(weights, jnp.asarray(seq),
                                        jnp.asarray(pos), forced)[0])
        scale = float(np.abs(want).max())
        have = np.stack(got[i])
        report.append({
            "bucket": int(dep.buckets[i]), "prompt_len": int(n),
            "max_abs_logit": scale,
            "err_own": float(np.abs(have - want[1:]).max()) / scale,
            "err_forced": float(np.abs(have - told_logits[1:]).max())
            / scale,
            "err_by_decode_step_own": [
                float(np.abs(have[t] - want[1 + t]).max()) / scale
                for t in range(steps)],
            "smallest_gap_in_a_decode_row": min(gaps),
            "rows_that_differ": len(differ),
            "decode_rows_that_differ": sum(
                d["decode_step"] is not None for d in differ),
            "differ": differ[:24]})
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, default=4146000205)
    ap.add_argument("--workload", default="nemotron-serve-offline")
    ap.add_argument("--buckets", default="")
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--tiny", type=int, default=0)
    args = ap.parse_args(argv)
    from benchmarks import architectures
    from benchmarks.harness import common, lm, serve
    cell = copy.deepcopy(lm.load_json("workloads", args.workload + ".json"))
    if args.buckets:
        cell["prompt_buckets"] = [int(b) for b in args.buckets.split(",")]
    cfg = lm.load_config(cell["config"])
    if args.tiny:
        cfg = architectures.load(cfg).tiny(cfg)
        cell["prompt_buckets"] = [16, 32]
    for owner, name, wrong in CONTROLS[args.control]():
        setattr(owner, name, wrong)
    env = common.Env(T_PROCESS, args.workload + ".control", cell["chips"],
                     False, require_tpu=not args.tiny, drain=False)
    dep = serve.Deployment(cell, cfg, args.seed, env)
    report = dep.check_report
    line = {
        "control": args.control, "seed": args.seed,
        "decode_logit_rel_err": report["worst_rel_err"],
        "prefill_token_rel_gap": report["worst_first_token_rel_gap"],
        "limit": report["rtol"],
        "fails_the_check": bool(max(report["worst_rel_err"],
                                    report["worst_first_token_rel_gap"])
                                > report["rtol"]),
        "per_bucket": report["per_bucket"],
        "kernel_paths": dep.kernel_paths}
    print(json.dumps(line), flush=True)
    if args.witness:
        line = {"witness": witness(dep, args.seed), "control": args.control,
                "seed": args.seed}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
