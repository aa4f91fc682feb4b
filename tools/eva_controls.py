#!/usr/bin/env python3
"""The controls of ``evabyte-serve-offline``'s check (ISSUE 42): the
harness's own comparison with the reference
(``benchmarks/harness/serve.py::Deployment``: a prompt a bucket prefilled,
8 decode steps through the window pool and the chunk pool across a window's
edge, logits against the reference's full forward) over a program with one
thing wrong. Each must read over the check's limit, or this says by how
little it passes:

* ``--control zeroed``: every summary is zeros, whoever writes it (the
  prefill, the decode step): the chunk pool zeroed;
* ``--control mean``: a chunk is pooled by its mean, not by the two learned
  softmaxes;
* ``--control unseen``: the decode step walks the window pool alone, and
  a prefill through the flash forward attends its windows alone (the
  summaries are written and never merged in: ``merge_walks`` is the one
  merge of both);
* ``--control none``: the configuration as it is.

    python3 tools/eva_controls.py --control zeroed --seed 4200000011

``--buckets`` takes fewer prompt buckets than the cell's (each is a
program to compile), and at least two: the check's prompts end ten
positions before the widest bucket's end, so a lone bucket's prompt never
crosses its window's edge while decoding (``4096,8192``: the first crosses
the edge at 4,096, the second decodes over 384 summaries). One process a
control (each holds the chip's memory
whole). Chip only. ``tests/test_evabyte_lm.py`` sets the first two at a
small size on the CPU.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pooled(change):
    """``eva_ops.pool_chunks`` replaced by ``change(k, v, chunk, real)``."""
    from paddle_tpu.ops import eva_ops
    real = eva_ops.pool_chunks
    return [(eva_ops, "pool_chunks",
             lambda k, v, mu, phi, nh, chunk: change(
                 k, v, chunk, lambda: real(k, v, mu, phi, nh, chunk)))]


def _zeroed():
    return _pooled(lambda k, v, chunk, real: tuple(0.0 * x for x in real()))


def _mean():
    import jax.numpy as jnp

    def mean(rows, chunk):
        chunks = rows.shape[:-2] + (-1, chunk, rows.shape[-1])
        return jnp.mean(rows.astype(jnp.float32).reshape(chunks), axis=-2)
    return _pooled(lambda k, v, chunk, real: (mean(k, chunk),
                                              mean(v, chunk)))


def _unseen():
    from paddle_tpu.ops import pallas_attention as pa
    real = pa.merge_walks
    return [(pa, "merge_walks", lambda walks, nh: real(walks[:1], nh))]


# control -> () -> [(owner, attribute, replacement)]: what makes the program
# wrong, set here for the process's life
CONTROLS = {"none": lambda: [], "zeroed": _zeroed, "mean": _mean,
            "unseen": _unseen}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, default=4200000011)
    ap.add_argument("--workload", default="evabyte-serve-offline")
    ap.add_argument("--buckets", default="")
    args = ap.parse_args(argv)
    from benchmarks.harness import common, lm, serve
    cell = copy.deepcopy(lm.load_json("workloads", args.workload + ".json"))
    if args.buckets:
        cell["prompt_buckets"] = [int(b) for b in args.buckets.split(",")]
    cfg = lm.load_config(cell["config"])
    for owner, name, wrong in CONTROLS[args.control]():
        setattr(owner, name, wrong)
    env = common.Env(T_PROCESS, args.workload + ".control", cell["chips"],
                     False, drain=False)
    dep = serve.Deployment(cell, cfg, args.seed, env)
    report = dep.check_report
    print(json.dumps({
        "control": args.control, "seed": args.seed,
        "decode_logit_rel_err": report["worst_rel_err"],
        "prefill_token_rel_gap": report["worst_first_token_rel_gap"],
        "limit": report["rtol"],
        "fails_the_check": bool(max(report["worst_rel_err"],
                                    report["worst_first_token_rel_gap"])
                                > report["rtol"]),
        "per_bucket": report["per_bucket"],
        "kernel_paths": dep.kernel_paths}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
