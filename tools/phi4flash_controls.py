#!/usr/bin/env python3
"""The witnesses of ``phi4flash-serve-offline``'s check (ISSUE 49): the
harness's own comparison with the reference
(``benchmarks/harness/serve.py::Deployment``: a prompt a bucket prefilled,
8 decode steps through the state pool and the paged kinds, logits against
the reference's full forward) with one thing wrong. Each has to read over
the check's limit, or this says by how little it passes.

* ``--control memory``: the gated memory units read ``m`` = zeros, not
  layer ``memory_from``'s scan output;
* ``--control pool``: every cross layer walks an empty pool (one block of
  zeros, whatever the table), not layer ``kv_from``'s keys and values;
* ``--control lambda``: lambda 0 in every differential layer (a pair's
  second softmax is dropped);
* ``--control state``: the prefill leaves a zero state in the slot's row
  (the scan's state is not carried from prefill to decode);
* ``--control none``: the configuration as it is.

    python3 tools/phi4flash_controls.py --control memory --seed 4249000011

One process a control (each holds the chip's memory whole). Chip only;
``tests/test_phi4flash_lm.py`` runs the four at a small size.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _memory():
    from paddle_tpu import layers
    from paddle_tpu.models.moe_lm import MoeLM
    gmu = MoeLM._gmu

    def _gmu(self, a, i, handed):
        return gmu(self, a, i, dict(
            handed, memory=layers.scale(handed["memory"], 0.0)))
    MoeLM._gmu = _gmu


def _pool():
    from paddle_tpu import layers
    from paddle_tpu.models import moe_lm
    attention = moe_lm.MoeLM._attention

    def _attention(self, a, i, ctx, handed=None):
        if ctx is not None and self.layer_types[i] == moe_lm.CROSS:
            at = self.site[self.kv_from]
            kind = self.cache_layers[at][1]
            caches, tables = list(ctx["caches"]), list(ctx["tables"])
            caches[at] = tuple(
                layers.fill_constant([1] + list(pool.shape[1:]), pool.dtype,
                                     0.0) for pool in caches[at])
            tables[kind] = layers.fill_constant(
                list(tables[kind].shape), "int32", 0)
            ctx = dict(ctx, caches=caches, tables=tables)
        return attention(self, a, i, ctx, handed)
    moe_lm.MoeLM._attention = _attention


def _lambda():
    from paddle_tpu.ops import attention_ops
    attention_ops.diff_lambda = lambda *_: 0.0


def _state():
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    scan = ssm_ops.s6_scan

    def s6_scan(*args):
        y, last = scan(*args)
        return y, jnp.zeros_like(last)
    ssm_ops.s6_scan = s6_scan


CONTROLS = {"none": lambda: None, "memory": _memory, "pool": _pool,
            "lambda": _lambda, "state": _state}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, default=4249000011)
    ap.add_argument("--workload", default="phi4flash-serve-offline")
    ap.add_argument("--buckets", default="",
                    help="the check's prompt buckets (default: the cell's)")
    args = ap.parse_args(argv)
    from benchmarks.harness import common, lm, serve
    cell = lm.load_json("workloads", args.workload + ".json")
    if args.buckets:
        cell["prompt_buckets"] = [int(b) for b in args.buckets.split(",")]
    cfg = lm.load_config(cell["config"])
    CONTROLS[args.control]()
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
