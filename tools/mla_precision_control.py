#!/usr/bin/env python3
"""The lower-precision controls of ``kimi-serve-offline``'s check (ISSUE 31,
point 7b): the harness's own comparison with the reference
(``benchmarks/harness/serve.py::Deployment``: a prompt a bucket prefilled,
8 decode steps through the latent cache, logits against the reference's full
forward) with one thing computed a precision lower than the configuration
states. Each must read over the check's limit, or this says by how little it
passes:

* ``--control cache``: the latent cache held in bfloat16 (and so attended in
  one bfloat16 pass: ``pallas_attention.cache_precision``);
* ``--control absorb``: the absorbed products (``W_uk`` into the query,
  ``W_uv`` after the sum) in one bfloat16 pass, the cache float32;
* ``--control none``: the configuration as it is.

    python3 tools/mla_precision_control.py --control cache --seed 3100000011

One process a control (each holds the chip's memory whole). Chip only.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _one_pass_absorb():
    """``mla_ops.exact_einsum`` with the two absorbed products rounded to
    bfloat16 and multiplied in one pass; the expansion stays exact."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import mla_ops
    exact = mla_ops.exact_einsum

    def einsum(spec, x, w):
        if spec in ("shd,chd->shc", "shc,chd->shd"):
            return jnp.einsum(spec, x.astype(jnp.bfloat16),
                              w.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.DEFAULT)
        return exact(spec, x, w)
    mla_ops.exact_einsum = einsum


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=("none", "cache", "absorb"),
                    required=True)
    ap.add_argument("--seed", type=int, default=3100000011)
    ap.add_argument("--workload", default="kimi-serve-offline")
    args = ap.parse_args(argv)
    from benchmarks.harness import common, lm, serve
    cell = lm.load_json("workloads", args.workload + ".json")
    cfg = copy.deepcopy(lm.load_config(cell["config"]))
    if args.control == "cache":
        cfg["deployment"]["serving"]["kv_dtype"] = "bfloat16"
    elif args.control == "absorb":
        _one_pass_absorb()
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
