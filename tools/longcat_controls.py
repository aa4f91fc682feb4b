#!/usr/bin/env python3
"""The controls of ``longcat-serve-offline``'s check (ISSUE 40): the
harness's own comparison with the reference
(``benchmarks/harness/serve.py::Deployment``: a prompt a bucket prefilled,
8 decode steps through the eight latent pools, logits against the
reference's full forward) over a program with one thing wrong. Each must
read over the check's limit, or this says by how little it passes:

* ``--control identity``: the identity experts' part zeroed (their pairs
  weigh nothing);
* ``--control shortcut``: the expert layer's result ``s`` never added
  (every pair weighs nothing);
* ``--control late_read``: ``s`` read at the second half's norm instead of
  the first (the block's spec, ``experts_read`` 1);
* ``--control held``: the held real experts' part zeroed, the identity part
  kept;
* ``--control bf16``: every projection's activations rounded to bfloat16 and
  multiplied in one pass, the precision below the configuration's;
* ``--control none``: the configuration as it is.

    python3 tools/longcat_controls.py --control held --seed 4000000011

``--buckets`` takes fewer prompt buckets than the cell's (each is a
program to compile). One process a control (each holds the chip's memory
whole). Chip only.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _weigh(change):
    """``moe_ops.route`` with its weights passed through ``change(sel,
    w)``."""
    from paddle_tpu.ops import moe_ops
    real = moe_ops.route

    def route(*args, **kw):
        sel, w = real(*args, **kw)
        return sel, change(sel, w)
    return [(moe_ops, "route", route)]


def _identity(cfg):
    import jax.numpy as jnp
    real_experts = cfg["n_routed_experts_published"]
    return _weigh(lambda sel, w: jnp.where(sel >= real_experts, 0.0, w))


def _shortcut(cfg):
    del cfg
    return _weigh(lambda sel, w: w * 0.0)


def _late_read(cfg):
    del cfg
    from benchmarks.architectures import longcat_flash as arch
    real = arch.sizes

    def sizes(c):
        s = real(c)
        return dict(s, block=dict(s["block"], experts_read=1))
    return [(arch, "sizes", sizes)]


def _held(cfg):
    del cfg
    from paddle_tpu.ops import moe_ops
    real = moe_ops._expert_rows
    return [(moe_ops, "_expert_rows", lambda *a: real(*a) * 0.0)]


def _bf16(cfg):
    del cfg
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops

    def dot(x, w, transposed=False):
        dims = (((1,), (1 if transposed else 0,)), ((), ()))
        return jax.lax.dot_general(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
    return [(moe_ops, "exact_dot", dot)]


# control -> (cfg) -> [(owner, attribute, replacement)]: what makes the
# program wrong. Set here for the process's life; tests/test_longcat_lm.py
# sets the same under monkeypatch, at a small size
CONTROLS = {"none": lambda cfg: [], "identity": _identity,
            "shortcut": _shortcut, "late_read": _late_read, "held": _held,
            "bf16": _bf16}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, default=4000000011)
    ap.add_argument("--workload", default="longcat-serve-offline")
    ap.add_argument("--buckets", default="")
    args = ap.parse_args(argv)
    from benchmarks.harness import common, lm, serve
    cell = copy.deepcopy(lm.load_json("workloads", args.workload + ".json"))
    if args.buckets:
        cell["prompt_buckets"] = [int(b) for b in args.buckets.split(",")]
    cfg = lm.load_config(cell["config"])
    for owner, name, wrong in CONTROLS[args.control](cfg):
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
