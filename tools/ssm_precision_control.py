#!/usr/bin/env python3
"""The controls of ``granite-serve-offline``'s check (ISSUE 33): the
harness's own comparison with the reference
(``benchmarks/harness/serve.py::Deployment``: a prompt a bucket prefilled,
8 decode steps through the state pool and the paged cache, logits against
the reference's full forward) with one thing wrong. Each should read over
the check's limit, or this says by how little it passes.

A wrong state, which the check must tell (the mixer's initial values are
chosen so that the state carries 0.4 of ``y``: ``layers/ssm.py``):

* ``--control zeroed``: the prefill leaves a zero state in the slot's row;
* ``--control stale``: a decode step never advances a row;
* ``--control crossed``: a decode step reads its neighbour slot's row (the
  state of another request).

One thing computed a precision lower than the configuration states:

* ``--control state``: the scan's state held in bfloat16 (every row the
  prefill leaves and every step writes is rounded to 8 bits of
  significand, as a bfloat16 pool would hold it);
* ``--control scan``: the chunked scan's products in one bfloat16 pass (the
  state float32);
* ``--control none``: the configuration as it is.

    python3 tools/ssm_precision_control.py --control state --seed 3300000011

One process a control (each holds the chip's memory whole). Chip only;
``tests/test_ssm_lm.py`` runs the three faults at a small size.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bfloat16_state():
    """``ssm_ops`` leaving every state it writes rounded to bfloat16."""
    import jax
    from paddle_tpu.ops import ssm_ops
    chunked, step = ssm_ops.ssd_chunked, ssm_ops.ssm_step

    def held(s):
        # not a cast there and back: the compiler may drop such a pair
        return jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)

    def ssd_chunked(*args):
        y, last = chunked(*args)
        return y, held(last)

    def ssm_step(*args):
        new, y = step(*args)
        return held(new), y
    ssm_ops.ssd_chunked, ssm_ops.ssm_step = ssd_chunked, ssm_step


def _one_pass_scan():
    import jax
    from paddle_tpu.ops import ssm_ops
    ssm_ops._HIGHEST = jax.lax.Precision.DEFAULT


def _zeroed():
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    chunked = ssm_ops.ssd_chunked

    def ssd_chunked(*args):
        y, last = chunked(*args)
        return y, jnp.zeros_like(last)
    ssm_ops.ssd_chunked = ssd_chunked


def _stale():
    from paddle_tpu.ops import ssm_ops
    step = ssm_ops.ssm_step

    def ssm_step(ssm, dt, a, x, b, c, fresh):
        return step(ssm, dt, a, x, b, c, fresh & False)
    ssm_ops.ssm_step = ssm_step


def _crossed():
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    step = ssm_ops.ssm_step

    def ssm_step(ssm, *args):
        return step(jnp.roll(ssm, 1, axis=0), *args)
    ssm_ops.ssm_step = ssm_step


CONTROLS = {"none": lambda: None, "zeroed": _zeroed, "stale": _stale,
            "crossed": _crossed, "state": _bfloat16_state,
            "scan": _one_pass_scan}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, default=3300000011)
    ap.add_argument("--workload", default="granite-serve-offline")
    args = ap.parse_args(argv)
    from benchmarks.harness import common, lm, serve
    cell = lm.load_json("workloads", args.workload + ".json")
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
