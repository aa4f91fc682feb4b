#!/usr/bin/env python3
"""Finds the knee of an open-loop cell: the highest arrival rate the system
sustains on the cell's traffic mix without a growing backlog.

    python benchmarks/sweeps/knee.py --workload lm-serve-online \
        --rates 4,6,8,10,12,14 --seconds 20 --seed 1

One process sets the deployment up once (as ``benchmarks/run.py`` does) and
then offers the mix at each fixed rate in turn, with the system drained
between rates. For each rate it prints one JSON line: requests sent,
tokens per second, the tails, and two signs of a backlog that grows: the
requests still waiting for their first token when the window closes, and
the median time to first token of the window's second half against its
first. The knee is the highest rate at which neither grows; the cell then
runs at about four fifths of it, and the rate is written into the cell's
file as a number. The driver never runs this; a later ``benchmark`` issue
does when the knee has moved. Output kept in ``benchmarks/sweeps/``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)



def sweep(workload, rates, seconds, seed, require_tpu=True):
    from benchmarks.harness import common, lm, serve
    cell = lm.load_json("workloads", workload + ".json")
    cfg = lm.load_config(cell["config"])
    env = common.Env(T_PROCESS, "knee-" + workload, cell["chips"], False,
                     require_tpu=require_tpu)
    dep = serve.Deployment(cell, cfg, seed, env)
    report = dep.check_report
    common.check(max(report["worst_rel_err"],
                     report["worst_first_token_rel_gap"]) <= report["rtol"],
                 "the deployment differs from its reference: %r", report)
    rows = []
    try:
        dep.open()
        for rate in rates:
            traffic = dict(cell["traffic"], rate_per_s=float(rate))
            got = serve.offer(dep, traffic, seed, seconds)
            w0, w1 = got["w0"], got["w1"]
            nums = serve.client_numbers(got["requests"], w0, w1,
                                        dep.arch.vocab(cfg))
            mid = (w0 + w1) / 2
            sent = [r for r in got["requests"] if r.sent is not None]
            half = [[], []]
            for r in sent:
                if r.tokens and w0 <= r.due < w1:
                    half[r.due >= mid].append((r.tokens[0][0] - r.due) * 1e3)
            waiting = sum(1 for r in sent if r.due < w1 and
                          (not r.tokens or r.tokens[0][0] >= w1))
            row = {
                "rate_per_s": rate, "window_s": w1 - w0,
                "sent": len(sent), "failed": nums["failed"],
                "output_tokens_per_s": nums["tokens_in_window"] / (w1 - w0),
                "ttft_p50_ms": common.quantile(nums["ttft_ms"], 0.5),
                "ttft_p90_ms": common.quantile(nums["ttft_ms"], 0.9),
                "ttft_p99_ms": common.quantile(nums["ttft_ms"], 0.99),
                "itl_p50_ms": common.quantile(nums["gaps_ms"], 0.5),
                "itl_p99_ms": common.quantile(nums["gaps_ms"], 0.99),
                "ttft_p50_first_half_ms": common.quantile(half[0], 0.5),
                "ttft_p50_second_half_ms": common.quantile(half[1], 0.5),
                "waiting_at_close": waiting,
                "gen_late_p99_ms": common.quantile(nums["late_ms"], 0.99),
                "compiles_in_window": got["compiles"]["compiles"],
                "device": common.device_stamp(env.devices),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        dep.close()
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", help="also write the rows to this file")
    args = ap.parse_args(argv)
    rows = sweep(args.workload, [float(r) for r in args.rates.split(",")],
                 args.seconds, args.seed)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
