#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name: its entry in ``BENCHMARK.json`` and its file
``benchmarks/workloads/<name>.json``, which names a configuration
(``benchmarks/configs/``), a kind of driver (``benchmarks/harness/<kind>.py``)
and a traffic mix. The process fails unless JAX has a TPU with the cell's
chips. The last line of standard output is the result; what else is worth
keeping goes on the line before it and into ``.bench_out/<name>/``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` wraps a
few seconds of the steady window in the profiler and reports its per-layer
metrics, the device's busy seconds and the breakdown.
"""

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell_metrics(bench, cell_name):
    """(end-to-end, per-layer) metric entries of ``BENCHMARK.json`` that this
    cell reports: a per-layer metric only where the metric it moves is."""
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell_name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, cell_name) and m["moves"] in names]
    return e2e, layer


def run_cell(bench, workload, seed, seconds, trace, require_tpu=True,
             out_root=None, drain=True):
    """Run a cell and build the result object (the last line's content) and
    the notes. Raises on anything that leaves no result worth printing."""
    from benchmarks.harness import common, lm, readers
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise common.BenchFailure("BENCHMARK.json has no workload %r"
                                  % (workload,))
    cell = lm.load_json("workloads", workload + ".json")
    for key in ("config", "chips"):
        common.check(cell[key] == entry[key], "workloads/%s.json and "
                     "BENCHMARK.json disagree on %s: %r vs %r", workload, key,
                     cell[key], entry[key])
    common.check(cell["traffic"]["name"] == entry["traffic"],
                 "workloads/%s.json and BENCHMARK.json disagree on traffic",
                 workload)
    cfg = lm.load_config(cell["config"])
    env = common.Env(T_PROCESS, workload, cell["chips"], trace,
                     require_tpu=require_tpu, out_root=out_root, drain=drain)
    driver = importlib.import_module("benchmarks.harness." + cell["kind"])
    facts = driver.run(cell, cfg, seed, seconds, env)
    # what holds for every kind of cell
    n_compiles = facts.compiles["compiles"]
    facts.observed["compiles_in_window"] = n_compiles
    facts.compare("compiles_in_window", n_compiles, 0,
                  "%d compilation(s) inside the window", n_compiles)
    if trace:
        facts.trace = env.reduce_trace()

    e2e, layer = cell_metrics(bench, workload)
    metrics = {}
    if trace:
        for m in layer:
            spec, read = readers.load_metric(m["name"])
            value = read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for m in e2e:
            value = facts.observed.get(m["name"])
            common.check(value is not None, "the run has no value for %s",
                         m["name"])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = common.device_stamp(env.devices)
    result = {"correct": bool(facts.correct), "attempted": facts.attempted,
              "failed": facts.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = facts.trace["busy_s"]
        device["window_s"] = facts.trace["window_s"]
        result["breakdown"] = {"device_ops": facts.trace["device_ops"],
                               "idle_gaps": facts.trace["idle_gaps"]}
    # what decided ``correct``, each number beside its limit: last on the line
    result["compared"] = facts.compared
    notes = dict(facts.notes, workload=workload, seed=seed,
                 problems=facts.problems, cache_dir=env.cache_dir,
                 compile=env.meter.snapshot(), setup_phases_s=env.phases,
                 observed=dict(facts.observed),
                 trace={k: v for k, v in (facts.trace or {}).items()
                        if k not in ("device_ops", "idle_gaps")})
    return result, dict(notes, samples=facts.samples), env


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        # this process ends with the run: nothing in flight is served out
        result, notes, env = run_cell(bench, args.workload, args.seed,
                                      args.seconds, bool(args.trace),
                                      drain=False)
    except Exception:  # noqa: BLE001 — no result line; the code says so
        traceback.print_exc()
        return 1
    samples = notes.pop("samples")      # too long for a line of output
    with open(os.path.join(env.out_dir, "last_run.json"), "w") as f:
        json.dump({"result": result, "notes": notes, "samples": samples}, f)
    print(json.dumps({"notes": notes}), flush=True)
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print("compared %s: %s (limit %s)" % (name, c["value"], c["limit"]),
              file=sys.stderr)
    print("correct: %s" % result["correct"], file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
