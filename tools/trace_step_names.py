#!/usr/bin/env python3
"""Which compiled steps a profiler trace holds, by name.

    python3 tools/trace_step_names.py .bench_out/lm-serve-offline/trace \
        [--out chiprun_out/names.json]

Since PR 36 a compiled step goes by its program's role: the host plane
shows ``PjitFunction(decode)``, ``PjitFunction(prefill_128)`` (``fn`` for a
step of before), and the device's plane lists the modules ``jit_decode``,
``jit_prefill_128`` on its "XLA Modules" line. Prints one JSON object:
the jitted calls of the host planes and the modules of the device planes,
each with its count and its seconds. Reads any ``.xplane.pb`` (a traced
run of the benchmark, ``utils.profiler.profiler(trace_dir=...)``); works
off the chip on a CPU trace, where there is no device plane.
"""

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def step_names(trace_dir):
    from jax.profiler import ProfileData
    from benchmarks.harness.trace_reduce import find_xplane
    path = find_xplane(trace_dir)
    host = collections.defaultdict(lambda: [0, 0.0])
    modules = collections.defaultdict(lambda: [0, 0.0])
    spans = collections.defaultdict(lambda: [0, 0.0])
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if device and line.name == "XLA Modules":
                    # "jit_decode(1234567890)": the module and its id
                    into = modules[re.sub(r"\(\d+\)$", "", name)]
                elif not device and name.startswith("PjitFunction("):
                    into = host[name]
                elif not device and name.startswith("executor:"):
                    into = spans[name]
                else:
                    continue
                into[0] += 1
                into[1] += e.duration_ns / 1e9

    def table(d):
        return {k: {"count": n, "seconds": s} for k, (n, s) in sorted(d.items())}
    return {"xplane": path, "host_jitted_calls": table(host),
            "device_modules": table(modules), "executor_spans": table(spans)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--out", help="also write the object to this file")
    args = ap.parse_args(argv)
    names = step_names(args.trace_dir)
    text = json.dumps(names, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
