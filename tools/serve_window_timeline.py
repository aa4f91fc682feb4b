#!/usr/bin/env python3
"""Where the tokens of a serving cell's window went: one run of the cell
exactly as ``benchmarks/run.py --trace 0`` makes it (the same functions, the
same result line), with the window's token stamps kept and read as a
timeline of events on the dispatcher thread.

    python3 tools/serve_window_timeline.py --workload kimi-serve-offline \
        --seed 3100000601 --seconds 45 --out chiprun_out/t/601.json

An *event* is a burst of token stamps under 2 ms apart: a decode step's
hand-over (one token a live slot) or a prefill's first token. Between two
decode events lies a decode step's time, and more where a prefill ran in
between or the host or the machine stalled. The median of the plain
intervals is the step; what a stretch takes beyond it is booked to
``prefill`` where a first token arrived in it and to ``stall`` where none
did. The last line printed holds, for the window and for each of the
harness's slices: tokens a second, decode events and tokens an event, the
prefills and their seconds, the stalls and their seconds. ``--out`` keeps
that with every event, the window's counter deltas and the histograms' sums
(prefill, decode step, host turn, device wait), so that a run that reads low
says where. Two watches say what a stall was: the interpreter's full
collections (``gc.callbacks``: they hold every thread) and a heartbeat
thread that sleeps 50 ms and notes each beat over 150 ms (late beats with
the dispatcher: the process or its machine stood still; beats on time: the
dispatcher alone waited, on the device or a lock). Chip only.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BURST_S = 2e-3
BEAT_S = 0.05


def watch():
    """Start the two watches; -> (collections, beats), lists that grow:
    (start, seconds) of every full collection and of every late beat, on
    ``perf_counter``'s clock."""
    collections, beats, began = [], [], []

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                began[:] = [time.perf_counter()]
            elif began:
                collections.append((began[0],
                                    time.perf_counter() - began[0]))
    gc.callbacks.append(on_gc)

    def beat():
        last = time.perf_counter()
        while True:
            time.sleep(BEAT_S)
            now = time.perf_counter()
            if now - last > 3 * BEAT_S:
                beats.append((last, now - last))
            last = now
    threading.Thread(target=beat, name="heartbeat", daemon=True).start()
    return collections, beats


def inside(spans, t0, seconds):
    """Those of ``spans`` that start in the window, on its clock."""
    return [(round(t - t0, 3), round(s, 4)) for t, s in spans
            if 0.0 <= t - t0 < seconds]


def events(requests):
    """[(time, tokens, first tokens)] of every burst of stamps, in order."""
    stamps = sorted((t, i == 0) for r in requests
                    for i, (t, _tok) in enumerate(r.tokens))
    out = []
    for t, first in stamps:
        if out and t - out[-1][0] < BURST_S:
            out[-1] = (t, out[-1][1] + 1, out[-1][2] + int(first))
        else:
            out.append((t, 1, int(first)))
    return out


def timeline(evs, w0, w1, edges):
    """The window's books: stretches from one pure decode event to the
    next, each booked as a step and what it took beyond one."""
    decode = [i for i, e in enumerate(evs) if not e[2] and w0 <= e[0] < w1]
    stretches = []      # (end time, seconds, first tokens inside)
    for a, b in zip(decode, decode[1:]):
        firsts = sum(e[2] for e in evs[a + 1:b])
        stretches.append((evs[b][0], evs[b][0] - evs[a][0], firsts))
    plain = [s for _, s, f in stretches if not f]
    step = float(np.median(plain)) if plain else 0.0

    def books(lo, hi):
        inside = [(t, s, f) for t, s, f in stretches if lo <= t < hi]
        pre = [(t, s - step, f) for t, s, f in inside if f]
        stall = [(t, s - step) for t, s, f in inside
                 if not f and s > 1.5 * step]
        toks = [e[1] for e in evs if not e[2] and lo <= e[0] < hi]
        return {"decode_events": len(toks),
                "tokens_per_decode_event": float(np.mean(toks))
                if toks else None,
                "prefills": int(sum(f for _, _, f in pre)),
                "prefill_s": float(sum(s for _, s, _ in pre)),
                "stalls": len(stall),
                "stall_s": float(sum(s for _, s in stall)),
                "worst_stalls": [(round(t - w0, 3), round(s, 4)) for t, s in
                                 sorted(stall, key=lambda x: -x[1])[:5]]}
    whole = dict(books(w0, w1), step_ms=step * 1e3)
    slices = [books(lo, hi) for lo, hi in zip(edges[:-1], edges[1:])]
    return whole, slices


def run(workload, seed, seconds, **run_cell_kwargs):
    """(the line to print, what ``--out`` keeps besides) of one run."""
    import benchmarks.run as bench_run
    from benchmarks.harness import serve
    bench_run.T_PROCESS = T_PROCESS
    kept, offer = {}, serve.offer

    def keeping_offer(*a, **kw):
        kept.update(offer(*a, **kw))
        return kept
    serve.offer = keeping_offer
    collections, beats = watch()
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        result, notes, _env = bench_run.run_cell(
            bench, workload, seed, seconds, False, drain=False,
            **run_cell_kwargs)
    finally:
        serve.offer = offer
    w0, w1 = kept["w0"], kept["w1"]
    rates = np.asarray(notes["samples"]["slice_tokens_per_s"])
    edges = np.linspace(w0, w1, rates.size + 1)
    evs = events(kept["requests"])
    whole, slices = timeline(evs, w0, w1, edges)
    for s, rate in zip(slices, rates):
        s["tokens_per_s"] = float(rate)
    whole["full_collections"] = inside(collections, kept["t_window"], w1 - w0)
    whole["late_beats"] = inside(beats, kept["t_window"], w1 - w0)
    line = {"seed": seed, "correct": result["correct"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "delivered_tokens_per_s": notes["delivered_tokens_per_s"],
            "requests_sent": notes["requests_sent"], "window": whole,
            "slices": [{k: (round(v, 4) if isinstance(v, float) else v)
                        for k, v in s.items() if k != "worst_stalls"}
                       for s in slices]}
    detail = {"slices": slices, "result": result,
              "counters": kept["counters"], "hists": kept["hists"],
              "events": [(round(t - w0, 5), n, first) for t, n, first in evs]}
    return line, detail


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    line, detail = run(args.workload, args.seed, args.seconds)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(line, **detail), f)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
