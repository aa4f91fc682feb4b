"""Telemetry smoke probe: the whole observability pipeline, headless.

Runs a few smallnet train steps on CPU with the ``telemetry`` flag on,
then prints the metrics registry (JSON + a Prometheus excerpt) and
writes the host Chrome trace — proving registry -> trainer/executor/
staging hooks -> export works end to end with no accelerator and no
TensorBoard. This replaces the ad-hoc probe scripts as the first thing
to run when a training job needs numbers (see PROFILE.md
"Observability workflow").

Usage:
    JAX_PLATFORMS=cpu python tools/telemetry_probe.py [trace.json]
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.smallnet import smallnet
    from paddle_tpu.observability import metrics, tracing
    from paddle_tpu.trainer import Trainer

    batch, steps, res = 8, 5, 28
    trace_path = sys.argv[1] if len(sys.argv) > 1 else \
        "/tmp/paddle_tpu_telemetry_trace.json"

    ptpu.config.set_flags(telemetry=True)

    main_prog, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main_prog, startup):
        img = layers.data("img", shape=[1, res, res])
        label = layers.data("label", shape=[1], dtype="int64")
        loss, acc, _ = smallnet(img, label)
        ptpu.optimizer.Momentum(learning_rate=0.01, momentum=0.9) \
            .minimize(loss, startup_program=startup)

    def reader():
        rs = np.random.RandomState(0)
        for _ in range(steps):
            yield {"img": rs.randn(batch, 1, res, res).astype("float32"),
                   "label": rs.randint(0, 10, (batch, 1)).astype("int64")}

    trainer = Trainer(loss, metrics={"acc": acc}, main_program=main_prog,
                      startup_program=startup, periodic_log_interval=2)
    trainer.train(lambda: reader(), num_passes=1)

    # -- exports ---------------------------------------------------------
    dump = metrics.REGISTRY.dump()
    print("== metrics JSON " + "=" * 50)
    print(json.dumps(dump, indent=1, sort_keys=True))

    print("== prometheus exposition (excerpt) " + "=" * 31)
    for line in metrics.REGISTRY.expose_text().splitlines():
        if line.startswith(("paddle_trainer", "paddle_executor",
                            "paddle_compile", "paddle_program",
                            "paddle_process")) \
                and "_bucket" not in line:
            print(line)

    tracing.emit_chrome_trace(trace_path)
    n_events = len(tracing.events())
    print("== chrome trace: %s (%d events) " % (trace_path, n_events))

    # -- smoke assertions (exit non-zero if the pipeline is broken) ------
    step_hist = dump["paddle_trainer_step_seconds"]["samples"][0]
    assert step_hist["count"] == steps, step_hist
    assert dump["paddle_trainer_examples_total"]["samples"][0]["value"] \
        == steps * batch
    assert dump["paddle_executor_cache_misses_total"]["samples"][0][
        "value"] >= 1
    assert dump["paddle_executor_cache_hits_total"]["samples"][0][
        "value"] >= steps - 1
    # the compile ledger: the training step's first call booked its
    # trace, lowering and compile to the step's role (the per-key gauges
    # paddle_executor_trace_seconds / _compile_seconds are gone)
    booked = {(s["labels"]["role"], s["labels"]["stage"]): s["value"]
              for s in dump["paddle_compile_seconds_total"]["samples"]}
    assert all(booked.get(("train", stage), 0) > 0
               for stage in ("trace", "lower")), booked
    assert booked.get(("train", "compile"), 0) + \
        booked.get(("train", "cache_read"), 0) > 0, booked
    runs = {s["labels"]["role"]: s["value"] for s in
            dump["paddle_executor_runs_total"]["samples"]}
    assert runs["train"] >= steps and runs["startup"] == 1, runs
    names = {e["name"] for e in tracing.events() if e.get("ph") == "X"}
    assert {"trainStep", "trainOneBatch", "executor:first_call",
            "executor:call"} <= names, names
    doc = json.load(open(trace_path))
    assert doc["traceEvents"], "empty chrome trace"
    print("TELEMETRY PROBE OK: %d steps, %d trace events, "
          "%d metric families"
          % (steps, n_events, len(dump)))


if __name__ == "__main__":
    main()
