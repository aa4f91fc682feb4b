"""Unified telemetry layer (observability/): registry semantics,
Prometheus/JSON exposition, Chrome-trace well-formedness, executor
compile-cache counters, trainer step telemetry, and the off-hot-path
guarantee when the ``telemetry`` flag is disabled."""

import json
import math

import numpy as np
import pytest

import paddle_tpu as ptpu
from paddle_tpu import layers
from paddle_tpu.observability import metrics, tracing
from paddle_tpu.observability.metrics import Registry
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils import profiler as prof_mod
from paddle_tpu.utils.stat import StatSet


@pytest.fixture
def telemetry():
    """Arm the telemetry flag for one test; always disarm after."""
    ptpu.config.set_flags(telemetry=True)
    tracing.clear()
    yield
    ptpu.config.set_flags(telemetry=False)


# -- registry semantics -----------------------------------------------------

def test_counter_semantics():
    reg = Registry()
    c = reg.counter("requests_total", "requests", labelnames=("code",))
    c.labels(code=200).inc()
    c.labels(code=200).inc(2.5)
    c.labels(code=500).inc()
    assert c.labels(code=200).value == 3.5
    assert c.labels(code=500).value == 1.0
    with pytest.raises(ValueError):
        c.labels(code=200).inc(-1)


def test_gauge_semantics():
    reg = Registry()
    g = reg.gauge("queue_depth")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5.0


def test_histogram_semantics():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    child = h.labels()
    assert child.count == 5
    assert child.sum == pytest.approx(56.05)
    assert child.vmin == 0.05 and child.vmax == 50.0
    # cumulative: <=0.1 ->1, <=1 ->3, <=10 ->4, +Inf ->5
    assert child.cumulative_buckets() == [
        (0.1, 1), (1.0, 3), (10.0, 4), (math.inf, 5)]


def test_family_reregistration_idempotent_and_checked():
    reg = Registry()
    a = reg.counter("x_total", "x", labelnames=("k",))
    assert reg.counter("x_total", "x", labelnames=("k",)) is a
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("other",))
    with pytest.raises(ValueError):
        a.labels(wrong="v")


def test_prometheus_exposition_format():
    reg = Registry()
    reg.counter("req_total", "total requests",
                labelnames=("path",)).labels(path='/a"b\\c').inc(3)
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.5, 2.0))
    h.observe(0.3)
    h.observe(1.0)
    text = reg.expose_text()
    lines = text.splitlines()
    assert "# HELP req_total total requests" in lines
    assert "# TYPE req_total counter" in lines
    assert 'req_total{path="/a\\"b\\\\c"} 3' in lines
    assert "# TYPE depth gauge" in lines
    assert "depth 2" in lines
    assert "# TYPE lat_seconds histogram" in lines
    assert 'lat_seconds_bucket{le="0.5"} 1' in lines
    assert 'lat_seconds_bucket{le="2"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 2' in lines
    assert "lat_seconds_sum 1.3" in lines
    assert "lat_seconds_count 2" in lines


def test_json_dump_well_formed():
    reg = Registry()
    reg.counter("c_total").inc(2)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    d = json.loads(reg.dump_json())
    assert d["c_total"]["type"] == "counter"
    assert d["c_total"]["samples"][0]["value"] == 2
    hs = d["h"]["samples"][0]
    assert hs["count"] == 1 and hs["sum"] == 0.5
    assert hs["buckets"]["1"] == 1 and hs["buckets"]["+Inf"] == 1


# -- legacy StatSet as a registry view -------------------------------------

def test_statset_is_a_registry_view():
    reg = Registry()
    ss = StatSet("ViewTest", registry=reg)
    with ss.span("stage"):
        pass
    ss.add("stage", 0.25)
    ss.set_gauges({"depth": 4, "active": True})
    rep = ss.report()
    assert "ViewTest" in rep and "stage" in rep and "depth" in rep
    assert ss.items()["stage"][0] == 2
    assert ss.gauges() == {"depth": 4.0, "active": 1.0}
    # the same numbers are visible through the registry exposition
    text = reg.expose_text()
    assert 'stat="stage"' in text and 'set="ViewTest"' in text
    ss.reset()
    assert ss.items() == {} and ss.gauges() == {}


def test_statset_survives_registry_reset():
    """reset() drops registry children; the StatSet's cached child
    handles must not keep counting into orphaned objects."""
    reg = Registry()
    ss = StatSet("ResetTest", registry=reg)
    ss.add("k", 0.1)
    reg.reset()
    assert ss.items() == {}
    ss.add("k", 0.2)  # must land in a fresh, reachable child
    assert ss.items()["k"] == (1, pytest.approx(0.2))


# -- tracing ----------------------------------------------------------------

def test_chrome_trace_wellformed_and_nested(tmp_path):
    tracing.start(clear=True)
    try:
        with tracing.span("outer"):
            with tracing.span("inner", detail="x"):
                pass
        with tracing.span("sibling"):
            pass
    finally:
        tracing.stop()
    path = str(tmp_path / "trace.json")
    tracing.emit_chrome_trace(path)
    doc = json.load(open(path))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner", "sibling"}
    for e in evs:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert by_name["inner"]["args"] == {"detail": "x"}
    # thread metadata present
    assert any(e.get("ph") == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])


def test_span_is_a_bare_annotation_when_inactive():
    """Unarmed, a span is the profiler's annotation and nothing else:
    it reaches any jax.profiler trace, and records no ring event."""
    assert not tracing.active()
    tracing.clear()
    assert type(tracing.span("anything", k=1)) is tracing.TraceAnnotation
    with tracing.span("anything"):
        pass
    assert tracing.events() == []


# -- profiler handle (satellite: report no longer discarded) ----------------

def test_profiler_yields_usable_handle(tmp_path):
    with prof_mod.profiler() as handle:
        with prof_mod.RecordEvent("stage_a"):
            pass
    assert "stage_a" in handle.report()
    path = str(tmp_path / "host_trace.json")
    handle.chrome_trace(path)
    doc = json.load(open(path))
    assert any(e.get("name") == "stage_a" for e in doc["traceEvents"])


def test_profiler_trace_windows_out_preexisting_events(tmp_path):
    """With always-on telemetry the span ring buffer holds history;
    handle.chrome_trace must only emit the profiled block's events."""
    tracing.start(clear=True)
    try:
        with tracing.span("stale_before"):
            pass
        with prof_mod.profiler() as handle:
            with prof_mod.RecordEvent("inside_block"):
                pass
    finally:
        tracing.stop()
    path = str(tmp_path / "windowed.json")
    handle.chrome_trace(path)
    names = {e["name"] for e in json.load(open(path))["traceEvents"]
             if e.get("ph") == "X"}
    assert "inside_block" in names
    assert "stale_before" not in names


# -- executor instrumentation -----------------------------------------------

def _hits():
    return metrics.REGISTRY.counter(
        "paddle_executor_cache_hits_total").value


def _misses():
    return metrics.REGISTRY.counter(
        "paddle_executor_cache_misses_total").value


def _compile_seconds(role, stage):
    return metrics.REGISTRY.counter(
        "paddle_compile_seconds_total",
        labelnames=("role", "stage")).labels(role=role, stage=stage).value


def test_executor_cache_hit_miss_counts(telemetry):
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.scale(x, scale=2.0)
    exe = ptpu.Executor()
    h0, m0 = _hits(), _misses()
    # nobody named the program: its steps go by the default role
    before = {st: _compile_seconds("program", st)
              for st in ("trace", "lower", "compile")}
    feed8 = {"x": np.ones((8, 4), "float32")}
    exe.run(main, feed=feed8, fetch_list=[y])      # miss (new key)
    exe.run(main, feed=feed8, fetch_list=[y])      # hit
    exe.run(main, feed=feed8, fetch_list=[y])      # hit
    exe.run(main, feed={"x": np.ones((3, 4), "float32")},
            fetch_list=[y])                        # miss (new shape)
    assert _misses() - m0 == 2
    assert _hits() - h0 == 2
    # per-key cost telemetry recorded for the missed keys
    d = metrics.REGISTRY.dump()
    flops = d["paddle_executor_step_flops"]["samples"]
    assert len(flops) >= 2
    assert all(s["value"] >= 0 for s in flops)
    # the AOT compile's seconds are on the compile ledger, by role and
    # stage (the per-key gauges paddle_executor_trace_seconds and
    # paddle_executor_compile_seconds are gone)
    assert all(_compile_seconds("program", st) > before[st]
               for st in before)
    assert "paddle_executor_compile_seconds" not in d
    assert "paddle_executor_trace_seconds" not in d


def test_lower_neither_counts_cache_nor_blocks_aot_telemetry(telemetry):
    """Executor.lower is a profiling entry, not a step: it must not
    move the hit/miss counters, and a later run() of the same key must
    still produce the per-key cost telemetry."""
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.scale(x, scale=3.0)
    exe = ptpu.Executor()
    feed = {"x": np.ones((4, 4), "float32")}
    h0, m0 = _hits(), _misses()
    n_flops0 = len(metrics.REGISTRY.dump()[
        "paddle_executor_step_flops"]["samples"]) \
        if "paddle_executor_step_flops" in metrics.REGISTRY.dump() else 0
    exe.lower(main, feed=feed, fetch_list=[y]).compile()
    assert (_hits(), _misses()) == (h0, m0)
    exe.run(main, feed=feed, fetch_list=[y])  # first RUN of the key
    assert _misses() - m0 == 0  # entry existed (lower populated it)...
    assert _hits() - h0 == 1    # ...so the run counts as a hit
    flops = metrics.REGISTRY.dump()[
        "paddle_executor_step_flops"]["samples"]
    assert len(flops) > n_flops0  # but cost telemetry still recorded


# -- trainer step telemetry (acceptance criteria) ---------------------------

def _toy_trainer(tmp_path=None, **kw):
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        yv = layers.data("y", shape=[1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, yv))
        ptpu.optimizer.SGD(learning_rate=0.05).minimize(
            loss, startup_program=startup)
    return Trainer(loss, main_program=main, startup_program=startup, **kw)


def _toy_reader(n_batches=6, batch=8):
    def reader():
        rs = np.random.RandomState(0)
        for _ in range(n_batches):
            yield {"x": rs.randn(batch, 4).astype("float32"),
                   "y": rs.randn(batch, 1).astype("float32")}
    return reader


def test_trainer_telemetry_metrics_and_trace(telemetry, tmp_path):
    d0 = metrics.REGISTRY.dump()

    def count_of(d, name):
        s = d.get(name, {}).get("samples", [])
        return s[0]["count"] if s else 0

    def value_of(d, name):
        s = d.get(name, {}).get("samples", [])
        return s[0]["value"] if s else 0.0

    steps0 = count_of(d0, "paddle_trainer_step_seconds")
    ex0 = value_of(d0, "paddle_trainer_examples_total")
    h0, m0 = _hits(), _misses()

    tr = _toy_trainer(checkpoint_dir=str(tmp_path / "ckpt"),
                      checkpoint_every_n_steps=3)
    tr.train(_toy_reader(6, 8), num_passes=1, staging=False, prefetch=0)

    d = metrics.REGISTRY.dump()
    # (a) step-latency histogram buckets, examples/sec, hit/miss counters
    step_hist = d["paddle_trainer_step_seconds"]["samples"][0]
    assert step_hist["count"] - steps0 == 6
    assert step_hist["buckets"]["+Inf"] >= 6
    assert value_of(d, "paddle_trainer_examples_total") - ex0 == 48
    # per-trainer labeled gauge: this trainer's child must be positive
    eps_samples = d["paddle_trainer_examples_per_second"]["samples"]
    assert any(s["value"] > 0 for s in eps_samples)
    assert all("trainer" in s["labels"] for s in eps_samples)
    assert _misses() - m0 >= 1     # startup + step compile
    assert _hits() - h0 >= 4       # 6 steps, one shape -> 5 step hits
    assert d["paddle_trainer_checkpoint_seconds"]["samples"][0]["count"] \
        >= 2
    # the same content is in the Prometheus exposition
    text = metrics.REGISTRY.expose_text()
    assert "paddle_trainer_step_seconds_bucket" in text
    assert "paddle_trainer_examples_per_second" in text
    assert "paddle_executor_cache_hits_total" in text

    # (b) Chrome trace: valid JSON, nested trainOneBatch/feed/checkpoint
    path = str(tmp_path / "trace.json")
    tracing.emit_chrome_trace(path)
    doc = json.load(open(path))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in evs}
    assert {"trainStep", "trainOneBatch", "feed",
            "saveCheckpoint"} <= names

    def contained(inner, outers):
        eps = 1.0  # us slack for float round-trip
        return any(o["ts"] - eps <= inner["ts"] and
                   inner["ts"] + inner["dur"] <=
                   o["ts"] + o["dur"] + eps and
                   o["tid"] == inner["tid"] for o in outers)

    steps = [e for e in evs if e["name"] == "trainStep"]
    assert len(steps) == 6
    for name in ("trainOneBatch", "feed"):
        for ev in (e for e in evs if e["name"] == name):
            assert contained(ev, steps), \
                "%s span not nested in a trainStep span" % name
    # periodic (per-step) checkpoints nest in a trainStep; the
    # end-of-pass checkpoint is legitimately outside any step
    ckpts = [e for e in evs if e["name"] == "saveCheckpoint"]
    assert len(ckpts) == 3  # steps 3, 6 + end of pass
    assert sum(contained(e, steps) for e in ckpts) == 2


def test_trainer_periodic_log(telemetry, monkeypatch):
    from paddle_tpu.utils import log as log_mod
    emitted = []
    monkeypatch.setattr(
        log_mod, "structured",
        lambda event, **fields: emitted.append((event, fields)))
    tr = _toy_trainer(periodic_log_interval=2)
    tr.train(_toy_reader(4, 8), num_passes=1, staging=False, prefetch=0)
    lines = [f for e, f in emitted if e == "train_throughput"]
    assert len(lines) == 2  # steps 2 and 4
    assert lines[-1]["step"] == 4
    assert lines[-1]["examples_per_sec"] > 0
    assert lines[-1]["step_ms"] > 0
    # and the structured formatter emits parseable JSON through the
    # package handler even at the default WARNING package level (the
    # telemetry child logger carries its own INFO level)
    import logging

    class _Capture(logging.Handler):
        def __init__(self):
            super().__init__()
            self.records = []

        def emit(self, record):
            self.records.append(record)

    monkeypatch.undo()
    lg = log_mod.logger()
    h = _Capture()
    lg.addHandler(h)
    try:
        log_mod.structured("evt", a=1, b="two")
    finally:
        lg.removeHandler(h)
    msg = h.records[-1].getMessage()
    assert msg.startswith("evt ")
    assert json.loads(msg.split(" ", 1)[1]) == {"a": 1, "b": "two"}


# -- off-hot-path guarantee -------------------------------------------------

def test_telemetry_disabled_is_a_flag_check(monkeypatch):
    assert not ptpu.config.get_flag("telemetry")
    tr = _toy_trainer()
    tr.startup()

    recorded = {"events": 0}
    orig = tracing.Tracer._record

    def counting_record(self, *a, **kw):
        recorded["events"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(tracing.Tracer, "_record", counting_record)

    d0 = metrics.REGISTRY.dump()
    tr.train(_toy_reader(3, 8), num_passes=1, staging=False, prefetch=0)
    d1 = metrics.REGISTRY.dump()

    # no trace events recorded, no span objects from the tracer
    assert recorded["events"] == 0
    assert type(tracing.span("x")) is tracing.TraceAnnotation
    # no telemetry metric moved
    for name in ("paddle_trainer_step_seconds",
                 "paddle_trainer_examples_total",
                 "paddle_trainer_examples_per_second",
                 "paddle_executor_cache_hits_total",
                 "paddle_executor_cache_misses_total"):
        assert d0.get(name) == d1.get(name), name


# -- staged-reader teardown guard (satellite) -------------------------------

class _FakeStaged:
    def __init__(self, stats_raises=False):
        self.stats_raises = stats_raises
        self.closed = False

    def stats(self):
        if self.stats_raises:
            raise RuntimeError("stats exploded")
        return {"staged_batches": 1}

    def close(self):
        self.closed = True


def test_teardown_guard_does_not_mask_original_exception():
    staged = _FakeStaged(stats_raises=True)
    # an exception is propagating: teardown errors must be swallowed
    Trainer._teardown_staged(staged, None, exc_live=True)
    assert staged.closed
    # no exception propagating: the teardown error must surface
    staged2 = _FakeStaged(stats_raises=True)
    with pytest.raises(RuntimeError, match="stats exploded"):
        Trainer._teardown_staged(staged2, None, exc_live=False)


def test_train_surfaces_reader_error_not_teardown_error(telemetry):
    tr = _toy_trainer()

    def bad_reader():
        yield {"x": np.ones((8, 4), "float32"),
               "y": np.ones((8, 1), "float32")}
        raise ValueError("reader exploded")

    class _BadStats:
        arena_active = True

        def __call__(self):
            def gen():
                for b in bad_reader():
                    yield b
            return gen()

        def stats(self):
            raise RuntimeError("stats exploded")

        def close(self):
            pass

    # drive the staged branch with a stats()-raising stand-in
    import paddle_tpu.reader.staging as staging_mod
    orig = staging_mod.StagedReader
    staging_mod.StagedReader = lambda *a, **kw: _BadStats()
    try:
        with pytest.raises(ValueError, match="reader exploded"):
            tr.train(lambda: bad_reader(), num_passes=1, staging=True,
                     prefetch=2)
    finally:
        staging_mod.StagedReader = orig
