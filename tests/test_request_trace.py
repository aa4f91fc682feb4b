"""Request-scoped tracing, flight recorder, live introspection, and
the registry satellites (label-cardinality cap, remove_labeled sweep,
per-metric bucket overrides) — ISSUE 12."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as ptpu
from paddle_tpu import layers, io
from paddle_tpu.models.transformer import (transformer_lm,
                                           transformer_lm_session)
from paddle_tpu.observability import flight, metrics
from paddle_tpu.observability import http as ohttp
from paddle_tpu.observability import request_trace as rtrace
from paddle_tpu.serving import (GenerationScheduler, GenerationSession,
                                MicroBatcher, ServingEngine)


@pytest.fixture(autouse=True)
def _reset_tracing():
    yield
    ptpu.config.set_flags(request_tracing=False, trace_sample_rate=1.0,
                          telemetry_port=0, flight_dir=None)
    rtrace.clear()
    flight.RECORDER.min_interval_sec = 1.0
    flight.RECORDER.clear()
    flight.RECORDER._last_bundle = None
    flight.RECORDER.last_dump_path = None
    flight.RECORDER._last_dump_t = float("-inf")


# -- tracer core -----------------------------------------------------------

class TestTracerCore:
    def test_off_by_default_mint_returns_none(self):
        assert ptpu.config.get_flag("request_tracing") is False
        assert ptpu.config.get_flag("trace_sample_rate") == 1.0
        assert ptpu.config.get_flag("telemetry_port") == 0
        assert ptpu.config.get_flag("flight_dir") is None
        assert rtrace.mint("x") is None
        assert rtrace.current() is None
        # event on a None ctx is a no-op, global_event records nowhere
        assert rtrace.event(None, "whatever") is None
        n0 = len(flight.RECORDER.ring)
        rtrace.global_event("whatever")
        assert len(flight.RECORDER.ring) == n0

    def test_sample_rate_zero_mints_nothing(self):
        ptpu.config.set_flags(request_tracing=True,
                              trace_sample_rate=0.0)
        assert all(rtrace.mint("x") is None for _ in range(50))

    def test_event_tree_and_activation(self):
        ptpu.config.set_flags(request_tracing=True)
        ctx = rtrace.mint("unit", who="test")
        assert ctx is not None and ctx.trace_id in rtrace.trace_ids()
        rtrace.event(ctx, "queueWait", dur_ms=1.5)
        parent = rtrace.event(ctx, "prefill", session=0)
        rtrace.event(ctx, "deviceCall", parent=parent, key=7)
        with rtrace.activate(ctx):
            assert rtrace.current() is ctx
            rtrace.global_event("breakerTransition", state="open")
        assert rtrace.current() is None
        tree = rtrace.span_tree(ctx.trace_id)
        assert tree["root"]["name"] == "request"
        assert tree["root"]["attrs"]["who"] == "test"
        kids = {c["name"]: c for c in tree["root"]["children"]}
        assert set(kids) == {"queueWait", "prefill",
                             "breakerTransition"}
        assert [c["name"] for c in kids["prefill"]["children"]] \
            == ["deviceCall"]
        # every event carries the one trace id
        assert all(e["trace_id"] == ctx.trace_id
                   for e in rtrace.trace_events(ctx.trace_id))

    def test_store_bounds(self):
        ptpu.config.set_flags(request_tracing=True)
        tracer = rtrace.RequestTracer()
        tracer.set_flag(True)
        tracer.MAX_TRACES = 4
        tracer.MAX_EVENTS_PER_TRACE = 3
        ctxs = [tracer.mint("x") for _ in range(8)]
        assert len(tracer.trace_ids()) == 4  # oldest evicted whole
        live = ctxs[-1]
        for i in range(10):
            tracer.event(live, "e%d" % i)
        assert len(tracer.trace_events(live.trace_id)) == 3
        assert tracer.dropped(live.trace_id) == 8  # 1 root + 10 - 3
        # events to an evicted trace don't resurrect it
        tracer.event(ctxs[0], "late")
        assert ctxs[0].trace_id not in tracer.trace_ids()


# -- registry satellites ---------------------------------------------------

class TestLabelLifecycle:
    def test_cardinality_cap_evicts_oldest_and_counts(self):
        reg = metrics.Registry()
        reg.label_cardinality_cap = 3
        g = reg.gauge("g", labelnames=("replica",))
        for i in range(7):
            g.labels(replica="r%d" % i).set(i)
        children = g.children()
        assert len(children) == 3
        assert set(c.labels_dict["replica"] for c in children.values()) \
            == {"r4", "r5", "r6"}
        assert reg.label_evictions == 4
        evs = reg.counter("paddle_metrics_label_evictions_total")
        assert evs.value == 4

    def test_cap_zero_means_unbounded(self):
        """0 = off, the repo-wide flag convention — and must not trip
        the eviction path on an empty family."""
        reg = metrics.Registry()
        reg.label_cardinality_cap = 0
        g = reg.gauge("g", labelnames=("replica",))
        for i in range(50):
            g.labels(replica="r%d" % i).set(i)
        assert len(g.children()) == 50
        assert reg.label_evictions == 0

    def test_remove_labeled_sweeps_every_family(self):
        reg = metrics.Registry()
        g = reg.gauge("healthy", labelnames=("replica",))
        c = reg.counter("runs", labelnames=("replica",))
        other = reg.gauge("depth", labelnames=("queue",))
        for label in ("g0:0", "g0:1", "g1:0", "e0:0"):
            g.labels(replica=label).set(1)
            c.labels(replica=label).inc()
        other.labels(queue="g0:0").set(5)  # different label name: kept
        removed = reg.remove_labeled("replica", prefix="g0:")
        assert removed == 4  # two families x two children
        assert {ch.labels_dict["replica"]
                for ch in g.children().values()} == {"g1:0", "e0:0"}
        assert len(other.children()) == 1
        # exact-value form
        assert reg.remove_labeled("replica", value="g1:0") == 2
        with pytest.raises(ValueError):
            reg.remove_labeled("replica")

    def test_scheduler_close_retires_gauge_namespace(self):
        """The generalized sweep is what scheduler shutdown uses: no
        g<N>:* child of ANY family survives close()."""
        scope = _lm_scope()
        sched = GenerationScheduler(_session(scope),
                                    breaker_failures=2)
        sid = sched._sched_id
        fam = metrics.REGISTRY.gauge("paddle_serving_replica_healthy",
                                     labelnames=("replica",))
        prefix = "g%d:" % sid
        assert any(ch.labels_dict["replica"].startswith(prefix)
                   for ch in fam.children().values())
        sched.close()
        assert not any(ch.labels_dict["replica"].startswith(prefix)
                       for ch in fam.children().values())


class TestBucketOverrides:
    def test_explicit_override_before_traffic(self):
        reg = metrics.Registry()
        h = reg.histogram("lat")
        assert h.buckets == metrics.DEFAULT_TIME_BUCKETS
        reg.histogram("lat", buckets=(1.0, 5.0))
        assert h.buckets == (1.0, 5.0)
        reg.set_buckets("lat", (2.0, 4.0, 8.0))
        assert h.buckets == (2.0, 4.0, 8.0)

    def test_fetch_without_buckets_never_rebuckets(self):
        reg = metrics.Registry()
        h = reg.histogram("lat", buckets=(1.0, 5.0))
        h.observe(0.5)
        assert reg.histogram("lat") is h  # plain fetch: fine
        assert h.buckets == (1.0, 5.0)

    def test_override_after_observations_raises(self):
        reg = metrics.Registry()
        h = reg.histogram("lat", buckets=(1.0, 5.0))
        h.observe(0.5)
        with pytest.raises(ValueError):
            reg.histogram("lat", buckets=(9.0,))
        with pytest.raises(ValueError):
            reg.set_buckets("lat", (9.0,))

    def test_override_rebins_unused_children(self):
        reg = metrics.Registry()
        fam = reg.histogram("lat", labelnames=("stage",),
                            buckets=(1.0,))
        child = fam.labels(stage="a")
        reg.set_buckets("lat", (2.0, 4.0))
        assert child.buckets == (2.0, 4.0)
        assert child.bucket_counts == [0, 0, 0]

    def test_latency_histograms_use_ms_buckets(self):
        assert rtrace.QUEUE_WAIT_MS.buckets == \
            metrics.LATENCY_MS_BUCKETS
        assert rtrace.E2E_MS.buckets == metrics.LATENCY_MS_BUCKETS
        assert metrics.LATENCY_MS_BUCKETS[0] < 1.0  # sub-ms
        assert metrics.LATENCY_MS_BUCKETS[-1] == 60000.0  # 60 s


# -- flight recorder -------------------------------------------------------

class TestFlightRecorder:
    def test_disarmed_records_and_dumps_nothing(self, tmp_path):
        flight.RECORDER.record({"name": "x"})
        assert len(flight.RECORDER.ring) == 0
        assert flight.RECORDER.trigger("unit") is None

    def test_bundle_contents_and_debounce(self, tmp_path):
        ptpu.config.set_flags(request_tracing=True,
                              flight_dir=str(tmp_path))
        flight.RECORDER.min_interval_sec = 3600.0
        flight.RECORDER._last_dump_t = float("-inf")
        ctx = rtrace.mint("unit")
        rtrace.event(ctx, "sessionFailure", session=0)
        path = flight.RECORDER.trigger("breaker_open", replica="g0:0")
        assert path is not None and path.startswith(str(tmp_path))
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["reason"] == "breaker_open"
        assert bundle["attrs"]["replica"] == "g0:0"
        assert any(e["name"] == "sessionFailure"
                   for e in bundle["events"])
        assert bundle["config"]["request_tracing"] is True
        assert "paddle_generation_requests_total" in bundle["metrics"]
        assert flight.RECORDER.latest()["reason"] == "breaker_open"
        # debounced: a failure storm yields one bundle per window
        assert flight.RECORDER.trigger("client_error") is None

    def test_client_error_hook_dumps_via_resolve(self, tmp_path):
        import time

        from concurrent.futures import Future

        from paddle_tpu.serving.batcher import _resolve
        ptpu.config.set_flags(request_tracing=True,
                              flight_dir=str(tmp_path))
        flight.RECORDER.min_interval_sec = 0.0
        fut = Future()
        _resolve(fut, exception=RuntimeError("boom"))
        assert isinstance(fut.exception(), RuntimeError)
        # the dump's registry-serialize + disk write runs on a
        # background thread (the dispatcher must not stall behind it)
        deadline = time.monotonic() + 10
        while flight.RECORDER.latest() is None and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        bundle = flight.RECORDER.latest()
        assert bundle is not None, "background flight dump never landed"
        assert bundle["reason"] == "client_error"
        assert "boom" in bundle["attrs"]["error"]

    def test_dumps_bounded(self, tmp_path):
        ptpu.config.set_flags(request_tracing=True,
                              flight_dir=str(tmp_path))
        flight.RECORDER.min_interval_sec = 0.0
        for i in range(flight.RECORDER.max_dumps + 4):
            assert flight.RECORDER.dump("unit_%d" % i) is not None
        dumps = [p for p in tmp_path.iterdir()
                 if p.name.startswith("flight_")]
        assert len(dumps) <= flight.RECORDER.max_dumps


# -- live introspection ----------------------------------------------------

def _get(url, expect=200):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as err:
        assert err.code == expect, (err.code, expect)
        return err.code, err.read().decode()


class TestIntrospectionServer:
    def test_endpoints(self, tmp_path):
        ptpu.config.set_flags(request_tracing=True,
                              flight_dir=str(tmp_path))
        flight.RECORDER.min_interval_sec = 0.0
        srv = ohttp.start_server(0)
        try:
            rtrace.E2E_MS.observe(1.0)  # families expose once used
            code, text = _get(srv.url + "/metrics")
            assert code == 200
            assert "# TYPE paddle_request_e2e_ms histogram" in text
            assert 'paddle_request_e2e_ms_bucket{le="0.25"}' in text

            ohttp.register_health("unit", lambda: {"healthy": True})
            code, body = _get(srv.url + "/healthz")
            assert code == 200 and json.loads(body)["status"] == "ok"
            ohttp.register_health("bad", lambda: {"healthy": False})
            code, body = _get(srv.url + "/healthz", expect=503)
            assert code == 503
            assert json.loads(body)["status"] == "degraded"
            ohttp.unregister_health("bad")
            # a GC'd component (callable returns None) drops out
            ohttp.register_health("stale", lambda: None)
            code, body = _get(srv.url + "/healthz")
            assert "stale" not in json.loads(body)["components"]

            ctx = rtrace.mint("unit")
            rtrace.event(ctx, "prefill", session=1)
            code, body = _get(srv.url + "/debug/trace")
            assert ctx.trace_id in json.loads(body)["traces"]
            code, body = _get(srv.url + "/debug/trace?id="
                              + ctx.trace_id)
            tree = json.loads(body)
            assert tree["root"]["name"] == "request"
            code, _ = _get(srv.url + "/debug/trace?id=nope",
                           expect=404)
            assert code == 404

            code, _ = _get(srv.url + "/debug/flight", expect=404)
            assert code == 404  # no dump yet
            flight.RECORDER.dump("unit")
            code, body = _get(srv.url + "/debug/flight")
            assert json.loads(body)["reason"] == "unit"
        finally:
            ohttp.unregister_health("unit")
            ohttp.unregister_health("stale")
            ohttp.stop_server()

    def test_flag_starts_and_stops_server(self):
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        ptpu.config.set_flags(telemetry_port=port)
        try:
            assert ohttp.active_server() is not None
            assert ohttp.active_server().port == port
            code, _ = _get("http://127.0.0.1:%d/metrics" % port)
            assert code == 200
        finally:
            ptpu.config.set_flags(telemetry_port=0)
        assert ohttp.active_server() is None

    def test_bind_failure_never_breaks_set_flags_and_is_retryable(self):
        import socket
        blocker = socket.socket()
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        port = blocker.getsockname()[1]
        try:
            ptpu.config.set_flags(telemetry_port=port)  # taken: logs
            assert ohttp.active_server() is None
            ptpu.config.set_flags(telemetry_port=99999)  # out of range
            assert ohttp.active_server() is None
        finally:
            blocker.close()
        # port freed: RE-ISSUING the same flag must retry the bind,
        # not dedupe into a silent no-op
        try:
            ptpu.config.set_flags(telemetry_port=port)
            assert ohttp.active_server() is not None
            assert ohttp.active_server().port == port
        finally:
            ptpu.config.set_flags(telemetry_port=0)


# -- serving-stack propagation --------------------------------------------

V, MAXLEN = 29, 12
KW = dict(d_model=16, num_heads=2, d_ff=32, num_layers=2)
BOS, EOS = 0, 1


def _lm_scope(seed=7):
    with ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[1, MAXLEN], dtype="int64",
                               append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, MAXLEN], dtype="int64",
                               append_batch_size=False)
            transformer_lm(toks, lbls, vocab_size=V, is_test=True,
                           **KW)
    exe = ptpu.Executor()
    scope = ptpu.Scope()
    with ptpu.scope_guard(scope):
        exe.run(startup)
    rs = np.random.RandomState(seed)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        scope.set_var(n, rs.standard_normal(cur.shape)
                      .astype(cur.dtype))
    return scope


def _session(scope, slots=2):
    spec = transformer_lm_session(V, max_len=MAXLEN, slots=slots,
                                  cache_len=MAXLEN,
                                  prompt_buckets=(4, 8, 12),
                                  bos_id=BOS, eos_id=EOS, **KW)
    return GenerationSession(spec, scope=scope)


def _hist_count(name):
    fam = metrics.REGISTRY.histogram(name)
    return fam._default().count


class TestGenerationTracing:
    def test_request_life_in_one_trace(self):
        scope = _lm_scope()
        ptpu.config.set_flags(request_tracing=True)
        rtrace.clear()
        sched = GenerationScheduler(_session(scope))
        try:
            got = sched.submit([BOS, 3], max_new_tokens=4,
                               eos_id=-1).result(timeout=60)
            assert len(got) == 4
        finally:
            sched.close()
        assert len(rtrace.trace_ids()) == 1
        tid = rtrace.trace_ids()[0]
        events = rtrace.trace_events(tid)
        names = [e["name"] for e in events]
        assert names[0] == "request"
        for expected in ("queueWait", "prefill", "deviceCall",
                         "decodeStep", "resolve"):
            assert expected in names, (expected, names)
        assert all(e["trace_id"] == tid for e in events)
        # decode steps carry slot-level annotations
        step = next(e for e in events if e["name"] == "decodeStep")
        assert {"session", "slot", "active",
                "token_index"} <= set(step["attrs"])
        resolve = next(e for e in events if e["name"] == "resolve")
        assert resolve["attrs"]["tokens"] == 4

    def test_stage_histograms_always_on(self):
        """queue_wait/prefill/decode_step/e2e observe with tracing
        OFF — the always-on per-stage latency surface."""
        scope = _lm_scope()
        assert not rtrace.enabled()
        before = {n: _hist_count(n) for n in (
            "paddle_request_queue_wait_ms",
            "paddle_request_prefill_ms",
            "paddle_request_decode_step_ms",
            "paddle_request_e2e_ms")}
        sched = GenerationScheduler(_session(scope))
        try:
            sched.submit([BOS], max_new_tokens=3,
                         eos_id=-1).result(timeout=60)
        finally:
            sched.close()
        for name, b in before.items():
            assert _hist_count(name) > b, name
        assert rtrace.trace_ids() == []  # but no spans recorded

    def test_healthz_tracks_scheduler(self):
        scope = _lm_scope()
        sched = GenerationScheduler(_session(scope))
        name = sched._health_name
        snap = ohttp.health_snapshot()
        assert snap["components"][name]["healthy"] is True
        sched.close()
        assert name not in ohttp.health_snapshot()["components"]


class TestServingTracing:
    def _export(self, tmp_path):
        with ptpu.scope_guard(ptpu.Scope()), ptpu.unique_name.guard():
            main, startup = ptpu.Program(), ptpu.Program()
            with ptpu.program_guard(main, startup):
                x = layers.data("x", shape=[6])
                out = layers.fc(x, 4, act="softmax")
            exe = ptpu.Executor()
            exe.run(startup)
            d = str(tmp_path / "model")
            io.save_inference_model(d, ["x"], [out], exe,
                                    main_program=main)
        return d

    def test_batcher_engine_propagation(self, tmp_path):
        d = self._export(tmp_path)
        ptpu.config.set_flags(request_tracing=True)
        rtrace.clear()
        eng = ServingEngine(d, buckets=(4,), warmup=True)
        batcher = MicroBatcher(eng, max_delay_ms=20.0)
        try:
            futs = [batcher.submit({"x": np.zeros(6, "float32")})
                    for _ in range(3)]
            for f in futs:
                f.result(timeout=30)
        finally:
            batcher.close()
            eng.close()
        assert len(rtrace.trace_ids()) == 3  # one per request
        flushed = lead = 0
        for tid in rtrace.trace_ids():
            names = [e["name"] for e in rtrace.trace_events(tid)]
            assert "queueWait" in names and "resolve" in names
            if "shapeGroupFlush" in names:
                flushed += 1
            if "dispatch" in names:  # the flush's lead context also
                lead += 1            # carries the engine-tier detail
                assert "deviceCall" in names
        assert flushed == 3 and lead >= 1

    def test_unsampled_flush_mints_no_orphan_trace(self, tmp_path):
        """A batcher flush whose members were all unsampled must not
        make the engine mint its own 'serving.run' trace — at low
        sample rates the bounded store would otherwise fill with
        orphans for requests the operator chose not to record."""
        d = self._export(tmp_path)
        ptpu.config.set_flags(request_tracing=True,
                              trace_sample_rate=0.0)
        rtrace.clear()
        eng = ServingEngine(d, buckets=(4,), warmup=True)
        batcher = MicroBatcher(eng, max_delay_ms=20.0)
        try:
            futs = [batcher.submit({"x": np.zeros(6, "float32")})
                    for _ in range(3)]
            for f in futs:
                f.result(timeout=30)
        finally:
            batcher.close()
            eng.close()
        assert rtrace.trace_ids() == []

    def test_direct_engine_run_mints_own_trace(self, tmp_path):
        d = self._export(tmp_path)
        ptpu.config.set_flags(request_tracing=True)
        rtrace.clear()
        eng = ServingEngine(d, buckets=(4,), warmup=True)
        try:
            eng.run({"x": np.zeros((2, 6), "float32")})
        finally:
            eng.close()
        assert len(rtrace.trace_ids()) == 1
        names = [e["name"] for e in
                 rtrace.trace_events(rtrace.trace_ids()[0])]
        assert "dispatch" in names and "deviceCall" in names
        # the engine owns this trace (no batcher above), so it also
        # records the terminal edge
        assert names[-1] == "resolve"

    def test_healthz_tracks_engine(self, tmp_path):
        d = self._export(tmp_path)
        eng = ServingEngine(d, buckets=(4,), warmup=False)
        name = eng._health_name
        snap = ohttp.health_snapshot()
        assert snap["components"][name]["healthy"] is True
        assert snap["components"][name]["replicas"] == ["closed"]
        eng.close()
        assert name not in ohttp.health_snapshot()["components"]
