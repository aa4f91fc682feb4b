"""Benchmarks: both BASELINE.json metrics on one TPU chip.

Prints one JSON line per metric; the LAST line is the headline metric
(ResNet-50 train images/sec):
  {"metric", "value", "unit", "vs_baseline", ...}

* resnet50_train_images_per_sec — baseline 84.08 img/s, the reference's
  best published in-tree ResNet-50 training number (2-socket Xeon 6148 +
  MKL-DNN, benchmark/IntelOptimizedPaddle.md:38-45; the reference has no
  in-tree GPU ResNet number, see BASELINE.md). Also reports MFU against
  the chip's bf16 peak.
* seq2seq_train_tokens_per_sec — the reference's seq2seq slot is
  "will be added later" (benchmark/README.md:139-141), so the baseline
  proxy is its closest published RNN number: LSTM hidden=512 bs=64
  seqlen=100 at 184 ms/batch = 34.8k tokens/s (benchmark/README.md:
  115-120).

Perf recipe (see PROFILE.md for the measured evidence): amp=bfloat16
activations (HBM-bandwidth-bound step), async dispatch with one
device-to-host sync at the end of the timed window (the train loop never
blocks on a per-step fetch), state donation keeping updates in-place.
"""

import glob
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

# bf16 peak FLOP/s by device kind (for MFU reporting)
_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


# -- regression tripwire (VERDICT r5 demand 6) ---------------------------
# Metrics are higher-is-better (throughput / overlap efficiency) unless
# the result line carries ``"higher_is_better": false`` (latencies like
# cold_start_ms / swap_blackout_ms); either way a change for the worse
# beyond REGRESSION_TOLERANCE vs the most recent recorded run flags
# regressed=true with drift context on that line.
REGRESSION_TOLERANCE = 0.10


def parse_bench_tail(text):
    """Metric -> value from a BENCH_r*.json "tail" (one JSON obj per
    line, non-JSON noise lines skipped)."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj and "value" in obj:
            out[obj["metric"]] = obj["value"]
    return out


def load_previous_metrics(repo_dir=None):
    """Metrics from the highest-numbered BENCH_r*.json next to this
    file (empty dict when none exist or parsing fails)."""
    repo = repo_dir or os.path.dirname(os.path.abspath(__file__))
    best, best_n = None, -1
    for path in glob.glob(os.path.join(repo, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m and int(m.group(1)) > best_n:
            best, best_n = path, int(m.group(1))
    if best is None:
        return {}
    try:
        with open(best) as f:
            doc = json.load(f)
        return parse_bench_tail(doc.get("tail", ""))
    except (OSError, ValueError):
        return {}


def annotate_regression(result, prev_metrics,
                        rel_tol=REGRESSION_TOLERANCE):
    """Add prev_value/drift/regressed to one bench result line.
    ``drift`` is the relative change vs the previous run, sign-flipped
    for lower-is-better metrics so + is ALWAYS an improvement;
    ``regressed`` trips when the metric got worse by more than
    ``rel_tol``."""
    if not isinstance(result, dict) or "value" not in result:
        return result
    prev = prev_metrics.get(result.get("metric"))
    if not prev:
        result["prev_value"] = None
        result["regressed"] = False
        return result
    drift = float(result["value"]) / float(prev) - 1.0
    if result.get("higher_is_better") is False:
        drift = -drift
    result["prev_value"] = prev
    result["drift"] = round(drift, 3)
    regressed = drift < -rel_tol
    floor = result.get("regression_floor")
    if regressed and floor is not None and \
            float(result["value"]) <= floor and float(prev) <= floor:
        # both readings under the metric's own noise floor (e.g. a
        # microsecond-scale lock hold where scheduler jitter dwarfs
        # any relative change): drift is reported, but not flagged
        regressed = False
    result["regressed"] = bool(regressed)
    return result


def _device_info():
    """(on_accel, bf16 peak FLOP/s, stamp). Off the chip every metric
    name gains ``_cpu_smoke`` and every line says ``"platform": "cpu"``;
    an accelerator the peaks table does not know is an error, not an
    MFU of ``None``."""
    import jax
    devs = jax.devices()
    stamp = {"platform": devs[0].platform,
             "device_kind": devs[0].device_kind,
             "device_count": len(devs)}
    on_accel = devs[0].platform != "cpu"
    peak = _PEAK_FLOPS.get(devs[0].device_kind)
    if on_accel and peak is None:
        raise RuntimeError("no peak FLOP/s on record for device kind %r"
                           % devs[0].device_kind)
    return on_accel, peak, stamp


def bench_resnet(on_accel, peak):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models import resnet

    batch = 256 if on_accel else 4
    res = 224 if on_accel else 32
    depth = 50 if on_accel else 20
    steps = 30 if on_accel else 3
    warmup = 5 if on_accel else 1

    main_prog, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main_prog, startup):
        img = layers.data("img", shape=[3, res, res])
        label = layers.data("label", shape=[1], dtype="int64")
        if on_accel:
            loss, acc, _ = resnet.resnet_imagenet(img, label, depth=depth)
        else:
            loss, acc, _ = resnet.resnet_cifar10(img, label, depth=depth)
        opt = ptpu.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        opt.minimize(loss, startup_program=startup)

    exe = ptpu.Executor()
    exe.run(startup)
    rs = np.random.RandomState(0)
    # Stage the batch in HBM once (an input pipeline prefetches/overlaps;
    # this measures the train-step compute path, like the reference's
    # benchmark which reads from a warm provider).
    feed = {"img": jax.device_put(jnp.asarray(
                rs.randn(batch, 3, res, res).astype("float32"))),
            "label": jax.device_put(jnp.asarray(
                rs.randint(0, 1000, (batch, 1)), dtype=jnp.int32))}

    for _ in range(warmup):
        outs = exe.run(main_prog, feed=feed, fetch_list=[loss],
                       return_numpy=False)
    np.asarray(outs[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        outs = exe.run(main_prog, feed=feed, fetch_list=[loss],
                       return_numpy=False)
    final_loss = float(np.asarray(outs[0]))  # one sync closes the window
    dt = time.perf_counter() - t0
    img_per_sec = batch * steps / dt

    out = {
        "metric": "resnet50_train_images_per_sec" if on_accel else
                  "resnet20_cifar_train_images_per_sec_cpu_smoke",
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / 84.08, 3),
        "loss": round(final_loss, 4),
    }
    if on_accel:
        out["ms_per_step"] = round(dt / steps * 1e3, 1)
        if peak:
            # ResNet-50 training ~= 3x forward = 12.3 GFLOP/img @224
            out["mfu"] = round(img_per_sec * 12.3e9 / peak, 4)
    return out


def bench_seq2seq(on_accel):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.seq2seq import seq2seq_attention

    batch = 128 if on_accel else 4
    src_len = trg_len = 50 if on_accel else 6
    vocab = 30000 if on_accel else 100
    emb, hid = (512, 512) if on_accel else (16, 16)
    steps = 20 if on_accel else 2
    warmup = 3 if on_accel else 1

    main_prog, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main_prog, startup):
        src = layers.data("src", shape=[src_len], dtype="int64")
        slen = layers.data("src_len", shape=[], dtype="int64")
        trg = layers.data("trg", shape=[trg_len], dtype="int64")
        tlen = layers.data("trg_len", shape=[], dtype="int64")
        lbl = layers.data("lbl", shape=[trg_len], dtype="int64")
        loss, _ = seq2seq_attention(src, slen, trg, tlen, lbl,
                                    src_vocab=vocab, trg_vocab=vocab,
                                    emb_dim=emb, hid_dim=hid)
        opt = ptpu.optimizer.Adam(learning_rate=1e-3)
        opt.minimize(loss, startup_program=startup)

    exe = ptpu.Executor()
    exe.run(startup)
    rs = np.random.RandomState(0)
    ids = lambda n, t: jnp.asarray(rs.randint(2, vocab, (n, t)),
                                   dtype=jnp.int32)
    feed = {"src": jax.device_put(ids(batch, src_len)),
            "trg": jax.device_put(ids(batch, trg_len)),
            "lbl": jax.device_put(ids(batch, trg_len)),
            "src_len": jax.device_put(
                jnp.full((batch,), src_len, jnp.int32)),
            "trg_len": jax.device_put(
                jnp.full((batch,), trg_len, jnp.int32))}

    for _ in range(warmup):
        outs = exe.run(main_prog, feed=feed, fetch_list=[loss],
                       return_numpy=False)
    np.asarray(outs[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        outs = exe.run(main_prog, feed=feed, fetch_list=[loss],
                       return_numpy=False)
    final_loss = float(np.asarray(outs[0]))
    dt = time.perf_counter() - t0
    # tokens = target tokens consumed per optimizer step (the NMT
    # convention); source-side work is additional, unreported margin.
    tok_per_sec = batch * trg_len * steps / dt

    return {
        "metric": "seq2seq_train_tokens_per_sec" if on_accel else
                  "seq2seq_train_tokens_per_sec_cpu_smoke",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tok_per_sec / 34783.0, 3),
        "loss": round(final_loss, 4),
        "ms_per_step": round(dt / steps * 1e3, 1),
    }


def bench_transformer_lm(on_accel, peak):
    """Causal transformer LM through the Pallas flash-attention kernel
    (config flash_attention=True) — the compute-dense counterpoint to
    ResNet-50's HBM-bound 17% cap (PROFILE.md round 4): the same
    Program/Executor/amp machinery at high MFU."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm

    vocab = 32768 if on_accel else 128
    d, L, H = (2048, 12, 16) if on_accel else (64, 2, 2)
    T = 1024 if on_accel else 32
    B = 8 if on_accel else 2
    # Round 8 stabilization (same discipline as the r5 pipeline bench):
    # a 376.5 -> 409.4 ms/step swing between two earlier runs was
    # indistinguishable from drift because each came from ONE window.
    # Now: warmup, then median over several independently-synced
    # windows, with the window spread reported as a drift field.
    windows = 5 if on_accel else 3
    steps = 4 if on_accel else 2  # per window
    warmup = 2 if on_accel else 1

    main_prog, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main_prog, startup):
        toks = layers.data("toks", shape=[T], dtype="int64")
        lbls = layers.data("lbls", shape=[T], dtype="int64")
        loss, _ = transformer_lm(toks, lbls, vocab_size=vocab,
                                 d_model=d, num_heads=H, d_ff=4 * d,
                                 num_layers=L)
        opt = ptpu.optimizer.Adam(learning_rate=1e-4)
        opt.minimize(loss, startup_program=startup)
    n_params = sum(
        int(np.prod(p.shape)) for p in
        main_prog.global_block().all_parameters())

    exe = ptpu.Executor()
    exe.run(startup)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(2, vocab, (B, T)), dtype=jnp.int32)
    feed = {"toks": jax.device_put(ids), "lbls": jax.device_put(ids)}

    for _ in range(warmup):
        outs = exe.run(main_prog, feed=feed, fetch_list=[loss],
                       return_numpy=False)
    np.asarray(outs[0])
    window_ms = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            outs = exe.run(main_prog, feed=feed, fetch_list=[loss],
                           return_numpy=False)
        final_loss = float(np.asarray(outs[0]))  # sync closes the window
        window_ms.append((time.perf_counter() - t0) / steps * 1e3)
    dt_ms = float(np.median(window_ms))
    tok_per_sec = B * T / (dt_ms / 1e3)

    out = {
        "metric": "transformer_lm_train_tokens_per_sec" if on_accel
        else "transformer_lm_train_tokens_per_sec_cpu_smoke",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tok_per_sec / 34783.0, 3),  # RNN proxy
        "loss": round(final_loss, 4),
        "ms_per_step": round(dt_ms, 1),
        "ms_per_step_drift": [round(min(window_ms), 1),
                              round(max(window_ms), 1)],
        "windows": windows,
        "n_params": n_params,
    }
    if on_accel and peak:
        # 6N per token (fwd+bwd+update matmuls) + causal attention
        # 6*L*T*d per token (PaLM appendix B convention)
        flops_per_tok = 6.0 * n_params + 6.0 * L * T * d
        out["mfu"] = round(tok_per_sec * flops_per_tok / peak, 4)
    return out


def bench_resnet_pipeline(on_accel):
    """ResNet through Trainer.train + the narrow-wire staged pipeline
    (reader/staging.py + core/ingest.py), vs the compute-only path.
    Round 8: the feed crosses the wire in WIRE form — uint8 images and
    int32 labels packed into one contiguous arena block, ONE device_put
    per batch — and the executor widens/normalizes on device inside the
    compiled step. That's ~4x fewer bytes than the r05 f32/int64 feed
    and N->1 transfer dispatches; both are reported (and the dispatch
    count asserted) via the staging wire counters.

    The metric is OVERLAP EFFICIENCY (steady-state step time vs
    max(compute, wire-H2D)); the H2D reference is bracketed before/after
    the pass and combined by median."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models import resnet
    from paddle_tpu.reader import staging as _staging
    from paddle_tpu.trainer import Trainer

    batch = 8 if on_accel else 4
    res = 224 if on_accel else 32
    depth = 50 if on_accel else 20
    steps = 16 if on_accel else 3

    main_prog, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main_prog, startup):
        img = layers.data("img", shape=[3, res, res],
                          wire_dtype="uint8", scale=1.0 / 255.0)
        label = layers.data("label", shape=[1], dtype="int64",
                            wire_dtype="int32")
        if on_accel:
            loss, acc, _ = resnet.resnet_imagenet(img, label,
                                                  depth=depth)
        else:
            loss, acc, _ = resnet.resnet_cifar10(img, label,
                                                 depth=depth)
        opt = ptpu.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        opt.minimize(loss, startup_program=startup)

    rs = np.random.RandomState(0)
    host_batches = [
        {"img": rs.randint(0, 256, (batch, 3, res, res), "int64")
            .astype("uint8"),
         "label": rs.randint(0, 1000, (batch, 1)).astype("int32")}
        for _ in range(3)]

    # compute-only reference: widened batch resident in HBM (the model
    # sees the same values the ingest prologue produces), async chain
    tr = Trainer(loss, main_program=main_prog,
                 startup_program=startup, async_metrics=True)
    tr.startup()
    dev_feed = {
        "img": jax.device_put(
            jnp.asarray(host_batches[0]["img"], jnp.float32)
            * np.float32(1.0 / 255.0)),
        "label": jax.device_put(jnp.asarray(host_batches[0]["label"]))}
    m = tr._train_feed(dev_feed)
    np.asarray(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        m = tr._train_feed(dev_feed)
    np.asarray(m["loss"])
    compute_ms = (time.perf_counter() - t0) / steps * 1e3

    wire_nbytes = sum(v.nbytes for v in host_batches[0].values())

    def h2d_reps(n):
        times = []
        for i in range(n):
            hb = host_batches[i % len(host_batches)]
            t0 = time.perf_counter()
            jax.block_until_ready(
                [jax.device_put(v) for v in hb.values()])
            times.append((time.perf_counter() - t0) * 1e3)
        return times

    h2d_samples = h2d_reps(4)  # bracket: before

    def reader():
        for i in range(steps):
            yield dict(host_batches[i % len(host_batches)])

    prev_flags = {"packed_feeds": ptpu.config.get_flag("packed_feeds"),
                  "telemetry": ptpu.config.get_flag("telemetry")}
    ptpu.config.set_flags(packed_feeds=True, telemetry=True)
    metrics = []
    try:
        # warm the packed-feed compile-cache entry (uint8 feed signature
        # != the f32 reference entry) OUTSIDE the timed window, like the
        # compute reference warms its own
        tr.train(lambda: iter([dict(host_batches[0])]), num_passes=1)
        c0 = (_staging._TRANSFERS.value, _staging._WIRE_BYTES.value,
              _staging._LEGACY_BYTES.value)
        t0 = time.perf_counter()
        tr.train(reader, num_passes=1,
                 event_handler=lambda e: metrics.append(e.metrics["loss"])
                 if hasattr(e, "metrics") and hasattr(e, "step_id")
                 else None)
        np.asarray(metrics[-1])
        pipeline_ms = (time.perf_counter() - t0) / steps * 1e3
        transfers = _staging._TRANSFERS.value - c0[0]
        wire_bytes = _staging._WIRE_BYTES.value - c0[1]
        legacy_bytes = _staging._LEGACY_BYTES.value - c0[2]
    finally:
        ptpu.config.set_flags(**prev_flags)
    # the fused single-copy contract: one H2D dispatch per batch
    if transfers != steps:
        raise RuntimeError(
            "packed feed path issued %d H2D dispatches over %d batches "
            "(want exactly 1 per batch)" % (transfers, steps))

    h2d_samples += h2d_reps(4)  # bracket: after
    h2d_ms = float(np.median(h2d_samples))

    bound = max(compute_ms, h2d_ms)
    ratio = bound / pipeline_ms
    return {
        "metric": "resnet_pipeline_overlap" if on_accel else
                  "resnet_pipeline_overlap_cpu_smoke",
        # 1.0 = perfect overlap; >1 means H2D sped up mid-pass
        # relative to the bracketed reference — capped (never better
        # than the bound)
        "value": round(min(ratio, 1.0), 3),
        "unit": "overlap_efficiency",
        "vs_baseline": 1.0,
        "raw_ratio": round(ratio, 3),
        "pipeline_ms_per_step": round(pipeline_ms, 1),
        "compute_ms_per_step": round(compute_ms, 1),
        "h2d_ms_per_batch": round(h2d_ms, 1),
        "h2d_drift_ms": [round(min(h2d_samples), 1),
                         round(max(h2d_samples), 1)],
        "h2d_gbps": round(wire_nbytes / (h2d_ms / 1e3) / 1e9, 3),
        "h2d_dispatches_per_batch": transfers // steps,
        "wire_bytes_per_batch": wire_bytes // steps,
        "legacy_bytes_per_batch": legacy_bytes // steps,
        "wire_cut": round(legacy_bytes / max(wire_bytes, 1), 2),
        "batch": batch,
    }


def bench_checkpoint(on_accel):
    """Checkpoint save+verify+restore latency through the crash-safe
    path (io.py: temp-dir write, sha256 manifest, atomic publish,
    digest-verified load). Reported as roundtrips/sec so the
    regression tripwire (higher-is-better) watches it — a silent 10%
    slowdown in the checkpoint path taxes every training job's step
    budget."""
    import shutil
    import tempfile

    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models import resnet

    res = 224 if on_accel else 32
    depth = 50 if on_accel else 20

    main_prog, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main_prog, startup):
        img = layers.data("img", shape=[3, res, res])
        label = layers.data("label", shape=[1], dtype="int64")
        if on_accel:
            loss, _, _ = resnet.resnet_imagenet(img, label, depth=depth)
        else:
            loss, _, _ = resnet.resnet_cifar10(img, label, depth=depth)
        ptpu.optimizer.Momentum(learning_rate=0.1, momentum=0.9) \
            .minimize(loss, startup_program=startup)

    exe = ptpu.Executor()
    exe.run(startup)
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        from paddle_tpu import io as pio
        # warm (first save pays makedirs etc.)
        pio.save_checkpoint(exe, ckpt_dir, 0, main_prog)
        reps = 5
        t_save = t_load = 0.0
        for i in range(1, reps + 1):
            t0 = time.perf_counter()
            pio.save_checkpoint(exe, ckpt_dir, i, main_prog)
            t1 = time.perf_counter()
            loaded = pio.load_checkpoint(exe, ckpt_dir, main_prog)
            t2 = time.perf_counter()
            if loaded != i:
                raise RuntimeError("checkpoint roundtrip loaded step "
                                   "%r, expected %d" % (loaded, i))
            t_save += t1 - t0
            t_load += t2 - t1
        state_bytes = sum(
            os.path.getsize(os.path.join(ckpt_dir,
                                         "checkpoint_%d" % reps, f))
            for f in os.listdir(os.path.join(ckpt_dir,
                                             "checkpoint_%d" % reps)))
        rt = reps / (t_save + t_load)
        return {
            "metric": "checkpoint_roundtrips_per_sec" if on_accel else
                      "checkpoint_roundtrips_per_sec_cpu_smoke",
            "value": round(rt, 2),
            "unit": "save+verify+restore/sec",
            "vs_baseline": 1.0,  # no reference analog; tripwire-only
            "save_ms": round(t_save / reps * 1e3, 1),
            "verify_restore_ms": round(t_load / reps * 1e3, 1),
            "state_mb": round(state_bytes / 1e6, 1),
        }
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _isolated(fn):
    """Run one bench in a private Scope + name namespace and release
    its device state afterwards (the 740M-param transformer's Adam
    state would otherwise sit in HBM under the batch-256 ResNet)."""
    import gc
    import paddle_tpu as ptpu
    with ptpu.scope_guard(ptpu.Scope()), ptpu.unique_name.guard():
        out = fn()
    gc.collect()
    return out


def bench_deploy(on_accel):
    """Deploy-layer latencies (ISSUE 7), both lower-is-better and
    watched by the tripwire via ``higher_is_better: false``:

    * ``cold_start_ms`` — ServingEngine construct + warmup + first
      response from an AOT-exported artifact (deserialize path); the
      compile-path time on the same artifact rides along as context.
    * ``swap_blackout_ms`` — the longest single-replica lock hold of a
      hot weight swap under the same engine.
    """
    import shutil
    import tempfile

    import paddle_tpu as ptpu
    from paddle_tpu import layers, io
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import ServingEngine

    tmp = tempfile.mkdtemp(prefix="bench_deploy_")
    suffix = "" if on_accel else "_cpu_smoke"
    try:
        def export(name, seed):
            with ptpu.scope_guard(ptpu.Scope()), \
                    ptpu.unique_name.guard():
                main_prog, startup = ptpu.Program(), ptpu.Program()
                with ptpu.program_guard(main_prog, startup):
                    x = layers.data("x", shape=[64])
                    h = layers.fc(x, 128, act="relu")
                    out = layers.fc(h, 10, act="softmax")
                exe = ptpu.Executor()
                exe.run(startup)
                scope = ptpu.global_scope()
                rs = np.random.RandomState(seed)
                for n in sorted(scope.var_names()):
                    cur = np.asarray(scope.find_var(n))
                    scope.set_var(n, rs.standard_normal(cur.shape)
                                  .astype(cur.dtype))
                d = os.path.join(tmp, name)
                io.save_inference_model(d, ["x"], [out], exe,
                                        main_program=main_prog,
                                        export_compiled=True)
            return d

        d_a, d_b = export("a", seed=1), export("b", seed=2)
        probe = {"x": np.zeros((1, 64), "float32")}

        t0 = time.perf_counter()
        eng = ServingEngine(d_a, warmup=True, use_exported=False)
        eng.run(probe)
        compile_ms = (time.perf_counter() - t0) * 1e3
        eng.close()

        aot0 = metrics.REGISTRY.counter(
            "paddle_deploy_aot_loads_total").value
        t0 = time.perf_counter()
        eng = ServingEngine(d_a, warmup=True)
        eng.run(probe)
        aot_ms = (time.perf_counter() - t0) * 1e3
        aot_loads = metrics.REGISTRY.counter(
            "paddle_deploy_aot_loads_total").value - aot0

        hist = metrics.REGISTRY.histogram(
            "paddle_deploy_swap_blackout_seconds").labels()
        count0 = hist.count
        eng.swap_weights(d_b, watch_requests=0)
        eng.run(probe)
        eng.close()
        if hist.count <= count0:
            raise RuntimeError("swap recorded no blackout sample")
        blackout_ms = hist.vmax * 1e3

        return [{
            "metric": "cold_start_ms" + suffix,
            "value": round(aot_ms, 1),
            "unit": "ms to first response",
            "higher_is_better": False,
            "vs_baseline": 1.0,  # no reference analog; tripwire-only
            "compile_path_ms": round(compile_ms, 1),
            "aot_buckets_loaded": int(aot_loads),
        }, {
            "metric": "swap_blackout_ms" + suffix,
            "value": round(blackout_ms, 4),
            "unit": "ms max single-replica flip hold",
            "higher_is_better": False,
            "vs_baseline": 1.0,
            # the flip is a microsecond-scale pointer swap; relative
            # drift below 1 ms is scheduler noise, not a regression
            "regression_floor": 1.0,
        }]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_generation(on_accel):
    """Autoregressive generation serving latencies (ISSUE 9), under the
    regression tripwire:

    * ``decode_tokens_per_sec`` — aggregate KV-cached decode throughput
      at full slot occupancy (higher is better).
    * ``time_to_first_token_ms`` — admit->first-token (prefill) on a
      warm session; lower is better.
    * ``inter_token_ms`` — median decode-step latency; lower is better.

    Latency metrics carry ``higher_is_better: false`` plus a noise
    floor (like ``swap_blackout_ms``): CPU scheduler jitter at the
    millisecond scale must not trip the wire.

    Each decode line is stamped with the ``compute_dtype`` it ran
    under (like PR 17's ``policy`` stamp); the ``_int8`` variants
    re-measure the same workload with ``serving_quant_compute`` armed
    — int8 weights through the MXU, no per-step dequantization."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import (transformer_lm_generate,
                                               transformer_lm_session)
    from paddle_tpu.serving.generation import GenerationSession

    vocab = 1024 if on_accel else 64
    kw = dict(d_model=512, num_heads=8, d_ff=2048, num_layers=4) \
        if on_accel else dict(d_model=64, num_heads=2, d_ff=128,
                              num_layers=2)
    steps = 64 if on_accel else 32
    slots = 8 if on_accel else 4
    max_len = 2 * steps
    suffix = "" if on_accel else "_cpu_smoke"

    # weights via the generate program's own startup (shared names)
    with ptpu.unique_name.guard():
        main_prog, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main_prog, startup):
            anchor = layers.data("anchor", shape=[1], dtype="int32")
            transformer_lm_generate(anchor, vocab_size=vocab,
                                    max_len=max_len, beam_size=1,
                                    **kw)
    exe = ptpu.Executor()
    exe.run(startup)

    spec = transformer_lm_session(vocab, max_len=max_len, slots=slots,
                                  cache_len=max_len,
                                  prompt_buckets=(8,), **kw)
    sess = GenerationSession(spec)
    rs = np.random.RandomState(0)

    def fill():
        return [sess.admit(list(rs.randint(2, vocab, 4)))[0]
                for _ in range(slots - len(sess.active_slots()))]

    fill()                      # warm: prefill + decode compiles
    sess.step()
    for s in sess.active_slots():
        sess.retire(s)

    ttft = []
    for _ in range(5):
        t0 = time.perf_counter()
        slot, _ = sess.admit([0])
        ttft.append((time.perf_counter() - t0) * 1e3)
        sess.retire(slot)
    fill()
    step_ms = []
    t0 = time.perf_counter()
    for _ in range(steps):
        t1 = time.perf_counter()
        sess.step()
        step_ms.append((time.perf_counter() - t1) * 1e3)
    dt = time.perf_counter() - t0
    tok_per_sec = slots * steps / dt
    stats = sess.compile_stats()
    if stats["compiles"] != 2:
        raise RuntimeError(
            "generation shape set not closed: %d compiles for 1 "
            "prompt bucket + 1 decode shape" % stats["compiles"])

    # int8 re-measure (ISSUE 19): arm serving_quant_compute on the SAME
    # weights — the session quantizes the scope in place, so this runs
    # only after every f32 window above has closed
    ptpu.config.set_flags(serving_quant_compute=True)
    try:
        spec8 = transformer_lm_session(vocab, max_len=max_len,
                                       slots=slots, cache_len=max_len,
                                       prompt_buckets=(8,), **kw)
        sess8 = GenerationSession(spec8)
        if not sess8._quant_armed:
            raise RuntimeError("int8 compute did not arm any weights")
        for _ in range(slots):
            sess8.admit(list(rs.randint(2, vocab, 4)))
        sess8.step()          # warm: prefill + int8 decode compiles
        step8_ms = []
        t0 = time.perf_counter()
        for _ in range(steps):
            t1 = time.perf_counter()
            sess8.step()
            step8_ms.append((time.perf_counter() - t1) * 1e3)
        dt8 = time.perf_counter() - t0
        tok8_per_sec = slots * steps / dt8
        if sess8.compile_stats()["compiles"] != 2:
            raise RuntimeError(
                "int8 generation shape set not closed: %d compiles"
                % sess8.compile_stats()["compiles"])
        sess8.close()
    finally:
        ptpu.config.set_flags(serving_quant_compute=False)

    return [{
        "metric": "decode_tokens_per_sec" + suffix,
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec (aggregate, %d slots)" % slots,
        "vs_baseline": 1.0,  # no reference analog; tripwire-only
        "slots": slots,
        "steps": steps,
        "policy": "greedy",  # decode-policy the line was measured under
        "compute_dtype": "float32",  # matmul dtype the line ran under
    }, {
        "metric": "decode_tokens_per_sec_int8" + suffix,
        "value": round(tok8_per_sec, 1),
        "unit": "tokens/sec (aggregate, %d slots, int8 weights)"
                % slots,
        "vs_baseline": 1.0,
        "slots": slots,
        "steps": steps,
        "policy": "greedy",
        "compute_dtype": "int8",
    }, {
        "metric": "inter_token_ms_int8" + suffix,
        "value": round(float(np.median(step8_ms)), 2),
        "unit": "ms per decode step (all slots, int8 weights)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "regression_floor": 2.0,
        "policy": "greedy",
        "compute_dtype": "int8",
    }, {
        "metric": "time_to_first_token_ms" + suffix,
        "value": round(float(np.median(ttft)), 2),
        "unit": "ms admit->first token (warm)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        # prefill is a single small-batch step; ms-scale host jitter
        # dominates relative drift below this
        "regression_floor": 5.0,
        "policy": "greedy",
    }, {
        "metric": "inter_token_ms" + suffix,
        "value": round(float(np.median(step_ms)), 2),
        "unit": "ms per decode step (all slots)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "regression_floor": 2.0,
        "policy": "greedy",
        "compute_dtype": "float32",
    }]


def bench_speculative(on_accel):
    """Speculative-decoding accept rate (ISSUE 17), tripwired:

    * ``speculative_accept_rate`` — accepted / drafted tokens of a
      1-layer truncated self-draft against the full target, single
      slot. A drop means the verify kernel, the draft mirror, or the
      COW rollback started disagreeing with the plain decode path —
      rate is a correctness canary, not just a perf number.

    The weight regime mirrors tools/decode_policy_probe.py: LayerNorms
    at real init (gain 1 / bias 0) and residual-writing projections
    (attention out-proj, ffn2) scaled by eps/sqrt(fan_in), so the
    stream is embedding-dominated and the truncated draft genuinely
    predicts the target's argmax most steps."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import (transformer_lm,
                                               transformer_lm_session)
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.decoding import DecodePolicy
    from paddle_tpu.serving.generation import GenerationSession

    vocab = 256
    kw = dict(d_model=256, num_heads=4, d_ff=1024, num_layers=6) \
        if on_accel else dict(d_model=128, num_heads=2, d_ff=512,
                              num_layers=4)
    steps = 96 if on_accel else 48
    max_len = 16 + steps
    suffix = "" if on_accel else "_cpu_smoke"

    with ptpu.unique_name.guard():
        main_prog, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main_prog, startup):
            toks = layers.data("toks", shape=[1, max_len],
                               dtype="int64", append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, max_len],
                               dtype="int64", append_batch_size=False)
            transformer_lm(toks, lbls, vocab_size=vocab, is_test=True,
                           **kw)
    exe = ptpu.Executor()
    exe.run(startup)
    scope = ptpu.global_scope()
    rs = np.random.RandomState(7)
    for n in sorted(scope.var_names()):
        cur = np.asarray(scope.find_var(n))
        if not np.issubdtype(cur.dtype, np.floating):
            continue
        if n.startswith("layer_norm"):
            continue
        w = rs.standard_normal(cur.shape)
        if ".o.w" in n or ".ffn2." in n:
            fan_in = cur.shape[0] if cur.ndim == 2 else 1
            w = w * (1e-3 / np.sqrt(max(fan_in, 1)))
        scope.set_var(n, w.astype(cur.dtype))

    def counter(name):
        for s in (metrics.REGISTRY.dump().get(name, {})
                  .get("samples", ())):
            return s["value"]
        return 0.0

    prompt = [0, 5, 7, 11]
    base_sess = GenerationSession(transformer_lm_session(
        vocab, max_len=max_len, slots=1, prompt_buckets=(8,),
        paged=True, block_size=16, **kw))
    base = base_sess.generate(prompt, max_new_tokens=steps, eos_id=-1)
    base_sess.close()

    d0 = counter("paddle_generation_speculative_drafted_total")
    a0 = counter("paddle_generation_speculative_accepted_total")
    sess = GenerationSession(transformer_lm_session(
        vocab, max_len=max_len, slots=1, prompt_buckets=(8,),
        paged=True, block_size=16,
        decode_policy=DecodePolicy(kind="greedy", speculate_k=4),
        **kw))
    out = sess.generate(prompt, max_new_tokens=steps, eos_id=-1)
    sess.check_pool_invariant()
    sess.close()
    if out != base:
        raise RuntimeError(
            "speculative decode diverged from plain greedy — the "
            "verify kernel re-decides every position, so any draft "
            "must be trajectory-neutral")
    drafted = counter(
        "paddle_generation_speculative_drafted_total") - d0
    accepted = counter(
        "paddle_generation_speculative_accepted_total") - a0

    return [{
        "metric": "speculative_accept_rate" + suffix,
        "value": round(accepted / max(drafted, 1.0), 3),
        "unit": "accepted/drafted tokens (1-layer self-draft, k=4)",
        "vs_baseline": 1.0,  # no reference analog; tripwire-only
        "steps": steps,
        "policy": "speculative(greedy,k=4)",
    }]


def bench_paged_kv(on_accel):
    """Paged KV cache + prefix reuse (ISSUE 11), under the regression
    tripwire:

    * ``kv_cache_bytes_per_token`` — HBM pinned per LIVE token at
      steady state on a shared-prefix workload (pool blocks in use x
      block bytes / live tokens). Lower is better; the dense layout's
      equivalent (slots x worst-case rows) rides along as context.
    * ``prefix_cache_hit_rate`` — prompt tokens served from cached
      prefix blocks / total prompt tokens submitted. Higher is
      better; on the shared-system-prompt workload the common prefix
      should prefill exactly once.
    * ``kv_cache_bytes_per_token_bf16`` — the same workload under
      ``generation_kv_dtype=bfloat16`` (ISSUE 19); must hold at half
      the f32 line."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import (transformer_lm_generate,
                                               transformer_lm_session)
    from paddle_tpu.serving.generation import GenerationSession

    kw = dict(d_model=512, num_heads=8, d_ff=2048, num_layers=4) \
        if on_accel else dict(d_model=64, num_heads=2, d_ff=128,
                              num_layers=2)
    vocab = 1024 if on_accel else 64
    suffix = "" if on_accel else "_cpu_smoke"
    slots, cache_len, block_size = 8, 64, 8
    max_len = cache_len

    with ptpu.unique_name.guard():
        main_prog, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main_prog, startup):
            anchor = layers.data("anchor", shape=[1], dtype="int32")
            transformer_lm_generate(anchor, vocab_size=vocab,
                                    max_len=max_len, beam_size=1, **kw)
    exe = ptpu.Executor()
    exe.run(startup)

    spec = transformer_lm_session(
        vocab, max_len=max_len, slots=slots, cache_len=cache_len,
        prompt_buckets=(8, 16), paged=True, block_size=block_size,
        prefix_cache=True, **kw)
    sess = GenerationSession(spec)
    rs = np.random.RandomState(0)
    system = list(rs.randint(2, vocab, 14))   # shared system prompt
    # one full pass warms every compile outside the measured window
    sess.generate(system + [2], max_new_tokens=4, eos_id=-1)

    live_slots = []
    prompt_tokens = 0
    for i in range(slots):
        prompt = system + [3 + i]
        prompt_tokens += len(prompt)
        live_slots.append(sess.admit(prompt)[0])
    for _ in range(8):
        sess.step()
    live_tokens = int(sess.lengths[live_slots].sum())
    pstats = sess.pool_stats()
    paged_bytes = pstats["blocks_in_use"] * pstats["bytes_per_block"]
    row_bytes = pstats["bytes_per_block"] / block_size
    dense_bytes = slots * cache_len * row_bytes
    xstats = sess.prefix_stats()
    hit_rate = xstats["shared_tokens"] / float(prompt_tokens)
    for s in live_slots:
        sess.retire(s)
    sess.check_pool_invariant()
    sess.close()

    # bf16 block pools (ISSUE 19): same workload under
    # generation_kv_dtype — bytes/token must track at half the f32
    # line (greedy-token parity is asserted in tests, not here)
    ptpu.config.set_flags(generation_kv_dtype="bfloat16")
    try:
        spec_bf = transformer_lm_session(
            vocab, max_len=max_len, slots=slots, cache_len=cache_len,
            prompt_buckets=(8, 16), paged=True, block_size=block_size,
            prefix_cache=True, **kw)
        sess_bf = GenerationSession(spec_bf)
        sess_bf.generate(system + [2], max_new_tokens=4, eos_id=-1)
        live_bf = [sess_bf.admit(system + [3 + i])[0]
                   for i in range(slots)]
        for _ in range(8):
            sess_bf.step()
        live_tokens_bf = int(sess_bf.lengths[live_bf].sum())
        pstats_bf = sess_bf.pool_stats()
        bf_bytes = pstats_bf["blocks_in_use"] \
            * pstats_bf["bytes_per_block"]
        for s in live_bf:
            sess_bf.retire(s)
        sess_bf.check_pool_invariant()
        sess_bf.close()
    finally:
        ptpu.config.set_flags(generation_kv_dtype=None)

    return [{
        "metric": "kv_cache_bytes_per_token" + suffix,
        "value": round(paged_bytes / live_tokens, 1),
        "unit": "cache bytes pinned per live token (paged pool, "
                "shared-prefix workload)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "dense_equiv_bytes_per_token": round(
            dense_bytes / live_tokens, 1),
        "pool_blocks_in_use": pstats["blocks_in_use"],
        "block_size": block_size,
        "kv_dtype": "float32",
    }, {
        "metric": "kv_cache_bytes_per_token_bf16" + suffix,
        "value": round(bf_bytes / live_tokens_bf, 1),
        "unit": "cache bytes pinned per live token (bf16 block pool, "
                "shared-prefix workload)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "kv_dtype": "bfloat16",
        "f32_bytes_per_token": round(paged_bytes / live_tokens, 1),
        "block_size": block_size,
    }, {
        "metric": "prefix_cache_hit_rate" + suffix,
        "value": round(hit_rate, 3),
        "unit": "shared prompt tokens / submitted prompt tokens",
        "vs_baseline": 1.0,
        "shared_tokens": xstats["shared_tokens"],
        "prompt_tokens": prompt_tokens,
    }]


def bench_generation_failover(on_accel):
    """Fault-to-resumed-decode latency of token-replay failover
    (ISSUE 10): a mid-decode session kill re-queues the request and
    re-prefills its journal (prompt ⊕ tokens-so-far); the recovery
    number is re-queue wait + replay prefill, read per trial off the
    ``paddle_generation_failover_recovery_seconds`` histogram. Lower
    is better; a noise floor keeps ms-scale CPU scheduler jitter from
    tripping the wire."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import (transformer_lm_generate,
                                               transformer_lm_session)
    from paddle_tpu.observability import metrics
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving.generation import (GenerationScheduler,
                                               GenerationSession)

    kw = dict(d_model=512, num_heads=8, d_ff=2048, num_layers=4) \
        if on_accel else dict(d_model=64, num_heads=2, d_ff=128,
                              num_layers=2)
    vocab = 1024 if on_accel else 64
    max_len = 32
    suffix = "" if on_accel else "_cpu_smoke"

    with ptpu.unique_name.guard():
        main_prog, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main_prog, startup):
            anchor = layers.data("anchor", shape=[1], dtype="int32")
            transformer_lm_generate(anchor, vocab_size=vocab,
                                    max_len=max_len, beam_size=1, **kw)
    exe = ptpu.Executor()
    exe.run(startup)

    spec = transformer_lm_session(vocab, max_len=max_len, slots=2,
                                  cache_len=max_len,
                                  prompt_buckets=(8, 16), **kw)
    sess = GenerationSession(spec)
    sess.generate([0], max_new_tokens=2, eos_id=-1)  # warm compiles
    hist = metrics.REGISTRY.histogram(
        "paddle_generation_failover_recovery_seconds")._default()
    sched = GenerationScheduler(sess, replay_attempts=2)
    recov_ms = []
    try:
        for trial in range(7):
            c0, s0 = hist.count, hist.sum
            # one-shot mid-decode kill: the request replays (same
            # session — no breakers, so placement re-admits it there
            # and the exhausted fault lets it finish)
            faults.arm("generation_step_fail", times=1)
            fut = sched.submit([0, 2 + trial], max_new_tokens=8,
                               eos_id=-1)
            if len(fut.result(timeout=300)) != 8:
                raise RuntimeError("failover bench request truncated")
            faults.disarm()
            if hist.count != c0 + 1:
                raise RuntimeError(
                    "expected exactly one replay recovery, got %d"
                    % (hist.count - c0))
            recov_ms.append((hist.sum - s0) * 1e3)
    finally:
        faults.disarm()
        sched.close()
    return {
        "metric": "generation_failover_recovery_ms" + suffix,
        "value": round(float(np.median(recov_ms)), 2),
        "unit": "ms fault->resumed decode (re-queue + replay prefill)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "trials": len(recov_ms),
        # the replay prefill is one small-batch step: host jitter
        # dominates relative drift below this
        "regression_floor": 5.0,
    }


def bench_tracing_overhead(on_accel):
    """What request-scoped span recording costs the serving hot path
    (ISSUE 12): the same generation workload timed with
    ``request_tracing`` off and on (sample_rate=1.0), INTERLEAVED on
    one warmed scheduler so host drift cancels, reported as the
    relative wall-time delta in percent. Lower is better; the noise
    floor keeps CPU scheduler jitter (which can swing a ~60 ms window
    by several percent either way) from tripping the wire — the line
    exists so span recording can never silently tax serving, not to
    resolve sub-percent deltas."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import (transformer_lm,
                                               transformer_lm_session)
    from paddle_tpu.serving.generation import (GenerationScheduler,
                                               GenerationSession)

    kw = dict(d_model=512, num_heads=8, d_ff=2048, num_layers=4) \
        if on_accel else dict(d_model=128, num_heads=4, d_ff=256,
                              num_layers=2)
    vocab = 1024 if on_accel else 64
    max_len = 32
    suffix = "" if on_accel else "_cpu_smoke"

    with ptpu.unique_name.guard():
        main_prog, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main_prog, startup):
            toks = layers.data("toks", shape=[1, max_len],
                               dtype="int64", append_batch_size=False)
            lbls = layers.data("lbls", shape=[1, max_len],
                               dtype="int64", append_batch_size=False)
            transformer_lm(toks, lbls, vocab_size=vocab, is_test=True,
                           **kw)
    exe = ptpu.Executor()
    exe.run(startup)

    def make_session():
        spec = transformer_lm_session(vocab, max_len=max_len, slots=4,
                                      cache_len=max_len,
                                      prompt_buckets=(8, 16), **kw)
        sess = GenerationSession(spec)
        sess.generate([0], max_new_tokens=2, eos_id=-1)  # warm
        return sess

    prompts = [[0, 2 + (i % 13)] for i in range(16)]

    def workload(sched):
        futs = [sched.submit(p, max_new_tokens=12, eos_id=-1)
                for p in prompts]
        return [tuple(int(t) for t in f.result(timeout=300))
                for f in futs]

    import gc
    sched = GenerationScheduler(make_session())
    t_off, t_on = [], []
    gc_was_enabled = gc.isenabled()
    try:
        workload(sched)  # warm the dispatch path
        # GC pauses landing inside one ~80 ms window read as percent-
        # scale phantom overhead: collect between windows, not during
        gc.disable()
        for _ in range(9):
            ptpu.config.set_flags(request_tracing=False)
            gc.collect()
            t0 = time.perf_counter()
            base = workload(sched)
            t_off.append(time.perf_counter() - t0)
            ptpu.config.set_flags(request_tracing=True,
                                  trace_sample_rate=1.0)
            gc.collect()
            t0 = time.perf_counter()
            traced = workload(sched)
            t_on.append(time.perf_counter() - t0)
            if traced != base:
                raise RuntimeError("tracing changed generated tokens")
    finally:
        if gc_was_enabled:
            gc.enable()
        ptpu.config.set_flags(request_tracing=False)
        sched.close()
    overhead = (float(np.median(t_on)) / float(np.median(t_off))
                - 1.0) * 100.0
    return {
        "metric": "tracing_overhead_pct" + suffix,
        "value": round(overhead, 2),
        "unit": "% wall-time delta, request_tracing on vs off "
                "(sample_rate=1.0, interleaved medians)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "t_off_ms": round(float(np.median(t_off)) * 1e3, 2),
        "t_on_ms": round(float(np.median(t_on)) * 1e3, 2),
        # The CPU smoke denominator is a ~330 us toy decode step, so
        # the fixed ~5 us/event recording cost reads as 4-9% here and
        # swings run to run with scheduler jitter (a chip-scale ms
        # step pays well under 1%). Only a move past this floor — an
        # event-path cost blowup, not jitter — trips the wire.
        "regression_floor": 12.0,
    }


def bench_fleet(on_accel):
    """Serving-fleet latencies (ISSUE 13), all tripwired: p99 request
    latency with one of two engine-worker PROCESSES SIGKILLed
    mid-generation (the router re-drives its journals on the peer —
    the bench RAISES on any client error or any token diverging from
    the fault-free baseline, so the zero-error/bit-identical contract
    is load-bearing, not just asserted in tests), cold-member
    scale-up measured as spawn-to-first-token against the warm
    persistent compile cache (PR 7), and the client-error count of a
    rolling deploy under concurrent traffic — which must be 0 (the
    bench raises otherwise; the metric line documents it)."""
    import tempfile
    import threading

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests"))
    import fleet_worker_child as child
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.serving import wire
    from paddle_tpu.serving.autoscale import FleetAutoscaler
    from paddle_tpu.serving.fleet import FleetRouter, TenantQuotaError

    suffix = "" if on_accel else "_cpu_smoke"
    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    cache_dir = os.path.join(tmp, "compile_cache")
    n_req, max_new = 12, 10
    prompts = child.chaos_prompts(n_req, seed=5)

    scope = child.build_scope(seed=7)
    np.savez(os.path.join(tmp, "v1.npz"),
             **child.model_params(scope, 1.01))
    sched = child.make_scheduler(scope, slots=4)
    futs = [sched.submit(p, max_new_tokens=max_new, eos_id=-1)
            for p in prompts]
    baseline = [[int(t) for t in f.result(timeout=300)] for f in futs]
    sched.close()

    def spawn(router, mid, *extra):
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "fleet_worker_child.py"),
             "--router", "%s:%d" % router.addr, "--member", mid,
             "--heartbeat-ms", "150", "--compile-cache", cache_dir]
            + list(extra),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        line = proc.stdout.readline().strip()
        if not line.startswith("READY"):
            proc.kill()
            raise RuntimeError("fleet worker failed: %r" % line)
        return proc, int(line.split()[2])

    router = FleetRouter(heartbeat_timeout_ms=700, replay_attempts=6,
                         breaker_failures=2,
                         breaker_cooldown_ms=60000.0)
    procs = []
    try:
        procs.append(spawn(router, "m0", "--kill-at-token", "4")[0])
        procs.append(spawn(router, "m1")[0])
        router.wait_members(2, timeout=300)

        # p99 under a mid-generation SIGKILL of m0
        done_at = {}
        t0 = time.perf_counter()
        futures = []
        for i, p in enumerate(prompts):
            fut = router.submit(p, max_new_tokens=max_new, eos_id=-1,
                                meta=True)
            fut.add_done_callback(
                lambda f, i=i: done_at.__setitem__(
                    i, time.perf_counter()))
            futures.append(fut)
        results = [f.result(timeout=300) for f in futures]
        # done-callbacks run AFTER result() waiters wake (Future
        # internals), so the last stamp can trail the collection
        # loop by a beat — wait them in, bounded
        wait_deadline = time.monotonic() + 10
        while len(done_at) < n_req and \
                time.monotonic() < wait_deadline:
            time.sleep(0.005)
        if len(done_at) < n_req:
            raise RuntimeError("missing completion stamps: %d/%d"
                               % (len(done_at), n_req))
        lat_ms = [(done_at[i] - t0) * 1e3 for i in range(n_req)]
        mism = [i for i, (got, want) in enumerate(zip(results,
                                                      baseline))
                if got["tokens"].tolist() != want]
        if mism:
            raise RuntimeError("fleet failover diverged from the "
                               "fault-free baseline: %r" % mism)
        if procs[0].poll() is None:
            raise RuntimeError("worker m0 was never killed")
        p99_kill = float(np.percentile(lat_ms, 99))

        # cold-member scale-up through the AUTOSCALER spawn path
        # (PR 18): request_scale_up launches the process, the
        # pending->REG sweep rides the router monitor, and the first
        # token is pulled from the joined member itself (warm cache)
        ports = {}

        def as_spawn(mid):
            proc, port = spawn(router, mid)
            procs.append(proc)
            ports[mid] = port
            return proc

        scaler = FleetAutoscaler(
            router, as_spawn, members_max=8, burn_threshold=1.0,
            cooldown_ms=200.0, idle_ms=3600e3,
            spawn_timeout_ms=120e3, spawn_failure_budget=2,
            member_prefix="up")
        t_up0 = time.perf_counter()
        up_mid = scaler.request_scale_up()
        if up_mid is None:
            raise RuntimeError("autoscaler refused the scale-up")
        join_deadline = time.monotonic() + 300
        while up_mid not in router.members_live():
            if time.monotonic() > join_deadline:
                raise RuntimeError("scale-up member never joined")
            time.sleep(0.02)
        # sweep pending -> joined before detaching (close() reaps
        # anything still pending; this member is the fleet's now)
        while scaler.doc()["pending"]:
            scaler.tick()
            time.sleep(0.01)
        if scaler.spawn_failures:
            raise RuntimeError("autoscaler charged a spawn failure "
                               "during the scale-up bench")
        scaler.close()
        conn = wire.LineConn.connect(("127.0.0.1", ports[up_mid]),
                                     timeout=300.0)
        conn.send({"cmd": "generate", "prompt": prompts[0],
                   "max_new": 2, "eos_id": -1})
        first_token_ms = None
        while True:
            msg = conn.recv()
            if msg is None or msg.get("ev") == "err":
                raise RuntimeError("scale-up member failed: %r" % msg)
            if msg.get("ev") == "tok" and first_token_ms is None:
                first_token_ms = (time.perf_counter() - t_up0) * 1e3
            if msg.get("ev") == "done":
                break
        conn.close()

        # rolling deploy under concurrent traffic: client errors
        # MUST be zero (canary failures replay onto stable members)
        stop = threading.Event()
        responses, errors = [], []

        def traffic():
            rs = np.random.RandomState(17)
            while not stop.is_set():
                p = [child.BOS] + [int(t) for t in
                                   rs.randint(2, child.VOCAB, 3)]
                try:
                    responses.append(router.submit(
                        p, max_new_tokens=4, eos_id=-1,
                        meta=True).result(timeout=120))
                except Exception as exc:  # noqa: BLE001
                    errors.append(repr(exc))
        threads = [threading.Thread(target=traffic, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        deploy = router.rolling_deploy(
            params_path=os.path.join(tmp, "v1.npz"), tag="v1",
            canary_requests=2, watch_timeout=120)
        stop.set()
        for t in threads:
            t.join(timeout=120)
        if not deploy.get("ok"):
            raise RuntimeError("rolling deploy failed: %r" % deploy)
        mixed = [r for r in responses
                 if r["version_start"] != r["version"]]
        if errors or mixed:
            raise RuntimeError(
                "rolling deploy broke the zero-error/one-version "
                "contract: errors=%r mixed=%d"
                % (errors[:3], len(mixed)))

        # two-tenant burst (PR 18): the burster floods past its
        # in-flight quota while the victim's steady trickle runs at
        # higher priority — the victim must NEVER shed (isolation),
        # and the SLO violation seconds across the burst are the
        # capacity-pressure tripwire
        router2 = FleetRouter(
            heartbeat_timeout_ms=700, replay_attempts=3,
            slo_target_p99_ms=250.0, slo_windows=(5.0, 60.0),
            tenants={"burst": {"quota": 2, "priority": 1},
                     "victim": {"quota": 0, "priority": 0}},
            member_inflight_limit=4)
        try:
            procs.append(spawn(router2, "t0")[0])
            router2.wait_members(1, timeout=300)
            burst_sheds, burst_errors = [], []
            victim_served, victim_errors = [], []
            burst_end = time.monotonic() + 2.0

            def burster(seed):
                rs = np.random.RandomState(seed)
                while time.monotonic() < burst_end:
                    p = [child.BOS] + [int(t) for t in
                                       rs.randint(2, child.VOCAB, 3)]
                    try:
                        router2.submit(
                            p, max_new_tokens=3, eos_id=-1,
                            tenant="burst").result(timeout=120)
                    except TenantQuotaError:
                        burst_sheds.append(1)  # its own quota: fine
                        time.sleep(0.005)      # refusal is instant;
                        # back off so the burst is load, not a spin
                    except Exception as exc:  # noqa: BLE001
                        burst_errors.append(repr(exc))

            def victim():
                rs = np.random.RandomState(29)
                while time.monotonic() < burst_end:
                    p = [child.BOS] + [int(t) for t in
                                       rs.randint(2, child.VOCAB, 3)]
                    try:
                        victim_served.append(router2.submit(
                            p, max_new_tokens=3, eos_id=-1,
                            tenant="victim").result(timeout=120))
                    except Exception as exc:  # noqa: BLE001
                        victim_errors.append(repr(exc))

            burst_threads = [threading.Thread(target=burster,
                                              args=(31 + i,),
                                              daemon=True)
                             for i in range(4)]
            burst_threads.append(threading.Thread(target=victim,
                                                  daemon=True))
            for t in burst_threads:
                t.start()
            for t in burst_threads:
                t.join(timeout=300)
            violation_s = (router2.slo.violation_seconds
                           if router2.slo is not None else 0.0)
            victim_label = "f%d:victim" % router2._rid
            victim_sheds = 0.0
            for s in obs_metrics.REGISTRY.dump().get(
                    "paddle_serving_tenant_shed_total",
                    {}).get("samples", ()):
                if s["labels"].get("tenant") == victim_label:
                    victim_sheds = s["value"]
            isolation = victim_sheds + len(victim_errors)
            if victim_errors or burst_errors:
                raise RuntimeError(
                    "two-tenant burst broke the zero-client-error "
                    "contract: victim=%r burster=%r"
                    % (victim_errors[:3], burst_errors[:3]))
            if not victim_served or not burst_sheds:
                raise RuntimeError(
                    "burst produced no pressure (victim=%d served, "
                    "burster sheds=%d) — the isolation metric would "
                    "be vacuous" % (len(victim_served),
                                    len(burst_sheds)))
        finally:
            router2.close()
    finally:
        router.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()

    return [{
        "metric": "fleet_p99_under_kill_ms" + suffix,
        "value": round(p99_kill, 1),
        "unit": "ms p99 request latency, 1 of 2 workers SIGKILLed "
                "mid-generation (%d concurrent requests, journal "
                "re-drive on the peer)" % n_req,
        "higher_is_better": False,
        "vs_baseline": 1.0,
        # connect-retry + heartbeat-deadline policy waits dominate
        # the tail on CPU; only a recovery-path blowup should trip
        "regression_floor": 500.0,
    }, {
        "metric": "scale_up_to_first_token_ms" + suffix,
        "value": round(first_token_ms, 1),
        "unit": "ms from FleetAutoscaler.request_scale_up to the "
                "spawned member's first generated token (process "
                "launch + REG join + decode, persistent compile "
                "cache warm)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        # interpreter + jax import dominates on CPU; the wire exists
        # to catch a cold-start (cache/AOT) regression, not import
        # jitter
        "regression_floor": 1500.0,
    }, {
        "metric": "rolling_deploy_client_errors" + suffix,
        "value": len(errors),
        "unit": "client-visible errors during a rolling deploy under "
                "concurrent traffic (MUST be 0 — the bench raises "
                "otherwise)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "responses_during_deploy": len(responses),
        "must_be_zero": True,
    }, {
        "metric": "slo_violation_seconds_per_burst" + suffix,
        "value": round(float(violation_s), 3),
        "unit": "seconds the fast-window burn rate spent above 1.0 "
                "across a 2 s two-tenant quota burst (burster over "
                "quota, victim steady)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "burster_quota_sheds": len(burst_sheds),
        # the burst is sized to shed the burster, not to melt the
        # fleet: sustained burn past the window length means victim
        # traffic is burning budget too
        "regression_floor": 10.0,
    }, {
        "metric": "tenant_shed_isolation" + suffix,
        "value": float(isolation),
        "unit": "victim-tenant sheds + victim client errors while "
                "the burster floods past its quota (MUST be 0 — "
                "quota refusals land on the burster alone)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "victim_served": len(victim_served),
        "must_be_zero": True,
    }]


def bench_model_paging(on_accel):
    """Multi-model paging costs (ISSUE 20), tripwired:

    * ``model_page_in_ms`` — wall clock of the FIRST request for a
      not-yet-resident catalog model on a warm fleet: the router
      demand-pages the model (manifest-verified staged load through
      the swap gates) onto a member and serves the full decode. This
      is the capacity move that replaces a cold spawn — compare
      ``scale_up_to_first_token_ms``, which pays a whole process
      launch (its CPU noise floor alone is 1500 ms); a page-in only
      pays a host-snapshot load + activation swap.
    * ``model_residency_hit_rate`` — fraction of mixed two-tenant
      requests whose model was already resident on a live member at
      placement, across steady traffic on a byte-budgeted fleet where
      paging model B in FORCED an LRU eviction of model A (the bench
      raises if the budget never evicted — a hit rate measured
      without residency pressure is vacuous). Higher is better; the
      single cold page-in is the only expected miss.
    * ``paging_client_errors`` — client-visible errors across all of
      the above, which must be 0 (the bench raises otherwise, and
      also raises on any token diverging from the per-model oracle:
      two models sharing members must never mix outputs)."""
    import tempfile

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests"))
    import fleet_worker_child as child
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.serving import model_paging as mp
    from paddle_tpu.serving.fleet import FleetRouter

    suffix = "" if on_accel else "_cpu_smoke"
    tmp = tempfile.mkdtemp(prefix="bench_model_paging_")
    cache_dir = os.path.join(tmp, "compile_cache")
    max_new, n_steady = 8, 12

    def csum(name, **labels):
        total = 0.0
        for s in obs_metrics.REGISTRY.dump().get(name, {}).get(
                "samples", ()):
            if all(s["labels"].get(k) == v for k, v in
                   labels.items()):
                total += s["value"]
        return total

    # two genuinely different models sharing one program shape —
    # distinct seeds, not a scaled copy (greedy attractors make a
    # scaled copy decode identically, faking bit-identity)
    scope_a = child.build_scope(seed=7)
    scope_b = child.build_scope(seed=11)
    path_a = os.path.join(tmp, "A.npz")
    path_b = os.path.join(tmp, "B.npz")
    np.savez(path_a, **child.model_params(scope_a))
    np.savez(path_b, **child.model_params(scope_b))
    mp.write_weights_manifest(path_a)
    mp.write_weights_manifest(path_b)
    nbytes = os.path.getsize(path_a)

    cold_prompt = [child.BOS, 5, 9]
    prompts_a = child.chaos_prompts(n_steady, seed=3)
    prompts_b = child.chaos_prompts(n_steady, seed=23)

    def oracle_tokens(scope, prompts):
        sched = child.make_scheduler(scope)
        futs = [sched.submit(p, max_new_tokens=max_new, eos_id=-1)
                for p in prompts]
        outs = [[int(t) for t in f.result(timeout=300)]
                for f in futs]
        sched.close()
        return outs

    base_a = oracle_tokens(scope_a, prompts_a)
    base_b = oracle_tokens(scope_b, [cold_prompt] + prompts_b)
    base_b_cold, base_b = base_b[0], base_b[1:]

    router = FleetRouter(
        heartbeat_timeout_ms=700, replay_attempts=4,
        models={"A": {"params_path": path_a, "tag": "A@v0",
                      "bytes": nbytes, "tenants": ("acme",)},
                "B": {"params_path": path_b, "tag": "B@v0",
                      "bytes": nbytes, "tenants": ("bravo",)}},
        # room for ONE model per member: paging B in MUST evict A
        resident_bytes=int(nbytes * 1.5),
        page_timeout_ms=120000.0)
    procs, errors = [], []
    page0 = csum("paddle_fleet_model_page_ins_total", outcome="ok")
    evict0 = csum("paddle_fleet_model_evictions_total")
    hits0 = csum("paddle_fleet_model_residency_hits_total")
    miss0 = csum("paddle_fleet_model_residency_misses_total")
    try:
        for mid in ("m0", "m1"):
            proc = subprocess.Popen(
                [sys.executable,
                 os.path.join(
                     os.path.dirname(os.path.abspath(__file__)),
                     "tests", "fleet_worker_child.py"),
                 "--router", "%s:%d" % router.addr, "--member", mid,
                 "--heartbeat-ms", "150",
                 "--compile-cache", cache_dir,
                 "--model", "A", "--version", "A@v0"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True)
            line = proc.stdout.readline().strip()
            if not line.startswith("READY"):
                proc.kill()
                raise RuntimeError("fleet worker failed: %r" % line)
            procs.append(proc)
        router.wait_members(2, timeout=300)

        # cold page-in: the first model-B request on a warm fleet
        t0 = time.perf_counter()
        out = router.submit(cold_prompt, max_new_tokens=max_new,
                            eos_id=-1, tenant="bravo",
                            meta=True).result(timeout=600)
        page_in_ms = (time.perf_counter() - t0) * 1e3
        if out["tokens"].tolist() != base_b_cold:
            raise RuntimeError("cold page-in diverged from the "
                               "model-B oracle")
        if csum("paddle_fleet_model_page_ins_total",
                outcome="ok") - page0 != 1.0:
            raise RuntimeError("the cold request did not demand-page")

        # steady mixed traffic: residency affinity must route every
        # request to a member already holding its model — zero
        # further page-ins, bit-identical to each model's oracle
        futs = []
        for pa, pb in zip(prompts_a, prompts_b):
            futs.append(router.submit(pa, max_new_tokens=max_new,
                                      eos_id=-1, tenant="acme"))
            futs.append(router.submit(pb, max_new_tokens=max_new,
                                      eos_id=-1, tenant="bravo"))
        got = []
        for f in futs:
            try:
                got.append([int(t) for t in f.result(timeout=300)])
            except Exception as exc:  # noqa: BLE001
                errors.append(repr(exc))
                got.append(None)
        want = [t for ab in zip(base_a, base_b) for t in ab]
        mism = [i for i, (g, w) in enumerate(zip(got, want))
                if g is not None and g != w]
        if errors or mism:
            raise RuntimeError(
                "mixed two-model traffic broke the zero-error/"
                "bit-identity contract: errors=%r diverged=%r"
                % (errors[:3], mism[:5]))
        hits = csum("paddle_fleet_model_residency_hits_total") - hits0
        misses = csum(
            "paddle_fleet_model_residency_misses_total") - miss0
        hit_rate = hits / max(1.0, hits + misses)
        if csum("paddle_fleet_model_page_ins_total",
                outcome="ok") - page0 != 1.0:
            raise RuntimeError("affinity re-paged during steady "
                               "mixed traffic")
        if csum("paddle_fleet_model_evictions_total") - evict0 < 1.0:
            raise RuntimeError(
                "the byte budget never forced an eviction — the "
                "hit rate ran without residency pressure")
    finally:
        router.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()

    return [{
        "metric": "model_page_in_ms" + suffix,
        "value": round(page_in_ms, 1),
        "unit": "ms for the FIRST request of a not-yet-resident "
                "catalog model on a warm fleet (manifest-verified "
                "demand page-in + activation swap + full decode) — "
                "the capacity move that replaces a cold spawn: "
                "compare scale_up_to_first_token_ms, whose CPU "
                "noise floor alone is 1500 ms",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        # host-snapshot load + swap, no process launch: only a
        # paging-path blowup should trip, not decode jitter
        "regression_floor": 500.0,
    }, {
        "metric": "model_residency_hit_rate" + suffix,
        "value": round(hit_rate, 3),
        "unit": "fraction of mixed two-tenant requests whose model "
                "was already resident on a live member at placement "
                "(byte budget sized to force an eviction; the one "
                "cold page-in is the only expected miss)",
        "vs_baseline": 1.0,
        "hits": int(hits),
        "misses": int(misses),
    }, {
        "metric": "paging_client_errors" + suffix,
        "value": len(errors),
        "unit": "client-visible errors across mixed two-tenant "
                "traffic on a byte-budgeted two-model fleet (MUST "
                "be 0 — the bench raises otherwise)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "steady_requests": len(got),
        "must_be_zero": True,
    }]


def bench_recsys(on_accel):
    """Recsys (wide&deep) training with row-sharded DistEmbedding
    tables (ISSUE 14): real sparse id batches cross the PR-4 packed
    wire (one H2D per batch), the tables live mod-interleaved across
    the mesh, and lookup/gradient exchange runs as the two-hop ICI
    all_to_all inside the jitted step. Emits two tripwire metrics:
    ``recsys_examples_per_sec`` (end-to-end train throughput) and
    ``embedding_lookup_rows_per_sec`` (ids resolved through the
    distributed tables per second — both tables count), plus the
    static ``embedding_a2a_bytes_per_step`` exchange-volume lines for
    the f32 and int8 wires (ISSUE 19).

    Defaults-off contract: the embedding flags must arrive False here
    (the subsystem is constructed only inside this bench's flag
    window)."""
    import jax
    import paddle_tpu as ptpu
    from paddle_tpu import layers, parallel
    from paddle_tpu.reader.staging import StagedReader
    from paddle_tpu.models.wide_deep import wide_deep

    for flag in ("embedding_shard_rows", "embedding_a2a"):
        if ptpu.config.get_flag(flag):
            raise RuntimeError("flag %s armed before bench_recsys — "
                               "defaults must construct none of the "
                               "subsystem" % flag)

    ndev = len(jax.devices())
    shards = 1
    while shards * 2 <= min(ndev, 8):
        shards *= 2
    vocab = 200_000 if on_accel else 20_000
    slots = 26 if on_accel else 8
    emb_dim = 32 if on_accel else 8
    batch = 4096 if on_accel else 16 * shards
    steps = 30 if on_accel else 8

    prev = {k: ptpu.config.get_flag(k) for k in
            ("embedding_shard_rows", "embedding_a2a", "packed_feeds")}
    ptpu.config.set_flags(embedding_shard_rows=True, embedding_a2a=True,
                          packed_feeds=True)
    try:
        strat = parallel.DataParallel(n_devices=shards) \
            if shards > 1 else None
        main_prog, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main_prog, startup):
            ids = layers.data("ids", shape=[slots], dtype="int64")
            dense = layers.data("dense", shape=[8])
            label = layers.data("label", shape=[1])
            loss, _, _ = wide_deep(ids, dense, label, vocab, slots,
                                   emb_dim=emb_dim, hidden=(64, 32),
                                   is_distributed=True)
            ptpu.optimizer.Adagrad(0.05).minimize(
                loss, startup_program=startup)
        exe = ptpu.Executor(strategy=strat)
        exe.run(startup)

        rs = np.random.RandomState(7)
        host_batches = [
            {"ids": rs.randint(0, vocab, (batch, slots)).astype("int32"),
             "dense": rs.randn(batch, 8).astype("float32"),
             "label": rs.randint(0, 2, (batch, 1)).astype("float32")}
            for _ in range(3)]

        def reader(n):
            def gen():
                for i in range(n):
                    yield dict(host_batches[i % len(host_batches)])
            return gen

        # warm the packed compile entry outside the timed window
        sr = StagedReader(reader(1), strategy=strat, program=main_prog)
        for staged in sr():
            exe.run(main_prog, feed=staged, fetch_list=[loss])
        sr.close()

        sr = StagedReader(reader(steps), strategy=strat,
                          program=main_prog)
        last = None
        t0 = time.perf_counter()
        for staged in sr():
            last = exe.run(main_prog, feed=staged, fetch_list=[loss],
                           return_numpy=False)[0]
        np.asarray(last)  # drain the async chain
        elapsed = time.perf_counter() - t0
        sr.close()
    finally:
        ptpu.config.set_flags(**prev)

    suffix = "" if on_accel else "_cpu_smoke"
    ex_per_sec = batch * steps / elapsed
    # two distributed tables (deep + wide) each resolve batch*slots ids
    rows_per_sec = 2 * batch * slots * steps / elapsed

    # static per-step lookup exchange volume (ISSUE 19): the two-hop
    # route's bytes are a function of batch geometry and wire dtype,
    # not runtime — same formula the subsystem's telemetry uses.
    # Summed over the deep (emb_dim) and wide (dim 1) tables; the int8
    # wire ships int8 rows plus one f32 scale per row
    from paddle_tpu.embeddings.sharded import a2a_step_bytes
    total = batch * slots
    f32_step = int8_step = 0
    for dim in (emb_dim, 1):
        ids_b, rows_b = a2a_step_bytes(total, dim, shards, itemsize=4)
        f32_step += ids_b + rows_b
        ids8, rows8 = a2a_step_bytes(total, dim, shards, itemsize=1)
        int8_step += ids8 + rows8 + shards * total * 4

    common = {"unit_note": "%d-shard tables, vocab %d, %d slots"
              % (shards, vocab, slots), "num_shards": shards,
              "batch": batch, "steps": steps}
    return [
        dict({"metric": "recsys_examples_per_sec" + suffix,
              "value": round(ex_per_sec, 1),
              "unit": "examples/sec"}, **common),
        dict({"metric": "embedding_lookup_rows_per_sec" + suffix,
              "value": round(rows_per_sec, 1),
              "unit": "rows/sec"}, **common),
        dict({"metric": "embedding_a2a_bytes_per_step" + suffix,
              "value": f32_step,
              "unit": "bytes exchanged per step (f32 wire, both "
                      "tables)",
              "higher_is_better": False,
              "vs_baseline": 1.0,
              "wire_dtype": "float32"}, **common),
        dict({"metric": "embedding_a2a_bytes_per_step_int8" + suffix,
              "value": int8_step,
              "unit": "bytes exchanged per step (int8 wire + f32 "
                      "row scales, both tables)",
              "higher_is_better": False,
              "vs_baseline": 1.0,
              "wire_dtype": "int8",
              "f32_wire_bytes": f32_step}, **common),
    ]


def bench_slo(on_accel):
    """Telemetry-plane costs and guarantees (ISSUE 16), tripwired:

    * ``slo_detection_latency_ms`` — simulated-clock time from a
      latency fault starting to the fast-window burn-rate alert
      tripping, on an SLOTracker at default windows ticked at the
      serving monitor cadence. Deterministic (the clock is driven, not
      read), so the wire catches an algorithmic regression in the
      multi-window burn math — not host jitter.
    * ``metrics_aggregation_overhead_pct`` — what one member's
      telemetry cycle (bounded snapshot build + encode + router-side
      ingest) costs relative to a 1 s ship interval, on a registry
      populated to a realistic fleet cardinality. The whole plane must
      stay a rounding error next to the work it observes."""
    from paddle_tpu.observability import aggregate, metrics, slo
    from paddle_tpu.serving import wire

    suffix = "" if on_accel else "_cpu_smoke"

    # -- detection latency (simulated clock) ---------------------------
    reg = metrics.Registry()
    hist = reg.histogram("paddle_bench_slo_e2e_ms", "bench latencies",
                         buckets=metrics.LATENCY_MS_BUCKETS)
    tracker = slo.SLOTracker(
        label="bench", target_p99_ms=100.0,
        source=slo.local_source(histogram="paddle_bench_slo_e2e_ms",
                                registry=reg))
    tick_s = 0.25  # the serving monitor-loop cadence
    now = 0.0
    tracker.tick(now)
    while now < 90.0:  # healthy history filling both windows
        now += tick_s
        for _ in range(8):
            hist.observe(10.0)
        tracker.tick(now)
    fault_start = now
    detected = None
    while now < fault_start + 60.0:
        now += tick_s
        for _ in range(8):
            hist.observe(800.0)  # the fault: everything over target
        tracker.tick(now)
        if tracker.alerting:
            detected = now
            break
    tracker.close()
    if detected is None:
        raise RuntimeError("fast-window burn alert never tripped "
                           "under a total latency fault")
    detection_ms = (detected - fault_start) * 1e3

    # -- aggregation overhead (real clock) -----------------------------
    reg = metrics.Registry()
    for i in range(20):
        c = reg.counter("paddle_bench_c%d_total" % i, "c",
                        labelnames=("route",))
        for j in range(16):
            c.labels(route="r%02d" % j).inc(j + 1)
    for i in range(6):
        h = reg.histogram("paddle_bench_h%d_ms" % i, "h",
                          labelnames=("route",),
                          buckets=metrics.LATENCY_MS_BUCKETS)
        for j in range(16):
            h.labels(route="r%02d" % j).observe(float(7 * j % 90))
    agg = aggregate.FleetAggregator("bench",
                                    registry=metrics.Registry())
    reps = 50 if on_accel else 20
    budget = wire.MAX_LINE - 1024
    t0 = time.perf_counter()
    for i in range(reps):
        snap = aggregate.build_snapshot(max_bytes=budget, registry=reg)
        aggregate.encode_snapshot(snap)
        agg.ingest("m0", "i1", snap)
    cycle_s = (time.perf_counter() - t0) / reps
    interval_s = 1.0
    overhead_pct = cycle_s / interval_s * 100.0

    return [{
        "metric": "slo_detection_latency_ms" + suffix,
        "value": round(detection_ms, 1),
        "unit": "ms fault-start -> fast-window burn alert "
                "(simulated clock, %g s ticks, default windows)"
                % tick_s,
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "fast_window_s": tracker.windows[0],
        "tick_s": tick_s,
    }, {
        "metric": "metrics_aggregation_overhead_pct" + suffix,
        "value": round(overhead_pct, 3),
        "unit": "% of a 1 s ship interval spent on snapshot build + "
                "encode + ingest (realistic fleet cardinality)",
        "higher_is_better": False,
        "vs_baseline": 1.0,
        "cycle_ms": round(cycle_s * 1e3, 3),
        "families": 26,
        "children": 26 * 16,
        # sub-ms cycles on a shared CPU rig: scheduler jitter swings
        # the percentage; only an actual cost blowup should trip
        "regression_floor": 2.0,
    }]


def bench_elastic_resume():
    """Measure the elastic control plane's recovery latency on this
    host: a registered peer goes silent, the master declares it dead
    (heartbeat deadline), and a live worker re-registers at G+1 and
    restores a small digest-verified checkpoint — the detect+restore
    half of a lost-host recovery (the full kill-to-resumed-step number
    comes from tools/multihost_chaos_probe.py). Returns seconds."""
    import tempfile
    import time as _time

    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.distributed import (GenerationMismatch,
                                        MasterClient, MasterServer)

    tmp = tempfile.mkdtemp(prefix="bench_elastic_")
    hb_timeout_ms = 400
    srv = MasterServer(os.path.join(tmp, "snap"), timeout_sec=30,
                       heartbeat_timeout_ms=hb_timeout_ms)
    try:
        with ptpu.scope_guard(ptpu.Scope()), ptpu.unique_name.guard():
            main, startup = ptpu.Program(), ptpu.Program()
            with ptpu.program_guard(main, startup):
                x = layers.data("x", shape=[64])
                h = layers.fc(x, 256)
                loss = layers.mean(layers.fc(h, 1))
            exe = ptpu.Executor()
            exe.run(startup)
            from paddle_tpu import io as pio
            pio.save_checkpoint(exe, os.path.join(tmp, "ckpt"), 1, main)

            # doomed first: a new member joining a non-empty cluster
            # bumps the generation, so registering it second would
            # fence "live" immediately and fake an instant detection
            MasterClient(srv.port).register("doomed")  # never beats
            c = MasterClient(srv.port)
            gen, _ = c.register("live")
            t0 = _time.perf_counter()
            # beat until the master declares "doomed" dead
            while True:
                try:
                    c.heartbeat("live", gen)
                except GenerationMismatch:
                    break
                _time.sleep(0.02)
                if _time.perf_counter() - t0 > 30:
                    raise RuntimeError("master never reaped the "
                                       "silent worker")
            new_gen, _ = c.register("live")
            assert new_gen == gen + 1
            step = pio.load_checkpoint(exe, os.path.join(tmp, "ckpt"),
                                       main)
            assert step == 1
            elapsed = _time.perf_counter() - t0
        # subtract nothing: the number includes the deadline wait — the
        # honest floor of any heartbeat-based detection
        return elapsed, hb_timeout_ms
    finally:
        srv.stop()


def main_multichip(n_devices):
    """The CPU dry run of the sharded paths on VIRTUAL devices
    (``__graft_entry__.dryrun_multichip`` — never a chip run; real chips
    are ``python chip_smoke.py --chips 4``) with a guaranteed tail:
    exactly one JSON line, the success metric or an explicit skipped
    line with the reason. This entry point just maps the outcome to an
    exit code; if even the import fails, print the skipped line here.
    The elastic_resume metric gets the same guarantee."""
    rc = 0
    try:
        import __graft_entry__ as _entry
    except BaseException as e:  # noqa: BLE001 — the line must print
        msg = "%s: %s" % (type(e).__name__, e)
        print(json.dumps({"metric": "multichip_cpu_dryrun",
                          "skipped": True, "reason": msg[:300]}),
              flush=True)
        rc = 1
    else:
        try:
            _entry.dryrun_multichip(n_devices)
        except BaseException:  # noqa: BLE001 — skipped line printed
            rc = 1
    try:
        elapsed, hb_ms = bench_elastic_resume()
        print(json.dumps({
            "metric": "elastic_resume", "value": round(elapsed, 4),
            "unit": "s", "heartbeat_timeout_ms": hb_ms,
            "includes": "death detection + re-register at G+1 + "
                        "digest-verified checkpoint restore"}),
            flush=True)
    except BaseException as e:  # noqa: BLE001 — the line must print
        msg = "%s: %s" % (type(e).__name__, e)
        print(json.dumps({"metric": "elastic_resume", "skipped": True,
                          "reason": msg[:300]}), flush=True)
        rc = 1
    return rc


def main():
    import paddle_tpu as ptpu

    if len(sys.argv) >= 2 and sys.argv[1] == "--multichip":
        n = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        return main_multichip(n)

    from paddle_tpu.core.compile_cache import enable_jax_cache
    enable_jax_cache(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))
    on_accel, peak, stamp = _device_info()
    if on_accel:
        ptpu.config.set_flags(amp="bfloat16", flash_attention=True)
    prev_metrics = load_previous_metrics()
    failed = []

    def emit(line):
        print(json.dumps(dict(annotate_regression(line, prev_metrics),
                              **stamp)), flush=True)

    # A chip belongs to one process and this one holds it: the fleet
    # benches spawn worker processes that each need a device of their
    # own, so on the chip they are not run (their children would fail,
    # hang, or quietly serve from the CPU under an on-chip metric name).
    needs_chip_per_member = {"fleet_p99_under_kill_ms",
                             "model_page_in_ms"} if on_accel else set()

    # every phase fenced, the headline resnet line last: a failure in
    # any must never cost the others their lines
    for name, fn in [
            ("seq2seq_train_tokens_per_sec",
             lambda: bench_seq2seq(on_accel)),
            ("transformer_lm_train_tokens_per_sec",
             lambda: bench_transformer_lm(on_accel, peak)),
            ("resnet_pipeline_overlap",
             lambda: bench_resnet_pipeline(on_accel)),
            ("checkpoint_roundtrips_per_sec",
             lambda: bench_checkpoint(on_accel)),
            ("cold_start_ms",
             lambda: bench_deploy(on_accel)),
            ("decode_tokens_per_sec",
             lambda: bench_generation(on_accel)),
            ("speculative_accept_rate",
             lambda: bench_speculative(on_accel)),
            ("kv_cache_bytes_per_token",
             lambda: bench_paged_kv(on_accel)),
            ("generation_failover_recovery_ms",
             lambda: bench_generation_failover(on_accel)),
            ("tracing_overhead_pct",
             lambda: bench_tracing_overhead(on_accel)),
            ("fleet_p99_under_kill_ms",
             lambda: bench_fleet(on_accel)),
            ("model_page_in_ms",
             lambda: bench_model_paging(on_accel)),
            ("recsys_examples_per_sec",
             lambda: bench_recsys(on_accel)),
            ("slo_detection_latency_ms",
             lambda: bench_slo(on_accel)),
            ("resnet50_train_images_per_sec",
             lambda: bench_resnet(on_accel, peak))]:
        if name in needs_chip_per_member:
            emit({"metric": name, "skipped": True,
                  "reason": "needs one chip per member — not run"})
            continue
        try:
            out = _isolated(fn)
            for line in (out if isinstance(out, list) else [out]):
                emit(line)
        except Exception as e:  # pragma: no cover
            msg = "%s: %s" % (type(e).__name__, e)
            failed.append(name)
            emit({"metric": name, "error": msg[:300]})
    if failed:
        print("bench.py: %d phase(s) failed: %s"
              % (len(failed), ", ".join(failed)), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
