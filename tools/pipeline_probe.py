"""Input-pipeline overlap probe: find where the step time goes in
bench_resnet_pipeline and quantify the host's H2D variance.

Instruments every stage of the staged path per batch:
  reader/feeder assembly -> arena memcpy -> device_put dispatch ->
  transfer completion (a scalar fetch through the array) ->
  consumer step.
Prints medians + spreads so H2D drift is visible instead of silently
corrupting the overlap ratio.
"""

import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def true_sync(x):
    """Force H2D/compute completion THROUGH the data: fetch a scalar
    computed from the array."""
    return float(jax.device_get(jnp.sum(x[(0,) * (x.ndim - 1)][:1])))


def main():
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models import resnet
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.reader.staging import StagedReader

    on_accel = jax.devices()[0].platform != "cpu"
    batch = 8 if on_accel else 4
    res = 224 if on_accel else 32
    steps = 12 if on_accel else 4

    ptpu.config.set_flags(amp="bfloat16")
    main_prog, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main_prog, startup):
        img = layers.data("img", shape=[3, res, res])
        label = layers.data("label", shape=[1], dtype="int64")
        if on_accel:
            loss, acc, _ = resnet.resnet_imagenet(img, label, depth=50)
        else:
            loss, acc, _ = resnet.resnet_cifar10(img, label, depth=20)
        opt = ptpu.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        opt.minimize(loss, startup_program=startup)

    rs = np.random.RandomState(0)
    host_batches = [
        {"img": rs.randn(batch, 3, res, res).astype("float32"),
         "label": rs.randint(0, 1000, (batch, 1)).astype("int64")}
        for _ in range(3)]
    nbytes = sum(v.nbytes for v in host_batches[0].values())

    tr = Trainer(loss, main_program=main_prog, startup_program=startup,
                 async_metrics=True)
    tr.startup()

    # -- compute-only reference (batch resident in HBM) ---------------
    dev_feed = {k: jax.device_put(v) for k, v in host_batches[0].items()}
    for v in dev_feed.values():
        true_sync(v)
    m = tr._train_feed(dev_feed)
    np.asarray(m["loss"])  # compile
    ts = []
    for _ in range(steps):
        t0 = time.perf_counter()
        m = tr._train_feed(dev_feed)
        np.asarray(m["loss"])  # per-step sync: honest per-step time
        ts.append((time.perf_counter() - t0) * 1e3)
    # async chain (bench's convention): one sync closes the window
    t0 = time.perf_counter()
    for _ in range(steps):
        m = tr._train_feed(dev_feed)
    np.asarray(m["loss"])
    compute_async_ms = (time.perf_counter() - t0) / steps * 1e3
    print("compute/step: median-synced %.1f ms, async-chain %.1f ms"
          % (np.median(ts), compute_async_ms), flush=True)

    # -- H2D: dispatch-only vs true-sync, and drift -------------------
    for mode in ("block_until_ready", "true_sync"):
        times = []
        for rep in range(6):
            hb = host_batches[rep % len(host_batches)]
            t0 = time.perf_counter()
            arrs = [jax.device_put(v) for v in hb.values()]
            if mode == "block_until_ready":
                jax.block_until_ready(arrs)
            else:
                for a in arrs:
                    true_sync(a)
            times.append((time.perf_counter() - t0) * 1e3)
        times = np.array(times)
        print("h2d %-17s: median %.0f ms  min %.0f  max %.0f  "
              "(%.1f MB/s median)" % (mode, np.median(times),
                                      times.min(), times.max(),
                                      nbytes / np.median(times) / 1e3),
              flush=True)

    # -- instrumented staged pipeline ---------------------------------
    phase = {"assembly": [], "dput": [], "transfer": []}

    class Instrumented(StagedReader):
        def _stage_feed(self, feed):
            t0 = time.perf_counter()
            staged, ptrs = {}, []
            for name, value in feed.items():
                arr = np.asarray(value)
                if self._arena is not None and arr.nbytes > 0:
                    dst, ptr = self._arena.alloc_array(
                        arr.shape, arr.dtype, arr.nbytes)
                else:
                    dst, ptr = None, None
                if dst is None:
                    dst = np.array(arr, copy=True)
                else:
                    np.copyto(dst, arr)
                    ptrs.append(ptr)
                staged[name] = dst
            t1 = time.perf_counter()
            if self.device_put:
                staged = {k: jax.device_put(v)
                          for k, v in staged.items()}
            t2 = time.perf_counter()
            phase["assembly"].append((t1 - t0) * 1e3)
            phase["dput"].append((t2 - t1) * 1e3)
            return staged, ptrs

    def reader():
        for i in range(steps):
            yield dict(host_batches[i % len(host_batches)])

    staged = Instrumented(reader, depth=8)
    step_times = []
    t_pass0 = time.perf_counter()
    gen = staged()
    prev = time.perf_counter()
    first_wait = None
    for i, feed in enumerate(gen):
        t_got = time.perf_counter()
        m = tr._train_feed(feed)
        if i == 0:
            first_wait = (t_got - prev) * 1e3
        step_times.append((time.perf_counter() - prev) * 1e3)
        prev = time.perf_counter()
    np.asarray(m["loss"])
    total_ms = (time.perf_counter() - t_pass0) * 1e3
    staged.close()

    st = np.array(step_times[1:])  # drop the cold first step
    print("staged pass: total %.0f ms over %d steps; first-batch wait "
          "%.0f ms" % (total_ms, steps, first_wait), flush=True)
    print("per-step (warm): median %.0f ms  min %.0f  max %.0f"
          % (np.median(st), st.min(), st.max()), flush=True)
    print("staging thread per batch: assembly median %.1f ms, "
          "device_put dispatch median %.1f ms"
          % (np.median(phase["assembly"]), np.median(phase["dput"])),
          flush=True)

    # in-window H2D: immediately re-measure with true sync
    t0 = time.perf_counter()
    arrs = [jax.device_put(v) for v in host_batches[1].values()]
    for a in arrs:
        true_sync(a)
    print("in-window h2d true-sync: %.0f ms"
          % ((time.perf_counter() - t0) * 1e3), flush=True)

    # -- narrow wire: bytes/batch + transfer dispatches ----------------
    # Same batches in wire form (uint8 images, int32 labels) through
    # the packed single-copy path vs the legacy per-array f32 path,
    # accounted by the staging counters (ISSUE 4 tentpole).
    from paddle_tpu.reader import staging as _staging

    wire_batches = [
        {"img": (hb["img"] * 255).clip(0, 255).astype("uint8"),
         "label": hb["label"].astype("int32")}
        for hb in host_batches]

    def run_counted(batches, pack):
        def rd():
            for i in range(steps):
                yield dict(batches[i % len(batches)])
        prev = ptpu.config.get_flag("telemetry")
        ptpu.config.set_flags(telemetry=True)
        c0 = (_staging._TRANSFERS.value, _staging._WIRE_BYTES.value,
              _staging._LEGACY_BYTES.value)
        sr = _staging.StagedReader(rd, depth=4, pack=pack,
                                   program=main_prog)
        t0 = time.perf_counter()
        for feed in sr():
            pass  # transfer cost only; no consumer step
        dt = (time.perf_counter() - t0) / steps * 1e3
        sr.close()
        ptpu.config.set_flags(telemetry=prev)
        return (_staging._TRANSFERS.value - c0[0],
                _staging._WIRE_BYTES.value - c0[1],
                _staging._LEGACY_BYTES.value - c0[2], dt)

    # declare the wire program vars so legacy-bytes accounting sees the
    # widened target dtypes
    wire_prog = ptpu.Program()
    with ptpu.program_guard(wire_prog, ptpu.Program()):
        layers.data("img", shape=[3, res, res], wire_dtype="uint8",
                    scale=1.0 / 255.0)
        layers.data("label", shape=[1], dtype="int64",
                    wire_dtype="int32")
    main_prog = wire_prog

    n_leg, b_leg, _, ms_leg = run_counted(host_batches, pack=False)
    n_wire, b_wire, b_as_legacy, ms_wire = run_counted(wire_batches,
                                                       pack=True)
    print("legacy feed : %5.2f MB/batch, %d device_put dispatches over "
          "%d batches (%.1f ms/batch staged)"
          % (b_leg / steps / 1e6, n_leg, steps, ms_leg), flush=True)
    print("wire  feed  : %5.2f MB/batch, %d device_put dispatches over "
          "%d batches (%.1f ms/batch staged)"
          % (b_wire / steps / 1e6, n_wire, steps, ms_wire), flush=True)
    print("wire cut    : %.2fx fewer bytes, %dx fewer dispatches"
          % (b_as_legacy / max(b_wire, 1), max(n_leg // max(n_wire, 1), 1)),
          flush=True)


if __name__ == "__main__":
    main()
