"""ResNet-50 train-step MFU probe: time + XLA cost analysis per config.

Usage (on the TPU chip):
  python tools/mfu_probe.py --batch 256 --amp bfloat16
  python tools/mfu_probe.py --batch 512 --amp bfloat16 --recompute
  python tools/mfu_probe.py --batch 256 --amp bfloat16 --top-hlo 25

Prints one JSON line: ms/step (host-fetch-synced window), img/s,
MFU vs the chip's bf16 peak, and the compiled step's cost analysis
(flops, bytes accessed -> HBM roofline ms at 819 GB/s). --top-hlo also
ranks the optimized HLO's largest-output instructions, which is where
the bytes/step actually go.
"""

import argparse
import json
import re
import sys
import time

import numpy as np

sys.path.insert(0, ".")

_PEAK = {"TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v4": 275e12,
         "TPU v5p": 459e12, "TPU v6 lite": 918e12}
_HBM = {"TPU v5 lite": 819e9, "TPU v5e": 819e9, "TPU v4": 1228e9,
        "TPU v5p": 2765e9, "TPU v6 lite": 1640e9}

_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
             "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1}


def build_step(batch, depth, recompute, steps_img=224):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models import resnet

    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.program_guard(main, startup):
        img = layers.data("img", shape=[3, steps_img, steps_img])
        label = layers.data("label", shape=[1], dtype="int64")
        loss, acc, _ = resnet.resnet_imagenet(img, label, depth=depth,
                                              recompute=recompute)
        opt = ptpu.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        opt.minimize(loss, startup_program=startup)

    exe = ptpu.Executor()
    exe.run(startup)
    rs = np.random.RandomState(0)
    feed = {"img": jax.device_put(jnp.asarray(
                rs.randn(batch, 3, steps_img, steps_img), jnp.float32)),
            "label": jax.device_put(jnp.asarray(
                rs.randint(0, 1000, (batch, 1)), jnp.int32))}
    return exe, main, startup, loss, feed


def cost_analysis(exe, main, loss, feed):
    """AOT-compile the step via Executor.lower — the EXACT run-path
    module (donated state outputs included, nothing DCE'd)."""
    compiled = exe.lower(main, feed=feed, fetch_list=[loss]).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return compiled, ca


def top_hlo(compiled, n):
    """Rank optimized-HLO ENTRY instructions by output bytes (a proxy
    for HBM writes; instructions inside fusion bodies never materialize
    and are excluded by slicing to the ENTRY computation)."""
    txt = compiled.as_text()
    i = txt.find("\nENTRY ")
    if i >= 0:
        txt = txt[i:]
        j = txt.find("\n}")
        if j >= 0:
            txt = txt[:j]
    rows = []
    # e.g.  %fusion.123 = bf16[256,64,112,112]{...} fusion(...), kind=kOutput
    pat = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(?:\()?([a-z0-9]+)\[([\d,]*)\]"
        r"[^=]*?\s(\w+)\(", re.M)
    for m in pat.finditer(txt):
        name, dt, dims, opkind = m.groups()
        if opkind in ("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast"):
            continue
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        b = numel * _DT_BYTES.get(dt, 4)
        rows.append((b, name, "%s[%s]" % (dt, dims), opkind))
    rows.sort(reverse=True)
    agg = {}
    for b, name, shape, opkind in rows:
        agg[opkind] = agg.get(opkind, 0) + b
    return rows[:n], sorted(agg.items(), key=lambda kv: -kv[1])[:12]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--amp", default="bfloat16")
    ap.add_argument("--recompute", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--top-hlo", type=int, default=0)
    ap.add_argument("--no-time", action="store_true",
                    help="cost analysis only (skip the timed window)")
    args = ap.parse_args()

    import jax
    import paddle_tpu as ptpu
    if args.amp and args.amp != "none":
        ptpu.config.set_flags(amp=args.amp)

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "")
    peak, hbm = _PEAK.get(kind), _HBM.get(kind)

    exe, main_p, startup, loss, feed = build_step(args.batch, args.depth,
                                                  args.recompute)
    out = {"batch": args.batch, "depth": args.depth, "amp": args.amp,
           "recompute": bool(args.recompute), "device": kind}

    compiled, ca = cost_analysis(exe, main_p, loss, feed)
    if ca:
        fl = ca.get("flops", 0.0)
        by = ca.get("bytes accessed", 0.0)
        out["ca_tflops_per_step"] = round(fl / 1e12, 2)
        out["ca_gb_per_step"] = round(by / 1e9, 2)
        if hbm:
            out["roofline_ms"] = round(by / hbm * 1e3, 1)

    if not args.no_time:
        for _ in range(max(args.warmup, 1)):
            r = exe.run(main_p, feed=feed, fetch_list=[loss],
                        return_numpy=False)
        np.asarray(r[0])
        t0 = time.perf_counter()
        for _ in range(args.steps):
            r = exe.run(main_p, feed=feed, fetch_list=[loss],
                        return_numpy=False)
        out["loss"] = round(float(np.asarray(r[0])), 4)
        dt = (time.perf_counter() - t0) / args.steps
        out["ms_per_step"] = round(dt * 1e3, 1)
        out["img_per_sec"] = round(args.batch / dt, 1)
        if peak and args.depth == 50:
            # 12.3 GFLOP/img (3x fwd) is ResNet-50-specific; other
            # depths report time/throughput only
            out["mfu"] = round(args.batch / dt * 12.3e9 / peak, 4)

    print(json.dumps(out), flush=True)

    if args.top_hlo:
        rows, agg = top_hlo(compiled, args.top_hlo)
        print("-- top HLO outputs by bytes --")
        for b, name, shape, opkind in rows:
            print("%8.1f MB  %-12s %-28s %s" % (b / 1e6, opkind, shape,
                                                name))
        print("-- output bytes by HLO kind --")
        for k, v in agg:
            print("%8.2f GB  %s" % (v / 1e9, k))


if __name__ == "__main__":
    main()
