#!/usr/bin/env python3
"""EVA attention's two forms alone on the chip, at the published widths
(32 heads of 128, window 2,048, chunks of 16, bfloat16 operands and pools),
against their bytes and operations (``benchmarks/architectures/evabyte.py``
counts both; PERF.md section 7, PR 42):

* the decode step's attention (``eva_ops``' two walks of
  ``decode_attention_paged`` and their merge) at ``--slots`` slots and each
  of ``--contexts`` positions a slot: the window pool's rows from the
  window's first position on, a summary for every chunk before it;
* the prefill form (``eva_summaries`` + ``eva_attention``) over a prompt of
  each of ``--prompts`` positions, in both of its forms in one call: the
  XLA form (``flash_attention`` off) and the flash form (the windows
  through the flash forward), each beside the path ``flash_attention``
  counted, and the largest difference between the two results
  (``--profile 1``: and where the flash form's time goes, operation by
  operation of a traced call);
* the pooling alone (``eva_summaries``, which the prefill row times with
  the attention) over the same prompts and in the decode step's form (the
  newest block of each of ``--slots`` slots through a table), its bytes
  counted as one read of the bfloat16 rows and one write of the summaries.

    python3 tools/eva_probe.py

Per line: milliseconds a call (the mean of ``--reps`` calls queued back to
back), the bytes and FLOPs counted, the share of the bytes' floor at 819
GB/s and of the FLOPs' floor at 197 TFLOP/s, and for the decode form the
largest difference from the XLA gather of the same call. Fails off the chip
(``--rehearse 1`` runs the interpreter at small sizes).
"""

import argparse
import functools
import json
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

HBM_BYTES_PER_S, PEAK_FLOPS = 819e9, 197e12     # TPU v5e (Google Cloud docs)


def say(**kw):
    print(json.dumps(kw), flush=True)


def _time(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3, out


def _profile(fn, args, reps=5, top=16):
    """The device's operations over ``reps`` traced calls of ``fn``, by
    self time a call, largest first (the benchmark's own reduction:
    ``benchmarks/harness/trace_reduce.py``)."""
    import shutil
    import jax
    from benchmarks.harness import trace_reduce
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".bench_out", "eva_probe", "trace")
    shutil.rmtree(out, ignore_errors=True)
    jax.block_until_ready(fn(*args))
    with jax.profiler.trace(out):
        for _ in range(reps):
            last = fn(*args)
        jax.block_until_ready(last)
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(out))
    ops = trace_reduce._device_ops(
        trace, rehearsal=jax.default_backend() != "tpu")[0]
    by_name = {}
    for name, _, ns in trace_reduce.self_times(
            trace_reduce._clip(ops, 0, 1 << 62)):
        by_name[name] = by_name.get(name, 0) + ns
    return [[name, round(ns / reps / 1e6, 4)] for name, ns in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def _shares(ms, flops, nbytes):
    return {"ms": round(ms, 4), "bytes": int(nbytes), "flops": int(flops),
            "share_of_bytes_floor": round(
                nbytes / HBM_BYTES_PER_S / (ms / 1e3), 4),
            "share_of_flops_floor": round(
                flops / PEAK_FLOPS / (ms / 1e3), 4)}


def _op(op_type, attrs, **inputs):
    """An op of ``eva_ops`` called as the executor calls it."""
    from paddle_tpu.core.registry import ExecContext, get_op_def
    op = SimpleNamespace(attrs=attrs, type=op_type)
    return get_op_def(op_type).compute(
        ExecContext(op, {slot: [v] for slot, v in inputs.items()}))


def decode(cfg, arch, slots, contexts, reps, seed):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    nh, d = cfg["num_attention_heads"], cfg["hidden_size"]
    w, c = cfg["window_size"], cfg["chunk_size"]
    attrs = {"num_heads": nh, "window": w, "chunk": c}
    rs = np.random.RandomState(seed % (2 ** 31))
    bf16 = jnp.bfloat16
    for ctx in contexts:
        pos = np.full(slots, ctx - 1, np.int32)
        edge = (ctx - 1) // w * w
        mb = -(-ctx // c)
        live = -(-(ctx - edge) // c)            # the window's blocks
        nb, nbc = slots * live, slots * max(1, -(-(edge // c) // c))
        wtab = np.full((slots, mb), nb, np.int32)
        wtab[:, edge // c:edge // c + live] = \
            rs.permutation(nb).reshape(slots, live)
        ctab = np.full((slots, mb), nbc, np.int32)
        ctab[:, :nbc // slots] = rs.permutation(nbc).reshape(slots, -1)
        pools = [jnp.asarray(rs.standard_normal((n, c, d)) * 0.8, bf16)
                 for n in (nb, nb, nbc, nbc)]
        q = jnp.asarray(rs.standard_normal((slots, 1, d)) * 0.8, bf16)

        def call(flash, q, ck, cv, sk, sv, pos, wtab, ctab):
            # the flag is read when the op is traced: a trace a path
            ptpu.config.set_flags(flash_attention=flash)
            return _op("eva_attention_decode_paged", attrs, Q=q, CacheK=ck,
                       CacheV=cv, ChunkK=sk, ChunkV=sv, Pos=pos, Table=wtab,
                       ChunkTable=ctab)["Out"]
        args = (q, *pools, jnp.asarray(pos), jnp.asarray(wtab),
                jnp.asarray(ctab))
        ms, out = _time(jax.jit(functools.partial(call, True)), args, reps)
        want = jax.jit(functools.partial(call, False))(*args)
        ptpu.config.set_flags(flash_attention=True)
        ops, nbytes = arch.eva_decode_ops_and_bytes(
            cfg, slots * (ctx - edge), slots * (edge // c), 2)
        say(what="eva_decode", slots=slots, context=ctx,
            window_rows=ctx - edge, summaries=edge // c,
            max_abs_diff_from_gather=float(jnp.max(jnp.abs(out - want))),
            **_shares(ms, ops, nbytes))


def prefill(cfg, arch, prompts, reps, seed, profile):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from benchmarks.harness import common
    from paddle_tpu.ops import kernel_path
    nh, d = cfg["num_attention_heads"], cfg["hidden_size"]
    w, c = cfg["window_size"], cfg["chunk_size"]
    rs = np.random.RandomState(seed % (2 ** 31))
    bf16 = jnp.bfloat16
    for t in prompts:
        q, k, v = (jnp.asarray(rs.standard_normal((1, t, d)) * 0.8, bf16)
                   for _ in range(3))
        mu, phi = (jnp.asarray(rs.standard_normal(d) * 0.05, bf16)
                   for _ in range(2))

        def call(flash, q, k, v, mu, phi):
            # the flag is read when the op is traced: a trace a form
            ptpu.config.set_flags(flash_attention=flash)
            s = _op("eva_summaries", {"num_heads": nh, "chunk": c}, K=k,
                    V=v, Mu=mu, Phi=phi)
            return _op("eva_attention",
                       {"num_heads": nh, "window": w, "chunk": c}, Q=q, K=k,
                       V=v, KBar=s["KBar"], VBar=s["VBar"])["Out"]
        ops, nbytes = arch.eva_prefill_ops_and_bytes(cfg, t, 2)
        outs = {}
        for form, flash in (("xla", False), ("flash", True)):
            before = kernel_path.counts()
            ms, outs[form] = _time(jax.jit(functools.partial(call, flash)),
                                   (q, k, v, mu, phi), reps)
            say(what="eva_prefill", form=form, tokens=t,
                flash_attention=common.kernel_paths_since(before).get(
                    "flash_attention", {}), **_shares(ms, ops, nbytes))
        if profile:
            say(what="eva_prefill_device_ops_ms", form="flash", tokens=t,
                ops=_profile(jax.jit(functools.partial(call, True)),
                             (q, k, v, mu, phi)))
        ptpu.config.set_flags(flash_attention=True)
        say(what="eva_prefill_forms", tokens=t, max_abs_diff=float(jnp.max(
            jnp.abs(outs["flash"] - outs["xla"]))),
            max_abs=float(jnp.max(jnp.abs(outs["xla"]))))


def pooling(cfg, slots, prompts, reps, seed, profile):
    """``eva_summaries`` alone: a prompt's rows [1, T, H*D] -> a summary a
    chunk, and the decode step's form (CacheK / CacheV, Pos, Table -> the
    block that holds each slot's newest row). Bytes: K and V read once, the
    two summaries written once, at 2 bytes; FLOPs: two scores, two
    softmaxes' sums and two weighted sums a lane, counted as 8 a lane."""
    import jax
    import jax.numpy as jnp
    nh, d, c = cfg["num_attention_heads"], cfg["hidden_size"], \
        cfg["chunk_size"]
    attrs = {"num_heads": nh, "chunk": c}
    rs = np.random.RandomState(seed % (2 ** 31))
    bf16 = jnp.bfloat16
    mu, phi = (jnp.asarray(rs.standard_normal(d) * 0.05, bf16)
               for _ in range(2))

    def counted(rows):
        return 8 * rows * d, 2 * 2 * (rows + rows // c) * d

    def summaries(*slots):
        def call(*arrays):
            s = _op("eva_summaries", attrs, Mu=mu, Phi=phi,
                    **dict(zip(slots, arrays)))
            return s["KBar"], s["VBar"]
        return jax.jit(call)

    def report(fn, args, rows, **what):
        ms, _ = _time(fn, args, reps)
        say(what="eva_pooling", **what, **_shares(ms, *counted(rows)))
        if profile:
            say(what="eva_pooling_device_ops_ms", **what,
                ops=_profile(fn, args))
    for t in prompts:
        k, v = (jnp.asarray(rs.standard_normal((1, t, d)) * 0.8, bf16)
                for _ in range(2))
        report(summaries("K", "V"), (k, v), t, form="rows", tokens=t)
    nb = slots * 8
    ck, cv = (jnp.asarray(rs.standard_normal((nb, c, d)) * 0.8, bf16)
              for _ in range(2))
    table = jnp.asarray(rs.permutation(nb).reshape(slots, 8), jnp.int32)
    pos = jnp.asarray(rs.randint(0, 8 * c, slots), jnp.int32)
    report(summaries("CacheK", "CacheV", "Pos", "Table"),
           (ck, cv, pos, table), slots * c, form="blocks", slots=slots)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="evabyte-6.5b-l8")
    ap.add_argument("--slots", type=int, default=0,
                    help="default: the configuration's")
    ap.add_argument("--contexts", default="2000,5500,12000")
    ap.add_argument("--prompts", default="2048,4096,8192")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=4200000021)
    ap.add_argument("--profile", type=int, default=0,
                    help="1: also trace the prefill's flash form and the "
                    "pooling and print their device operations by time")
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    from benchmarks import architectures
    from benchmarks.harness import lm
    cfg = lm.load_config(args.config)
    arch = architectures.load(cfg)
    if args.rehearse:
        cfg = arch.tiny(cfg)
    elif jax.default_backend() != "tpu":
        raise SystemExit("the probe times kernels: it needs the chip "
                         "(--rehearse 1 runs the interpreter)")
    slots = args.slots or cfg["deployment"]["serving"]["slots"]
    say(what="device", kind=jax.devices()[0].device_kind,
        config=cfg["name"], slots=slots)
    decode(cfg, arch, slots, [int(x) for x in args.contexts.split(",")],
           args.reps, args.seed)
    prompts = [int(x) for x in args.prompts.split(",")]
    prefill(cfg, arch, prompts, args.reps, args.seed, args.profile)
    pooling(cfg, slots, prompts, args.reps, args.seed, args.profile)
    return 0


if __name__ == "__main__":
    sys.exit(main())
