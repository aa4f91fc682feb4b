#!/usr/bin/env python3
"""The kernels of the ``kimi_k2`` decode step alone on the chip, at the
published widths, against their bytes and operations
(``benchmarks/architectures/kimi_k2.py`` counts both; PERF.md section 7,
PR 31):

* ``decode_attention_paged`` over a paged latent pool (64 query heads on one
  KV head whose value is the leading 512 lanes of its key's 640-lane row,
  float32, products at the highest precision) at ``--slots`` slots and each
  of ``--contexts`` rows a slot, for each of ``--block-sizes``;
* the held experts' three grouped matmuls (``moe_ops._expert_rows``: 12
  experts of 7168 x 2048 in bfloat16, float32 rows in three pieces) for
  each of ``--routed-pairs``: that many pairs are drawn uniformly over 384
  experts and the ones that fall on the 12 held are computed, in passes of
  ``moe_ops.SHARE_ROWS`` as the op takes them.

    python3 tools/mla_probe.py

Per line: milliseconds a call (the mean of ``--reps`` calls queued back to
back), the bytes and FLOPs counted once, the share of the bytes' floor at
819 GB/s and of the FLOPs' floor at 197 TFLOP/s (counted once: exact
products take three to six passes), and the largest difference from the
XLA reference of the same call (for the latent decode, of its first
slot). Fails off the chip (``--rehearse 1`` runs
the interpreter at small sizes).
"""

import argparse
import json
import os
import sys
import time

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


def _shares(ms, flops, nbytes):
    return {"ms": round(ms, 4), "bytes": int(nbytes), "flops": int(flops),
            "share_of_bytes_floor": round(
                nbytes / HBM_BYTES_PER_S / (ms / 1e3), 4),
            "share_of_flops_floor_counted_once": round(
                flops / PEAK_FLOPS / (ms / 1e3), 4)}


def latent_decode(cfg, arch, slots, contexts, block_sizes, reps, interpret,
                  seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_attention as pa
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    width = arch.row_width(cfg)
    scale = 0.1
    rs = np.random.RandomState(seed % (2 ** 31))
    for bs in block_sizes:
        for ctx in contexts:
            mb = -(-ctx // bs)
            nb = slots * mb
            pool = jnp.asarray(rs.standard_normal((nb, bs, width)) * 0.3,
                               jnp.float32)
            tables = jnp.asarray(rs.permutation(nb).reshape(slots, mb),
                                 jnp.int32)
            lens = jnp.full((slots,), ctx, jnp.int32)
            q = jnp.asarray(rs.standard_normal((slots, 1, nh * width)),
                            jnp.float32)
            kw = dict(num_kv_heads=1, v_width=rank, scale=scale)
            kernel = jax.jit(lambda q, p, l, t: pa.decode_attention_paged(
                q, p, None, l, t, nh, interpret=interpret, **kw))
            ms, out = _time(kernel, (q, pool, lens, tables), reps)
            # the XLA reference gathers every head's rows: one slot of it
            want = jax.jit(lambda q, p, l, t: pa._decode_paged_reference(
                q, p, None, l, t, nh, **kw))(q[:1], pool, lens[:1],
                                             tables[:1])
            flops, nbytes = arch.latent_decode_ops_and_bytes(
                cfg, slots * ctx, 4)
            say(kernel="decode_attention_paged", pool="latent float32",
                slots=slots, context=ctx, block_size=bs,
                page_bytes=bs * width * 4,
                max_diff_from_reference=float(jnp.abs(out[:1] - want).max()),
                **_shares(ms, flops, nbytes))


def grouped_matmul(cfg, arch, routed_pairs, reps, seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops, pallas_moe
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, published = cfg["n_routed_experts"], \
        cfg["n_routed_experts_published"]
    key = jax.random.PRNGKey(seed % (2 ** 31))
    kg, ku, kd, kx = jax.random.split(key, 4)
    wg = (jax.random.normal(kg, (held, d, f)) * 0.02).astype(jnp.bfloat16)
    wu = (jax.random.normal(ku, (held, d, f)) * 0.02).astype(jnp.bfloat16)
    wd = (jax.random.normal(kd, (held, f, d)) * 0.02).astype(jnp.bfloat16)
    rs = np.random.RandomState(seed % (2 ** 31))
    for pairs in routed_pairs:
        experts = rs.randint(0, published, pairs)
        counts = np.bincount(experts[experts < held], minlength=held)
        total = int(counts.sum())
        rows = min(pairs, moe_ops.SHARE_ROWS,
                   pallas_moe.MAX_PAIRS_PER_EXPERT * held)
        passes = max(1, -(-total // rows))
        xs = jax.random.normal(kx, (passes * rows, d), jnp.float32)
        end = np.cumsum(counts)
        start = end - counts
        per_pass = [np.clip(np.minimum(end, (i + 1) * rows)
                            - np.maximum(start, i * rows), 0, rows)
                    for i in range(passes)]
        per_pass = jnp.asarray(np.stack(per_pass), jnp.int32)

        def call(xs, wg, wu, wd, per_pass, admit):
            if not admit:
                pallas_moe_admits, pallas_moe.admits = \
                    pallas_moe.admits, lambda *a: False
            try:
                return jnp.concatenate([
                    moe_ops._expert_rows(xs[i * rows:(i + 1) * rows], wg, wu,
                                         wd, per_pass[i])
                    for i in range(passes)])
            finally:
                if not admit:
                    pallas_moe.admits = pallas_moe_admits
        kernel = jax.jit(lambda *a: call(*a, True))
        ms, out = _time(kernel, (xs, wg, wu, wd, per_pass), reps)
        want = jax.jit(lambda *a: call(*a, False))(xs, wg, wu, wd, per_pass)
        touched = int((counts > 0).sum())
        flops, nbytes = arch.grouped_matmul_ops_and_bytes(cfg, total, touched)
        say(kernel="moe_grouped_matmul", routed_pairs=pairs,
            held_pairs=total, experts_touched=touched, passes=passes,
            rows_a_pass=rows,
            max_diff_from_ragged_dot=float(jnp.abs(out - want).max()),
            **_shares(ms, flops, nbytes))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--contexts", default="512,3300,8000")
    ap.add_argument("--block-sizes", default="32,16")
    ap.add_argument("--routed-pairs", default="256,32768")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3100000001)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    from benchmarks import architectures
    from benchmarks.harness import lm
    from paddle_tpu.ops import kernel_path
    cfg = lm.load_config("kimi-k2.7-code-l6")
    arch = architectures.load(cfg)
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        say(ok=False, why="no TPU backend: %s" % jax.default_backend())
        return 1
    if args.rehearse:
        cfg = arch.tiny(cfg)
    say(device=jax.devices()[0].device_kind, rehearsal=bool(args.rehearse))
    ints = lambda s: [int(x) for x in s.split(",") if x]     # noqa: E731
    before = kernel_path.counts()
    latent_decode(cfg, arch, args.slots, ints(args.contexts),
                  ints(args.block_sizes), args.reps, not on_chip, args.seed)
    grouped_matmul(cfg, arch, ints(args.routed_pairs), args.reps, args.seed)
    after = kernel_path.counts()
    say(kernel_paths={k: {p: n - before.get(k, {}).get(p, 0)
                          for p, n in v.items()} for k, v in after.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
