#!/usr/bin/env python3
"""The kernels of the ``kimi_k2`` decode step alone on the chip, at the
published widths, against their bytes and operations
(``benchmarks/architectures/kimi_k2.py`` counts both; PERF.md section 7,
PR 31), and beside them ``trinity-mini``'s walk of the same kernel:

* ``decode_attention_paged`` over a paged latent pool (64 query heads on one
  KV head whose value is the leading 512 lanes of its key's 640-lane row,
  float32, products at the highest precision) at ``--slots`` slots and each
  of ``--contexts`` rows a slot, for each of ``--block-sizes``;
* the same kernel over float32 K and V pools under grouped queries (32
  heads on 4 KV heads of 128) at ``--gq-slots`` slots and each of
  ``--gq-contexts`` rows, for each of ``--gq-block-sizes``, over the whole
  context and over the configuration's sliding window of 2,048;
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
XLA reference of the same call (for a walk, of its first slot). A walk's
line also says how many pages it fetched and nanoseconds a page (of K and V
pools: a pair), and the first two block sizes of a context are solved for
the part of the time that goes with the pages and the part that goes with
the rows (``ns_with_a_page``, ``ms_with_the_rows``: the products of
``streamed_rows`` query rows against every row attended). An empty list
leaves a part out (``--routed-pairs ""``). Fails off the chip
(``--rehearse 1`` runs the interpreter at small sizes).
"""

import argparse
import functools
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


def _walk(label, slots, contexts, block_sizes, reps, interpret, seed,
          heads, kv_heads, width, count, window=None, **kw):
    """One line for each block size and context of a paged walk: ``slots``
    slots of ``ctx`` rows over pools ``width`` lanes wide (``kv_heads`` 1
    and a ``v_width`` in ``kw``: one latent pool; else a K and a V pool),
    ``count(rows)`` its FLOPs and bytes. Then, for each context, the two
    block sizes' lines solved for the part of the time that goes with the
    pages fetched and the part that goes with the rows attended."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_attention as pa
    rs = np.random.RandomState(seed % (2 ** 31))
    kw = dict(kw, num_kv_heads=kv_heads, window=window)
    lines = {}
    for bs in block_sizes:
        for ctx in contexts:
            mb = -(-ctx // bs)
            nb = slots * mb
            pools = [jnp.asarray(rs.standard_normal((nb, bs, width)) * 0.3,
                                 jnp.float32)
                     for _ in range(1 if kw.get("v_width") else 2)]
            tables = jnp.asarray(rs.permutation(nb).reshape(slots, mb),
                                 jnp.int32)
            lens = jnp.full((slots,), ctx, jnp.int32)
            q = jnp.asarray(rs.standard_normal(
                (slots, 1, heads * width // kv_heads)), jnp.float32)

            def call(fn, q, lens, tables, *pools, **more):
                return fn(q, pools[0], pools[1] if len(pools) > 1 else None,
                          lens, tables, heads, **kw, **more)
            kernel = jax.jit(functools.partial(
                call, pa.decode_attention_paged, interpret=interpret))
            ms, out = _time(kernel, (q, lens, tables, *pools), reps)
            # the XLA reference gathers every head's rows: one slot of it
            want = jax.jit(functools.partial(
                call, pa._decode_paged_reference))(
                    q[:1], lens[:1], tables[:1], *pools)
            # what the walk reads: the rows the query can see, and the
            # pages that hold them
            seen = min(ctx, window or ctx)
            pages = slots * (mb - (ctx - seen) // bs)
            flops, nbytes = count(slots * seen)
            lines[bs, ctx] = (ms, pages)
            say(kernel="decode_attention_paged", pool=label, slots=slots,
                context=ctx, window=window, block_size=bs,
                page_bytes=bs * width * 4, pages=pages,
                ns_a_page=round(ms * 1e6 / pages, 2),
                max_diff_from_reference=float(jnp.abs(out[:1] - want).max()),
                **_shares(ms, flops, nbytes))
    if len(block_sizes) < 2:
        return
    a, b = block_sizes[:2]
    for ctx in contexts:
        (ms_a, pages_a), (ms_b, pages_b) = lines[a, ctx], lines[b, ctx]
        if pages_a == pages_b:
            continue
        # ms = pages x (a page's part) + (the rows' part), the rows' part
        # the same at both block sizes
        page_ns = (ms_a - ms_b) * 1e6 / (pages_a - pages_b)
        say(kernel="decode_attention_paged", pool=label, context=ctx,
            window=window, block_sizes=[a, b], streamed_rows=heads,
            ns_with_a_page=round(page_ns, 2),
            ms_with_the_rows=round(ms_a - page_ns * pages_a / 1e6, 4))


def latent_decode(cfg, arch, slots, contexts, block_sizes, reps, interpret,
                  seed):
    """The latent walk: 64 heads on one pool whose row is key and value."""
    _walk("latent float32", slots, contexts, block_sizes, reps, interpret,
          seed, cfg["num_attention_heads"], 1, arch.row_width(cfg),
          lambda rows: arch.latent_decode_ops_and_bytes(cfg, rows, 4),
          v_width=cfg["kv_lora_rank"], scale=0.1)


def grouped_decode(cfg, slots, contexts, block_sizes, reps, interpret, seed):
    """The grouped-query walk of ``trinity-mini``: 32 heads on 4 KV heads
    of 128, a K and a V pool, over the whole context and over the
    configuration's sliding window."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width = kv_heads * cfg["head_dim"]
    for window in (None, cfg["sliding_window"]):
        _walk("grouped-query float32", slots, contexts, block_sizes, reps,
              interpret, seed, heads, kv_heads, width,
              lambda rows: (4 * heads * cfg["head_dim"] * rows,
                            2 * width * 4 * rows), window=window)


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
    ap.add_argument("--gq-slots", type=int, default=64)
    ap.add_argument("--gq-contexts", default="1024,2048,4096")
    ap.add_argument("--gq-block-sizes", default="16,32")
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
    gq_cfg = lm.load_config("trinity-mini-l5")
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        say(ok=False, why="no TPU backend: %s" % jax.default_backend())
        return 1
    if args.rehearse:
        cfg = arch.tiny(cfg)
        gq_cfg = architectures.load(gq_cfg).tiny(gq_cfg)
    say(device=jax.devices()[0].device_kind, rehearsal=bool(args.rehearse))
    ints = lambda s: [int(x) for x in s.split(",") if x]     # noqa: E731
    before = kernel_path.counts()
    latent_decode(cfg, arch, args.slots, ints(args.contexts),
                  ints(args.block_sizes), args.reps, not on_chip, args.seed)
    grouped_decode(gq_cfg, args.gq_slots, ints(args.gq_contexts),
                   ints(args.gq_block_sizes), args.reps, not on_chip,
                   args.seed)
    grouped_matmul(cfg, arch, ints(args.routed_pairs), args.reps, args.seed)
    after = kernel_path.counts()
    say(kernel_paths={k: {p: n - before.get(k, {}).get(p, 0)
                          for p, n in v.items()} for k, v in after.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
