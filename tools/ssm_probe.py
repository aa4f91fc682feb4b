#!/usr/bin/env python3
"""The two halves of the Mamba-2 mixer that are not matrix products with a
weight, alone on the chip at ``granite-4.0-h-small-l10``'s widths, against
the bytes and operations ``benchmarks/architectures/granitemoehybrid.py``
counts for them (PERF.md section 7, PR 33):

* the **decode update** (``ssm_ops.ssm_step``): one step of the recurrence
  over the state pool of one layer, ``--slots`` rows of ``[128, 64, 128]``
  float32, in place (the pool is donated from call to call as the
  executor donates it);
* the **chunked scan** (``ssm_ops.ssd_chunked``): one sequence of each of
  ``--tokens`` rows in chunks of 256, from a zero state.

    python3 tools/ssm_probe.py

Per line: milliseconds a call (the mean of ``--reps`` calls queued back to
back), the bytes and FLOPs counted once, the share of the bytes' floor at
819 GB/s and of the FLOPs' floor at 197 TFLOP/s (counted once: float32
products at the highest precision take six passes), and the largest
difference from the reference's sequential recurrence of the same call.
Fails off the chip (``--rehearse 1`` runs small sizes on the CPU).
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


def _shares(ms, flops, nbytes):
    return {"ms": round(ms, 4), "bytes": int(nbytes), "flops": int(flops),
            "share_of_bytes_floor": round(
                nbytes / HBM_BYTES_PER_S / (ms / 1e3), 4),
            "share_of_flops_floor_counted_once": round(
                flops / PEAK_FLOPS / (ms / 1e3), 4)}


def _draw(rs, cfg, rows):
    """(x [rows, H, P], dt [rows, H], a [H], b, c [rows, N]) at the sizes a
    layer's activations have."""
    import jax.numpy as jnp
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    f32 = lambda v: jnp.asarray(v, jnp.float32)     # noqa: E731
    return (f32(rs.standard_normal((rows, h, p))),
            f32(np.exp(rs.uniform(np.log(1e-3), np.log(1e-1), (rows, h)))),
            f32(-rs.uniform(1, 16, h)),
            f32(rs.standard_normal((rows, n))),
            f32(rs.standard_normal((rows, n))))


def decode_update(cfg, arch, slots, reps, seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    rs = np.random.RandomState(seed % (2 ** 31))
    x, dt, a, b, c = _draw(rs, cfg, slots)
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    pool = jnp.asarray(rs.standard_normal((slots, h, p, n)), jnp.float32)
    # one row's step by the reference, before the pool is given away
    s0 = pool[0]
    want = jnp.exp(dt[0] * a)[:, None, None] * s0 + \
        (dt[0][:, None] * x[0])[:, :, None] * b[0][None, None, :]
    want_y = jnp.einsum("hpn,n->hp", want, c[0],
                        precision=jax.lax.Precision.HIGHEST)
    step = jax.jit(ssm_ops.ssm_step, donate_argnums=0)
    fresh = jnp.ones((slots,), bool)
    pool, y = step(pool, dt, a, x, b, c, fresh)
    diff = max(float(jnp.abs(pool[0] - want).max()),
               float(jnp.abs(y[0] - want_y).max()))
    jax.block_until_ready(pool)
    t0 = time.perf_counter()
    for _ in range(reps):
        pool, y = step(pool, dt, a, x, b, c, fresh)
    jax.block_until_ready((pool, y))
    ms = (time.perf_counter() - t0) / reps * 1e3
    # the probe's pool holds the scan's state alone: the convolution's
    # rows, 3% of what the counting function books a row, are not moved
    flops, _ = arch.ssm_decode_ops_and_bytes(cfg, slots)
    nbytes = 2 * 4 * slots * h * p * n
    say(op="ssm_step", slots=slots, max_diff_from_reference=diff,
        **_shares(ms, flops, nbytes))


def chunked_scan(cfg, arch, tokens, reps, seed):
    import jax
    from paddle_tpu.ops import ssm_ops
    from benchmarks.reference import granitemoehybrid as ref
    chunk = cfg["mamba_chunk_size"]
    for t in tokens:
        rs = np.random.RandomState((seed + t) % (2 ** 31))
        x, dt, a, b, c = _draw(rs, cfg, t)
        q = min(chunk, t)
        scan = jax.jit(lambda *v: ssm_ops.ssd_chunked(*v, q))
        y, last = scan(x, dt, a, b, c)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(reps):
            y, last = scan(x, dt, a, b, c)
        jax.block_until_ready((y, last))
        ms = (time.perf_counter() - t0) / reps * 1e3
        with jax.default_matmul_precision("highest"):
            want_y, want_last = jax.jit(ref.mamba_scan)(
                x, dt, a, b, c, np.zeros(a.shape, np.float32))
        flops, nbytes = arch.ssd_prefill_ops_and_bytes(cfg, t)
        say(op="ssd_chunked", tokens=t, chunk=q,
            max_diff_from_recurrence=float(abs(y - want_y).max()),
            max_state_diff=float(abs(last - want_last).max()),
            largest_output=float(abs(want_y).max()),
            **_shares(ms, flops, nbytes))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=96)
    ap.add_argument("--tokens", default="256,512,1024,2048")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3300000001)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    from benchmarks import architectures
    from benchmarks.harness import lm
    cfg = lm.load_config("granite-4.0-h-small-l10")
    arch = architectures.load(cfg)
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        say(ok=False, why="no TPU backend: %s" % jax.default_backend())
        return 1
    tokens = [int(x) for x in args.tokens.split(",") if x]
    if args.rehearse:
        cfg = arch.tiny(cfg)
        tokens = [8, 32]
    say(device=jax.devices()[0].device_kind, rehearsal=bool(args.rehearse))
    decode_update(cfg, arch, 4 if args.rehearse else args.slots, args.reps,
                  args.seed)
    chunked_scan(cfg, arch, tokens, args.reps, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
