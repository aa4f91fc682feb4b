#!/usr/bin/env python3
"""The experts' three grouped matmuls of ``moe_ffn`` alone on the chip, at
Trinity-Mini's widths (128 experts of 2048 x 1024 in bfloat16, top-8,
float32 activations, exact products): ``exact_ragged_dot`` (XLA's
``ragged_dot`` kernels) against ``ops/pallas_moe.py`` at each number of
tokens, with the row tiles asked for. This is what sized
``pallas_moe.MAX_PAIRS_PER_EXPERT`` and ``_ROW_TILE`` (PERF.md section 6,
PR 28).

    python3 tools/moe_ffn_probe.py --tokens 64,256,1024,4096 --tm 0,16,128

Per line: milliseconds a call (the mean of ``--reps`` calls queued back to
back), the experts that took a row, the share of the bytes' floor (touched
experts x 12.6 MB at 819 GB/s) and of the FLOPs' floor (three pieces a
pair at 197 TFLOP/s), and the kernel's largest difference from
``exact_ragged_dot`` and from a float64 product of the pieces on 16 rows.
``--tm 0`` is the op's own row tile; ``ceiling`` is a kernel that moves the
same blocks and multiplies nothing. Fails off the chip
(``--rehearse 1`` runs the interpreter at small ``--widths``).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

K = 8
HBM_BYTES_PER_S, PEAK_FLOPS = 819e9, 197e12     # TPU v5e (Google Cloud docs)


def say(**kw):
    print(json.dumps(kw), flush=True)


def _ceiling(xs, ws, items, tm, interpret):
    """``pallas_moe.grouped_matmul``'s blocks, moved and not multiplied."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, d = xs.shape
    f = ws[0].shape[2]

    def kernel(e_ref, t_ref, lo_ref, hi_ref, n_ref, x_ref, *refs):
        o_ref = refs[-1]
        acc = jnp.zeros(o_ref.shape, jnp.float32) + x_ref[:, :1]
        for w_ref in refs[:-1]:
            acc = acc + w_ref[0, :tm, :].astype(jnp.float32)
        o_ref[...] = acc

    w_spec = pl.BlockSpec((1, d, f), lambda c, i, e, *_: (e[i], 0, c))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1, items[0].shape[0]),
            in_specs=[pl.BlockSpec((tm, d),
                                   lambda c, i, e, t, *_: (t[i], 0))]
            + [w_spec] * len(ws),
            out_specs=pl.BlockSpec((tm, f),
                                   lambda c, i, e, t, *_: (t[i], c))),
        out_shape=jax.ShapeDtypeStruct((n, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * len(ws) * d * f + (16 << 20)),
        name="moe_ceiling", interpret=interpret)(*items, xs, *ws)


def two_matrix(args, interpret):
    """The relu2 form: the kernels against ``exact_ragged_dot`` over an up
    matrix laid ``[d, f]`` (what a holder without the kernels would run),
    at each number of pairs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops, pallas_moe
    E, D, F = (int(w) for w in args.widths.split(","))
    key = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 2)
    wu, wd = (jax.random.normal(k, (E, F, D), jnp.bfloat16) * 0.02
              for k in key)
    wu_t = jnp.swapaxes(wu, 1, 2)       # made once, outside the timed call
    rs = np.random.RandomState(args.seed % (2 ** 31))
    assert pallas_moe.admits(1, wu, "relu2"), (E, D, F)

    @jax.jit
    def ragged(xs, counts, wu_t, wd):
        inner = jnp.square(jax.nn.relu(
            moe_ops.exact_ragged_dot(xs, wu_t, counts)))
        return moe_ops.exact_ragged_dot(inner, wd, counts)

    def kernel_of(tm):
        return jax.jit(lambda xs, counts, wu, wd: pallas_moe.expert_ffn(
            xs, None, wu, wd, counts, interpret, tm, act="relu2"))

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = fn(*a).block_until_ready()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        out.block_until_ready()
        return out, (time.perf_counter() - t0) / args.reps * 1e3, first

    for n in (int(t) for t in args.pairs.split(",")):
        c = rs.multinomial(n, np.full(E, 1.0 / E)).astype(np.int32)
        x = rs.randn(n, D).astype(np.float32)
        x /= np.sqrt((x ** 2).mean(-1, keepdims=True))
        xs, counts = jnp.asarray(x), jnp.asarray(c)
        touched = int((c > 0).sum())
        bytes_s = touched * 2 * D * F * 2 / HBM_BYTES_PER_S
        flops_s = n * 2 * 3 * 2 * D * F / PEAK_FLOPS
        common = dict(form="relu2", pairs=n, pairs_per_expert=n / E,
                      touched=touched, busiest=int(c.max()))
        want, ms, first = timed(ragged, xs, counts, wu_t, wd)
        say(path="ragged_dot", ms=ms, first_call_s=first,
            bytes_floor_share=bytes_s / ms * 1e3,
            flops_floor_share=flops_s / ms * 1e3, **common)
        want = np.asarray(want)
        scale = float(np.abs(want).max())
        for tm in (int(t) or pallas_moe._ROW_TILE
                   for t in args.tm.split(",")):
            got, ms, first = timed(kernel_of(tm), xs, counts, wu, wd)
            say(path="pallas_moe", tm=tm, ms=ms, first_call_s=first,
                bytes_floor_share=bytes_s / ms * 1e3,
                flops_floor_share=flops_s / ms * 1e3,
                err_vs_ragged_dot=float(
                    np.abs(np.asarray(got) - want).max()) / scale, **common)
    say(summary=True, device=str(jax.devices()[0]), widths=[E, D, F])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--form", default="swiglu", choices=("swiglu", "relu2"))
    ap.add_argument("--pairs", default="384,5120",
                    help="relu2: rows of a call, spread over the experts")
    ap.add_argument("--tokens", default="64,256,1024,4096")
    ap.add_argument("--tm", default="0", help="row tiles; 0: the op's own")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2800000001)
    ap.add_argument("--ceiling", type=int, default=1)
    ap.add_argument("--widths", default="128,2048,1024",
                    help="experts, d, f")
    ap.add_argument("--rehearse", type=int, default=0,
                    help="1: run off the chip, interpreted; times mean nothing")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops, pallas_moe
    E, D, F = (int(w) for w in args.widths.split(","))
    interpret = jax.default_backend() != "tpu"
    if interpret and not args.rehearse:
        raise SystemExit("moe_ffn_probe.py measures a chip: no TPU here")
    if args.form == "relu2":
        return two_matrix(args, interpret)
    key = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 4)
    wg, wu = (jax.random.normal(key[i], (E, D, F), jnp.bfloat16) * 0.02
              for i in (0, 1))
    wd = jax.random.normal(key[2], (E, F, D), jnp.bfloat16) * 0.02
    rw = jax.random.normal(key[3], (D, E), jnp.float32) * 0.02
    rs = np.random.RandomState(args.seed % (2 ** 31))

    @jax.jit
    def sort(x, rw):
        sel, _ = moe_ops.route(x, rw, jnp.zeros(E), K, True, 1.0)
        order = jnp.argsort(sel.reshape(-1))
        counts = jnp.bincount(sel.reshape(-1), length=E).astype(jnp.int32)
        return x[order // K], counts

    ws = (wg, wu, wd)         # arguments: a closure would compile them in

    @jax.jit
    def ragged(xs, counts, wg, wu, wd):
        inner = jax.nn.silu(moe_ops.exact_ragged_dot(xs, wg, counts)) * \
            moe_ops.exact_ragged_dot(xs, wu, counts)
        return moe_ops.exact_ragged_dot(inner, wd, counts)

    def kernel_of(tm):
        return jax.jit(lambda xs, counts, *ws: pallas_moe.expert_ffn(
            xs, *ws, counts, interpret, tm))

    def ceiling_of(tm):
        @jax.jit
        def fn(xs, counts, wg, wu, wd):
            items = pallas_moe.work_items(counts, xs.shape[0] // tm, tm)
            inner = _ceiling(xs, (wg, wu), items, tm, interpret)
            return _ceiling(inner, (wd,), items, tm, interpret)
        return fn

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = fn(*a).block_until_ready()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        out.block_until_ready()
        return out, (time.perf_counter() - t0) / args.reps * 1e3, first

    for n in (int(t) for t in args.tokens.split(",")):
        # RMSNorm's output: unit rows with a direction in common, as much
        # of one as gives the cell's routing (64 tokens touch ~115 experts,
        # the busiest takes ~16 pairs; PERF.md section 5)
        x = (rs.randn(n, D) + 0.35 * rs.randn(1, D)).astype(np.float32)
        x /= np.sqrt((x ** 2).mean(-1, keepdims=True))
        xs, counts = sort(jnp.asarray(x), rw)
        c = np.asarray(counts)
        touched = int((c > 0).sum())
        bytes_s = touched * 3 * D * F * 2 / HBM_BYTES_PER_S
        flops_s = n * K * 3 * 3 * 2 * D * F / PEAK_FLOPS
        common = dict(tokens=n, pairs_per_expert=n * K / E, touched=touched,
                      busiest=int(c.max()))
        want, ms, first = timed(ragged, xs, counts, *ws)
        say(path="ragged_dot", ms=ms, first_call_s=first,
            bytes_floor_share=bytes_s / ms * 1e3,
            flops_floor_share=flops_s / ms * 1e3, **common)
        want = np.asarray(want)
        # float64 product of the pieces, 16 rows spread over the groups
        rows = np.linspace(0, n * K - 1, 16).astype(int)
        group = np.searchsorted(np.cumsum(c), rows, side="right")
        f64 = lambda a: np.asarray(a.astype(jnp.float32)).astype(  # noqa: E731
            np.float64)
        xr = np.asarray(xs)[rows].astype(np.float64)
        line = []
        for r, g in enumerate(group):
            a = xr[r] @ f64(wg[g])
            inner = (a / (1 + np.exp(-a)) * (xr[r] @ f64(wu[g]))).astype(
                np.float32).astype(np.float64)
            line.append(inner @ f64(wd[g]))
        line = np.stack(line)
        scale = float(np.abs(want).max())
        for tm in (int(t) or pallas_moe._ROW_TILE
                   for t in args.tm.split(",")):
            got, ms, first = timed(kernel_of(tm), xs, counts, *ws)
            got = np.asarray(got)
            say(path="pallas_moe", tm=tm, ms=ms, first_call_s=first,
                bytes_floor_share=bytes_s / ms * 1e3,
                flops_floor_share=flops_s / ms * 1e3,
                err_vs_ragged_dot=float(np.abs(got - want).max()) / scale,
                err_vs_float64_rows=float(
                    np.abs(got[rows] - line).max()) / scale,
                ragged_err_vs_float64_rows=float(
                    np.abs(want[rows] - line).max()) / scale, **common)
            if args.ceiling:
                _, ms, _ = timed(ceiling_of(tm), xs, counts, *ws)
                say(path="ceiling", tm=tm, ms=ms,
                    bytes_floor_share=bytes_s / ms * 1e3, **common)
    say(summary=True, device=str(jax.devices()[0]),
        max_pairs_per_expert=pallas_moe.MAX_PAIRS_PER_EXPERT)


if __name__ == "__main__":
    main()
