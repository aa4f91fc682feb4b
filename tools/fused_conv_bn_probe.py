"""The VERDICT-named lever, built and measured: a Pallas fused
1x1-conv kernel with BN-apply + ReLU consumed in the matmul PROLOGUE
(the normalized activation never materializes in HBM) and the output's
BN statistics accumulated in the EPILOGUE (no separate stats pass).

Compares, on ResNet-50 bottleneck shapes, the XLA path
    stats = mean/var(c); z = relu(c*a+b); y = conv1x1(z, W);
    ystats = mean/var(y)
against one Pallas kernel doing all four. Prints ms + the achieved
bytes for both. Run on the TPU chip:
    python tools/fused_conv_bn_probe.py
"""

import functools
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

sys.path.insert(0, ".")


def _kernel(x_ref, a_ref, b_ref, w_ref, y_ref, s_ref, ss_ref, *,
            block_n, nsteps):
    """One N-tile: y = relu(x*a+b) @ W, accumulating per-channel
    sum/sumsq of y across the grid (sequential on TPU) for the NEXT
    BN's stats."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)
        ss_ref[:] = jnp.zeros_like(ss_ref)

    x = x_ref[:]                       # [block_n, C] raw conv output
    z = jnp.maximum(x * a_ref[:] + b_ref[:], 0.0)  # prologue BN+relu
    y = jnp.dot(z, w_ref[:], preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
    y_ref[:] = y.astype(y_ref.dtype)
    # epilogue: stats of the OUTPUT (consumed by the next layer's BN)
    s_ref[:] += jnp.sum(y, axis=0, keepdims=True)
    ss_ref[:] += jnp.sum(y * y, axis=0, keepdims=True)


def fused_conv1x1_bn(x, a, b, w, block_n=1024):
    """x: [N, C] raw pre-BN activations; a,b: [C] folded BN scale/shift
    of THIS layer; w: [C, O]. Returns (y [N, O] bf16, sum [O],
    sumsq [O]) — stats for the consumer BN come free."""
    n, c = x.shape
    o = w.shape[1]
    grid = (n // block_n,)
    y, s, ss = pl.pallas_call(
        functools.partial(_kernel, block_n=block_n, nsteps=grid[0]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((c, o), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, o), lambda i: (i, 0)),
            pl.BlockSpec((1, o), lambda i: (0, 0)),
            pl.BlockSpec((1, o), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, o), x.dtype),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
        ],
        interpret=jax.default_backend() not in ("tpu",),
    )(x, a.reshape(1, -1), b.reshape(1, -1), w)
    return y, s[0], ss[0]


def xla_path(x, a, b, w):
    z = jnp.maximum(x * a + b, 0.0)
    y = jnp.dot(z, w, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT).astype(x.dtype)
    s = jnp.sum(y.astype(jnp.float32), axis=0)
    ss = jnp.sum(jnp.square(y.astype(jnp.float32)), axis=0)
    return y, s, ss


def bench(fn, args, iters=24):
    """Chain ``iters`` calls INSIDE one jit (scan with a varying scalar
    defeating CSE) — per-call dispatch would otherwise swamp the
    kernel time."""
    x, a, b, w = args

    @jax.jit
    def chained(x, a, b, w):
        def step(carry, t):
            y, s, ss = fn(x * (1.0 + t * 1e-6).astype(x.dtype), a, b,
                          w)
            return carry + s[0], ss
        tot, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32),
                              jnp.arange(iters, dtype=jnp.float32))
        return tot

    out1 = fn(*args)
    tot = chained(x, a, b, w)
    np.asarray(tot)
    t0 = time.perf_counter()
    tot = chained(x, a, b, w)
    np.asarray(tot)
    return (time.perf_counter() - t0) / iters * 1e3, out1


def main():
    rs = np.random.RandomState(0)
    # bottleneck conv3 shapes per stage (B=256): [N=B*H*W, C] -> O
    cases = [
        ("stage2 28x28 128->512", 256 * 28 * 28, 128, 512),
        ("stage3 14x14 256->1024", 256 * 14 * 14, 256, 1024),
        ("stage1 56x56 64->256", 256 * 56 * 56, 64, 256),
    ]
    for name, n, c, o in cases:
        x = jnp.asarray(rs.randn(n, c), jnp.bfloat16)
        a = jnp.asarray(rs.rand(c) + 0.5, jnp.bfloat16)
        b = jnp.asarray(rs.randn(c) * 0.1, jnp.bfloat16)
        w = jnp.asarray(rs.randn(c, o) * 0.05, jnp.bfloat16)

        jx = jax.jit(xla_path)
        jf = jax.jit(fused_conv1x1_bn)
        ms_x, out_x = bench(jx, (x, a, b, w))
        ms_f, out_f = bench(jf, (x, a, b, w))
        # correctness (MXU bf16 tolerance)
        err = float(jnp.max(jnp.abs(
            out_x[0].astype(jnp.float32) -
            out_f[0].astype(jnp.float32))))
        serr = float(jnp.max(jnp.abs(out_x[1] - out_f[1]))) / n
        # ideal bytes: read x once + write y once (+ tiny a/b/w)
        ideal_gb = (n * c * 2 + n * o * 2) / 1e9
        print({"case": name, "xla_ms": round(ms_x, 2),
               "pallas_ms": round(ms_f, 2),
               "speedup": round(ms_x / ms_f, 3),
               "max_err": round(err, 4),
               "stats_err_per_row": round(serr, 6),
               "ideal_GB": round(ideal_gb, 3)})


if __name__ == "__main__":
    main()
