"""Probe: conv layout + dtype throughput on the attached TPU chip.

Measures a ResNet-50-representative conv stack under
{NCHW,NHWC} x {f32,bf16} to pick the fast path. Not part of the library.

Every timing below fetches one element to the host to close the
window.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    np.asarray(jax.device_get(x.ravel()[0:1]))


def timeit(fn, *args, iters=10):
    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def conv_stack(layout, dtype):
    # representative resnet-50 mid-stage: 3x3 conv, C=256, HW=28, bs=256
    B, C, H, W = 256, 256, 28, 28
    key = jax.random.PRNGKey(0)
    if layout == "NCHW":
        x = jax.random.normal(key, (B, C, H, W), dtype)
        w = jax.random.normal(key, (C, C, 3, 3), dtype)
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        x = jax.random.normal(key, (B, H, W, C), dtype)
        w = jax.random.normal(key, (3, 3, C, C), dtype)
        dn = ("NHWC", "HWIO", "NHWC")

    @jax.jit
    def f(x, w):
        y = x
        for _ in range(8):
            y = jax.lax.conv_general_dilated(
                y, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn)
            y = jax.nn.relu(y)
        return y

    dt = timeit(f, x, w)
    flops = 8 * 2 * B * H * W * C * C * 9
    return dt, flops / dt / 1e12


def main():
    dev = jax.devices()[0]
    print("device:", dev.device_kind, dev.platform)

    a = jax.random.normal(jax.random.PRNGKey(0), (8192, 8192), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    dt = timeit(mm, a, a, iters=20)
    print(f"matmul 8k^3 bf16: {dt*1e3:7.2f} ms  "
          f"{2*8192**3/dt/1e12:6.1f} TFLOP/s")

    for layout in ("NCHW", "NHWC"):
        for dtype in (jnp.float32, jnp.bfloat16):
            dt, tf = conv_stack(layout, dtype)
            print(f"{layout} {np.dtype(dtype).name:8s}: {dt*1e3:7.2f} ms  "
                  f"{tf:6.1f} TFLOP/s  ({tf/197*100:4.1f}% of v5e peak)")


if __name__ == "__main__":
    main()
