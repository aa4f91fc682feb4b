"""int8 / fp8 matmul throughput probe on the local chip: settle
whether low-precision matmul is a usable lever for any bench model on
this chip.

Method: square matmuls at several sizes, each timed over many in-jit
chained iterations (dispatch amortized); sync point is a scalar
device->host fetch. Results go to PROFILE.md.
"""

import time

import numpy as np

import jax
import jax.numpy as jnp
from functools import partial


def _sync(x):
    np.asarray(jax.device_get(x))


@partial(jax.jit, static_argnames=("n_iter", "acc", "dtype"))
def _chain(a, b, n_iter, acc, dtype):
    def body(bc, _):
        # the FULL output becomes the next rhs: no dead-code narrowing
        # (consuming only out[0,0] lets XLA shrink the dot to a row
        # product — measured 585 "TFLOP/s" > peak), iterations serialize
        out = jax.lax.dot(a, bc, preferred_element_type=acc)
        if dtype == jnp.int8:
            nxt = (out & 127).astype(jnp.int8)
        else:
            nxt = (out * 1e-2).astype(dtype)
        return nxt, None
    bn, _ = jax.lax.scan(body, b, None, length=n_iter)
    return bn[0, 0]


def bench_dtype(m, dtype, acc, n_iter=32, reps=3):
    rs = np.random.RandomState(0)
    if dtype in (jnp.int8,):
        a = rs.randint(-127, 127, (m, m)).astype(np.int8)
        b = rs.randint(-127, 127, (m, m)).astype(np.int8)
    else:
        a = (rs.randn(m, m) * 0.1).astype(np.float32)
        b = (rs.randn(m, m) * 0.1).astype(np.float32)
        a = jnp.asarray(a).astype(dtype)
        b = jnp.asarray(b).astype(dtype)
    a, b = jax.device_put(a), jax.device_put(b)
    _sync(_chain(a, b, n_iter, acc, dtype))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(_chain(a, b, n_iter, acc, dtype))
        best = min(best, (time.perf_counter() - t0) / n_iter)
    tflops = 2 * m ** 3 / best / 1e12
    return best * 1e3, tflops


def main():
    dev = jax.devices()[0]
    print("device:", dev.device_kind, dev.platform)
    rows = []
    for m in (4096, 8192):
        for name, dtype, acc in [
                ("bf16", jnp.bfloat16, jnp.float32),
                ("int8", jnp.int8, jnp.int32),
                ("fp8_e4m3", jnp.float8_e4m3fn, jnp.float32),
                ("fp8_e5m2", jnp.float8_e5m2, jnp.float32)]:
            try:
                ms, tf = bench_dtype(m, dtype, acc)
                rows.append((m, name, ms, tf))
                print("m=%d %-9s %8.3f ms  %7.1f TFLOP/s"
                      % (m, name, ms, tf), flush=True)
            except Exception as e:
                msg = str(e).split("\n")[0][:160]
                rows.append((m, name, None, None))
                print("m=%d %-9s FAILED: %s" % (m, name, msg),
                      flush=True)
    return rows


if __name__ == "__main__":
    main()
