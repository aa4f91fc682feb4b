"""Transformer-LM MFU probe (VERDICT r4 demand 4): attention fraction
of the step, flash block-size sweep, and longer-T configs — decide
whether 0.55 MFU is reachable or 0.51 is this chip's cap for the
bench family.

Usage (on the TPU chip):
  python tools/transformer_mfu_probe.py --mode step [--batch 8 --seqlen 1024]
  python tools/transformer_mfu_probe.py --mode kernel   # fwd and bwd alone
  python tools/transformer_mfu_probe.py --mode sweep    # fwd block pairs
"""

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

_PEAK = {"TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v4": 275e12,
         "TPU v5p": 459e12}
_HBM = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def _sync(x):
    import jax
    np.asarray(jax.device_get(x))


def bench_step(batch, seqlen, d=2048, L=12, H=16, vocab=32768,
               steps=8, warmup=2, flash=True, cost=True):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm

    ptpu.config.set_flags(amp="bfloat16", flash_attention=flash)
    dev = jax.devices()[0]
    peak = _PEAK.get(dev.device_kind, 197e12)
    hbm = _HBM.get(dev.device_kind, 819e9)

    with ptpu.scope_guard(ptpu.Scope()), ptpu.unique_name.guard():
        main, startup = ptpu.Program(), ptpu.Program()
        with ptpu.program_guard(main, startup):
            toks = layers.data("toks", shape=[seqlen], dtype="int64")
            lbls = layers.data("lbls", shape=[seqlen], dtype="int64")
            loss, _ = transformer_lm(toks, lbls, vocab_size=vocab,
                                     d_model=d, num_heads=H, d_ff=4 * d,
                                     num_layers=L)
            opt = ptpu.optimizer.Adam(learning_rate=1e-4)
            opt.minimize(loss, startup_program=startup)
        n_params = sum(int(np.prod(p.shape)) for p in
                       main.global_block().all_parameters())
        exe = ptpu.Executor()
        exe.run(startup)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(2, vocab, (batch, seqlen)),
                          dtype=jnp.int32)
        feed = {"toks": jax.device_put(ids), "lbls": jax.device_put(ids)}

        out = {"batch": batch, "T": seqlen, "flash": flash}
        if cost:
            try:
                low = exe.lower(main, feed=feed, fetch_list=[loss])
                ca = low.compile().cost_analysis()
                out["xla_gflops"] = round(ca.get("flops", 0) / 1e9, 1)
                out["xla_gbytes"] = round(
                    ca.get("bytes accessed", 0) / 1e9, 2)
                out["roofline_ms"] = round(
                    ca.get("bytes accessed", 0) / hbm * 1e3, 1)
            except Exception as e:
                out["cost_err"] = str(e)[:120]

        try:
            for _ in range(warmup):
                o = exe.run(main, feed=feed, fetch_list=[loss],
                            return_numpy=False)
            np.asarray(o[0])
            t0 = time.perf_counter()
            for _ in range(steps):
                o = exe.run(main, feed=feed, fetch_list=[loss],
                            return_numpy=False)
            final = float(np.asarray(o[0]))
            dt = (time.perf_counter() - t0) / steps
        except Exception as e:
            out["err"] = str(e)[:200]
            return out
        tok_s = batch * seqlen / dt
        flops_per_tok = 6.0 * n_params + 6.0 * L * seqlen * d
        out.update(ms=round(dt * 1e3, 1), tok_s=round(tok_s),
                   mfu=round(tok_s * flops_per_tok / peak, 4),
                   loss=round(final, 3))
        return out


# (block_q, block_k) of the forward kernel that --mode sweep times; --mode
# kernel times the pair ``pa._tiles`` picks, which is one of them (my chip
# runs, PR 41: CHANGES.md has the table)
_BLOCK_PAIRS = [(256, 512), (512, 512), (256, 1024), (512, 1024),
                (1024, 512), (1024, 1024)]
# MXU products a (q-block, k-block) tile: Q K^T and P V forward; the
# scores, dP, dV, dK and dQ backward
_PRODUCTS = {"flash_attention_fwd": 2, "flash_attention_bwd": 5}


def _live_share(t, bq, bk, causal):
    """Share of the (q-block, k-block) tiles a causal mask leaves."""
    if not causal:
        return 1.0
    nq, nk = t // bq, t // bk
    live = sum(1 for i in range(nq) for j in range(nk)
               if i * bq + bq - 1 >= j * bk)
    return live / (nq * nk)


def _time(fn, *args, n_iter):
    import jax
    jax.block_until_ready(fn(*args))          # compile and warm
    t0 = time.perf_counter()
    for _ in range(n_iter):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n_iter * 1e3


def bench_kernel(block_q=None, block_k=None, b=4, h=16, t=2048, dd=128,
                 causal=True, n_iter=20, bwd=True):
    """The flash kernels alone at the training cells' attention shape
    ([64, 2048, 128] bf16 a chip): the forward under differentiation
    (``pa._forward`` on blocks of ``block_q`` x ``block_k``, by default those
    ``pa._tiles`` picks; row statistics kept) and, with ``bwd``, the
    backward kernel (``pa._backward``, its own blocks) on that forward's
    output. Each is a jitted call on device
    arrays, timed over ``n_iter`` calls. ``peak_share`` counts every
    tile's products (``products_a_tile``: 2 forward, 5 backward, each
    2*T*T*D a batch-head), ``peak_share_live`` only the tiles the causal
    mask leaves."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_attention as pa

    dev = jax.devices()[0]
    peak = _PEAK[dev.device_kind]
    rs = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rs.randn(b * h, t, dd), jnp.bfloat16)
                   for _ in range(4))
    if block_q is None:
        block_q, block_k = pa._tiles(q, k, None, False, lanes=True)
    product = 2.0 * t * t * dd * b * h
    out = {"shape": [b * h, t, dd], "causal": causal,
           "device": dev.device_kind}

    def row(kernel, bq, bk, ms):
        n = _PRODUCTS[kernel]
        share = n * product / ms / 1e-3 / peak
        return dict(out, kernel=kernel, block_q=bq, block_k=bk,
                    ms=round(ms, 3), products_a_tile=n,
                    peak_share=round(share, 4),
                    peak_share_live=round(
                        share * _live_share(t, bq, bk, causal), 4))
    try:
        fwd = jax.jit(lambda *a: pa._forward(
            *a, None, causal, block_q, block_k, False, with_lse=True))
        rows = [row("flash_attention_fwd", block_q, block_k,
                    _time(fwd, q, k, v, n_iter=n_iter))]
        if bwd:
            o, lse = fwd(q, k, v)
            ms = _time(jax.jit(lambda *a: pa._backward(
                *a[:5], None, a[5], causal, False)), q, k, v, o, lse, do,
                n_iter=n_iter)
            rows.append(row("flash_attention_bwd",
                            pa._block_size(t, pa._BWD_BLOCK, 128),
                            pa._block_size(t, pa._BWD_BLOCK), ms))
        return rows
    except Exception as e:
        return [dict(out, block_q=block_q, block_k=block_k,
                     err=str(e)[:160])]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="step",
                    choices=["step", "kernel", "sweep", "configs"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seqlen", type=int, default=1024)
    ap.add_argument("--no-flash", action="store_true")
    args = ap.parse_args()

    if args.mode == "step":
        print(json.dumps(bench_step(args.batch, args.seqlen,
                                    flash=not args.no_flash)),
              flush=True)
    elif args.mode == "configs":
        for b, t in [(8, 1024), (4, 2048), (2, 4096), (6, 1536),
                     (12, 1024)]:
            print(json.dumps(bench_step(b, t)), flush=True)
    elif args.mode == "kernel":
        for row in bench_kernel():
            print(json.dumps(row), flush=True)
    elif args.mode == "sweep":
        for bq, bk in _BLOCK_PAIRS:
            print(json.dumps(bench_kernel(bq, bk, bwd=False)[0]),
                  flush=True)


if __name__ == "__main__":
    main()
