#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: device, train, serve, resnet
    python chip_smoke.py --chips 4   # four chips: the cross-chip paths only

One process drives the main path through the entry points a user calls
(``ptpu.Executor().run``; ``transformer_lm_session`` +
``GenerationScheduler.submit``) at the full width of the transformer LM
the repo trains (d_model 2048, 12 layers, 16 heads, d_ff 8192, vocab
32768 — 740,519,936 parameters) with ``amp="bfloat16"`` and
``flash_attention=True``, then a ResNet-50 ImageNet train step. Weights
and data come from a seed; nothing is read from disk but the repo.

It prints one JSON line per phase (compile seconds, wall seconds, loss
or tokens, device memory — observations, not metrics) and, last, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with exit code 0. Any phase failing — no TPU first of all — gives a last
line with ``"ok": false`` and a non-zero exit. There is no size option
and no CPU mode: the phases are functions of their sizes so that
tests/test_chip_smoke.py can walk the same control flow at a tiny width
under interpret mode, and ``main`` calls them only past the device check.

JAX's persistent compilation cache is on: at ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else at ``<repo>/.jax_cache`` — a second run reports
cache hits in place of compile seconds.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 21

# the LM the repo trains on the chip
LM = dict(vocab=32768, d_model=2048, num_heads=16, d_ff=8192,
          num_layers=12)


# Kernel vs reference decode logits, as a share of the largest |logit|.
# Both paths multiply in bf16 and the logits themselves are bf16 under
# amp (ulp 2^-8 of the value): 4e-2 is ten ulps at the top of the range,
# room for twelve layers of differently-rounded attention outputs and far
# below what a wrong block or mask does (errors of the order of 1).
LOGIT_RTOL = 4e-2


class SmokeFailure(AssertionError):
    """A phase saw something wrong; the message says what."""


def _check(cond, msg, *args):
    if not cond:
        raise SmokeFailure(msg % args)


# -- what a phase observes ------------------------------------------------

class _CompileMeter:
    """Sums JAX's own compile events: backend compile seconds (cache
    retrieval included) and persistent-cache hits/misses. Registered
    once per process; phases read deltas."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


_METER = None


@contextlib.contextmanager
def _phase(name, **sizes):
    """Run one phase: time it and print its JSON line whatever happens.
    The body fills in the yielded dict; a raise marks the line
    ``"ok": false`` and propagates. ``peak_bytes`` is the process's
    high-water mark so far, ``bytes_in_use_before`` what earlier phases
    left behind."""
    global _METER
    import jax
    from paddle_tpu.ops import kernel_path
    if _METER is None:
        _METER = _CompileMeter()
    line = {"phase": name, "ok": False}
    line.update(sizes)
    gc.collect()    # the previous phase's cycles, before this one allocates
    dev = jax.devices()[0]
    line["bytes_in_use_before"] = (dev.memory_stats() or {}).get(
        "bytes_in_use")
    c0, h0, m0 = _METER.snapshot()
    k0 = kernel_path.counts()
    t0 = time.perf_counter()
    try:
        yield line, k0
        line["ok"] = True
    except BaseException as e:
        line["error"] = "%s: %s" % (type(e).__name__, str(e)[:500])
        raise
    finally:
        c1, h1, m1 = _METER.snapshot()
        line["wall_s"] = round(time.perf_counter() - t0, 2)
        line["compile_s"] = round(c1 - c0, 2)
        line["jax_cache"] = {"hits": h1 - h0, "misses": m1 - m0}
        line["peak_bytes"] = (dev.memory_stats() or {}).get(
            "peak_bytes_in_use")
        print(json.dumps(line), flush=True)


@contextlib.contextmanager
def _flags(**kw):
    import paddle_tpu as ptpu
    prev = {k: ptpu.config.get_flag(k) for k in kw}
    ptpu.config.set_flags(**kw)
    try:
        yield
    finally:
        ptpu.config.set_flags(**prev)


@contextlib.contextmanager
def _private_state():
    """Fresh Scope + name namespace, so a phase's parameters die with it
    (the train phase's 8.9 GB must be gone before serve starts)."""
    import paddle_tpu as ptpu
    with ptpu.scope_guard(ptpu.Scope()), ptpu.unique_name.guard():
        yield


def _kernel_taken(kernel, before, hlo):
    """The Pallas kernel ran as itself: no call site of it fell back to
    the XLA reference during this phase, and — on a TPU — it was
    compiled (a ``tpu_custom_call`` in the step's HLO), never
    interpreted. Off-TPU (the rehearsal test) interpret mode is the only
    mode there is, and the HLO check is skipped."""
    import jax
    from paddle_tpu.ops import kernel_path
    now = kernel_path.counts().get(kernel, {})
    # the paths taken during this phase: one that only an earlier phase (or
    # an earlier test of the process) took is left out, not reported as 0
    was = before.get(kernel, {})
    delta = {p: n - was.get(p, 0) for p, n in now.items()
             if n != was.get(p, 0)}
    _check(not delta.get("xla"), "%s: %d call site(s) fell back to the "
           "XLA reference", kernel, delta.get("xla", 0))
    if jax.default_backend() == "tpu":
        _check(not delta.get("interpret"),
               "%s traced in interpret mode on a TPU backend", kernel)
        _check(delta.get("compiled", 0) > 0,
               "%s was never traced: %r", kernel, delta)
        _check("tpu_custom_call" in hlo,
               "no tpu_custom_call in the compiled step's HLO (%s)",
               kernel)
    else:
        _check(delta.get("interpret", 0) > 0,
               "%s was never traced: %r", kernel, delta)
    return delta


def _hlo(exe, program, feed, fetch_list, scope=None):
    """HLO text of the step ``exe.run`` executed for these arguments:
    re-lowering the same jitted step hands back the computation the call
    already compiled, so this costs a trace, not a compile."""
    return exe.lower(program, feed=feed, fetch_list=fetch_list,
                     scope=scope).compile().as_text()


def _scalar(fetched):
    return float(np.asarray(fetched).reshape(-1)[0])


def _falling(losses, what):
    _check(all(np.isfinite(losses)), "%s loss not finite: %r", what,
           losses)
    _check(losses[-1] < losses[0], "%s loss did not fall: %r", what,
           losses)


# -- phases ---------------------------------------------------------------

def phase_device(chips):
    """The device stamp as JAX reports it, or a raise: a TPU backend
    with at least the chips this run was asked to use."""
    import jax
    devs = jax.devices()
    _check(devs[0].platform == "tpu",
           "JAX's default backend is %r, not a TPU", devs[0].platform)
    _check(len(devs) >= chips, "asked for %d chips, JAX sees %d", chips,
           len(devs))
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    print(json.dumps({"phase": "device", "ok": True, "jax": jax.__version__,
                      "device": stamp}), flush=True)
    return stamp


def _lm_program(seq_len, train, vocab, d_model, num_heads, d_ff,
                num_layers):
    """(main, startup, loss) of the seeded LM at these sizes — with Adam
    when ``train``, else the bare forward whose startup program makes the
    weights a serving session reads by name."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm
    main, startup = ptpu.Program(), ptpu.Program()
    main.random_seed = startup.random_seed = SEED
    with ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[seq_len], dtype="int64")
        lbls = layers.data("lbls", shape=[seq_len], dtype="int64")
        loss, _ = transformer_lm(toks, lbls, vocab_size=vocab,
                                 d_model=d_model, num_heads=num_heads,
                                 d_ff=d_ff, num_layers=num_layers,
                                 is_test=not train)
        if train:
            ptpu.optimizer.Adam(learning_rate=1e-4).minimize(
                loss, startup_program=startup)
    return main, startup, loss


def _lm_batch(vocab, batch, seq_len):
    ids = np.random.RandomState(SEED).randint(
        2, vocab, (batch, seq_len)).astype("int32")
    return {"toks": ids, "lbls": np.roll(ids, -1, axis=1)}


def _lm_train_steps(sizes, batch, seq_len, steps, strategy=None):
    """Startup + ``steps`` train steps on one repeated seeded batch in a
    private scope. Returns (losses, step seconds, HLO text, scope, main
    program) — the scope is alive only until the caller drops it."""
    import paddle_tpu as ptpu
    with _private_state():
        main, startup, loss = _lm_program(seq_len, True, **sizes)
        exe = ptpu.Executor(strategy=strategy)
        exe.run(startup)
        feed = _lm_batch(sizes["vocab"], batch, seq_len)
        losses, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            out, = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(_scalar(out))     # fetched to host: step done
            step_s.append(time.perf_counter() - t0)
        hlo = _hlo(exe, main, feed, [loss])
        return losses, step_s, hlo, ptpu.global_scope(), main


def phase_train(vocab, d_model, num_heads, d_ff, num_layers, batch,
                seq_len, steps):
    """LM + Adam through ``Executor.run``; loss finite and falling; the
    flash kernels, forward and backward, compiled into the step."""
    sizes = dict(vocab=vocab, d_model=d_model, num_heads=num_heads,
                 d_ff=d_ff, num_layers=num_layers)
    with _phase("train", batch=batch, seq_len=seq_len, **sizes) \
            as (line, k0), _flags(amp="bfloat16", flash_attention=True):
        losses, step_s, hlo, _, main = _lm_train_steps(
            sizes, batch, seq_len, steps)
        n_params = sum(int(np.prod(p.shape))
                       for p in main.global_block().all_parameters())
        line.update(n_params=n_params, loss=[round(v, 4) for v in losses],
                    first_step_s=round(step_s[0], 2),
                    step_s=round(float(np.median(step_s[1:])), 4))
        _falling(losses, "LM train")
        line["kernels"] = {k: _kernel_taken(k, k0, hlo) for k in
                           ("flash_attention", "flash_attention_bwd")}
    return line


def _logits_var(program, fetch_name):
    """Name of the [slots, V] logits row the decode program's greedy
    epilogue (argmax) reads."""
    for op in program.global_block().ops:
        if op.type == "arg_max" and \
                fetch_name in sum(op.outputs.values(), []):
            return op.inputs["X"][0]
    raise SmokeFailure("decode program has no argmax producing %r"
                       % fetch_name)


def phase_serve(vocab, d_model, num_heads, d_ff, num_layers, max_len,
                slots, prompt_buckets, prompt_lens, new_tokens, kv_dtype,
                logit_rtol):
    """Paged-KV generation through ``GenerationScheduler``: every prompt
    answered with ``new_tokens`` tokens, the paged decode kernel compiled
    into the decode step, and one decode step's logits within
    ``logit_rtol`` (of the largest |logit|) of the dense XLA gather path
    (``_decode_paged_reference``) run on the same device and state."""
    import paddle_tpu as ptpu
    from paddle_tpu.models.transformer import transformer_lm_session
    from paddle_tpu.serving.generation import (GenerationScheduler,
                                               GenerationSession)
    sizes = dict(vocab=vocab, d_model=d_model, num_heads=num_heads,
                 d_ff=d_ff, num_layers=num_layers)
    with _phase("serve", max_len=max_len, slots=slots,
                kv_dtype=kv_dtype, prompt_lens=list(prompt_lens),
                new_tokens=new_tokens, **sizes) as (line, k0), \
            _flags(amp="bfloat16", flash_attention=True,
                   generation_kv_dtype=kv_dtype), _private_state():
        with ptpu.unique_name.guard():
            _, startup, _ = _lm_program(max_len, False, **sizes)
        ptpu.Executor().run(startup)

        spec = transformer_lm_session(
            vocab, d_model=d_model, num_heads=num_heads, d_ff=d_ff,
            num_layers=num_layers, max_len=max_len, slots=slots,
            cache_len=max_len, prompt_buckets=prompt_buckets)
        sess = GenerationSession(spec)
        rs = np.random.RandomState(SEED)
        prompts = [rs.randint(2, vocab, n).astype("int64")
                   for n in prompt_lens]

        sched = GenerationScheduler(sess)
        try:
            t0 = time.perf_counter()
            futs = [sched.submit(p, max_new_tokens=new_tokens, eos_id=-1)
                    for p in prompts]
            outs = [np.asarray(f.result(timeout=900)) for f in futs]
            serve_s = time.perf_counter() - t0
        finally:
            sched.close()
        for n, out in zip(prompt_lens, outs):
            _check(out.shape == (new_tokens,), "prompt of %d tokens got "
                   "%r tokens back, want %d", n, out.shape, new_tokens)
            _check(((out >= 0) & (out < vocab)).all(),
                   "prompt of %d tokens: token ids out of range", n)
        line.update(requests=len(outs), tokens=int(sum(map(len, outs))),
                    serve_s=round(serve_s, 2),
                    compiles=sess.compile_stats()["compiles"])

        # one decode step, kernel against reference, on a fresh batch of
        # two sequences of different depth. The step is idempotent on
        # the cache (it rewrites the same K/V row), so both runs see the
        # same state.
        for p in prompts[1:3]:
            sess.admit(p)
        _, _, feed = sess.step_prepare()
        logits = _logits_var(spec.decode_program, spec.decode_fetch)
        fetch = [logits, spec.decode_fetch]
        got = sess.exe.run(spec.decode_program, feed=feed,
                           fetch_list=fetch, scope=sess.scope)[0]
        hlo = _hlo(sess.exe, spec.decode_program, feed,
                   [spec.decode_fetch], scope=sess.scope)
        with _flags(flash_attention=False):
            want = sess.exe.run(spec.decode_program, feed=feed,
                                fetch_list=fetch, scope=sess.scope)[0]
        live = sess.active_slots()
        got = np.asarray(got, np.float32)[live]     # bf16 under amp
        want = np.asarray(want, np.float32)[live]
        _check(np.isfinite(got).all() and got.shape == (len(live), vocab),
               "decode logits: shape %r, finite %s", got.shape,
               bool(np.isfinite(got).all()))
        err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max())
        line.update(logit_max_abs_err=err, logit_max_abs=scale,
                    logit_rtol=logit_rtol)
        _check(err <= logit_rtol * scale, "paged decode kernel vs dense "
               "gather reference: max |dlogit| %g > %g x %g", err,
               logit_rtol, scale)
        line["kernels"] = {"decode_attention_paged": _kernel_taken(
            "decode_attention_paged", k0, hlo)}
        sess.close()
    return line


def phase_resnet(depth, batch, res, class_dim, steps):
    """ResNet ImageNet train step (Momentum, bf16 amp) — the conv/BN/NCHW
    lowering, which shares nothing with the LM."""
    import jax
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models import resnet
    with _phase("resnet", depth=depth, batch=batch, res=res,
                class_dim=class_dim) as (line, _), \
            _flags(amp="bfloat16"), _private_state():
        main, startup = ptpu.Program(), ptpu.Program()
        main.random_seed = startup.random_seed = SEED
        with ptpu.program_guard(main, startup):
            img = layers.data("img", shape=[3, res, res])
            label = layers.data("label", shape=[1], dtype="int64")
            loss, _, _ = resnet.resnet_imagenet(img, label, depth=depth,
                                                class_dim=class_dim)
            ptpu.optimizer.Momentum(learning_rate=0.1, momentum=0.9) \
                .minimize(loss, startup_program=startup)
        exe = ptpu.Executor()
        exe.run(startup)
        rs = np.random.RandomState(SEED)
        # staged on the device once, as an input pipeline would
        feed = {"img": jax.device_put(
                    rs.randn(batch, 3, res, res).astype("float32")),
                "label": jax.device_put(
                    rs.randint(0, class_dim, (batch, 1)).astype("int32"))}
        losses, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            out, = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(_scalar(out))
            step_s.append(time.perf_counter() - t0)
        line.update(loss=[round(v, 4) for v in losses],
                    first_step_s=round(step_s[0], 2),
                    step_s=round(float(np.median(step_s[1:])), 4))
        _falling(losses, "ResNet train")
    return line


def _distinct_devices(array):
    return {s.device for s in array.addressable_shards}


def phase_cross_chip(chips, lm, lm_batch, lm_seq_len, lm_steps, wd_vocab,
                     wd_slots, wd_emb_dim, wd_batch, wd_steps, loss_rtol):
    """What exists only across chips, and what each is compared with.

    (a) The LM train step under ``DistStrategy`` on a ``data=chips``
    mesh (flash kernel per shard under ``shard_map``) against the
    one-chip step on the same batch and seed: losses agree, parameters
    and feeds have shards on ``chips`` distinct devices, the HLO holds
    an all-reduce. (b) The wide&deep step with row-sharded tables and
    the hand-written all_to_all exchange (``embedding_a2a``) against the
    GSPMD-gather mode of the same program."""
    import jax
    import paddle_tpu as ptpu
    from paddle_tpu import layers, parallel
    from paddle_tpu.models.wide_deep import wide_deep
    devices = jax.devices()[:chips]     # make_mesh refuses too few

    with _phase("cross_chip_lm", chips=chips, batch=lm_batch,
                seq_len=lm_seq_len, **lm) as (line, k0), \
            _flags(amp="bfloat16", flash_attention=True):
        strat = parallel.DistStrategy(
            parallel.make_mesh({"data": chips}, devices))
        losses_n, step_n, hlo, scope, main = _lm_train_steps(
            lm, lm_batch, lm_seq_len, lm_steps, strategy=strat)
        param = main.global_block().all_parameters()[0].name
        on = _distinct_devices(scope.find_var(param))
        _check(len(on) == chips, "parameter %s lives on %d device(s), "
               "want %d", param, len(on), chips)
        toks = strat.shard_feed(
            "toks", _lm_batch(lm["vocab"], lm_batch, lm_seq_len)["toks"])
        _check(len(_distinct_devices(toks)) == chips and
               toks.addressable_shards[0].data.shape[0] ==
               lm_batch // chips,
               "feed is not batch-sharded over %d devices", chips)
        _check("all-reduce" in hlo, "no all-reduce in the sharded LM "
               "step's HLO")
        kernels = {k: _kernel_taken(k, k0, hlo) for k in
                   ("flash_attention", "flash_attention_bwd")}
        del scope, main, toks
        gc.collect()
        losses_1, step_1, _, _, _ = _lm_train_steps(
            lm, lm_batch, lm_seq_len, lm_steps)
        line.update(loss_sharded=[round(v, 4) for v in losses_n],
                    loss_one_chip=[round(v, 4) for v in losses_1],
                    loss_rtol=loss_rtol, kernels=kernels,
                    step_s_sharded=round(float(np.median(step_n[1:])), 4),
                    step_s_one_chip=round(float(np.median(step_1[1:])), 4))
        _falling(losses_n, "sharded LM train")
        _check(np.allclose(losses_n, losses_1, rtol=loss_rtol),
               "sharded vs one-chip LM losses differ: %r vs %r",
               losses_n, losses_1)

    with _phase("cross_chip_embedding", chips=chips, vocab=wd_vocab,
                slots=wd_slots, emb_dim=wd_emb_dim, batch=wd_batch) \
            as (line, _):
        rs = np.random.RandomState(SEED)
        feed = {"ids": rs.randint(0, wd_vocab, (wd_batch, wd_slots))
                .astype("int32"),
                "dense": rs.randn(wd_batch, 8).astype("float32"),
                "label": rs.randint(0, 2, (wd_batch, 1))
                .astype("float32")}

        def run(a2a):
            with _flags(embedding_shard_rows=True, embedding_a2a=a2a), \
                    _private_state():
                main, startup = ptpu.Program(), ptpu.Program()
                main.random_seed = startup.random_seed = SEED
                with ptpu.program_guard(main, startup):
                    ids = layers.data("ids", shape=[wd_slots],
                                      dtype="int64")
                    dense = layers.data("dense", shape=[8])
                    label = layers.data("label", shape=[1])
                    loss, _, _ = wide_deep(
                        ids, dense, label, wd_vocab, wd_slots,
                        emb_dim=wd_emb_dim, hidden=(64, 32),
                        is_distributed=True)
                    ptpu.optimizer.Adagrad(0.05).minimize(
                        loss, startup_program=startup)
                exe = ptpu.Executor(strategy=parallel.DistStrategy(
                    parallel.make_mesh({"data": chips}, devices)))
                exe.run(startup)
                losses = [_scalar(exe.run(main, feed=feed,
                                          fetch_list=[loss])[0])
                          for _ in range(wd_steps)]
                table = ptpu.global_scope().find_var("deep_embedding")
                on = _distinct_devices(table)
                rows = table.addressable_shards[0].data.shape[0]
                _check(len(on) == chips and
                       rows * chips == table.shape[0],
                       "deep_embedding is not row-sharded over %d "
                       "devices (%d devices, %d of %d rows each)", chips,
                       len(on), rows, table.shape[0])
                return losses, np.asarray(table), _hlo(exe, main, feed,
                                                       [loss])

        loss_a2a, table_a2a, hlo_a2a = run(True)
        loss_gather, table_gather, hlo_gather = run(False)
        line.update(loss_a2a=[round(v, 5) for v in loss_a2a],
                    loss_gather=[round(v, 5) for v in loss_gather])
        _check("all-to-all" in hlo_a2a, "no all-to-all in the "
               "embedding_a2a step's HLO")
        _check("all-to-all" not in hlo_gather, "the gather-mode step "
               "holds an all-to-all: the two modes are not two modes")
        _falling(loss_a2a, "wide&deep a2a")
        _check(np.allclose(loss_a2a, loss_gather, rtol=1e-4, atol=1e-6),
               "a2a vs gather losses differ: %r vs %r", loss_a2a,
               loss_gather)
        _check(np.allclose(table_a2a, table_gather, rtol=1e-4, atol=1e-6),
               "a2a vs gather trained tables differ (max %g)",
               float(np.abs(table_a2a - table_gather).max()))
    return line


# -- entry point ----------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths, on four chips")
    args = ap.parse_args(argv)
    stamp = None
    try:
        from paddle_tpu.core.compile_cache import enable_jax_cache
        enable_jax_cache(os.path.join(REPO, ".jax_cache"))
        stamp = phase_device(args.chips)
        if args.chips == 4:
            phase_cross_chip(4, LM, lm_batch=8, lm_seq_len=1024,
                             lm_steps=3, wd_vocab=200_000, wd_slots=26,
                             wd_emb_dim=32, wd_batch=4096, wd_steps=3,
                             loss_rtol=2e-2)
        else:
            phase_train(batch=8, seq_len=1024, steps=4, **LM)
            for kv_dtype in ("bfloat16", "float32"):
                phase_serve(max_len=1024, slots=8,
                            prompt_buckets=(64, 1024),
                            prompt_lens=(5, 37, 300, 900), new_tokens=32,
                            kv_dtype=kv_dtype, logit_rtol=LOGIT_RTOL,
                            **LM)
            phase_resnet(depth=50, batch=256, res=224, class_dim=1000,
                         steps=3)
    except Exception as e:  # noqa: BLE001 — the last line must print
        import traceback
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": stamp,
                          "error": "%s: %s" % (type(e).__name__,
                                               str(e)[:500])}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": stamp}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
