"""The ``train`` kind of cell: the LM with Adam through ``Executor.run``,
one donated step per call, a fresh seeded batch fed from the host every
step, the loss kept on the device and fetched every ``fetch_every`` steps
as a trainer logs it. With a mesh in the cell file the step runs under the
cell's ``DistStrategy``.

The loss of a block of ``fetch_every`` steps is fetched after the next
block has been handed to the device, as a trainer that logs without
stalling does, so the device always has work queued and a slow or paused
host thread costs it nothing. Every fetched loss is a time stamp at which
all steps up to it have finished on the device; the window opens on an
idle device and closes on such a stamp. ``train_tokens_per_s`` is the
tokens of a step over the *median* seconds per step, taken over pairs of
stamps up to three blocks apart (``common.median_slope``), so neither a
late stamp nor one block that a stall lengthened moves it (the driver's
first check of PR 22 read a spread of 2.1-2.8% on tokens over elapsed
seconds where six runs in one call had read 0.003%).
"""

import collections
import time

import numpy as np

from . import common, lm
from .. import architectures
from .common import check

# Program vs reference loss on the check step. The program multiplies in
# bf16 under amp and keeps bf16 logits before a float32 cross-entropy; the
# reference is float32 at "highest". Rounding errors of single tokens
# average out over the 2,048 tokens of the sequence: on the chip the two
# losses differed by 4e-5 and 5e-5 at a loss of 10.86 (my chip runs, PR 22;
# 1.2e-3 in the CPU rehearsal at a tiny size). 1e-3 is twenty times the
# chip's reading. At random initialisation the loss sits about 0.04 above
# ln(vocab) = 10.825, and that excess is what a wrong mask, position or
# weight changes, so the check resolves a fortieth of it.
LOSS_ATOL = 1e-3


def _scalar(fetched):
    """A fetched loss as a float; waits for the device."""
    return float(np.asarray(fetched).reshape(-1)[0])


def run(cell, cfg, seed, seconds, env):
    """One run of a training cell; returns the facts."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu.ops import kernel_path
    facts = common.Facts(cell, cfg, env.devices, seconds)
    arch, ref = architectures.load(cfg), architectures.reference(cfg)
    traffic = cell["traffic"]
    batch, seq_len = int(traffic["batch"]), int(traffic["seq_len"])
    fetch_every = int(traffic["fetch_loss_every"])
    check(seq_len <= arch.max_positions(cfg),
          "seq_len %d exceeds the configuration's %d positions", seq_len,
          arch.max_positions(cfg))
    mesh = cell.get("mesh")
    strategy = arch.strategy(cfg, mesh, env.devices) if mesh else None
    rs = np.random.RandomState(seed)

    def step_feed():
        return arch.train_feed(rs, cfg, traffic)["feed"]

    kernels0 = kernel_path.counts()
    with lm.flags(**cfg["flags"]), ptpu.scope_guard(ptpu.Scope()), \
            ptpu.unique_name.guard():
        with env.phase("init_weights"):
            main, startup, loss = arch.train_program(cfg, traffic, seed)
            exe = ptpu.Executor(strategy=strategy)
            exe.run(startup)
        scope = ptpu.global_scope()

        # -- the check step: one seeded sequence in every row of the batch,
        # so the program's mean loss is that sequence's loss; the reference
        # reads the initial weights before the step donates them.
        with env.phase("check_and_warm"):
            one = arch.train_feed(rs, cfg, dict(traffic, batch=1))
            weights = ref.gather_weights(scope.find_var, cfg)
            want = float(jax.jit(lambda w, *row: ref.loss(w, *row, cfg))(
                weights, *(jnp.asarray(r[0]) for r in one["reference_rows"])))
            del weights
            feed = {k: np.repeat(v, batch, axis=0)
                    for k, v in one["feed"].items()}
            units = one["units_per_step"] * batch    # tokens, for an LM
            got = _scalar(exe.run(main, feed=feed, fetch_list=[loss])[0])
            facts.compare(
                "check_loss_abs_diff", abs(got - want), LOSS_ATOL,
                "first step's loss %.6f differs from the reference's %.6f "
                "by more than %g", got, want, LOSS_ATOL)
            warm = []
            for _ in range(int(traffic.get("warm_steps", 2))):
                warm.append(_scalar(exe.run(
                    main, feed=step_feed(), fetch_list=[loss])[0]))
        paths = common.kernel_paths_since(kernels0)
        for kernel in arch.kernels("train"):
            common.check_kernel_compiled(kernel, paths, env.on_tpu)

        # -- the window
        stamps, losses, count = [], [], [0]
        # blocks handed to the device and not fetched yet:
        # (steps so far, the last step's loss on the device, when handed over)
        pending = collections.deque()

        def dispatch_block():
            """Hand the device the next ``fetch_every`` steps; waits for
            nothing."""
            handed = time.perf_counter()
            for _ in range(fetch_every):
                with common.span("feed"):
                    out = exe.run(main, feed=step_feed(),
                                  fetch_list=[loss], return_numpy=False)[0]
                count[0] += 1
            pending.append((count[0], out, handed))

        def fetch_oldest():
            k, out, _ = pending.popleft()
            with common.span("fetch_loss"):
                losses.append(_scalar(out))
            stamps.append((k, time.perf_counter()))

        def closes(deadline):
            """Whether the block the device is running should end at or after
            ``deadline``, by what the last block took: then nothing is queued
            behind it, and the window closes on an idle device."""
            if not pending or len(stamps) < 2:
                return False
            (ka, ta), (kb, tb) = stamps[-2:]
            k, _, handed = pending[0]
            # it started when the block before it ended, or, on an idle
            # device, when it was handed over
            return max(tb, handed) + (tb - ta) / (kb - ka) * (k - kb) \
                >= deadline

        def steps_until(deadline):
            """Run blocks of steps until a fetched loss is stamped at or
            after ``deadline`` on the host clock, with one block queued
            behind the running one. Returns with nothing in flight: what
            was handed over beyond the deadline is the window's too."""
            while True:
                while len(pending) < 2 and not closes(deadline):
                    dispatch_block()
                fetch_oldest()
                if stamps[-1][1] >= deadline:
                    while pending:
                        fetch_oldest()
                    return

        comp0 = env.meter.snapshot()
        t0 = time.perf_counter()
        stamps.append((0, t0))
        if env.trace:
            # the last few seconds of the window are traced; both ends of
            # the traced part fall on a fetched loss, and the window closes
            # before the profiler stops and writes its trace
            trace_s = float(cell.get("trace_seconds", 3.0))
            steps_until(t0 + max(0.0, seconds - trace_s))
            with env.traced():
                steps_until(time.perf_counter() + trace_s)
        else:
            steps_until(t0 + seconds)
        n, t1 = stamps[-1]
        compiles = env.meter.delta(env.meter.snapshot(), comp0)

    window = t1 - t0
    tokens = n * units
    facts.attempted, facts.failed = n, 0
    facts.compare("window_losses_not_finite",
                  sum(1 for x in losses if not np.isfinite(x)), 0,
                  "loss not finite inside the window: %r", losses[:8])
    facts.compiles = compiles
    # host clock between neighbouring fetched losses over the steps between
    # them: kept for the notes (how far single blocks lie from the median)
    per_step = [(tb - ta) / (kb - ka) * 1e3
                for (ka, ta), (kb, tb) in zip(stamps, stamps[1:]) if kb > ka]
    obs = facts.observed
    obs["setup_s"] = env.setup_seconds(t0)
    step_s = common.median_slope(stamps)
    obs["train_step_p50_ms"] = step_s * 1e3
    obs["train_tokens_per_s"] = units / step_s
    obs["seq_len"] = seq_len
    facts.samples = {"stamps": [[k, t - t0] for k, t in stamps]}
    facts.notes = {
        "window_s": window, "steps": n, "tokens": tokens,
        "tokens_per_s_whole_window": tokens / window,
        "step_samples": len(per_step),
        "train_step_p50_ms": obs["train_step_p50_ms"],
        "block_step_p50_ms": common.quantile(per_step, 0.5),
        "block_step_p90_ms": common.quantile(per_step, 0.9),
        "check_loss": {"program": got, "reference": want, "atol": LOSS_ATOL},
        "warm_losses": warm, "window_losses": losses[:3] + losses[-3:],
        "kernel_paths": paths, "mesh": mesh,
        "executor_compiles": exe.compile_stats(),
    }
    return facts
