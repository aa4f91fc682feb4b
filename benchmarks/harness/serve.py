"""The ``serve`` kind of cell: the LM behind ``GenerationScheduler.submit``,
the entry point a user calls, under a traffic mix from the load generator.

Set-up builds the weights on the device through the startup program, makes
the paged session of the configuration's deployment geometry with the cell's
prompt buckets, and warms exactly those programs by the correctness check
itself: one seeded prompt per bucket is prefilled and decoded for
``CHECK_STEPS`` steps through the paged cache, and the decode logits are
compared with the plain reference's full forward over the same tokens.
"""

import contextlib
import threading
import time

import numpy as np

from . import common, lm, loadgen
from .. import architectures
from .common import check

CHECK_STEPS = 8
# after the window closes and before the dispatcher is parked: longer than
# any token gap of a healthy run, so that the last step's tokens are seen
LINGER_S = 1.5
# the window is cut into slices of this many seconds and
# ``output_tokens_per_s`` is the mean over the middle half of them of the
# tokens a second, so that a slice in which the host or the machine stalled
# does not move it. The middle half and not the median: on the chip the
# slices of lm-serve-offline differ by +-3.5% (a decode step costs more as
# the contexts in its batch grow), so neighbours in their order lie 0.5%
# apart and one spoiled slice would move a median by that. Tokens over the
# whole window's seconds stay as ``delivered_tokens_per_s``
SLICE_S = 3.0

# Program vs reference logits, as a share of the largest |reference logit|.
# The program multiplies in bf16, its logits are bf16 under amp (ulp 2^-8 of
# the value) and its K/V blocks are bf16; the reference is float32 at
# "highest". On the chip the worst reading over all five buckets was 0.0086
# of the largest |logit| of 1.2 (my chip runs, PR 22): two bf16 ulps.
# 2.5e-2 is three times that, room for another seed's draw, and far below
# what a wrong block, position or mask does (errors of the order of the
# logits themselves). chip_smoke.py's 4e-2, kernel against XLA, was the
# starting point.
LOGIT_RTOL = 2.5e-2


class Deployment:
    """The system under test, set up once for a cell."""

    def __init__(self, cell, cfg, seed, env):
        import paddle_tpu as ptpu
        from paddle_tpu.ops import kernel_path
        from paddle_tpu.serving.generation import GenerationSession
        self.cell, self.cfg, self.env = cell, cfg, env
        self.arch = architectures.load(cfg)
        self.geometry = dict(cfg["deployment"]["serving"])
        self.buckets = tuple(cell["prompt_buckets"])
        # flags and a private scope for the deployment's life; close() ends it
        self._life = contextlib.ExitStack()
        self._life.enter_context(lm.flags(
            generation_paged_kv=True,
            generation_kv_dtype=self.geometry["kv_dtype"], **cfg["flags"]))
        self._life.enter_context(ptpu.scope_guard(ptpu.Scope()))
        kernels0 = kernel_path.counts()
        with env.phase("init_weights"):
            with ptpu.unique_name.guard():
                startup = self.arch.serve_startup(cfg, seed)
            ptpu.Executor().run(startup)
        self.spec = self.arch.serve_spec(cfg, self.geometry, self.buckets)
        check(self.spec.paged, "the session is not paged")
        self.session = GenerationSession(self.spec)
        self.scheduler = None
        self._parking, self._parked = threading.Event(), threading.Event()
        self._forever = threading.Event()       # never set
        with env.phase("check_and_warm"):
            self.check_report = self._check_against_reference(seed)
        self.kernel_paths = common.kernel_paths_since(kernels0)
        for kernel in self.arch.kernels("serve"):
            common.check_kernel_compiled(kernel, self.kernel_paths,
                                         env.on_tpu)

    def _check_against_reference(self, seed):
        """Prefill one seeded prompt per bucket, decode CHECK_STEPS steps
        through the paged cache, and compare the decode logits (and the
        prefill's token) with the reference's full forward."""
        import jax
        import jax.numpy as jnp
        ref = architectures.reference(self.cfg)
        sess, spec, cfg = self.session, self.spec, self.cfg
        vocab = self.arch.vocab(cfg)
        rs = np.random.RandomState(seed + 7919)
        width = self.buckets[-1]
        # a prompt that fills most of its bucket and leaves room to decode
        lens = [min(b - 2, width - CHECK_STEPS - 2) for b in self.buckets]
        prompts = [rs.randint(2, vocab, n).astype(np.int64) for n in lens]
        slots, toks = [], []
        for p in prompts:
            slot, first = sess.admit(p)
            slots.append(slot)
            toks.append([first])
        logits_name = lm.logits_var(spec.decode_program, spec.decode_fetch)
        got = [[] for _ in prompts]
        for _ in range(CHECK_STEPS):
            prepared = sess.step_prepare()
            # the decode step with its logits fetched beside the token; the
            # step rewrites the same K/V row, so running it twice is safe
            logits = sess.exe.run(
                spec.decode_program, feed=prepared[2],
                fetch_list=[logits_name, spec.decode_fetch],
                scope=sess.scope)[0]
            logits = np.asarray(logits, np.float32)
            out = sess.step_run(prepared)
            for i, slot in enumerate(slots):
                got[i].append(logits[slot])
                toks[i].append(out[slot])
        for slot in slots:
            sess.retire(slot)

        weights = ref.gather_weights(sess.scope.find_var, cfg)
        ref_fn = jax.jit(lambda w, t, pos: ref.logits_at(w, t, pos, cfg))
        report = []
        for i, (p, n) in enumerate(zip(prompts, lens)):
            seq = np.zeros(width, np.int32)
            seq[:n] = p
            seq[n:n + CHECK_STEPS + 1] = toks[i]
            pos = np.arange(n - 1, n + CHECK_STEPS, dtype=np.int32)
            want = np.asarray(ref_fn(weights, jnp.asarray(seq),
                                     jnp.asarray(pos)))
            scale = float(np.abs(want).max())
            err = float(np.abs(np.stack(got[i]) - want[1:]).max())
            first = toks[i][0]
            first_gap = float(want[0].max() - want[0][first])
            report.append({"bucket": int(self.buckets[i]), "prompt_len": n,
                           "max_abs_err": err, "max_abs_logit": scale,
                           "first_token_gap": first_gap})

        def worst(key):
            # np.max and not max(): a logit that is no number stays the worst
            return float(np.max([r[key] / r["max_abs_logit"]
                                 for r in report]))
        return {"rtol": LOGIT_RTOL, "worst_rel_err": worst("max_abs_err"),
                "worst_first_token_rel_gap": worst("first_token_gap"),
                "per_bucket": report}

    def open(self):
        from paddle_tpu.serving.generation import GenerationScheduler
        self.scheduler = GenerationScheduler(
            self.session, max_queue=int(self.cell.get("max_queue", 1024)),
            deadline_ms=0)
        return self

    def submit(self, req):
        """One request through the front door. The observer runs on the
        dispatcher thread: it appends one (time, token) pair, and once the
        run is over it parks the dispatcher (see ``park``)."""
        stamp, clock, parking = (req.tokens.append, time.perf_counter,
                                 self._parking)

        def on_token(tok):
            stamp((clock(), tok))
            if parking.is_set():
                self._parked.set()
                self._forever.wait()
        return self.scheduler.submit(
            req.prompt, max_new_tokens=req.n_out, eos_id=-1,
            on_token=on_token)

    def park(self, timeout=30.0):
        """End a run without serving out what is in flight: the scheduler
        has no way to abandon accepted requests, and serving them out can
        take minutes that every run of every later check would pay. The
        observer holds the dispatcher at its next token, which is between
        two device calls, so the process can then exit with the device
        idle. Nothing can be offered afterwards. An idle dispatcher (no
        request in flight) never reaches the observer, and needs no parking."""
        self._parking.set()
        self._parked.wait(timeout)

    def close(self):
        """Serve out everything accepted, then release the session."""
        if self.scheduler is not None:
            self.scheduler.drain(timeout=600.0)
            self.scheduler = None
        self.session.close()
        self._life.close()


def offer(dep, traffic, seed, seconds, trace_window=None, drain=True):
    """Offer ``traffic`` to an open deployment: lead-in, then a window of
    ``seconds``; then wait for what was sent (``drain``), or park the
    dispatcher and take what has resolved. Returns the requests, the window
    on the generator's clock, the program's counter deltas over the window
    and the compile-meter delta."""
    lead_in = float(traffic.get("lead_in_s", 0.0))
    vocab = dep.arch.vocab(dep.cfg)
    # 30 s more than the plan, so that the generator cannot run dry before
    # the window (and a traced run's traced seconds) has closed
    requests = loadgen.draw_requests(traffic, seed, vocab,
                                     lead_in + seconds + 30.0)
    gen = loadgen.LoadGenerator(traffic, requests, dep.submit,
                                span=common.span)
    meter = dep.env.meter
    gen.start()
    time.sleep(max(0.0, lead_in - gen.now()))
    w0 = gen.now()
    reg0, comp0 = common.registry_snapshot(), meter.snapshot()
    # in a traced run the measured window closes before the profiler starts:
    # stopping it holds every Python thread for seconds (6 s read on the
    # chip), which would count as the system's tail and the generator's
    # lateness; the traced seconds that follow give the device's shares only
    measured = seconds - (trace_window or 0.0)
    time.sleep(max(0.0, w0 + measured - gen.now()))
    reg1, comp1 = common.registry_snapshot(), meter.snapshot()
    w1 = gen.now()
    if trace_window:
        with dep.env.traced():
            time.sleep(trace_window)
            gen.stop()      # nothing is sent while the profiler stops
    else:
        gen.stop()
    if not drain:
        # let the step that straddles the window's end hand over its tokens
        time.sleep(LINGER_S)
        dep.park()
    for r in requests:
        if r.future is not None and (drain or r.future.done()):
            try:
                r.result = np.asarray(r.future.result(timeout=600.0))
            except Exception as e:  # noqa: BLE001 — counted as failed
                r.error = "%s: %s" % (type(e).__name__, str(e)[:200])
    # the generator's clock: perf_counter minus gen.t0
    for r in requests:
        r.tokens = [(t - gen.t0, tok) for t, tok in r.tokens]
    counters, hists = common.registry_delta(reg1, reg0)
    return {"requests": requests, "w0": w0, "w1": w1, "counters": counters,
            "hists": hists, "compiles": meter.delta(comp1, comp0),
            "sent": gen.sent, "t_window": gen.t0 + w0}


def client_numbers(requests, w0, w1, vocab):
    """Everything the client side saw, from the observer's (time, token)
    pairs and the futures that have resolved.

    ``problems`` holds what makes the run incorrect: a resolved request
    that did not get exactly the tokens it asked for, a request that saw
    more tokens than it asked for, or a token id out of range. A request
    still in flight when the run ended (only where the run did not drain)
    is checked on what it has."""
    sent = [r for r in requests if r.sent is not None]
    gaps_ms, ttft_ms, problems = [], [], []
    failed = resolved = 0
    # tokens handed over up to each edge of the window's slices. A request's
    # first token counts whole at its time; a later one is spread evenly
    # over the gap that ends in it, so a count moves smoothly with an edge,
    # not by a whole decode step of tokens
    edges = np.linspace(w0, w1, max(1, int((w1 - w0) // SLICE_S)) + 1)
    handed = np.zeros(edges.size, np.float64)
    for r in sent:
        t = np.asarray([x[0] for x in r.tokens], np.float64)
        ids = np.asarray([x[1] for x in r.tokens], np.int64)
        if t.size:
            handed += np.where(edges >= t[0], 1.0 + np.interp(
                edges, t, np.arange(t.size, dtype=np.float64)), 0.0)
        if t.size > 1:
            later, gap = t[1:], np.diff(t)
            gaps_ms.extend((gap[(later >= w0) & (later < w1)] * 1e3).tolist())
        start = r.due if r.due is not None else r.sent
        in_window = w0 <= start < w1
        if r.error is not None:
            failed += 1
            if in_window:
                ttft_ms.append(np.inf)  # a failure counts as the worst
            continue
        if in_window:
            # no first token yet: it waited at least until the window closed
            ttft_ms.append((t[0] - start) * 1e3 if t.size else np.inf)
        if t.size > r.n_out or not ((ids >= 0) & (ids < vocab)).all():
            problems.append("request %d asked for %d tokens and observed %d;"
                            " ids in range: %s" % (
                                r.index, r.n_out, t.size,
                                bool(((ids >= 0) & (ids < vocab)).all())))
        if r.result is not None:
            resolved += 1
            if r.result.shape != (r.n_out,) or t.size != r.n_out or \
                    not np.array_equal(r.result, ids):
                problems.append("request %d asked for %d tokens, resolved "
                                "with %d, observed %d" % (
                                    r.index, r.n_out, r.result.size, t.size))
    ttft_ms = np.asarray(ttft_ms, np.float64)
    if ttft_ms.size and np.isinf(ttft_ms).any():
        # "the worst": above every wait that was measured or still runs
        worst = max([w1 - (r.due if r.due is not None else r.sent)
                     for r in sent] + [0.0]) * 1e3
        finite = ttft_ms[np.isfinite(ttft_ms)]
        ttft_ms[np.isinf(ttft_ms)] = max(worst, finite.max(initial=0.0))
    return {"attempted": len(sent), "failed": failed, "resolved": resolved,
            "tokens_in_window": float(handed[-1] - handed[0]),
            "slice_tokens_per_s": np.diff(handed) / np.diff(edges),
            "gaps_ms": np.asarray(gaps_ms), "ttft_ms": ttft_ms,
            "late_ms": loadgen.lateness_ms(
                [r for r in sent if r.due is not None and w0 <= r.due < w1]),
            "problems": problems}


def run(cell, cfg, seed, seconds, env):
    """One run of a serving cell; returns the facts."""
    facts = common.Facts(cell, cfg, env.devices, seconds)
    dep = Deployment(cell, cfg, seed, env)
    try:
        dep.open()
        got = offer(dep, cell["traffic"], seed, seconds,
                    trace_window=float(cell.get("trace_seconds", 3.0))
                    if env.trace else None, drain=env.drain)
        session_compiles = dep.session.compile_stats()
    finally:
        if env.drain:
            dep.close()
    nums = client_numbers(got["requests"], got["w0"], got["w1"],
                          dep.arch.vocab(cfg))
    window = got["w1"] - got["w0"]
    facts.attempted, facts.failed = nums["attempted"], nums["failed"]
    report = dep.check_report
    facts.compare(
        "decode_logit_rel_err", report["worst_rel_err"], report["rtol"],
        "decode logits differ from the reference's (max_abs_err over "
        "max_abs_logit, tolerance %g): %r", report["rtol"],
        report["per_bucket"])
    facts.compare(
        "prefill_token_rel_gap", report["worst_first_token_rel_gap"],
        report["rtol"], "a prefill's token lies below the reference's best "
        "logit (first_token_gap over max_abs_logit, tolerance %g): %r",
        report["rtol"], report["per_bucket"])
    facts.compare("requests_with_wrong_tokens", len(nums["problems"]), 0,
                  "%s", "; ".join(nums["problems"][:5]))
    facts.counters, facts.hists = got["counters"], got["hists"]
    facts.compiles = got["compiles"]
    facts.samples = {k: nums[k].tolist()
                     for k in ("ttft_ms", "gaps_ms", "late_ms",
                               "slice_tokens_per_s")}
    obs = facts.observed
    obs["setup_s"] = env.setup_seconds(got["t_window"])
    obs["output_tokens_per_s"] = common.midmean(nums["slice_tokens_per_s"])
    obs["delivered_tokens_per_s"] = nums["tokens_in_window"] / window
    obs["itl_p50_ms"] = common.quantile(nums["gaps_ms"], 0.50)
    obs["itl_p99_ms"] = common.quantile(nums["gaps_ms"], 0.99)
    obs["ttft_p90_ms"] = common.quantile(nums["ttft_ms"], 0.90)
    obs["gen_late_p99_ms"] = common.quantile(nums["late_ms"], 0.99)
    facts.notes = {
        "window_s": window, "requests_sent": got["sent"],
        "requests_resolved": nums["resolved"],
        "requests_due_in_window": int(nums["ttft_ms"].size),
        "token_gaps_in_window": int(nums["gaps_ms"].size),
        "tokens_in_window": nums["tokens_in_window"],
        "delivered_tokens_per_s": obs["delivered_tokens_per_s"],
        "output_tokens_per_s": obs["output_tokens_per_s"],
        "slices": int(nums["slice_tokens_per_s"].size),
        "ttft_p50_ms": common.quantile(nums["ttft_ms"], 0.50),
        "itl_p50_ms": obs["itl_p50_ms"],
        "gen_late_p50_ms": common.quantile(nums["late_ms"], 0.5),
        "ttft_p90_ms": obs["ttft_p90_ms"], "itl_p99_ms": obs["itl_p99_ms"],
        "reference_check": dep.check_report,
        "kernel_paths": dep.kernel_paths,
        "session_compiles": session_compiles,
    }
    return facts
