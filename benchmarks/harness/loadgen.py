"""The one general load generator: reads a traffic mix (a data file of
parameters) and a seed, draws every request before the window opens, and
offers them in an open loop (Poisson arrivals at a fixed rate, each request
timed from the moment it was *due*) or a closed loop (a fixed number of
clients, each sending its next request when its last resolves).

A traffic mix, as it sits under ``"traffic"`` in a cell file:

    {"loop": "open", "rate_per_s": 12.0, "schedule_seed": 22,
     "prompt_len": {"dist": "lognormal", "median": 600, "sigma": 0.7,
                    "lo": 64, "hi": 1984},
     "output_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                    "lo": 8, "hi": 48},
     "lead_in_s": 3.0}

    {"loop": "closed", "clients": 64, "ramp_requests": 32, ...}

The generator shares its process, and the interpreter lock, with the system
under test (one process holds the chip), so inside the window a client only
sleeps until the due time and submits. How late it ran is part of the result.
"""

import contextlib
import queue
import statistics
import threading
import time

import numpy as np


_NORMAL = statistics.NormalDist()
# requests are drawn in blocks of this many: inside a block the lengths and
# the arrival gaps are a stratified sample (one draw from each of the
# block's equal-probability strata, in seeded order), so that every run
# offers nearly the same amount of work whatever its seed, and the seed
# decides only the order and the token ids
BLOCK = 64


def stratified_uniforms(rs, n, block=None):
    """``n`` numbers in (0, 1): each block of ``block`` holds one draw from
    each of its strata, shuffled."""
    block = block or BLOCK
    u = np.empty(n, np.float64)
    for start in range(0, n, block):
        m = min(block, n - start)
        u[start:start + m] = (rs.permutation(m) + rs.uniform(0, 1, m)) / m
    return np.clip(u, 1e-9, 1 - 1e-9)


def draw_lengths(spec, rs, n):
    """``n`` integer lengths from a distribution spec, through its
    quantile function on stratified uniforms."""
    dist = spec["dist"]
    u = stratified_uniforms(rs, n)
    if dist == "uniform":
        out = np.floor(spec["lo"] + u * (spec["hi"] + 1 - spec["lo"]))
    elif dist == "lognormal":
        z = np.asarray([_NORMAL.inv_cdf(x) for x in u])
        out = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError("unknown length distribution %r" % (dist,))
    lo, hi = spec.get("lo", 1), spec.get("hi", np.inf)
    return np.clip(out, lo, hi).astype(np.int64)


class Request:
    """One request and everything the client side observes about it.
    ``tokens`` is appended to by the system's ``on_token`` observer: one
    (time, token) pair per token, nothing else."""

    __slots__ = ("index", "prompt", "n_out", "due", "sent", "tokens",
                 "future", "error", "result")

    def __init__(self, index, prompt, n_out, due=None):
        self.index = index
        self.prompt = prompt
        self.n_out = int(n_out)
        self.due = due          # seconds after the generator's start
        self.sent = None        # same clock
        self.tokens = []        # (time on the same clock, token id)
        self.future = None
        self.error = None
        self.result = None


def draw_requests(traffic, seed, vocab, horizon_s):
    """Every request the run can need. The *schedule* (arrival times, prompt
    and output lengths) is drawn from the traffic file's ``schedule_seed``
    and is the same in every run; the run's ``seed`` draws the token ids.
    Decoding is greedy with no end-of-sequence token, so timing does not
    depend on token values, and every run offers exactly the same work: at a
    hundred-odd requests a window, the work of a fresh Poisson draw differs
    by 6% between seeds, more than any bound on a tail could absorb.

    Open loop: the Poisson arrivals of ``horizon_s`` seconds, each with its
    due time. Closed loop: ``max_requests`` requests, to be taken in order."""
    sched = np.random.RandomState(int(traffic["schedule_seed"]))
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        n_max = int(rate * horizon_s * 1.5) + BLOCK
        # exponential gaps (a Poisson process), stratified like the lengths
        due = np.cumsum(-np.log1p(-stratified_uniforms(sched, n_max)) / rate)
        due = due[due < horizon_s]
        n = due.size
    else:
        n = int(traffic["max_requests"])
        due = [None] * n
    plen = draw_lengths(traffic["prompt_len"], sched, n)
    olen = draw_lengths(traffic["output_len"], sched, n)
    ramp = int(traffic.get("ramp_requests", 0))
    if ramp:
        # the first admissions would otherwise all end together: cut each
        # to a uniform share of its length, as if it had started earlier
        cut = np.rint(olen[:ramp] * sched.uniform(0.05, 1.0, ramp))
        olen[:ramp] = np.maximum(1, cut).astype(np.int64)
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(2, vocab, int(p)).astype(np.int64) for p in plen]
    return [Request(i, prompts[i], olen[i],
                    None if due[i] is None else float(due[i]))
            for i in range(n)]


class LoadGenerator:
    """Offers ``requests`` through ``submit(request) -> Future`` from one
    thread. ``start()`` fixes time zero; ``stop()`` ends the offering (what
    was sent is still served) and joins the thread."""

    def __init__(self, traffic, requests, submit, span=None):
        self.traffic = traffic
        self.requests = requests
        self._submit = submit
        self._span = span or (lambda _name: contextlib.nullcontext())
        self._stop = threading.Event()
        self._done = queue.SimpleQueue()     # closed loop: finished clients
        self._thread = None
        self.t0 = None
        self.sent = 0
        self.error = None       # why the offering ended early, if it did

    def now(self):
        return time.perf_counter() - self.t0

    def start(self):
        target = self._open_loop if self.traffic["loop"] == "open" \
            else self._closed_loop
        self.t0 = time.perf_counter()
        self._thread = threading.Thread(target=target, name="loadgen",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout=10.0):
        self._stop.set()
        self._done.put(None)
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("load generator did not stop")
        if self.error:
            raise RuntimeError(self.error)

    def _send(self, req):
        req.sent = self.now()
        try:
            with self._span("submit"):
                req.future = self._submit(req)
        except Exception as e:  # noqa: BLE001 — a refusal is a result
            req.error = "%s: %s" % (type(e).__name__, str(e)[:200])
        self.sent += 1

    def _open_loop(self):
        for req in self.requests:
            wait = req.due - self.now()
            if wait > 0:
                with self._span("generator_sleep"):
                    if self._stop.wait(wait):
                        return
            elif self._stop.is_set():
                return
            self._send(req)

    def _closed_loop(self):
        pending = iter(self.requests)
        for _ in range(int(self.traffic["clients"])):
            req = next(pending, None)
            if req is None:
                break
            self._send_closed(req)
        while True:
            with self._span("generator_sleep"):
                got = self._done.get()
            if got is None or self._stop.is_set():
                return
            req = next(pending, None)
            if req is None:
                self.error = ("closed loop ran out of drawn requests: raise "
                              "max_requests in the traffic file")
                return
            self._send_closed(req)

    def _send_closed(self, req):
        self._send(req)
        if req.future is None:
            self._done.put(req.index)   # refused: the client moves on
        else:
            req.future.add_done_callback(
                lambda _f, i=req.index: self._done.put(i))


def lateness_ms(requests):
    """Send time minus due time of every request that was sent, in ms."""
    return np.asarray([(r.sent - r.due) * 1e3 for r in requests
                       if r.sent is not None and r.due is not None])
