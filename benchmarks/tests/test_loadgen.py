"""The load generator: the same seed gives the same requests, due times
are accounted as the cell's metrics assume, and the closed loop keeps its
number of clients in flight."""

import threading
import time
from concurrent.futures import Future

import numpy as np

from benchmarks.harness import loadgen

OPEN = {"loop": "open", "rate_per_s": 200.0, "schedule_seed": 5,
        "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.7,
                       "lo": 4, "hi": 60},
        "output_len": {"dist": "uniform", "lo": 2, "hi": 6}}
CLOSED = {"loop": "closed", "clients": 5, "ramp_requests": 3,
          "schedule_seed": 5,
          "max_requests": 200,
          "prompt_len": {"dist": "uniform", "lo": 4, "hi": 9},
          "output_len": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                         "lo": 10, "hi": 80}}


def _same(a, b):
    return len(a) == len(b) and all(
        x.due == y.due and x.n_out == y.n_out and
        np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_same_seed_same_requests():
    a = loadgen.draw_requests(OPEN, 3, 100, 2.0)
    assert _same(a, loadgen.draw_requests(OPEN, 3, 100, 2.0))
    c = loadgen.draw_requests(CLOSED, 3, 100, 2.0)
    assert _same(c, loadgen.draw_requests(CLOSED, 3, 100, 2.0))


def test_seed_draws_the_tokens_and_the_file_the_schedule():
    a = loadgen.draw_requests(OPEN, 3, 100, 2.0)
    b = loadgen.draw_requests(OPEN, 4, 100, 2.0)
    # another seed: the same work at the same times, other tokens
    assert [(r.due, r.n_out, r.prompt.size) for r in a] == \
        [(r.due, r.n_out, r.prompt.size) for r in b]
    assert not _same(a, b)
    # another schedule_seed: another schedule
    c = loadgen.draw_requests(dict(OPEN, schedule_seed=6), 3, 100, 2.0)
    assert [r.due for r in a] != [r.due for r in c]


def test_draws_stay_inside_their_limits():
    reqs = loadgen.draw_requests(OPEN, 1, 100, 5.0)
    assert 800 < len(reqs) < 1200            # 200/s for 5 s
    due = np.asarray([r.due for r in reqs])
    assert (np.diff(due) > 0).all() and due[-1] < 5.0
    assert all(4 <= r.prompt.size <= 60 for r in reqs)
    assert all(2 <= r.n_out <= 6 for r in reqs)
    assert all(((r.prompt >= 2) & (r.prompt < 100)).all() for r in reqs)
    # mean gap of a Poisson process at 200/s
    assert abs(np.diff(due).mean() - 1 / 200.0) < 1e-3


def test_ramp_cuts_only_the_first_requests():
    reqs = loadgen.draw_requests(CLOSED, 1, 100, 1.0)
    full = loadgen.draw_requests(dict(CLOSED, ramp_requests=0), 1, 100, 1.0)
    assert all(a.n_out <= b.n_out for a, b in zip(reqs[:3], full[:3]))
    assert any(a.n_out < b.n_out for a, b in zip(reqs[:3], full[:3]))
    assert [r.n_out for r in reqs[3:]] == [r.n_out for r in full[3:]]


def _resolve_later(delay, futures, timers=None):
    def submit(req):
        f = Future()
        futures.append(f)
        timer = threading.Timer(delay, f.set_result, args=(req.index,))
        if timers is not None:
            timers.append(timer)
        timer.start()
        return f
    return submit


def test_open_loop_sends_at_due_times_whatever_the_system_does():
    reqs = loadgen.draw_requests(dict(OPEN, rate_per_s=100.0), 2, 100, 0.5)
    futures, timers = [], []
    # a system that never answers inside the run: the open loop must not
    # slow down for it
    gen = loadgen.LoadGenerator(OPEN, reqs,
                                _resolve_later(5.0, futures, timers))
    gen.start()
    time.sleep(0.6)
    gen.stop()
    assert gen.sent == len(reqs) == len(futures)
    late = loadgen.lateness_ms(reqs)
    assert late.size == len(reqs) and (late >= 0).all()
    assert np.median(late) < 20.0
    # latency is timed from the due time: the send time is never earlier
    assert all(r.sent >= r.due for r in reqs)
    for timer in timers:
        timer.cancel()


def test_open_loop_stop_ends_the_offering():
    reqs = loadgen.draw_requests(dict(OPEN, rate_per_s=50.0), 2, 100, 5.0)
    gen = loadgen.LoadGenerator(OPEN, reqs, _resolve_later(0.0, []))
    gen.start()
    time.sleep(0.2)
    gen.stop()
    assert 0 < gen.sent < len(reqs)
    assert all(r.sent is None for r in reqs[gen.sent:])


def test_closed_loop_keeps_its_clients_in_flight():
    reqs = loadgen.draw_requests(dict(CLOSED, max_requests=2000), 2, 100, 1.0)
    in_flight, peak, lock = [0], [0], threading.Lock()

    def submit(req):
        f = Future()
        with lock:
            in_flight[0] += 1
            peak[0] = max(peak[0], in_flight[0])

        def done():
            with lock:
                in_flight[0] -= 1
            f.set_result(req.index)
        threading.Timer(0.01, done).start()
        return f
    gen = loadgen.LoadGenerator(CLOSED, reqs, submit)
    gen.start()
    time.sleep(0.5)
    gen.stop()
    assert peak[0] == CLOSED["clients"]
    # about 5 clients / 10 ms for half a second, taken in drawn order
    assert 50 < gen.sent <= len(reqs)
    assert all(r.sent is not None for r in reqs[:gen.sent])


def test_closed_loop_client_moves_on_after_a_refusal():
    reqs = loadgen.draw_requests(dict(CLOSED, clients=1), 2, 100, 1.0)

    def submit(req):
        time.sleep(0.005)
        raise RuntimeError("refused")
    gen = loadgen.LoadGenerator(dict(CLOSED, clients=1), reqs, submit)
    gen.start()
    time.sleep(0.1)
    gen.stop()
    assert gen.sent > 1 and reqs[0].error.startswith("RuntimeError")


def test_closed_loop_says_when_it_ran_dry():
    traffic = dict(CLOSED, clients=2, max_requests=4)
    reqs = loadgen.draw_requests(traffic, 2, 100, 1.0)
    gen = loadgen.LoadGenerator(traffic, reqs, _resolve_later(0.0, []))
    gen.start()
    time.sleep(0.2)
    try:
        gen.stop()
    except RuntimeError as e:
        assert "ran out of drawn requests" in str(e)
    else:
        raise AssertionError("a dry closed loop must not pass in silence")
