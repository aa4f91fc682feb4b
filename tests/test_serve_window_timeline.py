"""tools/serve_window_timeline.py: the books it makes of a window's token
stamps, on stamps written by hand (the run itself is the chip's)."""

import importlib.util
import os
import types

import numpy as np
import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "serve_window_timeline.py")
_spec = importlib.util.spec_from_file_location("serve_window_timeline", _PATH)
swt = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(swt)

STEP = 0.010


def _requests():
    """Two requests get their first tokens at t = 0 and decode a step apart; a third's prefill takes
    30 ms after the step at 0.05 and joins them; after the step at 0.15
    nothing is handed over for 25 ms more than a step."""
    times, t = [], 0.0
    for i in range(20):
        times.append(t)
        t += STEP + (0.030 if i == 5 else 0.0) + (0.025 if i == 14 else 0.0)
    a = [(x, 7) for x in times]
    b = [(x + 2e-4, 8) for x in times]
    first = times[5] + 0.029
    c = [(first, 9)] + [(x + 4e-4, 9) for x in times[6:]]
    return [types.SimpleNamespace(tokens=r) for r in (a, b, c)], times


def test_events_are_bursts_of_stamps_with_their_first_tokens():
    requests, times = _requests()
    evs = swt.events(requests)
    assert len(evs) == len(times) + 1
    assert evs[0][1:] == (2, 2)
    assert [e[1:] for e in evs[1:6]] == [(2, 0)] * 5
    assert evs[6][1:] == (1, 1)
    assert all(e[1:] == (3, 0) for e in evs[7:])


@pytest.mark.parametrize("slices", [1, 3])
def test_the_books_tell_a_prefill_from_a_stall(slices):
    requests, times = _requests()
    w0, w1 = 0.005, times[-1] + 0.001     # opens after the first tokens
    edges = np.linspace(w0, w1, slices + 1)
    whole, parts = swt.timeline(swt.events(requests), w0, w1, edges)
    assert whole["step_ms"] == pytest.approx(STEP * 1e3)
    assert whole["decode_events"] == 19
    assert (whole["prefills"], whole["stalls"]) == (1, 1)
    assert whole["prefill_s"] == pytest.approx(0.030, abs=5e-4)
    assert whole["stall_s"] == pytest.approx(0.025)
    assert whole["worst_stalls"] == [(round(times[15] - w0, 3), 0.025)]
    assert whole["tokens_per_decode_event"] == pytest.approx(
        (5 * 2 + 14 * 3) / 19)
    assert len(parts) == slices
    for key in ("decode_events", "prefills", "stalls"):
        assert sum(p[key] for p in parts) == whole[key]
