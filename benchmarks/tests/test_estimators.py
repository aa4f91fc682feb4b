"""The throughput estimators look at the middle of the window's parts (the
median block of steps, the middle half of the slices), so that a stall of the
host or the machine in one part does not move them; the plain ratio of tokens
to seconds is kept beside them and does move."""

import numpy as np
import pytest

from benchmarks.harness import common, loadgen, serve


def _decoding(slots, gap, t_first, t_last, stall_at=None, stall=0.0):
    """``slots`` requests that each get a token every ``gap`` seconds; from
    ``stall_at`` on every token comes ``stall`` seconds later."""
    out = []
    for i in range(slots):
        times = np.arange(t_first + i * 1e-3, t_last, gap)
        if stall_at is not None:
            times = np.where(times >= stall_at, times + stall, times)
        r = loadgen.Request(i, np.zeros(4, np.int64), times.size, due=None)
        r.sent = 0.0
        r.tokens = [(float(t), 5) for t in times]
        out.append(r)
    return out


def test_midmean_drops_a_quarter_at_each_end():
    assert common.midmean([]) is None
    assert common.midmean([7.0]) == 7.0
    assert common.midmean([1.0, 2.0, 3.0]) == 2.0       # 3 // 4 == 0 dropped
    assert common.midmean([100.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, -50.0]) == 3.5
    # the slices of lm-serve-offline as read on the chip (my chip run,
    # PR 22), and the same with one slice that lost half its seconds
    chip = [111.5, 112.11, 112.87, 113.28, 113.31, 113.81, 114.5, 115.05,
            115.55, 115.88, 116.28, 117.19, 118.09, 118.92, 119.59]
    spoiled = [57.5 if x == 115.05 else x for x in chip]
    assert common.midmean(chip) == pytest.approx(114.98, abs=0.01)
    assert abs(common.midmean(spoiled) / common.midmean(chip) - 1) < 0.003
    assert abs(np.mean(spoiled) / np.mean(chip) - 1) > 0.03


def test_output_rate_is_the_middle_of_the_slices_and_ignores_a_stall():
    w0, w1 = 10.0, 55.0
    quiet = serve.client_numbers(_decoding(8, 0.25, 1.0, 70.0), w0, w1, 128)
    stalled = serve.client_numbers(
        _decoding(8, 0.25, 1.0, 70.0, stall_at=29.0, stall=1.5), w0, w1, 128)
    assert quiet["slice_tokens_per_s"].size == 15
    np.testing.assert_allclose(quiet["slice_tokens_per_s"], 32.0, rtol=1e-9)
    # tokens over seconds loses the stall: 1.5 s of 45
    assert quiet["tokens_in_window"] == pytest.approx(32.0 * 45.0)
    assert stalled["tokens_in_window"] == pytest.approx(32.0 * 43.5)
    # one slice of fifteen saw it; the middle half did not
    low = stalled["slice_tokens_per_s"] < 31.9
    assert low.sum() == 1
    assert common.midmean(stalled["slice_tokens_per_s"]) == pytest.approx(32.0)
    assert not stalled["problems"] and not quiet["problems"]


def test_slices_add_up_to_the_window_and_count_edges_by_share():
    # one request, tokens at 0.9, 1.1, 1.3 ...; the window opens at 1.0, in
    # the middle of the first gap, so half of that token is the window's
    r = loadgen.Request(0, np.zeros(4, np.int64), 40, due=None)
    r.sent = 0.0
    r.tokens = [(0.9 + 0.2 * i, 3) for i in range(40)]
    nums = serve.client_numbers([r], 1.0, 7.0, 128)
    assert nums["slice_tokens_per_s"].size == 2
    assert nums["tokens_in_window"] == pytest.approx(30.0)
    assert nums["slice_tokens_per_s"].sum() * 3.0 == pytest.approx(30.0)
    # a first token inside the window counts whole, at its time
    r2 = loadgen.Request(1, np.zeros(4, np.int64), 3, due=None)
    r2.sent = 0.0
    r2.tokens = [(2.0, 3), (2.5, 3), (3.5, 3)]
    nums = serve.client_numbers([r2], 1.0, 3.0, 128)
    assert nums["tokens_in_window"] == pytest.approx(1.0 + 1.0 + 0.5)


def _stamps(late=(), stall_at=None, stall=0.0, jitter=None):
    """Twelve stamps of blocks of 10 steps at 0.4275 s a step; ``late``
    stamps come 1.5 s late, from block ``stall_at`` on the device itself is
    ``stall`` seconds behind."""
    t = np.arange(12) * 4.275
    if jitter is not None:
        t = t + jitter
    for i in late:
        t[i] += 1.5
    if stall_at is not None:
        t[stall_at:] += stall
    return [(10 * i, float(x)) for i, x in enumerate(t)]


def test_median_slope_is_blind_to_late_stamps_and_to_one_stall():
    assert common.median_slope([]) is None
    assert common.median_slope([(0, 1.0)]) is None
    assert common.median_slope([(0, 1.0), (10, 5.275)]) == \
        pytest.approx(0.4275)
    assert common.median_slope(_stamps()) == pytest.approx(0.4275)
    # a stamp the host took late is in 6 of the 30 pairs
    assert common.median_slope(_stamps(late=[5])) == pytest.approx(0.4275)
    assert common.median_slope(_stamps(late=[0, 11])) == \
        pytest.approx(0.4275)
    # a stall of the device shifts every later stamp, and is in 6 pairs too;
    # tokens over elapsed seconds loses it whole
    stalled = _stamps(stall_at=6, stall=1.5)
    assert common.median_slope(stalled) == pytest.approx(0.4275)
    assert (stalled[-1][1] - stalled[0][1]) / 110 > 0.4275 * 1.03


def test_median_slope_averages_small_lateness_better_than_neighbours():
    rs = np.random.RandomState(0)
    wide, narrow = [], []
    for _ in range(400):
        st = _stamps(jitter=rs.exponential(0.08, 12))
        times = np.asarray([t for _, t in st])
        narrow.append(np.median(np.diff(times)) / 10)
        wide.append(common.median_slope(st))
    def spread(x):
        return np.subtract(*np.quantile(x, [0.75, 0.25])) / 0.4275
    assert abs(np.median(wide) / 0.4275 - 1) < 5e-4
    assert spread(wide) < 0.6 * spread(narrow)
    assert spread(wide) < 0.003
