"""The trace reduction: against a trace written by hand, where every number
can be counted on paper, and against a small slice of a trace recorded on
the chip (``data/``), where it is checked for what must hold of any trace."""

import glob
import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

MS = 1_000_000      # ns


def _trace(device_events, host_lines, chips=1, window=(0, 100 * MS)):
    planes = [{"name": "/device:TPU:%d" % c,
               "lines": [{"name": "XLA Ops", "events": device_events},
                         {"name": "XLA Modules",
                          "events": [["jit_fn", 0, 100 * MS, ""]]}]}
              for c in range(chips)]
    lines = [{"name": "python", "events": [
        [tr.WINDOW_SPAN, window[0], window[1] - window[0], ""]]}]
    lines += [{"name": "thread%d" % i, "events": evs}
              for i, evs in enumerate(host_lines)]
    planes.append({"name": "/host:CPU", "lines": lines})
    return {"planes": planes}


# device: busy [10,40) with a nested child [15,25), [50,70), and a
# collective [80,90): union 60 ms of a 100 ms window
DEVICE = [
    ["while -> (s32[], f32[64,128])", 10 * MS, 30 * MS, "while"],
    ["fusion(kOutput) -> f32[64,128]", 15 * MS, 10 * MS, "fusion"],
    ["custom-call(tpu_custom_call) -> bf16[32,1,128]", 50 * MS, 20 * MS,
     tr.MOSAIC],
    ["all-reduce -> f32[128,128]", 80 * MS, 10 * MS, tr.COLLECTIVE_CAT],
]
# host: the feed covers the first gap [0,10); fetch_loss covers [40,50) and
# [70,80); a long sleep on another thread covers everything
HOST = [
    [["bench:feed", 0, 10 * MS, ""],
     ["bench:fetch_loss", 40 * MS, 10 * MS, ""],
     ["bench:fetch_loss", 70 * MS, 10 * MS, ""]],
    [["bench:generator_sleep", 0, 100 * MS, ""]],
]


def test_busy_union_and_idle_share_by_hand():
    r = tr.reduce_trace(_trace(DEVICE, HOST))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.060)      # nested child not twice
    assert r["idle_share"] == pytest.approx(0.40)
    assert r["n_gaps"] == 4 and r["longest_gap_s"] == pytest.approx(0.010)


def test_self_time_and_top_operations_by_hand():
    r = tr.reduce_trace(_trace(DEVICE, HOST))
    ops = dict((n, s) for n, s in r["device_ops"])
    # the while's self time is its 30 ms minus the child's 10
    assert ops["while -> (s32[], f32[64,128])"] == pytest.approx(0.020)
    assert ops["fusion(kOutput) -> f32[64,128]"] == pytest.approx(0.010)
    assert ops["custom-call(tpu_custom_call) -> bf16[32,1,128]"] == \
        pytest.approx(0.020)
    assert sorted(s for _, s in r["device_ops"]) == \
        [s for _, s in r["device_ops"]][::-1]       # most time first
    assert sum(ops.values()) == pytest.approx(r["busy_s"])


def test_same_operation_in_every_layer_adds_up_under_one_name():
    twice = DEVICE + [[DEVICE[2][0], 92 * MS, 5 * MS, tr.MOSAIC]]
    r = tr.reduce_trace(_trace(twice, HOST))
    ops = dict((n, s) for n, s in r["device_ops"])
    assert ops[DEVICE[2][0]] == pytest.approx(0.025)


def test_hlo_event_names_are_parsed():
    text = ('%fusion.1552 = (f32[8192,2048]{1,0:T(8,128)}, bf16[8192,2048]'
            '{1,0:T(8,128)(2,1)S(1)}) fusion(f32[2048,50257]{0,1:T(8,128)} '
            '%state_rw__lm_head_w__.1, bf16[8192]{0} %custom-call.9), '
            'kind=kOutput, calls=%fused_computation.2215')
    assert tr.parse_hlo_event(text) == (
        "fusion.1552", "fusion(kOutput) -> (f32[8192,2048], bf16[8192,2048])",
        "fusion")       # an operand named custom-call does not make it one
    kernel = ('%fn.31 = bf16[32,1,2048]{2,1,0:T(2,128)(2,1)S(1)} custom-call('
              's32[32]{0:T(128)} %x), custom_call_target="tpu_custom_call"')
    assert tr.parse_hlo_event(kernel) == (
        "fn.31", "custom-call(tpu_custom_call) -> bf16[32,1,2048]", tr.MOSAIC)
    coll = ('%all-reduce-start.3 = f32[2048,2048]{1,0} all-reduce-start('
            'f32[2048,2048]{1,0} %p), replica_groups={{0,1}}')
    assert tr.parse_hlo_event(coll)[1:] == (
        "all-reduce-start -> f32[2048,2048]", tr.COLLECTIVE_CAT)
    assert tr.parse_hlo_event("dot_general.1") == (
        "dot_general.1", "dot_general.1", "")


def test_shares_by_hand():
    r = tr.reduce_trace(_trace(DEVICE, HOST))
    assert r["collective_share"] == pytest.approx(10 / 60)
    assert r["mosaic_share"] == pytest.approx(20 / 60)


def test_gap_goes_to_the_most_specific_span():
    r = tr.reduce_trace(_trace(DEVICE, HOST))
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    # the sleep covers every gap but is the least specific; the last gap
    # [90,100) has only the sleep
    assert gaps == {"bench:feed": pytest.approx(0.010),
                    "bench:fetch_loss": pytest.approx(0.020),
                    "bench:generator_sleep": pytest.approx(0.010)}


def test_short_gaps_are_the_devices_own():
    # two operations 10 us apart inside the second busy stretch
    split = DEVICE[:2] + [
        [DEVICE[2][0], 50 * MS, 10 * MS - 10_000, tr.MOSAIC],
        [DEVICE[2][0], 60 * MS, 10 * MS, tr.MOSAIC]] + DEVICE[3:]
    r = tr.reduce_trace(_trace(split, HOST))
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    assert gaps[tr.BETWEEN_OPS] == pytest.approx(10e-6)
    assert gaps["bench:fetch_loss"] == pytest.approx(0.020)


def test_gap_without_a_span_is_unattributed():
    r = tr.reduce_trace(_trace(DEVICE, [HOST[0]]))
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    assert gaps["host:unattributed"] == pytest.approx(0.010)


def test_events_are_cut_to_the_window():
    r = tr.reduce_trace(_trace(DEVICE, HOST, window=(20 * MS, 60 * MS)))
    # inside [20,60): busy [20,40) and [50,60)
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.030)


def test_busy_is_the_mean_over_the_chips_used():
    t = _trace(DEVICE, HOST, chips=2)
    t["planes"][1]["lines"][0]["events"] = DEVICE[:1]   # chip 1: 30 ms busy
    r = tr.reduce_trace(t)
    assert r["busy_s_per_chip"] == [pytest.approx(0.060),
                                    pytest.approx(0.030)]
    assert r["busy_s"] == pytest.approx(0.045)
    assert tr.reduce_trace(t, chips=1)["busy_s"] == pytest.approx(0.060)


def test_a_trace_without_window_or_device_is_an_error():
    t = _trace(DEVICE, HOST)
    t["planes"][-1]["lines"] = t["planes"][-1]["lines"][1:]
    with pytest.raises(ValueError, match="bench:window"):
        tr.reduce_trace(t)
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce_trace({"planes": _trace(DEVICE, HOST)["planes"][-1:]})


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "slice_*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_slice_of_a_chip_trace(path):
    """A slice cut from a trace of the v5e (my chip run, PR 22). The slice
    has no window span of its own; the cut's bounds are its window."""
    with open(path) as f:
        cut = json.load(f)
    a, b = cut["cut"]
    cut["planes"].append({"name": "/host:recorded", "lines": [{
        "name": "window", "events": [[tr.WINDOW_SPAN, a, b - a, ""]]}]})
    r = tr.reduce_trace(cut)
    expect = cut["expect"]      # written down when the slice was recorded
    assert r["window_s"] == pytest.approx((b - a) / 1e9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert r["idle_share"] == pytest.approx(expect["idle_share"], rel=1e-9)
    assert r["device_ops"][0][0] == expect["top_op"]
    assert sum(s for _, s in r["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-9
    ops_total = sum(s for _, s in tr.reduce_trace(cut, top=10 ** 6)
                    ["device_ops"])
    assert ops_total == pytest.approx(r["busy_s_per_chip"][0], rel=1e-9)
