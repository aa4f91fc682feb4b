"""``collective_core_share`` (PR 38): the reader that sees a collective on
the core in either form, by opcode or as one half of the pair of fusions
that XLA:TPU makes of an asynchronous one. Checked on instruction texts as
the chip's trace names them, on a line of events counted by hand, and on a
small ``.xplane.pb`` written here as a text proto. Everything runs on the
CPU: none of its numbers is a device number."""

import json
import os
import types

import pytest

from benchmarks.harness import lm, readers, trace_reduce as tr
from benchmarks.layer_metrics import collective_core_share as ccs

MS = 1_000_000      # ns
NAME = "collective_core_share"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

START = ("%async-collective-start.3 = (bf16[2048,8192]{1,0:T(8,128)(2,1)}, "
         "u32[]{:S(2)}) fusion(bf16[2048,8192]{1,0:T(8,128)(2,1)} %fusion.7), "
         "kind=kCustom, calls=%fused_computation.81")
DONE = ("%async-collective-done.3 = bf16[2048,8192]{1,0:T(8,128)(2,1)} "
        "fusion((bf16[2048,8192]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) "
        "%async-collective-start.3), kind=kCustom, calls=%fused_computation.82")
PLAIN = ("%all-reduce.11 = f32[50257,2048]{1,0:T(8,128)} all-reduce("
         "f32[50257,2048]{1,0:T(8,128)} %fusion.2), channel_id=4, "
         "replica_groups={{0,1,2,3}}, to_apply=%add.1")
PRODUCT = ("%fusion.7 = bf16[2048,8192]{1,0:T(8,128)(2,1)} fusion(bf16[4,2048,"
           "2048]{2,1,0} %p.1, bf16[4,2048,8192]{2,1,0} %p.2), kind=kOutput, "
           "calls=%fused_computation.7")
PREFETCH = ("%slice-start.4 = ((f32[8,8]{1,0}), f32[2,8]{1,0}, s32[]) "
            "async-start(f32[8,8]{1,0} %u), calls=%async_computation.1")
CUSTOM = ("%fusion.9 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p.3), "
          "kind=kCustom, calls=%fused_computation.9")


def test_a_collective_is_told_by_opcode_or_by_the_pairs_name():
    assert [ccs.is_collective(t) for t in
            (START, DONE, PLAIN, PRODUCT, PREFETCH, CUSTOM)] == \
        [True, True, True, False, False, False]
    # what collective_share goes by sees the plain form alone
    assert [tr.parse_hlo_event(t)[2] == tr.COLLECTIVE_CAT
            for t in (START, DONE, PLAIN)] == [False, False, True]


# chip 0: a start [10,12), the product beside the transfer [12,40), the
# wait at the done [40,46) with nothing inside, a plain all-reduce [50,60)
# that holds a nested child [52,54), and an update [70,90) that the window
# [0,80) cuts: busy 2 + 28 + 6 + 10 + 10 = 56 ms, collectives 2 + 6 + 8
COLL = tr.COLLECTIVE_CAT
LINE = [[START, 10 * MS, 2 * MS, COLL], [PRODUCT, 12 * MS, 28 * MS, ""],
        [DONE, 40 * MS, 6 * MS, COLL], [PLAIN, 50 * MS, 10 * MS, COLL],
        [CUSTOM, 52 * MS, 2 * MS, ""], [CUSTOM, 70 * MS, 20 * MS, ""]]


def test_share_is_self_time_over_busy_time_inside_the_window():
    assert ccs.share(LINE, (0, 80 * MS)) == pytest.approx(100 * 16 / 56)
    assert ccs.share([e for e in LINE if not e[3]], (0, 80 * MS)) == 0.0
    assert ccs.share(LINE, (200 * MS, 300 * MS)) is None


def _xplane(tmp_path, planes):
    """An ``.xplane.pb`` where ``find_xplane`` looks for it, from
    ``{plane name: {line name: [(event name, start_ns, dur_ns), ...]}}``."""
    from jax.profiler import ProfileData
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        meta = {n: i for i, n in enumerate(names, 1)}
        body = ["id: %d name: %s" % (pid, json.dumps(plane))]
        for lid, (line, evs) in enumerate(lines.items(), 1):
            events = " ".join(
                "events { metadata_id: %d offset_ps: %d duration_ps: %d }"
                % (meta[n], s * 1000, d * 1000) for n, s, d in evs)
            body.append("lines { id: %d name: %s timestamp_ns: 0 %s }"
                        % (lid, json.dumps(line), events))
        body += ["event_metadata { key: %d value { id: %d name: %s } }"
                 % (i, i, json.dumps(n)) for n, i in meta.items()]
        out.append("planes { %s }" % " ".join(body))
    where = tmp_path / "trace" / "plugins" / "profile" / "t0"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(out)))
    return str(tmp_path / "trace")


def _facts(window_s=0.080):
    return types.SimpleNamespace(cell={"name": "lm-train-4chip"},
                                 trace={"window_s": window_s})


def test_read_takes_chip_0_and_the_window_from_the_runs_xplane(
        tmp_path, monkeypatch):
    ops = [(t, s, d) for t, s, d, _ in LINE]
    trace_dir = _xplane(tmp_path, {
        # chip 1 holds collectives alone: it is not the one that is read
        "/device:TPU:1": {"XLA Ops": [(PLAIN, 0, 80 * MS)]},
        "/device:TPU:0": {"XLA Ops": ops,
                          "XLA Modules": [("jit_train", 0, 90 * MS)]},
        "/host:CPU": {"python": [(tr.WINDOW_SPAN, 0, 80 * MS),
                                 ("bench:feed", 0, 5 * MS)]}})
    window, events = ccs.load(tr.find_xplane(trace_dir))
    assert window == (0, 80 * MS)
    assert events == LINE

    monkeypatch.setattr(lm, "CHECKOUT", str(tmp_path / "checkout"))
    os.makedirs(tmp_path / "checkout" / ".bench_out" / "lm-train-4chip")
    read = readers.load_metric(NAME)[1]
    assert read(_facts()) is None                   # no trace was written
    os.rename(trace_dir, tmp_path / "checkout" / ".bench_out" /
              "lm-train-4chip" / "trace")
    assert read(_facts()) == pytest.approx(100 * 16 / 56)
    assert read(_facts(window_s=3.0)) is None       # another run's trace
    assert read(types.SimpleNamespace(trace=None)) is None  # --trace 0


def test_a_trace_with_no_device_plane_has_nothing_to_read(
        tmp_path, monkeypatch):
    """The CPU rehearsal: host planes only."""
    trace_dir = _xplane(tmp_path / "checkout" / ".bench_out" /
                        "lm-train-4chip",
                        {"/host:CPU": {"python": [(tr.WINDOW_SPAN, 0,
                                                   80 * MS)]}})
    assert ccs.load(tr.find_xplane(trace_dir)) == ((0, 80 * MS), [])
    monkeypatch.setattr(lm, "CHECKOUT", str(tmp_path / "checkout"))
    assert readers.load_metric(NAME)[1](_facts()) is None


def test_benchmark_json_lists_it_after_what_was_there():
    """Appended after PR 36's six (``test_setup_metrics`` pins those), for
    the four-chip cell alone, under the sharding layer's accepted name."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr34.json")) as f:
        was = json.load(f)
    added = now["per_layer"][len(was["per_layer"]) + 6:]
    assert added[0] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "sharding (parallel/)",
        "moves": "train_tokens_per_s", "workloads": ["lm-train-4chip"]}
    accepted = next(m for m in now["per_layer"]
                    if m["name"] == "collective_share")
    assert {k: accepted[k] for k in ("layer", "moves", "workloads")} == \
        {k: added[0][k] for k in ("layer", "moves", "workloads")}
