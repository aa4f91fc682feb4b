"""A CPU rehearsal of every cell at a tiny size: the real cell, metric and
``BENCHMARK.json`` files with only the sizes and the traffic numbers shrunk,
through the same ``run_cell`` the command line calls. It checks the control
flow, the last line's keys and the counts; none of its numbers is a device
number. The command line itself must refuse to run here."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import architectures, run as bench_run
from benchmarks.harness import lm, peaks, serve, train

ROOT = lm.CHECKOUT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """A copy of the benchmark's data files with tiny sizes, and the
    harness pointed at it."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(os.path.join(lm.BENCH_DIR, "layer_metrics"),
                    tmp / "layer_metrics")
    os.makedirs(tmp / "configs")
    os.makedirs(tmp / "workloads")
    for c in BENCH["configs"]:
        cfg = lm.load_json("configs", c["name"] + ".json")
        cfg = architectures.load(cfg).tiny(cfg)
        with open(tmp / "configs" / (c["name"] + ".json"), "w") as f:
            json.dump(cfg, f)
    for name in CELLS:
        cell = lm.load_json("workloads", name + ".json")
        cell["trace_seconds"] = 1.0
        t = cell["traffic"]
        if cell["kind"] == "train":
            t.update(batch=4, seq_len=64, fetch_loss_every=3, warm_steps=1)
        else:
            online = t["loop"] == "open"
            cell["prompt_buckets"] = [16, 32] if online else [16]
            t["prompt_len"] = {"dist": "uniform", "lo": 4,
                               "hi": 30 if online else 16}
            t["output_len"] = {"dist": "uniform", "lo": 4, "hi": 12}
            t["lead_in_s"] = 0.5
            if online:
                t["rate_per_s"] = 5.0
            else:
                t.update(clients=8, ramp_requests=4, max_requests=4096)
        with open(tmp / "workloads" / (name + ".json"), "w") as f:
            json.dump(cell, f)
    real, lm.BENCH_DIR = lm.BENCH_DIR, str(tmp)
    # the table of peaks refuses a device it does not know, as it must; the
    # rehearsal lends the CPU a row so that the mfu reader can be walked
    peaks.PEAKS["cpu"] = dict(peaks.PEAKS["TPU v5 lite"], source="rehearsal")
    # the loss tolerance is set from the chip's reading at the real size; a
    # sequence of 64 tokens averages less rounding away (1.2e-3 read here)
    chip_atol, train.LOSS_ATOL = train.LOSS_ATOL, 5e-3
    yield tmp
    train.LOSS_ATOL = chip_atol
    del peaks.PEAKS["cpu"]
    lm.BENCH_DIR = real


@pytest.mark.parametrize("trace", [0, 1], ids=["e2e", "traced"])
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(tiny_bench, name, trace):
    result, notes, _ = bench_run.run_cell(
        BENCH, name, seed=3, seconds=3.0, trace=bool(trace),
        require_tpu=False, out_root=str(tiny_bench / "out"))
    assert json.loads(json.dumps(result)) == result     # plain JSON
    want = {"correct", "attempted", "failed", "metrics", "device", "compared"}
    assert set(result) == want | ({"breakdown"} if trace else set())
    assert result["correct"] is True, notes["problems"]
    # each number compared beside its limit, last on the line
    assert list(result)[-1] == "compared" and len(result["compared"]) >= 2
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    dev = result["device"]
    assert dev["platform"] == "cpu" and dev["count"] == entry["chips"]
    e2e, layer = bench_run.cell_metrics(BENCH, name)
    units = {m["name"]: m["unit"] for m in e2e + layer}
    got = result["metrics"]
    assert all(got[m]["unit"] == units[m] for m in got)
    assert all(isinstance(v["value"], (int, float)) for v in got.values())
    if trace:
        assert set(got) <= {m["name"] for m in layer}
        assert "compiles_in_window" in got
        assert got["compiles_in_window"]["value"] == 0
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
        # every reader whose source exists off the chip found its number
        missing = {m["name"] for m in layer} - set(got)
        assert missing <= {"collective_share"}, missing
    else:
        assert set(got) == {m["name"] for m in e2e}
        assert "setup_s" in got and len(got) >= 2
        assert all(v["value"] > 0 for v in got.values())
        # the notes say where set-up went
        phases = notes["setup_phases_s"]
        assert set(phases) == {"imports_and_devices", "init_weights",
                               "check_and_warm"}
        assert 0 < sum(phases.values()) <= got["setup_s"]["value"]
    cell = lm.load_json("workloads", name + ".json")
    if cell["kind"] == "train":
        assert notes["steps"] == result["attempted"]
        assert notes["tokens"] == notes["steps"] * 4 * 64
        # whole blocks of ``fetch_loss_every`` steps, nothing left in flight
        assert notes["steps"] % 3 == 0
        assert notes["step_samples"] == notes["steps"] // 3
        if not trace:
            # tokens of a step over the median seconds per step
            assert got["train_tokens_per_s"]["value"] == pytest.approx(
                4 * 64 / (notes["train_step_p50_ms"] * 1e-3))
            assert notes["tokens_per_s_whole_window"] > 0
        assert abs(notes["check_loss"]["program"] -
                   notes["check_loss"]["reference"]) <= 5e-3
    else:
        assert notes["requests_sent"] == result["attempted"]
        assert notes["slices"] >= 1 and notes["delivered_tokens_per_s"] > 0
        if trace:
            assert "delivered_tokens_per_s" in got
        else:
            # the open loop's rate is its offered load: recorded, not judged
            assert ("output_tokens_per_s" in got) == \
                (cell["traffic"]["loop"] == "closed")
        assert notes["reference_check"]["worst_rel_err"] <= \
            notes["reference_check"]["rtol"]
        assert len(notes["reference_check"]["per_bucket"]) == \
            len(cell["prompt_buckets"])


def test_a_run_that_ends_its_process_parks_the_dispatcher(tiny_bench,
                                                          monkeypatch):
    """``drain=False``, as the command line runs: what is in flight when the
    window closes is not served out, and the result is judged on what was
    observed. (No lingering here: at this size everything would finish.)"""
    monkeypatch.setattr(serve, "LINGER_S", 0.0)
    result, notes, _ = bench_run.run_cell(
        BENCH, "lm-serve-offline", seed=4, seconds=2.0, trace=False,
        require_tpu=False, out_root=str(tiny_bench / "out"), drain=False)
    assert result["correct"] is True, notes["problems"]
    assert result["failed"] == 0
    assert 0 < notes["requests_resolved"] < notes["requests_sent"]
    assert result["metrics"]["output_tokens_per_s"]["value"] > 0


def _wrong_labels(arch, monkeypatch):
    """The step trains on other labels than the reference is shown."""
    real = arch.train_feed

    def train_feed(rs, cfg, traffic):
        step = real(rs, cfg, traffic)
        step["feed"]["lbls"] = step["feed"]["lbls"][:, ::-1].copy()
        return step
    monkeypatch.setattr(arch, "train_feed", train_feed)
    return "check_loss_abs_diff"


def _wrong_first_token(arch, monkeypatch):
    """A prefill hands back another token than the one it computed."""
    from paddle_tpu.serving.generation import GenerationSession
    real = GenerationSession.admit

    def admit(self, prompt, *args, **kw):
        slot, first = real(self, prompt, *args, **kw)
        return slot, (int(first) + 1) % 128
    monkeypatch.setattr(GenerationSession, "admit", admit)
    return "prefill_token_rel_gap"


@pytest.mark.parametrize("name,break_it", [
    ("lm-train-1chip", _wrong_labels), ("lm-serve-offline", _wrong_first_token)])
def test_a_broken_timed_path_is_not_correct(tiny_bench, monkeypatch, name,
                                            break_it):
    """The rest of a run over a path broken underneath: the run reaches its
    end, prints its line, and ``correct`` is false with the number that
    caught it over its limit."""
    cfg = lm.load_config(lm.load_json("workloads", name + ".json")["config"])
    caught_by = break_it(architectures.load(cfg), monkeypatch)
    result, notes, _ = bench_run.run_cell(
        BENCH, name, seed=3, seconds=2.0, trace=False, require_tpu=False,
        out_root=str(tiny_bench / "out"))
    assert result["correct"] is False and notes["problems"]
    c = result["compared"][caught_by]
    assert c["value"] > 3 * c["limit"]
    assert set(result["metrics"]) == {
        m["name"] for m in bench_run.cell_metrics(BENCH, name)[0]}


def test_command_line_refuses_to_run_without_a_tpu():
    """No CPU mode: the command fails before building anything and prints
    no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_fewer_chips_than_the_cell_asks_for_is_a_failure():
    from benchmarks.harness import common
    with pytest.raises(common.BenchFailure, match="asks for 64 chips"):
        common.devices_for(64, require_tpu=False)
    with pytest.raises(common.BenchFailure, match="not a TPU"):
        common.devices_for(1, require_tpu=True)
