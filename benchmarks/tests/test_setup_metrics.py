"""The per-layer metrics of set-up (PR 36): five readers of the compile
ledger's process totals and one of the executor's host milliseconds, on a
rehearsal run and on a program without the counters; and the proof that
the PR only added to the benchmark. Everything here runs on the CPU: none
of its numbers is a device number."""

import hashlib
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import common, lm, readers
from benchmarks.layer_metrics import process_totals
from test_rehearsal import BENCH, tiny_bench  # noqa: F401 — the fixture

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SETUP = ("setup_infer_shape_s", "setup_trace_lower_s", "setup_compile_s",
         "setup_cache_read_s", "setup_cache_misses")
TRAIN = "train_host_ms_per_step"
CELLS = [w["name"] for w in BENCH["workloads"]]


def _read(name, facts=None):
    return readers.load_metric(name)[1](facts)


@pytest.fixture(scope="module")
def rehearsed(tiny_bench):  # noqa: F811
    """One traced rehearsal of a training cell and one of a serving cell,
    with the registry's own totals read right after each."""
    out = {}
    for cell in ("lm-train-1chip", "lm-serve-offline"):
        result, notes, _ = bench_run.run_cell(
            BENCH, cell, seed=2_500_000_011, seconds=2.0, trace=True,
            require_tpu=False, out_root=str(tiny_bench / "out_setup"))
        out[cell] = {"metrics": result["metrics"], "notes": notes,
                     "correct": result["correct"],
                     "after": {name: _read(name) for name in SETUP}}
    return out


@pytest.mark.parametrize("cell", ["lm-train-1chip", "lm-serve-offline"])
def test_a_traced_run_prints_the_five_setup_metrics(rehearsed, cell):
    run = rehearsed[cell]
    assert run["correct"] is True
    got = run["metrics"]
    units = {"setup_cache_misses": "count"}
    for name in SETUP:
        assert got[name]["unit"] == units.get(name, "s")
        # a process total: what the registry holds after the run
        assert got[name]["value"] == pytest.approx(run["after"][name])
    assert got["setup_infer_shape_s"]["value"] > 0
    assert got["setup_trace_lower_s"]["value"] > 0
    # compiled or read from JAX's cache, by what the checkout holds
    assert got["setup_compile_s"]["value"] + \
        got["setup_cache_read_s"]["value"] > 0
    assert (got["setup_cache_misses"]["value"] == 0) == \
        (got["setup_compile_s"]["value"] == 0)
    assert (TRAIN in got) == (cell == "lm-train-1chip")


def test_the_ledger_closes_on_the_harness_own_compile_note(rehearsed):
    """The seconds of compiles and cache reads over all roles are the
    compile meter's (it sums the same ``backend_compile_duration`` events
    from outside, from its registration on), hits and misses alike."""
    run = rehearsed["lm-serve-offline"]
    note = run["notes"]["compile"]
    read = process_totals.total("paddle_compile_events_total",
                                stage="cache_read")
    assert read >= note["cache_hits"]
    booked = run["after"]["setup_compile_s"] + \
        run["after"]["setup_cache_read_s"]
    # the meter of this run registered after the training cell's run had
    # compiled its own: the ledger holds at least the meter's
    assert booked >= note["compile_s"] * 0.99
    assert run["after"]["setup_cache_misses"] + read >= note["compiles"]


def test_train_host_ms_is_the_steady_runs_own(rehearsed):
    got = rehearsed["lm-train-1chip"]["metrics"][TRAIN]
    assert got["unit"] == "ms" and 0 < got["value"] < 1000
    ms = process_totals.total(
        "paddle_executor_host_ms_total", role="train",
        phase=("prepare", "call", "writeback"))
    runs = process_totals.total("paddle_executor_runs_total", role="train")
    first = process_totals.total("paddle_executor_first_calls_total",
                                 role="train")
    assert first >= 1 and runs > first
    assert _read(TRAIN) == pytest.approx(ms / (runs - first))
    # the first runs are in neither side of the ratio
    whole = process_totals.total("paddle_executor_host_ms_total",
                                 role="train", phase="first_call")
    assert whole > ms / (runs - first)


@pytest.mark.parametrize("name", SETUP + (TRAIN,))
def test_a_program_without_the_counters_reads_nothing(monkeypatch, name):
    """The parent of PR 36 has neither the ledger nor the executor's
    counters: the readers return None and the line leaves the metric out."""
    from paddle_tpu.observability import metrics
    bare = metrics.Registry()
    bare.counter("paddle_executor_cache_hits_total").inc()
    monkeypatch.setattr(metrics, "REGISTRY", bare)
    assert _read(name, common.Facts({}, {}, [type(
        "D", (), {"device_kind": "cpu"})()], 45.0)) is None


def test_a_family_with_no_matching_child_reads_zero(monkeypatch):
    """A warm run has the ledger and no ``stage=compile`` child: 0, not
    nothing."""
    from paddle_tpu.observability import metrics
    warm = metrics.Registry()
    for family in ("paddle_compile_seconds_total",
                   "paddle_compile_events_total"):
        warm.counter(family, labelnames=("role", "stage")).labels(
            role="decode", stage="cache_read").inc(2.5)
    monkeypatch.setattr(metrics, "REGISTRY", warm)
    assert _read("setup_compile_s") == 0.0
    assert _read("setup_cache_misses") == 0.0
    assert _read("setup_cache_read_s") == 2.5
    assert _read("setup_trace_lower_s") == 0.0
    assert _read("setup_infer_shape_s") is None


def test_the_new_metrics_are_listed_where_they_are_read():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SETUP:
        m = entries[name]
        assert m["moves"] == "setup_s" and m["workloads"] == CELLS
        assert m["source"] == "program_counter"
        assert m["layer"] == "executor (core/executor.py)"
    m = entries[TRAIN]
    assert m["moves"] == "train_tokens_per_s"
    assert m["workloads"] == ["lm-train-1chip", "lm-train-4chip"]
    for cell in CELLS:
        _, layer = bench_run.cell_metrics(BENCH, cell)
        names = {x["name"] for x in layer}
        assert set(SETUP) <= names
        assert (TRAIN in names) == cell.startswith("lm-train")


def test_no_file_that_was_under_benchmarks_changed():
    """PR 36 added readers as files: every file that was under
    ``benchmarks/`` at its parent (33b0490) has the hash it had."""
    with open(os.path.join(DATA, "files_at_pr34.json")) as f:
        was = json.load(f)
    assert len(was) > 100
    for rel, digest in was.items():
        with open(os.path.join(lm.CHECKOUT, "benchmarks", rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, rel


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 34 left it: every list starts with
    what it held, unchanged, and what follows the old per-layer entries
    starts with PR 36's six. Later PRs append after them."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr34.json")) as f:
        was = json.load(f)
    assert list(now) == list(was)
    for key, old in was.items():
        if isinstance(old, list) and key not in ("command", "paths"):
            assert now[key][:len(old)] == old, key
            assert [list(e) for e in now[key][:len(old)]] == \
                [list(e) for e in old], key
        else:
            assert now[key] == old, key
    for key in ("configs", "workloads", "end_to_end"):
        assert len(now[key]) >= len(was[key])
    added = now["per_layer"][len(was["per_layer"]):]
    assert [m["name"] for m in added[:6]] == list(SETUP) + [TRAIN]
