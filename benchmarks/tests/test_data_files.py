"""``BENCHMARK.json`` and the data files against the benchmark's contract,
and the proof that the harness is driven by data: a fifth cell is one new
file and entries appended to ``BENCHMARK.json``, with no code touched."""

import copy
import json
import os
import re
import shutil

import pytest

from benchmarks import architectures, run as bench_run
from benchmarks.harness import lm, readers

ROOT = lm.CHECKOUT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    return _bench()


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells has to fit the driver's budget
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"], e["name"]
    for m in bench["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_entries_have_just_their_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_configurations_have_files_of_their_own(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith("benchmarks/configs/") for f in files)


@pytest.mark.parametrize("name", [c["name"] for c in _bench()["configs"]])
def test_configuration_keeps_the_published_widths(bench, name):
    """What may never be cut (a width is the model) and what ``reduced``
    may list come from the architecture's module."""
    c = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    published = architectures.load(cfg).published(cfg)
    for key, value in published["widths"].items():
        assert cfg[key] == value, (name, key)
        assert key not in c["reduced"]
    for key, value in published["reducible"].items():
        assert (cfg[key] != value) == (key in c["reduced"]), (name, key)
    assert set(c["reduced"]) <= set(published["reducible"])
    for what, (built, value) in published["as_built"].items():
        assert built == value, (name, what)
    assert os.path.exists(os.path.join(
        lm.BENCH_DIR, "reference", cfg["architecture"] + ".py"))
    assert isinstance(cfg["departures"], list) and cfg["departures"]
    assert "assumed" in cfg and "sizing" in cfg
    assert any(w["config"] == name for w in bench["workloads"])


def test_cells_have_their_files_and_chips(bench):
    pairs = set()
    for w in bench["workloads"]:
        cell = lm.load_json("workloads", w["name"] + ".json")
        assert cell["name"] == w["name"]
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert NAME.match(w["traffic"])
        assert cell["kind"] in ("train", "serve")
        assert os.path.exists(os.path.join(
            lm.BENCH_DIR, "harness", cell["kind"] + ".py"))
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert any(c["name"] == w["config"] for c in bench["configs"])
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert 2 <= len(bench["workloads"]) <= 24


def test_every_cell_reports_what_it_must(bench):
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        e2e, layer = bench_run.cell_metrics(bench, w["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert layer, w["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e_names
        for cell in m.get("workloads", []):
            _, layer = bench_run.cell_metrics(bench, cell)
            assert m["name"] in {x["name"] for x in layer}, (m["name"], cell)


def test_every_layer_metric_is_a_file_of_its_own(bench):
    for m in bench["per_layer"]:
        spec, read = readers.load_metric(m["name"])
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert callable(read)


def test_online_cell_carries_its_rate_and_sweep(bench):
    cell = lm.load_json("workloads", "lm-serve-online.json")
    t = cell["traffic"]
    assert t["loop"] == "open" and t["rate_per_s"] > 0
    knee = cell["knee"]
    assert os.path.exists(os.path.join(ROOT, knee["sweep_file"]))
    assert t["rate_per_s"] == pytest.approx(0.8 * knee["rate_per_s"], rel=0.1)


def test_a_fifth_cell_is_one_file_and_appended_entries(bench, tmp_path):
    """The dry listing: a later PR adds ``lm-serve-offline-short`` on the
    existing configuration. It writes one file under ``workloads/`` and
    appends to ``BENCHMARK.json`` one entry and the cell's name in the
    ``workloads`` lists of the metrics it reports. No file that is there
    changes, and no code."""
    before = {}
    for base, _, files in os.walk(lm.BENCH_DIR):
        for f in files:
            if "__pycache__" not in base:
                p = os.path.join(base, f)
                before[os.path.relpath(p, lm.BENCH_DIR)] = os.path.getmtime(p)
    tmp = tmp_path / "benchmarks"
    shutil.copytree(lm.BENCH_DIR, tmp,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = lm.load_json("workloads", "lm-serve-offline.json")
    cell["name"] = "lm-serve-offline-short"
    cell["traffic"] = dict(cell["traffic"], name="closed-64-short-output",
                           output_len={"dist": "uniform", "lo": 16, "hi": 64})
    with open(tmp / "workloads" / "lm-serve-offline-short.json", "w") as f:
        json.dump(cell, f)
    new = copy.deepcopy(bench)
    new["workloads"].append({
        "name": cell["name"], "config": cell["config"],
        "traffic": cell["traffic"]["name"], "chips": 1,
        "why": "short outputs: prefill's share of the closed loop grows"})
    offline = "lm-serve-offline"
    for m in new["end_to_end"] + new["per_layer"]:
        if offline in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    real, lm.BENCH_DIR = lm.BENCH_DIR, str(tmp)
    try:
        e2e, layer = bench_run.cell_metrics(new, cell["name"])
        want = bench_run.cell_metrics(bench, offline)
        assert [m["name"] for m in e2e] == [m["name"] for m in want[0]]
        assert [m["name"] for m in layer] == [m["name"] for m in want[1]]
        assert lm.load_json("workloads", cell["name"] + ".json") == cell
        for m in layer:
            readers.load_metric(m["name"])
    finally:
        lm.BENCH_DIR = real
    added = sorted(
        os.path.relpath(os.path.join(b, f), tmp)
        for b, _, fs in os.walk(tmp) for f in fs
        if os.path.relpath(os.path.join(b, f), tmp) not in before)
    assert added == ["workloads/lm-serve-offline-short.json"]
    assert new["workloads"][:-1] == bench["workloads"]
