"""The proof that the seam is enough: a second architecture is files only.

``tests/data/second_architecture/`` holds a toy one as a later
``model_config`` PR would bring it: ``architectures/<name>.py``,
``reference/<name>.py``, a configuration whose size keys are the catalog's,
a ``train`` and a ``serve`` cell, and the entries to append to
``BENCHMARK.json``. The test drops them into a copy of the benchmark and
runs, in the copy, every data-file test and the rehearsal of both new cells
(through ``run_cell``, traced and not). No file that was there changes."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

from benchmarks.harness import lm

ROOT = lm.CHECKOUT
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "second_architecture")
ENTRIES = "benchmark_entries.json"


def _files(top):
    out = {}
    for base, _, files in os.walk(top):
        if "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_second_architecture_is_files_only(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(lm.BENCH_DIR, copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(copy / "benchmarks")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    # the listing: the files, each into its directory
    toy = sorted(set(_files(TOY)) - {ENTRIES})
    assert toy == ["architectures/toy_decoder.py", "configs/toy-decoder.json",
                   "reference/toy_decoder.py", "workloads/toy-serve.json",
                   "workloads/toy-train.json"]
    for rel in toy:
        assert rel not in before
        shutil.copy(os.path.join(TOY, rel), copy / "benchmarks" / rel)
    cfg = json.load(open(os.path.join(TOY, "configs", "toy-decoder.json")))
    assert {"hidden_size", "num_hidden_layers", "num_attention_heads",
            "intermediate_size", "max_position_embeddings"} <= set(cfg)
    assert not [k for k in cfg if k.startswith("n_")]
    assert cfg["reduced"] == ["num_hidden_layers"]
    # ... and the entries, appended: a configuration, its cells, and each
    # cell's name in the ``workloads`` of the metrics it reports
    with open(os.path.join(TOY, ENTRIES)) as f:
        entries = json.load(f)
    new = json.loads(json.dumps(bench))
    new["configs"] += entries["configs"]
    new["workloads"] += entries["workloads"]
    for cell, like in entries["reports_the_metrics_of"].items():
        for m in new["end_to_end"] + new["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(new, f, indent=1)

    # in the copy: every data-file test, and the rehearsal of the new cells
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "benchmarks/tests/test_data_files.py",
         "benchmarks/tests/test_rehearsal.py::test_cell_rehearsal",
         "-k", "test_data_files or toy-"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=900)
    tail = p.stdout[-4000:] + p.stderr[-2000:]
    assert p.returncode == 0, tail
    passed = set(re.findall(r"::(\S+) PASSED", p.stdout))
    assert {"test_cell_rehearsal[%s-%s]" % (cell, how)
            for cell in ("toy-train", "toy-serve")
            for how in ("e2e", "traced")} <= passed, tail
    assert "test_configuration_keeps_the_published_widths[toy-decoder]" \
        in passed, tail
    assert "test_a_fifth_cell_is_one_file_and_appended_entries" in passed

    # no file that was there changed, and nothing else was added
    after = _files(copy / "benchmarks")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == set(toy)
    assert new["configs"][:len(bench["configs"])] == bench["configs"]
    assert new["workloads"][:len(bench["workloads"])] == bench["workloads"]
    for was, now in zip(bench["end_to_end"] + bench["per_layer"],
                        new["end_to_end"] + new["per_layer"]):
        assert {k: v for k, v in now.items() if k != "workloads"} == \
            {k: v for k, v in was.items() if k != "workloads"}
        assert now.get("workloads", [])[:len(was.get("workloads", []))] == \
            was.get("workloads", [])
