"""The ``afmoe`` architecture module: its counts by hand at the published
sizes, what a program without the new counters gives its readers, that PR 27
edited no file the benchmark had, and that ``BENCHMARK.json`` grew by
appended entries only (the cell ``trinity-serve-offline`` is rehearsed with
the others by ``test_rehearsal.py``)."""

import hashlib
import json
import os

import pytest

from benchmarks import architectures
from benchmarks.architectures import afmoe
from benchmarks.harness import lm, readers

CFG = lm.load_config("trinity-mini-l5")
CELL = "trinity-serve-offline"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_the_configuration_is_the_catalogs_row_but_for_what_it_reduces():
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(row):
        pytest.skip("no catalog here")
    with open(row) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "Trinity-Mini"' in line)
    assert CFG["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items() if CFG[k] != v)
    assert differs == sorted(CFG["reduced"])
    assert CFG["layer_types"] == ["sliding_attention"] * 4 + \
        ["full_attention"]


def test_matmul_params_by_hand():
    attention = 2048 * 4096 * 3 + 2 * 2048 * 512         # q, gate, o; k, v
    assert attention == 27_262_976
    expert = 3 * 2048 * 1024
    per_token = (5 * attention + 3 * 2048 * 6144          # the dense layer
                 + 4 * (2048 * 128 + 9 * expert)          # router, 1 + 8
                 + 2048 * 200192)
    assert afmoe.matmul_params(CFG) == per_token == 811_597_824
    counts = afmoe.param_counts(CFG)
    held = (5 * attention + 3 * 2048 * 6144 + 4 * (2048 * 128 + 129 * expert)
            + 2 * 2048 * 200192)
    assert held == 4_241_489_920                          # the issue's 4,242 M
    assert (counts["full_layers"], counts["window_layers"],
            counts["expert_layers"]) == (1, 4, 4)


def test_decode_ops_and_bytes_by_hand():
    """10 steps of 64 tokens, 126 experts touched a layer step, contexts of
    3,000 of which the window layers attend 2,048."""
    counters = {
        "paddle_generation_decode_steps_total": 10,
        "paddle_generation_tokens_total": 640,
        "paddle_generation_context_tokens_total": 10 * 64 * 3000,
        "paddle_generation_window_context_tokens_total": 10 * 64 * 4 * 2048,
        "paddle_generation_experts_touched_total": 10 * 4 * 126,
        "paddle_generation_moe_layer_steps_total": 40}
    nflops, nbytes = afmoe.decode_ops_and_bytes(CFG, counters, 4, 2)
    attended = 10 * 64 * (3000 + 4 * 2048)
    assert nflops == 2 * 811_597_824 * 640 + 4 * 4096 * attended
    always = 2 * (5 * 27_262_976 + 3 * 2048 * 6144 + 4 * 3 * 2048 * 1024
                  + 2048 * 200192) + 4 * 4 * 2048 * 128
    assert nbytes == 10 * always + 2 * 3 * 2048 * 1024 * 5040 \
        + 2 * 512 * 2 * attended
    # 9.0 GB a step: the experts 6.3, keys and values 1.5, the head 0.8,
    # attention's and the other feed-forward weights 0.4
    assert 8.9e9 < nbytes / 10 < 9.1e9
    # the 4 bytes the roofline reader passes are not what this program holds
    assert afmoe.decode_ops_and_bytes(CFG, counters, 2, 2) == (nflops, nbytes)


def test_a_program_without_the_counters_gives_the_readers_nothing():
    class Facts:
        cfg, counters, hists, trace = CFG, {
            "paddle_generation_decode_steps_total": 10,
            "paddle_generation_tokens_total": 640,
            "paddle_generation_context_tokens_total": 1000}, {
            "paddle_request_decode_step_ms": (10, 400.0)}, None
        device_kind = "TPU v5 lite"
    assert afmoe.decode_ops_and_bytes(CFG, Facts.counters, 4, 2) is None
    for name in ("experts_touched_per_layer_step", "expert_load_imbalance",
                 "window_attended_share", "decode_step_roofline_share"):
        assert readers.load_metric(name)[1](Facts) is None, name


def test_the_new_readers_read_the_counters():
    class Facts:
        cfg, hists, trace = CFG, {}, None
        counters = {
            "paddle_generation_context_tokens_total": 1000.0,
            "paddle_generation_window_context_tokens_total": 2600.0,
            "paddle_generation_moe_layer_steps_total": 8.0,
            "paddle_generation_experts_touched_total": 1000.0,
            "paddle_generation_expert_assignments_total": 4096.0,
            "paddle_generation_expert_max_load_total": 88.0}
    read = {n: readers.load_metric(n)[1](Facts) for n in (
        "experts_touched_per_layer_step", "expert_load_imbalance",
        "window_attended_share")}
    assert read == {"experts_touched_per_layer_step": 125.0,
                    "expert_load_imbalance": 88 * 128 / 4096,
                    "window_attended_share": 65.0}


def test_training_entry_points_say_why_they_are_not_there():
    for fn in (afmoe.train_program, afmoe.train_feed, afmoe.strategy,
               afmoe.train_flops_per_token):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            fn(CFG, {}, 0)
    with pytest.raises(KeyError):
        afmoe.kernels("train")


def test_a_share_of_the_experts_is_refused_with_a_sentence():
    for share in ({"expert_offset": 32}, {"experts_held": 32}):
        with pytest.raises(ValueError, match="needs the exchange"):
            afmoe.sizes(dict(CFG, **share))
    assert afmoe.sizes(dict(CFG, experts_held=128))["num_experts"] == 128


def test_tiny_keeps_every_mechanism():
    tiny = afmoe.tiny(CFG)
    s = afmoe.sizes(tiny)
    assert s["layer_types"] == CFG["layer_types"] and s["top_k"] == 2
    assert s["num_heads"] > s["num_kv_heads"] and s["param_dtype"] == "float32"
    assert tiny["deployment"]["serving"]["cache_len"] > \
        3 * tiny["sliding_window"]
    assert architectures.load(tiny) is afmoe


def test_no_file_that_was_under_benchmarks_changed():
    """PR 27 added a configuration and a cell as files: every file that was
    under ``benchmarks/`` at PR 26 has the hash it had."""
    with open(os.path.join(lm.BENCH_DIR, "tests", "data",
                           "files_at_pr26.json")) as f:
        was = json.load(f)
    assert len(was) > 50
    for rel, digest in was.items():
        with open(os.path.join(lm.BENCH_DIR, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, rel


@pytest.mark.parametrize("part, more", [
    ("ffn", ["--tokens", "8,64"]),
    ("model", ["--buckets", "16,32", "--sets", "1"]),
    ("float32", ["--buckets", "16,32", "--slots", "4", "--kernel", "0"])])
def test_the_routing_diagnostic_runs_at_the_tiny_size(part, more, capsys):
    """``sweeps/routing_agreement.py`` on the CPU: in float32 the program
    and the reference choose the same experts everywhere, the selections
    recomputed from the fetched inputs match the op's counts, and the
    reference handed them changes nothing."""
    from benchmarks.sweeps import routing_agreement
    assert routing_agreement.main(["--part", part, "--tiny"] + more) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["summary"] and last["part"] == part
    if part == "ffn":
        assert last["worst_err_vs_same_selections"] < 1e-5
    elif part == "model":
        assert last["positions"] == 16 and last["prompt_decisions"] > 100
        assert last["counts_mismatches"] == last["flips"] == 0
        assert last["worst_free"] == last["worst_forced"] < 1e-4
    else:
        assert last["passes"] and last["report"]["worst_rel_err"] < 1e-4


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 26 left it: every list starts with
    what it held, an entry that was there differs at most by cells appended
    to its ``workloads``, and PR 27 brings one configuration, one cell on
    one chip and three per-layer metrics."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr26.json")) as f:
        was = json.load(f)
    assert {k: v for k, v in now.items() if not isinstance(v, list)
            or k in ("command", "paths")} == \
        {k: v for k, v in was.items() if not isinstance(v, list)
         or k in ("command", "paths")}
    added = {}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[key], now[key]):
            lists = old.get("workloads"), new.get("workloads")
            assert dict(old, workloads=None) == dict(new, workloads=None)
            if lists[0] != lists[1]:
                assert lists[1][:len(lists[0])] == lists[0]
                assert lists[1][len(lists[0]):] == [CELL], old["name"]
        added[key] = now[key][len(was[key]):]
    assert [c["name"] for c in added["configs"]] == ["trinity-mini-l5"]
    assert [(w["name"], w["chips"]) for w in added["workloads"]] == \
        [(CELL, 1)]
    assert added["end_to_end"] == []
    assert sorted(m["name"] for m in added["per_layer"]) == [
        "expert_load_imbalance", "experts_touched_per_layer_step",
        "window_attended_share"]
    assert all(m["workloads"] == [CELL] for m in added["per_layer"])
    assert [w["name"] for w in now["workloads"] if w["chips"] == 4] == \
        ["lm-train-4chip"]
    with open(os.path.join(lm.BENCH_DIR, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert cell["traffic"]["name"] == added["workloads"][0]["traffic"]
    assert cell["why"] == added["workloads"][0]["why"]
