"""The ``kimi_k2`` architecture module: its counts by hand at the published
widths, what a program without the new counters gives the new readers, that
PR 31 edited no file the benchmark had, and that ``BENCHMARK.json`` grew by
appended entries only (the cell ``kimi-serve-offline`` is rehearsed with the
others by ``test_rehearsal.py``)."""

import hashlib
import json
import os

import pytest

from benchmarks import architectures
from benchmarks.architectures import kimi_k2
from benchmarks.harness import lm, readers

CFG = lm.load_config("kimi-k2.7-code-l6")
CELL = "kimi-serve-offline"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_the_configuration_is_the_catalogs_row_but_for_what_it_reduces():
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(row):
        pytest.skip("no catalog here")
    with open(row) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "Kimi-K2.7-Code"' in line)
    assert CFG["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items() if CFG[k] != v)
    assert differs == sorted(CFG["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["n_routed_experts_published"] == \
        entry["config"]["n_routed_experts"] == 384
    assert CFG["deployment"]["chips_sharing_a_layer"] * \
        CFG["n_routed_experts"] == 384
    serving = CFG["deployment"]["serving"]
    assert serving["num_blocks"] * serving["block_size"] == \
        serving["slots"] * serving["cache_len"]


def test_parameters_by_hand():
    """ISSUE 31's arithmetic: 101.12 M of attention a layer, 44.04 M an
    expert, 4,173 M held."""
    attention = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                 + 512 * 64 * 256 + 8192 * 7168)
    assert attention == 101_122_048
    expert, router, dense = 3 * 7168 * 2048, 7168 * 384, 3 * 7168 * 18432
    assert (expert, router, dense) == (44_040_192, 2_752_512, 396_361_728)
    head = 20480 * 7168
    held = 6 * attention + dense + 5 * (router + 13 * expert) + 2 * head
    assert kimi_k2.parameters_held(CFG) == held == 4_173_070_336
    assert CFG["parameters_as_built"] == held
    # a token's own: in balance 8 x 12 / 384 = a quarter of an expert
    per_token = 6 * attention + dense + 5 * (router + 1.25 * expert) + head
    assert kimi_k2.matmul_params(CFG) == per_token == 1_438_908_416
    assert CFG["matmul_parameters_a_token"] == per_token
    assert kimi_k2.row_width(CFG) == 640
    assert kimi_k2.latent_row_flops(CFG) == 64 * (576 + 512) * 2 == 139_264


COUNTERS = {
    "paddle_generation_decode_steps_total": 10,
    "paddle_generation_tokens_total": 320,
    "paddle_generation_context_tokens_total": 10 * 32 * 3300,
    "paddle_generation_latent_rows_attended_total": 10 * 32 * 3300 * 6,
    "paddle_generation_experts_touched_total": 10 * 5 * 6,
    "paddle_generation_expert_assignments_total": 10 * 5 * 8,
    "paddle_generation_expert_max_load_total": 10 * 5 * 2,
    "paddle_generation_routed_pairs_total": 10 * 5 * 256,
    "paddle_generation_moe_layer_steps_total": 50,
}


def test_decode_ops_and_bytes_by_hand():
    """10 steps of 32 tokens at contexts of 3,300: 6 of the 12 held experts
    touched and 8 pairs computed a layer step."""
    attention, expert, router = 101_122_048, 44_040_192, 2_752_512
    outside = 6 * attention + 3 * 7168 * 18432 + 5 * expert + 20480 * 7168
    always = (2 * outside + 4 * 5 * router) * 10
    experts = 2 * expert * 300
    rows = 10 * 32 * 3300 * 6
    latent = 640 * 4 * rows
    flops = 2 * (outside + 5 * router) * 320 + 2 * expert * 400 \
        + 139_264 * rows
    got = kimi_k2.decode_ops_and_bytes(CFG, COUNTERS, weight_bytes=4,
                                       kv_bytes=4)
    assert got == (flops, always + experts + latent)
    # weight_bytes is ignored, as afmoe ignores it
    assert kimi_k2.decode_ops_and_bytes(CFG, COUNTERS, 2, 4) == got
    # a step: 2.80 GB always, 2.6 GB of experts at 6 touched, 1.62 GB of rows
    assert 2.79e9 < always / 10 < 2.81e9
    assert 1.61e9 < latent / 10 < 1.63e9
    b = kimi_k2.decode_breakdown(CFG, COUNTERS, 4)
    assert (b["always_bytes"], b["expert_bytes"], b["latent_bytes"],
            b["flops"]) == (always, experts, latent, flops)


def test_the_kernels_counts_by_hand():
    assert kimi_k2.latent_decode_ops_and_bytes(CFG, 32 * 3300, 4) == \
        (139_264 * 32 * 3300, 2560 * 32 * 3300)
    flops, nbytes = kimi_k2.grouped_matmul_ops_and_bytes(CFG, 1024, 12)
    assert flops == 2 * 44_040_192 * 1024
    assert nbytes == 2 * 44_040_192 * 12 + 4 * 1024 * 2 * (7168 + 2048)


def test_the_new_readers_read_the_counters():
    class Facts:
        cfg, hists, trace, counters = CFG, {}, None, COUNTERS
    ratio = readers.load_metric("held_expert_pairs_ratio")[1](Facts)
    assert ratio == 8 / 256 == 12 / 384
    share = readers.load_metric("latent_cache_bytes_share")[1](Facts)
    b = kimi_k2.decode_breakdown(CFG, COUNTERS, 4)
    assert share == pytest.approx(100 * b["latent_bytes"] / (
        b["always_bytes"] + b["expert_bytes"] + b["latent_bytes"]))
    assert 20 < share < 26
    assert readers.load_metric("experts_touched_per_layer_step")[1](Facts) \
        == 6.0
    # the busiest of the 12 held took 2 of a layer step's 8 pairs
    assert readers.load_metric("held_expert_load_imbalance")[1](Facts) \
        == 2 * 12 / 8


def test_a_program_without_the_counters_gives_the_readers_nothing():
    """The parent's program under this PR's benchmark files: no
    ``routed_pairs_total``, no ``latent_rows_attended_total``; and an
    architecture with no latent cache."""
    old = {k: v for k, v in COUNTERS.items()
           if "routed_pairs" not in k and "latent" not in k}

    class Facts:
        cfg, counters, hists, trace = CFG, old, {
            "paddle_request_decode_step_ms": (10, 400.0)}, None
        device_kind = "TPU v5 lite"
    assert kimi_k2.decode_ops_and_bytes(CFG, old, 4, 4) is None
    for name in ("held_expert_pairs_ratio", "latent_cache_bytes_share",
                 "held_expert_load_imbalance", "decode_step_roofline_share"):
        assert readers.load_metric(name)[1](Facts) is None, name

    class Dense(Facts):
        cfg, counters = lm.load_config("cerebras-gpt-1.3b"), COUNTERS
    for name in ("latent_cache_bytes_share", "held_expert_load_imbalance"):
        assert readers.load_metric(name)[1](Dense) is None, name


def test_training_entry_points_say_why_they_are_not_there():
    for fn in (kimi_k2.train_program, kimi_k2.train_feed, kimi_k2.strategy,
               kimi_k2.train_flops_per_token):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            fn(CFG, {}, 0)
    with pytest.raises(KeyError):
        kimi_k2.kernels("train")
    assert kimi_k2.kernels("serve") == ("decode_attention_paged",
                                        "moe_grouped_matmul")


def test_what_the_module_does_not_build_is_refused_with_a_sentence():
    for other in ({"n_shared_experts": 2}, {"scoring_func": "softmax"},
                  {"n_group": 8}, {"moe_layer_freq": 2},
                  {"rope_scaling": dict(CFG["rope_scaling"], mscale=0.7)}):
        with pytest.raises(ValueError, match="the kimi_k2 module builds"):
            kimi_k2.sizes(dict(CFG, **other))


def test_sizes_and_tiny_keep_every_mechanism():
    s = kimi_k2.sizes(CFG)
    assert (s["num_experts"], s["experts_held"], s["top_k"]) == (384, 12, 8)
    assert s["attention"] == "latent" and not s["post_norms"] \
        and s["embed_scale"] is None
    assert s["latent"] == dict(q_rank=1536, kv_rank=512, nope_dim=128,
                               rope_dim=64, v_dim=128)
    assert kimi_k2.vocab(CFG) == 20480
    tiny = kimi_k2.tiny(CFG)
    t = kimi_k2.sizes(tiny)
    assert t["num_experts"] > t["experts_held"] > t["top_k"] >= 2
    assert t["num_dense_layers"] == 1 and len(t["layer_types"]) == 3
    assert t["rope_scaling"]["type"] == "yarn"
    assert architectures.load(tiny) is kimi_k2


def test_no_file_that_was_under_benchmarks_changed():
    """PR 31 added a configuration and a cell as files: every file that was
    under ``benchmarks/`` at its parent (9577154) has the hash it had."""
    with open(os.path.join(DATA, "files_at_pr30.json")) as f:
        was = json.load(f)
    assert len(was) > 70
    for rel, digest in was.items():
        with open(os.path.join(lm.BENCH_DIR, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, rel


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 30 left it: every list starts with
    what it held, an entry that was there differs at most by this cell
    appended to its ``workloads``, and PR 31 brings one configuration, one
    cell on one chip and two per-layer metrics."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr30.json")) as f:
        was = json.load(f)
    assert {k: v for k, v in now.items() if not isinstance(v, list)
            or k in ("command", "paths")} == \
        {k: v for k, v in was.items() if not isinstance(v, list)
         or k in ("command", "paths")}
    added, grew = {}, []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[key], now[key]):
            lists = old.get("workloads"), new.get("workloads")
            assert dict(old, workloads=None) == dict(new, workloads=None)
            if lists[0] != lists[1]:
                assert lists[1] == lists[0] + [CELL], old["name"]
                grew.append(old["name"])
        added[key] = now[key][len(was[key]):]
    assert [c["name"] for c in added["configs"]] == ["kimi-k2.7-code-l6"]
    assert [(w["name"], w["chips"]) for w in added["workloads"]] == \
        [(CELL, 1)]
    assert added["end_to_end"] == []
    assert [m["name"] for m in added["per_layer"]] == [
        "held_expert_pairs_ratio", "latent_cache_bytes_share",
        "held_expert_load_imbalance"]
    assert all(m["workloads"] == [CELL] for m in added["per_layer"])
    assert "expert_load_imbalance" not in grew and \
        "window_attended_share" not in grew and len(grew) == 17
    assert len(now["workloads"]) == 6 and [
        w["name"] for w in now["workloads"] if w["chips"] == 4] == \
        ["lm-train-4chip"]
    cell = lm.load_json("workloads", CELL + ".json")
    assert cell["traffic"]["name"] == added["workloads"][0]["traffic"]
    assert cell["why"] == added["workloads"][0]["why"]
