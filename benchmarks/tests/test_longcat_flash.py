"""The ``longcat_flash`` architecture module: its counts by hand at the
published widths, its two readers, what ``published`` refuses, the cell's
rehearsal at ``tiny(cfg)``, that PR 40 edited no file the benchmark had, and
that ``BENCHMARK.json`` grew by appended entries and appended names only
(the cell ``longcat-serve-offline`` is rehearsed beside the others by
``test_rehearsal.py`` too)."""

import copy
import hashlib
import json
import os

import pytest

from benchmarks import architectures, run as bench_run
from benchmarks.architectures import longcat_flash as longcat
from benchmarks.harness import lm, readers

CFG = lm.load_config("longcat-flash-chat-l4")
CELL = "longcat-serve-offline"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_the_configuration_is_the_catalogs_row_but_for_what_it_reduces():
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(row):
        pytest.skip("no catalog here")
    with open(row) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "LongCat-Flash-Chat"' in line)
    assert CFG["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items() if CFG[k] != v)
    assert differs == sorted(CFG["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    assert CFG["n_routed_experts_published"] == \
        entry["config"]["n_routed_experts"] == 512
    assert CFG["deployment"]["chips_sharing_a_layer"] * \
        CFG["n_routed_experts"] == 512
    assert CFG["vocab_size"] * 8 == entry["config"]["vocab_size"]
    assert CFG["num_layers"] == 4
    serving = CFG["deployment"]["serving"]
    assert serving["num_blocks"] * serving["block_size"] == \
        serving["slots"] * serving["cache_len"]
    assert (serving["slots"], serving["cache_len"]) == (64, 2048)
    for key in ("assumed", "departures", "sizing", "published"):
        assert CFG[key]
    for item in ("latent scale factors", "router", "identity experts",
                 "block"):
        assert item in CFG["assumed"]
    assert "1.0 pairs" in CFG["deployment"]["expert_load"]


def test_parameters_by_hand():
    """ISSUE 40's arithmetic: 90.57 M an attention, 226.49 M a dense
    feed-forward, 638.87 M a layer outside its experts, 37.75 M an expert,
    5,172.7 M held."""
    attention = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                 + 512 * 64 * 256 + 8192 * 6144)
    assert attention + 1536 + 512 == 90_572_800
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    router = 6144 * 768 + 768
    assert (dense, expert, router) == (226_492_416, 37_748_736, 4_719_360)
    outside = 2 * (attention + 1536 + 512) + 2 * dense + router + 4 * 6144
    assert outside == 638_874_368
    layer = outside + 16 * expert
    assert layer == 1_242_854_144
    held = 4 * layer + 2 * 16384 * 6144 + 6144
    assert longcat.parameters_held(CFG) == held == 5_172_749_312
    assert CFG["parameters_as_built"] == held
    # a token's own: in balance 12 x 16 / 768 = a quarter of an expert
    per_token = 4 * (2 * (attention + dense) + 6144 * 768 + 0.25 * expert) \
        + 16384 * 6144
    assert longcat.matmul_params(CFG) == per_token == 2_693_791_744
    assert CFG["matmul_parameters_a_token"] == per_token
    assert longcat.row_width(CFG) == 640
    assert longcat.latent_row_flops(CFG) == 64 * (576 + 512) * 2
    # the cache: 8 sites x 2,560 B a token, 131,072 rows a pool
    serving = CFG["deployment"]["serving"]
    rows = serving["num_blocks"] * serving["block_size"]
    assert rows == 131_072
    assert 8 * rows * 640 * 4 == 2_684_354_560
    assert CFG["sizing"]["serve_decode_64slots"]["argument_bytes"] > \
        2 * held + 2_684_354_560 - 2 * (4 * 6144 * 768)


# ten steps of 64 tokens at contexts of 820 over 8 sites: 10.3 of 16 held
# experts touched a layer step, 16 held pairs and 256 identity pairs of 768
COUNTERS = {
    "paddle_generation_decode_steps_total": 10,
    "paddle_generation_tokens_total": 640,
    "paddle_generation_context_tokens_total": 10 * 64 * 820,
    "paddle_generation_latent_rows_attended_total": 10 * 64 * 820 * 8,
    "paddle_generation_experts_touched_total": 412,
    "paddle_generation_expert_assignments_total": 10 * 4 * 16,
    "paddle_generation_zero_expert_pairs_total": 10 * 4 * 256,
    "paddle_generation_expert_max_load_total": 10 * 4 * 3,
    "paddle_generation_routed_pairs_total": 10 * 4 * 768,
    "paddle_generation_moe_layer_steps_total": 40,
}


def test_decode_breakdown_by_hand():
    attention, dense, expert = 90_570_752, 226_492_416, 37_748_736
    head, router = 16384 * 6144, 6144 * 768
    outside = 4 * 2 * (attention + dense) + head
    always = (2 * outside + 4 * 4 * router) * 10
    experts = 2 * expert * 412
    rows = 10 * 64 * 820 * 8
    latent = 640 * 4 * rows
    flops = 2 * (outside + 4 * router) * 640 + 2 * expert * 640 \
        + 2 * 6144 * 10240 + 64 * (576 + 512) * 2 * rows
    b = longcat.decode_breakdown(CFG, COUNTERS, 4)
    assert (b["always_bytes"], b["expert_bytes"], b["latent_bytes"],
            b["flops"]) == (always, experts, latent, flops)
    got = longcat.decode_ops_and_bytes(CFG, COUNTERS, weight_bytes=4,
                                       kv_bytes=4)
    assert got == (flops, always + experts + latent)
    # weight_bytes is ignored, as the other sparse modules ignore it
    assert longcat.decode_ops_and_bytes(CFG, COUNTERS, 2, 4) == got
    # a step: ISSUE 40's 5.3e9 always, about 3.1e9 of experts, about 1.0e9
    # of latent rows; 11.6 ms at 819 GB/s
    assert 5.30e9 < always / 10 < 5.40e9
    assert 3.05e9 < experts / 10 < 3.15e9
    assert 1.05e9 < latent / 10 < 1.10e9
    assert 11.4e-3 < (always + experts + latent) / 10 / 819e9 < 11.8e-3


def test_the_two_readers_read_the_counters():
    class Facts:
        cfg, hists, trace, counters = CFG, {}, None, COUNTERS
    b = longcat.decode_breakdown(CFG, COUNTERS, 4)
    total = b["always_bytes"] + b["expert_bytes"] + b["latent_bytes"]
    assert readers.load_metric("expert_bytes_share")[1](Facts) == \
        pytest.approx(100 * b["expert_bytes"] / total)
    assert 31 < readers.load_metric("expert_bytes_share")[1](Facts) < 34
    assert readers.load_metric("zero_expert_pairs_share")[1](Facts) == \
        pytest.approx(100 / 3)
    assert readers.load_metric("latent_cache_bytes_share")[1](Facts) == \
        pytest.approx(100 * b["latent_bytes"] / total)
    assert readers.load_metric("held_expert_pairs_ratio")[1](Facts) == \
        pytest.approx(16 / 768)
    assert readers.load_metric("experts_touched_per_layer_step")[1](Facts) \
        == 10.3
    assert readers.load_metric("held_expert_load_imbalance")[1](Facts) == 3.0
    for spec in ("zero_expert_pairs_share", "expert_bytes_share"):
        assert "Neither direction is better" in \
            readers.load_metric(spec)[0]["what"]


def test_a_program_without_the_counters_gives_the_readers_nothing():
    """A program before PR 40 has no ``zero_expert_pairs_total``; one
    before PR 31 no routing counters at all; other architectures' modules
    break a step's bytes down into other parts."""
    old = {k: v for k, v in COUNTERS.items() if "zero_expert" not in k}

    class Facts:
        cfg, counters, hists, trace = CFG, old, {
            "paddle_request_decode_step_ms": (10, 200.0)}, None
        device_kind = "TPU v5 lite"
    assert readers.load_metric("zero_expert_pairs_share")[1](Facts) is None
    # the bytes do not need the new counter: the share and the roofline read
    assert readers.load_metric("expert_bytes_share")[1](Facts) is not None
    share = readers.load_metric("decode_step_roofline_share")[1](Facts)
    assert 55 < share < 60           # 11.6 ms of a 20 ms step
    Facts.counters = {k: v for k, v in old.items()
                      if "experts_touched" not in k}
    for name in ("expert_bytes_share", "decode_step_roofline_share"):
        assert readers.load_metric(name)[1](Facts) is None, name

    class Granite(Facts):
        cfg = lm.load_config("granite-4.0-h-small-l10")
        counters = dict(COUNTERS, **{
            "paddle_generation_state_rows_updated_total": 10 * 96 * 9})

    class Dense(Facts):
        cfg, counters = lm.load_config("cerebras-gpt-1.3b"), COUNTERS
    for facts in (Granite, Dense):
        assert readers.load_metric("expert_bytes_share")[1](facts) is None


def test_published_refuses_a_cut_width():
    """``published`` names every width with its published value: the test
    of the data files (``test_data_files.py``) holds the file to them, so a
    file with a cut width, a narrower router or fewer identity experts is
    refused there; and a file may not list a width under ``reduced``."""
    pub = longcat.published(CFG)
    assert set(pub["reducible"]) == {"num_layers", "n_routed_experts",
                                     "vocab_size"}
    for key, value in pub["widths"].items():
        assert CFG[key] == value, key
    for what, (built, value) in pub["as_built"].items():
        assert built == value, what
    for key in ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
                "moe_topk", "zero_expert_num", "q_lora_rank", "kv_lora_rank",
                "n_routed_experts_published"):
        cut = dict(CFG, **{key: CFG[key] // 2})
        assert cut[key] != longcat.published(cut)["widths"][key], key
    narrow = longcat.published(dict(CFG, zero_expert_num=128))
    assert narrow["as_built"]["router_width"] == (640, 768)
    few = longcat.published(dict(CFG, n_routed_experts=8))
    assert few["as_built"]["experts_a_chip"] == (8, 16)
    with pytest.raises(KeyError):
        longcat.published(dict(CFG, source="https://example.com/other"))


def test_training_entry_points_say_why_they_are_not_there():
    for fn in (longcat.train_program, longcat.train_feed, longcat.strategy,
               longcat.train_flops_per_token):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            fn(CFG, {}, 0)
    with pytest.raises(KeyError):
        longcat.kernels("train")
    assert longcat.kernels("serve") == ("decode_attention_paged",
                                        "moe_grouped_matmul")


def test_sizes_and_tiny_keep_every_mechanism():
    s = longcat.sizes(CFG)
    assert (s["num_experts"], s["experts_held"], s["zero_experts"],
            s["top_k"]) == (512, 16, 256, 12)
    assert (s["scoring"], s["route_norm"], s["route_scale"]) == \
        ("softmax_bias", False, 6.0)
    assert s["block"] == dict(halves=2, experts_read=0, experts_join=1)
    assert s["latent"]["q_scale"] == 2.0
    assert s["latent"]["kv_scale"] == pytest.approx(12 ** 0.5)
    assert (s["d_ff"], s["moe_d_ff"], s["num_dense_layers"]) == \
        (12288, 2048, 0)
    assert longcat.vocab(CFG) == 16384
    assert longcat.max_positions(CFG) == 2048
    for other in ({"attention_method": "MHA"}, {"attention_bias": True},
                  {"zero_expert_type": "copy"}):
        with pytest.raises(ValueError,
                           match="the longcat_flash module builds"):
            longcat.sizes(dict(CFG, **other))
    tiny = longcat.tiny(CFG)
    t = longcat.sizes(tiny)
    assert t["num_experts"] > t["experts_held"] > t["top_k"] >= 2
    # a third of the router's outputs are identity experts, as published
    assert t["zero_experts"] * 3 == t["num_experts"] + t["zero_experts"]
    assert t["block"] == s["block"]
    assert architectures.load(tiny) is longcat
    assert tiny["deployment"]["chips_sharing_a_layer"] == 32


def test_the_cells_rehearsal_at_tiny(tmp_path, monkeypatch):
    """``run_cell`` on the cell's own files with the sizes of ``tiny(cfg)``
    and the traffic shrunk: the traced line holds both new metrics, the
    routing adds up, and the eight pools are attended."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for part in ("configs", "workloads"):
        os.makedirs(tmp_path / part)
    os.symlink(os.path.join(lm.BENCH_DIR, "layer_metrics"),
               tmp_path / "layer_metrics")
    with open(tmp_path / "configs" / (CFG["name"] + ".json"), "w") as f:
        json.dump(longcat.tiny(CFG), f)
    cell = copy.deepcopy(lm.load_json("workloads", CELL + ".json"))
    cell.update(trace_seconds=1.0, prompt_buckets=[16])
    cell["traffic"].update(
        prompt_len={"dist": "uniform", "lo": 4, "hi": 16},
        output_len={"dist": "uniform", "lo": 4, "hi": 12}, lead_in_s=0.5,
        clients=8, ramp_requests=4)
    with open(tmp_path / "workloads" / (CELL + ".json"), "w") as f:
        json.dump(cell, f)
    monkeypatch.setattr(lm, "BENCH_DIR", str(tmp_path))
    from benchmarks.harness import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(
        peaks.PEAKS["TPU v5 lite"], source="rehearsal"))
    result, notes, _ = bench_run.run_cell(
        bench, CELL, seed=2**31 + 5, seconds=3.0, trace=True,
        require_tpu=False, out_root=str(tmp_path / "out"))
    assert result["correct"] is True, notes["problems"]
    assert result["failed"] == 0 and result["attempted"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    _, layer = bench_run.cell_metrics(bench, CELL)
    assert {m["name"] for m in layer} - set(got) <= {"collective_share"}
    assert 15 < got["zero_expert_pairs_share"] < 55      # 8 of 24 outputs
    assert 0 < got["expert_bytes_share"] < 100
    assert 0 < got["latent_cache_bytes_share"] < 100
    assert 0 < got["held_expert_pairs_ratio"] < 0.5       # 4 of 24
    assert got["compiles_in_window"] == 0
    paths = notes["kernel_paths"]
    assert set(paths["decode_attention_paged"]) == {"interpret"}
    assert set(paths["moe_grouped_matmul"]) == {"interpret"}


def test_no_file_that_was_under_benchmarks_changed():
    """PR 40 added a configuration and a cell as files: every file that was
    under ``benchmarks/`` at its parent (129d118) has the hash it had."""
    with open(os.path.join(DATA, "files_at_pr38.json")) as f:
        was = json.load(f)
    assert len(was) > 115
    for rel, digest in was.items():
        with open(os.path.join(lm.BENCH_DIR, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, rel


JOINED = ["output_tokens_per_s", "itl_p50_ms", "queue_wait_mean_ms",
          "tokens_per_decode_step", "decode_step_mean_ms", "prefill_mean_ms",
          "ttft_p90_ms", "itl_p99_ms", "delivered_tokens_per_s",
          "pallas_share_serve", "device_idle_share_serve",
          "decode_host_ms_per_step", "decode_device_wait_ms_per_step",
          "decode_step_roofline_share", "prefill_useful_token_share",
          "experts_touched_per_layer_step", "decode_steps_ahead_share",
          "held_expert_pairs_ratio", "latent_cache_bytes_share",
          "held_expert_load_imbalance", "setup_infer_shape_s",
          "setup_trace_lower_s", "setup_compile_s", "setup_cache_read_s",
          "setup_cache_misses"]


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 38 left it: every list starts with
    what it held, an entry that was there differs at most by cells appended
    to its ``workloads`` (this cell first), and what follows the old
    entries starts with PR 40's one configuration, one cell on one chip and
    two per-layer metrics. Later PRs append after them: nothing here counts
    the lists."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr38.json")) as f:
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
            assert list(old) == list(new)
            if lists[0] != lists[1]:
                assert lists[1][:len(lists[0]) + 1] == lists[0] + [CELL], \
                    old["name"]
                grew.append(old["name"])
        added[key] = now[key][len(was[key]):]
    assert grew == [m["name"] for m in was["end_to_end"] + was["per_layer"]
                    if m["name"] in JOINED] and len(grew) == len(JOINED)
    assert added["end_to_end"] == []
    config = added["configs"][0]
    assert (config["name"], config["reduced"]) == (
        "longcat-flash-chat-l4", ["num_layers", "n_routed_experts",
                                  "vocab_size"])
    assert config["source"] == CFG["source"] and \
        config["file"] == "benchmarks/configs/longcat-flash-chat-l4.json"
    entry = added["workloads"][0]
    assert (entry["name"], entry["config"], entry["chips"]) == \
        (CELL, "longcat-flash-chat-l4", 1)
    for m, name in zip(added["per_layer"], ("zero_expert_pairs_share",
                                            "expert_bytes_share")):
        assert (m["name"], m["workloads"], m["moves"], m["unit"]) == \
            (name, [CELL], "itl_p50_ms", "%")
        assert m["layer"] == "expert FFN op (ops/moe_ops.py moe_ffn)"
        assert m["source"] == "program_counter"
    assert [w["name"] for w in now["workloads"][:len(was["workloads"]) + 1]
            if w["chips"] == 4] == ["lm-train-4chip"]
    cell = lm.load_json("workloads", CELL + ".json")
    assert cell["traffic"]["name"] == entry["traffic"] == \
        "closed-96-chat-generation"
    assert cell["why"] == entry["why"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    t = cell["traffic"]
    assert (t["clients"], t["ramp_requests"], t["schedule_seed"],
            t["lead_in_s"], cell["trace_seconds"]) == (96, 64, 40, 8.0, 3.0)
    assert cell["prompt_buckets"] == [256, 512, 1024]
    assert t["prompt_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 0.6, "lo": 64, "hi": 1000}
    assert t["output_len"] == {"dist": "lognormal", "median": 768,
                               "sigma": 0.5, "lo": 256, "hi": 1024}
    # the longest prompt and the longest output fit the cache
    assert t["prompt_len"]["hi"] + t["output_len"]["hi"] < \
        CFG["deployment"]["serving"]["cache_len"]
