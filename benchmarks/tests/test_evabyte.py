"""The ``evabyte`` architecture module: its counts by hand at the published
widths, its two readers, what ``published`` refuses, the cell's rehearsal at
``tiny(cfg)``, that PR 42 edited no file the benchmark had, and that
``BENCHMARK.json`` grew by appended entries and appended names only (the
cell ``evabyte-serve-offline`` is rehearsed beside the others by
``test_rehearsal.py`` too)."""

import copy
import hashlib
import json
import os

import pytest

from benchmarks import architectures, run as bench_run
from benchmarks.architectures import evabyte
from benchmarks.harness import lm, readers

CFG = lm.load_config("evabyte-6.5b-l8")
CELL = "evabyte-serve-offline"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_the_configuration_is_the_catalogs_row_but_for_its_depth():
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(row):
        pytest.skip("no catalog here")
    with open(row) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "EvaByte"' in line)
    assert CFG["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items() if CFG[k] != v)
    assert differs == CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 8 and \
        CFG["published"]["num_hidden_layers"] == 32
    # no width cut
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["intermediate_size"],
            CFG["window_size"], CFG["chunk_size"], CFG["rope_theta"],
            CFG["vocab_size"], CFG["num_pred_heads"]) == \
        (4096, 32, 32, 11008, 2048, 16, 100000, 320, 8)
    dep = CFG["deployment"]
    assert (dep["pipeline_stages"], dep["layers_a_stage"],
            dep["chips_sharing_a_layer"]) == (4, 8, 1)
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 32
    serving = dep["serving"]
    # the worst case of both kinds: a window of blocks a slot, and a
    # summary for every 16 positions of the longest sequence
    assert serving["block_size"] == CFG["chunk_size"]
    assert serving["num_blocks"] == serving["slots"] * \
        CFG["window_size"] // serving["block_size"]
    assert serving["chunk_num_blocks"] * serving["block_size"] * \
        CFG["chunk_size"] == serving["slots"] * serving["cache_len"]
    assert serving["cache_len"] % CFG["window_size"] == 0
    assert serving["kv_dtype"] == CFG["torch_dtype"] == "bfloat16"
    assert CFG["flags"]["amp"] == "bfloat16"
    for key in ("assumed", "departures", "sizing", "published"):
        assert CFG[key]
    for item in ("the two poolings", "what a query sees", "rotary",
                 "the head", "initial values"):
        assert item in CFG["assumed"]


def test_parameters_by_hand():
    """ISSUE 42's arithmetic: 202.38 M a layer's matrices, 1.631e9
    parameters held, 3.26e9 bytes."""
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert layer == 202_375_168
    held = 8 * (layer + 2 * 4096 + 2 * 4096) + 320 * 4096 + 4096 \
        + 4096 * 8 * 320
    assert evabyte.parameters_held(CFG) == held == 1_630_932_992
    assert CFG["parameters_as_built"] == held
    per_token = 8 * layer + 4096 * 2560
    assert evabyte.matmul_params(CFG) == per_token == \
        CFG["matmul_parameters_a_token"]
    assert evabyte.row_bytes(CFG, 2) == 16384      # K and V of 32 heads
    serving = CFG["deployment"]["serving"]
    pools = 8 * evabyte.row_bytes(CFG, 2) * 16 * (
        serving["num_blocks"] + serving["chunk_num_blocks"])
    assert pools == serving["slots"] * 8 * 16384 * (2048 + 768)
    sized = CFG["sizing"]["serve_decode_%dslots" % serving["slots"]]
    assert 0 < sized["argument_bytes"] - pools - 2 * held < 2e7
    assert CFG["sizing"]["serve_prefill_live_bytes"]["8192"] < 15.8e9
    assert sized["live_bytes"] > 0.70 * 16.91e9


# ten steps of 24 slots at contexts of 5,500 (window rows 1,404, 256
# summaries) over 8 layers; 15 chunks completed
COUNTERS = {
    "paddle_generation_decode_steps_total": 10,
    "paddle_generation_tokens_total": 240,
    "paddle_generation_context_tokens_total": 10 * 24 * 5500,
    "paddle_generation_eva_window_rows_total": 10 * 24 * 1404 * 8,
    "paddle_generation_eva_chunk_rows_total": 10 * 24 * 256 * 8,
    "paddle_generation_eva_chunks_written_total": 15 * 8,
}


def test_decode_breakdown_by_hand():
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    weights = 2 * (8 * (layer + 2 * 4096) + 4096 * 2560) + 4 * 17 * 4096
    rows, summaries = 10 * 24 * 1404 * 8, 10 * 24 * 256 * 8
    b = evabyte.decode_breakdown(CFG, COUNTERS, 2)
    assert b["always_bytes"] == 10 * weights
    assert b["window_bytes"] == 16384 * rows
    assert b["chunk_bytes"] == 16384 * summaries
    assert b["written_bytes"] == 16384 * 17 * 120
    flops = 2 * (8 * layer + 4096 * 2560) * 240 \
        + 4 * 4096 * (rows + summaries) + 8 * 4096 * 16 * 120
    assert b["flops"] == flops
    got = evabyte.decode_ops_and_bytes(CFG, COUNTERS, weight_bytes=4,
                                       kv_bytes=2)
    total = sum(b[k] for k in ("always_bytes", "window_bytes",
                               "chunk_bytes", "written_bytes"))
    assert got == (flops, total)
    assert evabyte.decode_ops_and_bytes(CFG, COUNTERS, 2, 2) == got
    # a step: 3.26e9 of weights, 5.2e9 of pools: 10.4 ms at 819 GB/s
    assert 3.25e9 < b["always_bytes"] / 10 < 3.27e9
    assert 5.1e9 < (total - b["always_bytes"]) / 10 < 5.3e9
    assert 10.2e-3 < total / 10 / 819e9 < 10.6e-3
    # the kernels alone
    assert evabyte.eva_decode_ops_and_bytes(CFG, 24 * 1404, 24 * 256, 2) == \
        (4 * 4096 * 24 * 1660, 16384 * 24 * 1660)
    ops, nbytes = evabyte.eva_prefill_ops_and_bytes(CFG, 8192, 2)
    exact, summed = 4 * 2048 * 2049 // 2, 2048 * 128 * (0 + 1 + 2 + 3)
    assert ops == 4 * 4096 * (exact + summed) + 8 * 4096 * 8192
    assert nbytes == 4096 * 2 * (3 * 8192 + 2 * 512) + 4 * 4096 * 8192


def test_the_two_readers_read_the_counters():
    class Facts:
        cfg, hists, trace, counters = CFG, {}, None, COUNTERS
    b = evabyte.decode_breakdown(CFG, COUNTERS, 2)
    pools = b["window_bytes"] + b["chunk_bytes"] + b["written_bytes"]
    share = readers.load_metric("eva_cache_bytes_share")[1](Facts)
    assert share == pytest.approx(100 * pools / (pools + b["always_bytes"]))
    assert 60 < share < 63
    assert readers.load_metric("eva_attended_share")[1](Facts) == \
        pytest.approx(100 * 1660 / 5500)
    for name in ("eva_cache_bytes_share", "eva_attended_share"):
        spec = readers.load_metric(name)[0]
        assert spec["layer"].startswith("cache manager")
        assert spec["moves"] == "itl_p50_ms"


def test_a_program_without_the_counters_gives_the_readers_nothing():
    """A program before PR 42 has no ``eva_*`` counters (and no such
    architecture); other architectures' modules break a step's bytes down
    into other parts."""
    old = {k: v for k, v in COUNTERS.items() if "eva_" not in k}

    class Facts:
        cfg, counters, hists, trace = CFG, old, {
            "paddle_request_decode_step_ms": (10, 150.0)}, None
        device_kind = "TPU v5 lite"
    for name in ("eva_cache_bytes_share", "eva_attended_share",
                 "decode_step_roofline_share"):
        assert readers.load_metric(name)[1](Facts) is None, name
    Facts.counters = COUNTERS
    share = readers.load_metric("decode_step_roofline_share")[1](Facts)
    assert 68 < share < 71              # 10.4 ms of a 15 ms step

    class Longcat(Facts):
        cfg = lm.load_config("longcat-flash-chat-l4")

    class Dense(Facts):
        cfg = lm.load_config("cerebras-gpt-1.3b")
    for facts in (Longcat, Dense):
        assert readers.load_metric("eva_cache_bytes_share")[1](facts) is None


def test_published_refuses_a_cut_width():
    pub = evabyte.published(CFG)
    assert set(pub["reducible"]) == {"num_hidden_layers"}
    for key, value in pub["widths"].items():
        assert CFG[key] == value, key
    for what, (built, value) in pub["as_built"].items():
        assert built == value, what
    for key in ("hidden_size", "intermediate_size", "window_size",
                "chunk_size", "vocab_size", "num_pred_heads"):
        cut = dict(CFG, **{key: CFG[key] // 2})
        assert cut[key] != evabyte.published(cut)["widths"][key], key
    narrow = evabyte.published(dict(CFG, num_attention_heads=64))
    assert narrow["as_built"]["head_dim"] == (64, 128)
    with pytest.raises(KeyError):
        evabyte.published(dict(CFG, source="https://example.com/other"))


def test_training_entry_points_say_why_they_are_not_there():
    for fn in (evabyte.train_program, evabyte.train_feed, evabyte.strategy,
               evabyte.train_flops_per_token):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            fn(CFG, {}, 0)
    with pytest.raises(KeyError):
        evabyte.kernels("train")
    assert evabyte.kernels("serve") == ("decode_attention_paged",)


def test_sizes_and_tiny_keep_every_mechanism():
    s = evabyte.sizes(CFG)
    assert (s["attention"], s["eva"]) == ("eva", dict(window=2048, chunk=16))
    assert (s["norm_offset"], s["pred_heads"], s["param_dtype"]) == \
        (1.0, 8, "bfloat16")
    assert (s["num_dense_layers"], s["num_experts"], s["post_norms"],
            s["embed_scale"]) == (8, 0, False, None)
    assert (s["head_dim"], s["rope_theta"], s["init_std"]) == \
        (128, 1e5, 0.01275)
    assert evabyte.vocab(CFG) == 320
    assert evabyte.max_positions(CFG) == 12288
    for other in ({"attention_class": "softmax"}, {"attention_bias": True},
                  {"num_key_value_heads": 8}, {"window_size": 2040}):
        with pytest.raises(ValueError, match="the evabyte module builds"):
            evabyte.sizes(dict(CFG, **other))
    tiny = evabyte.tiny(CFG)
    t = evabyte.sizes(tiny)
    assert t["eva"]["window"] % t["eva"]["chunk"] == 0
    assert t["pred_heads"] > 1 and t["norm_offset"] == 1.0
    serving = tiny["deployment"]["serving"]
    assert serving["block_size"] == tiny["chunk_size"]
    assert serving["cache_len"] > 2 * tiny["window_size"]
    assert architectures.load(tiny) is evabyte
    assert tiny["deployment"]["pipeline_stages"] == 4


def test_the_cells_rehearsal_at_tiny(tmp_path, monkeypatch):
    """``run_cell`` on the cell's own files with the sizes of ``tiny(cfg)``
    and the traffic shrunk: the traced line holds both new metrics, the
    contexts cross windows, and both pools are walked by the kernel."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for part in ("configs", "workloads"):
        os.makedirs(tmp_path / part)
    os.symlink(os.path.join(lm.BENCH_DIR, "layer_metrics"),
               tmp_path / "layer_metrics")
    tiny = evabyte.tiny(CFG)
    with open(tmp_path / "configs" / (CFG["name"] + ".json"), "w") as f:
        json.dump(tiny, f)
    cell = copy.deepcopy(lm.load_json("workloads", CELL + ".json"))
    cell.update(trace_seconds=1.0, prompt_buckets=[32, 64])
    cell["traffic"].update(
        prompt_len={"dist": "uniform", "lo": 8, "hi": 60},
        output_len={"dist": "uniform", "lo": 20, "hi": 60}, lead_in_s=0.5,
        clients=6, ramp_requests=4)
    with open(tmp_path / "workloads" / (CELL + ".json"), "w") as f:
        json.dump(cell, f)
    monkeypatch.setattr(lm, "BENCH_DIR", str(tmp_path))
    from benchmarks.harness import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(
        peaks.PEAKS["TPU v5 lite"], source="rehearsal"))
    result, notes, _ = bench_run.run_cell(
        bench, CELL, seed=2**31 + 7, seconds=3.0, trace=True,
        require_tpu=False, out_root=str(tmp_path / "out"))
    assert result["correct"] is True, notes["problems"]
    assert result["failed"] == 0 and result["attempted"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    _, layer = bench_run.cell_metrics(bench, CELL)
    assert {m["name"] for m in layer} - set(got) <= {"collective_share"}
    assert 0 < got["eva_attended_share"] < 100
    assert 0 < got["eva_cache_bytes_share"] < 100
    assert got["compiles_in_window"] == 0
    assert set(notes["kernel_paths"]["decode_attention_paged"]) == \
        {"interpret"}
    # every bucket's check crosses a window's edge while decoding
    check = notes["reference_check"]["per_bucket"]
    assert [r["prompt_len"] for r in check] == [30, 54]
    # under the configuration's ``amp`` the products are bfloat16 here too
    assert 0 < notes["reference_check"]["worst_rel_err"] < \
        notes["reference_check"]["rtol"]


def test_no_file_that_was_under_benchmarks_changed():
    """PR 42 added a configuration and a cell as files: every file that was
    under ``benchmarks/`` at its parent (00ca9f3) has the hash it had."""
    with open(os.path.join(DATA, "files_at_pr41.json")) as f:
        was = json.load(f)
    assert len(was) > 130
    for rel, digest in was.items():
        with open(os.path.join(lm.BENCH_DIR, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, rel


JOINED = ["output_tokens_per_s", "itl_p50_ms", "queue_wait_mean_ms",
          "tokens_per_decode_step", "decode_step_mean_ms", "prefill_mean_ms",
          "ttft_p90_ms", "itl_p99_ms", "delivered_tokens_per_s",
          "pallas_share_serve", "device_idle_share_serve",
          "decode_host_ms_per_step", "decode_device_wait_ms_per_step",
          "decode_step_roofline_share", "prefill_useful_token_share",
          "decode_steps_ahead_share", "setup_infer_shape_s",
          "setup_trace_lower_s", "setup_compile_s", "setup_cache_read_s",
          "setup_cache_misses"]


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 41 left it: every list starts with
    what it held, an entry that was there differs at most by cells appended
    to its ``workloads`` (this cell first), and what follows the old
    entries starts with PR 42's one configuration, one cell on one chip and
    two per-layer metrics. Later PRs append after them: nothing here counts
    the lists."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr41.json")) as f:
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
        "evabyte-6.5b-l8", ["num_hidden_layers"])
    assert config["source"] == CFG["source"] and \
        config["file"] == "benchmarks/configs/evabyte-6.5b-l8.json"
    entry = added["workloads"][0]
    assert (entry["name"], entry["config"], entry["chips"]) == \
        (CELL, "evabyte-6.5b-l8", 1)
    for m, (name, better) in zip(added["per_layer"], (
            ("eva_attended_share", "lower"),
            ("eva_cache_bytes_share", "higher"))):
        assert (m["name"], m["workloads"], m["moves"], m["unit"],
                m["better"]) == (name, [CELL], "itl_p50_ms", "%", better)
        assert m["layer"] == \
            "cache manager (serving/paged_cache.py, GenerationSession)"
        assert m["source"] == "program_counter"
    assert [w["name"] for w in now["workloads"][:len(was["workloads"]) + 1]
            if w["chips"] == 4] == ["lm-train-4chip"]
    cell = lm.load_json("workloads", CELL + ".json")
    assert cell["traffic"]["name"] == entry["traffic"] == \
        "closed-42-pasted-text-bytes"
    assert cell["why"] == entry["why"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    t = cell["traffic"]
    assert (t["clients"], t["ramp_requests"], t["schedule_seed"],
            t["lead_in_s"], t["max_requests"], cell["trace_seconds"]) == \
        (42, 28, 42, 10.0, 4096, 3.0)
    assert cell["prompt_buckets"] == [2048, 4096, 8192]
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.5, "lo": 1024, "hi": 8000}
    assert t["output_len"] == {"dist": "lognormal", "median": 1536,
                               "sigma": 0.5, "lo": 512, "hi": 4096}
    # a bucket is a whole number of windows; the longest prompt and the
    # longest output fit the cache
    assert all(b % CFG["window_size"] == 0 for b in cell["prompt_buckets"])
    assert t["prompt_len"]["hi"] + t["output_len"]["hi"] < \
        CFG["deployment"]["serving"]["cache_len"]
