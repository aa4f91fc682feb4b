"""The ``phi4flash`` architecture module: its counts by hand at the
published widths, the readers that hold for its cell (the new
``borrowed_kv_bytes_share`` among them), what ``published`` refuses, the
cell's rehearsal at ``tiny(cfg)``, that PR 49 edited no file the cell runs
through, and that ``BENCHMARK.json`` grew by appended entries and appended
names only (the cell ``phi4flash-serve-offline`` is rehearsed beside the
others by ``test_rehearsal.py`` too). Everything runs on the CPU: none of
its numbers is a device number."""

import copy
import hashlib
import json
import os
import types

import pytest

from benchmarks import architectures, run as bench_run
from benchmarks.architectures import phi4flash
from benchmarks.harness import lm, readers

CFG = lm.load_config("phi-4-mini-flash-reasoning")
CELL = "phi4flash-serve-offline"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_the_configuration_is_the_catalogs_row_whole():
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(row):
        pytest.skip("no catalog here")
    with open(row) as f:
        entry = next(json.loads(line) for line in f if
                     '"name": "Phi-4-mini-flash-reasoning"' in line)
    assert CFG["source"] == entry["source_url"]
    assert {k: CFG[k] for k in entry["config"]} == entry["config"]
    assert CFG["reduced"] == []
    assert (CFG["hidden_size"], CFG["num_hidden_layers"],
            CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["intermediate_size"], CFG["sliding_window"],
            CFG["vocab_size"], CFG["mb_per_layer"]) == \
        (2560, 32, 40, 20, 10240, 512, 200064, 2)
    # what the catalog does not give, each under ``assumed`` and unverified
    assert (CFG["mamba_d_state"], CFG["mamba_d_conv"], CFG["mamba_expand"],
            CFG["mamba_dt_rank"], CFG["memory_from"], CFG["kv_from"]) == \
        (16, 4, 2, 160, 16, 17)
    unverified = [k for k, v in CFG["assumed"].items() if "UNVERIFIED" in v]
    assert len(unverified) == 8
    for item in ("attention_bias true", "norms", "positions",
                 "differential attention", "the gated memory unit",
                 "the window", "initial values"):
        assert item in CFG["assumed"], item
    assert any("held packed" in d for d in CFG["departures"])
    assert any("last row only" in d for d in CFG["departures"])
    dep = CFG["deployment"]
    assert dep["chips_sharing_a_layer"] == 1
    assert dep["serving"] == dict(
        slots=64, cache_len=8192, block_size=16, num_blocks=32768,
        window_num_blocks=2560, kv_dtype="bfloat16", state_dtype="float32")
    # no ``amp``: one bfloat16 pass a product read 3.4% on the chip
    assert CFG["flags"] == {"flash_attention": True}
    assert any("NOT served in one bfloat16 pass" in d
               for d in CFG["departures"])
    sizing = CFG["sizing"]
    assert sizing["serve_decode_64slots"]["live_bytes"] < 16.91e9
    assert max(sizing["serve_prefill_live_bytes"].values()) < 15.8e9
    assert sizing["serve_decode_64slots"]["tpu_custom_calls"] == 16


def test_parameters_by_hand():
    d, di, f = 2560, 5120, 10240
    mixer = d * 2 * di + di * 192 + 160 * di + di * d
    mixer_rest = 5 * di + di + 16 * di + di
    attention, attention_rest = d * 5120 + d * d, 5120 + d + 6 * 64
    cross, cross_rest = 2 * d * d, 2 * d + 6 * 64
    gmu, ffn, norms = 2 * d * di, 3 * d * f, 4 * d
    c = phi4flash.param_counts(CFG)
    assert (c["mixer_matmuls"], c["mixer_rest"], c["attention"],
            c["attention_rest"], c["cross"], c["cross_rest"], c["gmu"],
            c["ffn"], c["norms"]) == \
        (mixer, mixer_rest, attention, attention_rest, cross, cross_rest,
         gmu, ffn, norms)
    assert (c["mamba_layers"], c["window_layers"], c["full_layers"],
            c["cross_layers"], c["gmu_layers"]) == (9, 8, 1, 7, 7)
    # ISSUE 49: 119.90 M, 98.32 M, 91.77 M, 104.87 M, 512.16 M: 3,852.6 M
    assert round((mixer + mixer_rest + ffn + norms) / 1e6, 2) == 119.90
    assert round((attention + attention_rest + ffn + norms) / 1e6, 2) == \
        98.32
    assert round((cross + cross_rest + ffn + norms) / 1e6, 2) == 91.77
    assert round((gmu + ffn + norms) / 1e6, 2) == 104.87
    total = 9 * (mixer + mixer_rest) + 9 * (attention + attention_rest) \
        + 7 * (cross + cross_rest) + 7 * gmu + 32 * (ffn + norms) \
        + d * 200064 + 2 * d
    assert phi4flash.parameters_held(CFG) == total == \
        CFG["parameters_as_built"] == 3852562944
    assert phi4flash.matmul_params(CFG) == CFG["matmul_parameters_a_token"] \
        == 9 * mixer + 9 * attention + 7 * cross + 7 * gmu + 32 * ffn \
        + d * 200064
    assert phi4flash.state_row_numbers(CFG) * 4 + 4 == 409604
    assert phi4flash.row_bytes(CFG, 2) == 5120


# ten decode steps of 64 slots at a mean context of 3,500: the full layer
# walks its pool once, the seven cross layers seven times more, the eight
# window layers 512 rows each
COUNTERS = {
    "paddle_generation_decode_steps_total": 10,
    "paddle_generation_tokens_total": 640,
    "paddle_generation_context_tokens_total": 640 * 3500,
    "paddle_generation_borrowed_context_tokens_total": 7 * 640 * 3500,
    "paddle_generation_window_context_tokens_total": 8 * 640 * 512,
    "paddle_generation_state_rows_updated_total": 10 * 9 * 64,
}


def test_decode_breakdown_by_hand():
    c = phi4flash.param_counts(CFG)
    matmuls = 9 * c["mixer_matmuls"] + 9 * c["attention"] + 7 * c["cross"] \
        + 7 * c["gmu"] + 32 * c["ffn"] + 2560 * 200064
    small = 9 * c["mixer_rest"] + 9 * c["attention_rest"] \
        + 7 * c["cross_rest"] + 32 * c["norms"] + 2 * 2560
    b = phi4flash.decode_breakdown(CFG, COUNTERS, 2)
    assert b["always_bytes"] == 10 * (2 * matmuls + 4 * small) \
        + 2 * 2560 * 640
    assert b["kv_bytes"] == 5120 * 640 * 3500
    assert b["borrowed_kv_bytes"] == 7 * b["kv_bytes"]
    assert b["window_bytes"] == 5120 * 8 * 640 * 512
    assert b["state_bytes"] == 2 * 409600 * 5760
    flops = 2 * matmuls * 640 \
        + 2 * 40 * 192 * (8 * 640 * 3500 + 8 * 640 * 512) \
        + 6 * 5120 * 16 * 5760
    assert b["flops"] == flops
    total = sum(v for k, v in b.items() if k.endswith("_bytes"))
    got = phi4flash.decode_ops_and_bytes(CFG, COUNTERS, weight_bytes=4,
                                         kv_bytes=2)
    assert got == (flops, total)
    assert phi4flash.decode_ops_and_bytes(CFG, COUNTERS, 2, 2) == got
    # ISSUE 49's step: 7.7e9 weights, 9.2e9 the eight walks, 1.3e9 the
    # windows, 0.5e9 the state rows = 18.7e9 B, 22.8 ms at 819 GB/s
    assert 7.70e9 < b["always_bytes"] / 10 < 7.72e9
    assert 9.1e9 < (b["kv_bytes"] + b["borrowed_kv_bytes"]) / 10 < 9.2e9
    assert 1.3e9 < b["window_bytes"] / 10 < 1.35e9
    assert 0.47e9 < b["state_bytes"] / 10 < 0.48e9
    assert 22.7e-3 < total / 10 / 819e9 < 22.9e-3
    # the scan alone
    assert phi4flash.s6_decode_ops_and_bytes(CFG, 64) == (
        6 * 5120 * 16 * 64, 2 * 409600 * 64)
    assert phi4flash.s6_prefill_ops_and_bytes(CFG, 2048) == (
        6 * 5120 * 16 * 2048,
        4 * (3 * 2048 * 5120 + 2 * 2048 * 16 + 16 * 5120))
    # a program that does not count the borrowed walks has nothing to read
    for missing in ("borrowed_context", "window_context", "state_rows"):
        fewer = {k: v for k, v in COUNTERS.items() if missing not in k}
        assert phi4flash.decode_breakdown(CFG, fewer, 2) is None


def _facts(**more):
    return types.SimpleNamespace(
        cfg=CFG, cell={"name": CELL}, counters=COUNTERS, trace=None,
        hists={"paddle_request_decode_step_ms": (10, 300.0)},
        device_kind="TPU v5 lite", **more)


def test_the_readers_read_the_cells_counters():
    b = phi4flash.decode_breakdown(CFG, COUNTERS, 2)
    total = sum(v for k, v in b.items() if k.endswith("_bytes"))
    facts = _facts()

    def read(name):
        return readers.load_metric(name)[1](facts)
    assert read("borrowed_kv_bytes_share") == \
        pytest.approx(100 * b["borrowed_kv_bytes"] / total)
    # ISSUE 49: 43% of the step are the seven borrowed walks
    assert 42 < read("borrowed_kv_bytes_share") < 44
    assert read("state_cache_bytes_share") == \
        pytest.approx(100 * b["state_bytes"] / total)
    assert 2 < read("state_cache_bytes_share") < 3
    # the eight window layers attend 512 of a mean 3,500 rows
    assert read("window_attended_share") == pytest.approx(100 * 512 / 3500)
    # 22.8 ms of a 30 ms step
    assert read("decode_step_roofline_share") == \
        pytest.approx(100 * total / 819e9 / 0.3)
    assert read("decode_step_roofline_share") < 100
    spec = readers.load_metric("borrowed_kv_bytes_share")[0]
    assert (spec["layer"], spec["moves"], spec["unit"], spec["source"],
            spec["better"]) == (
        "cache manager (serving/paged_cache.py, GenerationSession)",
        "itl_p50_ms", "%", "program_counter", "higher")


def test_the_new_reader_finds_nothing_where_no_layer_borrows():
    read = readers.load_metric("borrowed_kv_bytes_share")[1]
    for other in ("granite-4.0-h-small-l10", "trinity-mini-l5",
                  "cerebras-gpt-1.3b"):
        facts = _facts()
        facts.cfg = lm.load_config(other)
        assert read(facts) is None, other
    facts = _facts()
    facts.counters = {k: v for k, v in COUNTERS.items()
                      if "borrowed" not in k}
    assert read(facts) is None                      # before the counter


# -- what the module refuses and keeps ----------------------------------------

def test_published_refuses_a_cut_width():
    pub = phi4flash.published(CFG)
    for key, value in pub["widths"].items():
        assert CFG[key] == value, key
    for key, value in pub["reducible"].items():
        assert CFG[key] == value, key          # and nothing is reduced
    for what, (built, value) in pub["as_built"].items():
        assert built == value, what
    for key in ("hidden_size", "num_attention_heads", "intermediate_size",
                "sliding_window", "mamba_d_state", "mamba_dt_rank",
                "mamba_expand"):
        cut = dict(CFG, **{key: CFG[key] // 2})
        assert cut[key] != phi4flash.published(cut)["widths"][key], key
    other = phi4flash.published(dict(
        CFG, layer_types=CFG["layer_types"][::-1]))
    assert other["as_built"]["layer_types"][0] != \
        other["as_built"]["layer_types"][1]
    with pytest.raises(KeyError):
        phi4flash.published(dict(CFG, source="https://example.com/other"))


def test_training_entry_points_say_why_they_are_not_there():
    for fn in (phi4flash.train_program, phi4flash.train_feed,
               phi4flash.strategy, phi4flash.train_flops_per_token):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            fn(CFG, {}, 0)
    with pytest.raises(KeyError):
        phi4flash.kernels("train")
    assert phi4flash.kernels("serve") == ("decode_attention_paged",)


def test_sizes_and_tiny_keep_every_mechanism():
    s = phi4flash.sizes(CFG)
    assert s["layer_types"] == ["mamba", "sliding_attention"] * 8 \
        + ["mamba", "full_attention"] + ["gmu", "cross_attention"] * 7
    assert (s["kv_from"], s["memory_from"]) == (17, 16)
    assert s["mamba"] == dict(scan="s6", d_inner=5120, state_dim=16,
                              conv_width=4, dt_rank=160, bc_std=0.06)
    assert (s["d_model"], s["num_heads"], s["num_kv_heads"], s["head_dim"],
            s["d_ff"], s["sliding_window"], s["num_dense_layers"]) == \
        (2560, 40, 20, 64, 10240, 512, 32)
    assert (s["norm"], s["attn_bias"], s["differential"],
            s["window_rotary"], s["tie_embeddings"], s["post_norms"],
            s["qk_norm"], s["attn_gate"], s["embed_scale"],
            s["param_dtype"]) == ("layer", True, True, False, True, False,
                                  False, False, None, "bfloat16")
    assert phi4flash.vocab(CFG) == 200064
    assert phi4flash.max_positions(CFG) == 8192
    for other in ({"hidden_act": "gelu"}, {"tie_word_embeddings": False},
                  {"mlp_bias": True}, {"mb_per_layer": 3},
                  {"num_hidden_layers": 30}, {"kv_from": 16},
                  {"mamba_conv_bias": False}, {"attention_bias": False}):
        with pytest.raises(ValueError, match="the phi4flash module builds"):
            phi4flash.sizes(dict(CFG, **other))
    tiny = phi4flash.tiny(CFG)
    t = phi4flash.sizes(tiny)
    assert set(t["layer_types"]) == {
        "mamba", "sliding_attention", "full_attention", "gmu",
        "cross_attention"}
    assert architectures.load(tiny) is phi4flash
    assert tiny["deployment"]["chips_sharing_a_layer"] == 1


def test_the_cells_rehearsal_at_tiny(tmp_path, monkeypatch):
    """``run_cell`` on the cell's own files with the sizes of ``tiny(cfg)``
    and the traffic shrunk: the traced line holds the accepted metrics that
    hold for the cell and the new one, every walk went through the kernel
    (interpreted), and the logits agree with the reference."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for part in ("configs", "workloads"):
        os.makedirs(tmp_path / part)
    os.symlink(os.path.join(lm.BENCH_DIR, "layer_metrics"),
               tmp_path / "layer_metrics")
    tiny = phi4flash.tiny(CFG)
    with open(tmp_path / "configs" / (CFG["name"] + ".json"), "w") as f:
        json.dump(tiny, f)
    cell = copy.deepcopy(lm.load_json("workloads", CELL + ".json"))
    cell.update(trace_seconds=1.0, prompt_buckets=[16, 32])
    cell["traffic"].update(
        prompt_len={"dist": "uniform", "lo": 4, "hi": 28},
        output_len={"dist": "uniform", "lo": 8, "hi": 24}, lead_in_s=0.5,
        clients=6, ramp_requests=4)
    with open(tmp_path / "workloads" / (CELL + ".json"), "w") as f:
        json.dump(cell, f)
    monkeypatch.setattr(lm, "BENCH_DIR", str(tmp_path))
    from benchmarks.harness import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(
        peaks.PEAKS["TPU v5 lite"], source="rehearsal"))
    result, notes, _ = bench_run.run_cell(
        bench, CELL, seed=2**31 + 49, seconds=3.0, trace=True,
        require_tpu=False, out_root=str(tmp_path / "out"))
    assert result["correct"] is True, notes["problems"]
    assert result["failed"] == 0 and result["attempted"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    _, layer = bench_run.cell_metrics(bench, CELL)
    # every per-layer metric of the cell has something to read
    assert {m["name"] for m in layer} == set(got)
    assert 0 < got["borrowed_kv_bytes_share"] < 100
    assert 0 < got["state_cache_bytes_share"] < 100
    assert 0 < got["window_attended_share"] <= 100
    assert got["compiles_in_window"] == 0
    assert set(notes["kernel_paths"]["decode_attention_paged"]) == \
        {"interpret"}
    # exact products in float32 at the tiny size: the sums' order
    assert 0 < notes["reference_check"]["worst_rel_err"] < 1e-3


# -- the benchmark's files ----------------------------------------------------

JOINED = ["output_tokens_per_s", "itl_p50_ms", "queue_wait_mean_ms",
          "tokens_per_decode_step", "decode_step_mean_ms", "prefill_mean_ms",
          "ttft_p90_ms", "itl_p99_ms", "delivered_tokens_per_s",
          "pallas_share_serve", "device_idle_share_serve",
          "decode_host_ms_per_step", "decode_device_wait_ms_per_step",
          "decode_step_roofline_share", "prefill_useful_token_share",
          "window_attended_share", "decode_steps_ahead_share",
          "state_cache_bytes_share", "setup_infer_shape_s",
          "setup_trace_lower_s", "setup_compile_s", "setup_cache_read_s",
          "setup_cache_misses"]


def test_the_files_the_cell_runs_through_are_the_parents():
    """PR 49 added a configuration, a cell and a metric as files and edited
    nothing that was under ``benchmarks/``: every file its parent (2c2d65d)
    had there has the hash it had."""
    with open(os.path.join(DATA, "files_at_pr48.json")) as f:
        was = json.load(f)
    assert {"run.py", "harness/serve.py", "harness/trace_reduce.py",
            "architectures/__init__.py", "reference/__init__.py",
            "reference/nemotron_h.py", "sweeps/sizing_kinds.py",
            "workloads/trinity-serve-offline.json"} <= set(was)
    assert {"layer_metrics/%s.json" % name for name in JOINED
            if name not in ("output_tokens_per_s", "itl_p50_ms")} <= set(was)
    for rel, digest in was.items():
        with open(os.path.join(lm.BENCH_DIR, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, rel


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 48 left it: every list starts with
    what it held, an entry that was there differs at most by cells appended
    to its ``workloads`` (this cell first), and what follows the old
    entries starts with PR 49's one configuration, one cell on one chip and
    one per-layer metric. Later PRs append after them: nothing here counts
    the lists."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr48.json")) as f:
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
                assert lists[1][:len(lists[0])] == lists[0], old["name"]
                if CELL in lists[1]:
                    assert lists[1][len(lists[0])] == CELL, old["name"]
                    grew.append(old["name"])
        added[key] = now[key][len(was[key]):]
    assert grew == [m["name"] for m in was["end_to_end"] + was["per_layer"]
                    if m["name"] in JOINED] and len(grew) == len(JOINED)
    assert added["end_to_end"] == []
    config = added["configs"][0]
    assert (config["name"], config["reduced"]) == (CFG["name"], [])
    assert config["source"] == CFG["source"] and config["file"] == \
        "benchmarks/configs/phi-4-mini-flash-reasoning.json"
    entry = added["workloads"][0]
    assert (entry["name"], entry["config"], entry["chips"]) == \
        (CELL, CFG["name"], 1)
    assert added["per_layer"][0] == {
        "name": "borrowed_kv_bytes_share", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "cache manager (serving/paged_cache.py, GenerationSession)",
        "moves": "itl_p50_ms", "workloads": [CELL]}
    assert [w["name"] for w in now["workloads"][:len(was["workloads"]) + 1]
            if w["chips"] == 4] == ["lm-train-4chip"]
    cell = lm.load_json("workloads", CELL + ".json")
    assert cell["traffic"]["name"] == entry["traffic"] == \
        "closed-96-reasoning"
    assert cell["why"] == entry["why"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    t = cell["traffic"]
    assert (t["loop"], t["clients"], t["ramp_requests"], t["schedule_seed"],
            t["lead_in_s"], t["max_requests"], cell["trace_seconds"]) == \
        ("closed", 96, 64, 49, 10.0, 4096, 3.0)
    assert cell["prompt_buckets"] == [1024, 2048, 4096]
    assert t["prompt_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 0.5, "lo": 512, "hi": 4000}
    assert t["output_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 0.5, "lo": 512, "hi": 4096}
