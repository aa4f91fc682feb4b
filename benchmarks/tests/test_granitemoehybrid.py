"""The ``granitemoehybrid`` architecture module: its counts by hand at the
published widths, what a program without the new counter gives the new
reader, that PR 33 edited no file the benchmark had, and that
``BENCHMARK.json`` grew by appended entries only (the cell
``granite-serve-offline`` is rehearsed with the others by
``test_rehearsal.py``)."""

import hashlib
import json
import os

import pytest

from benchmarks import architectures
from benchmarks.architectures import granitemoehybrid as granite
from benchmarks.harness import lm, readers

CFG = lm.load_config("granite-4.0-h-small-l10")
CELL = "granite-serve-offline"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROW = 4 * (128 * 64 * 128 + 4 * 8448)           # a slot's row in a layer


def test_the_configuration_is_the_catalogs_row_but_for_what_it_reduces():
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(row):
        pytest.skip("no catalog here")
    with open(row) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "granite-4.0-h-small"' in line)
    assert CFG["source"] == entry["source_url"]
    differs = sorted(k for k, v in entry["config"].items() if CFG[k] != v)
    assert differs == sorted(CFG["reduced"]) == [
        "layer_types", "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert CFG["layer_types"] == entry["config"]["layer_types"][:10]
    assert CFG["layer_types"].count("mamba") == 9
    assert CFG["num_local_experts_published"] == \
        entry["config"]["num_local_experts"] == 72
    assert CFG["deployment"]["chips_sharing_a_layer"] * \
        CFG["num_local_experts"] == 72
    assert CFG["vocab_size"] * 4 == entry["config"]["vocab_size"]
    serving = CFG["deployment"]["serving"]
    assert serving["num_blocks"] * serving["block_size"] == \
        serving["slots"] * serving["cache_len"]
    assert (serving["slots"], serving["state_dtype"]) == (96, "float32")
    for key in ("assumed", "departures", "sizing", "published"):
        assert CFG[key]
    for item in ("router", "split order", "gate before the norm",
                 "initial values", "intermediate_size"):
        assert item in CFG["assumed"]


def test_parameters_by_hand():
    """ISSUE 33's arithmetic: 102.29 M a mixer, 291.33 M a Mamba layer with
    its 18 experts, 230.99 M the attention layer, 2,955.76 M held with the
    embedding counted once (the head is the same rows)."""
    mixer = 4096 * (8192 + 8448 + 128) + 4 * 8448 + 8448 + 3 * 128 + 8192 \
        + 8192 * 4096
    assert mixer == 102_286_976
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    expert, shared, router = 3 * 4096 * 768, 3 * 4096 * 1536, 4096 * 72
    assert (attention, expert, shared, router) == (
        41_943_040, 9_437_184, 18_874_368, 294_912)
    each = 2 * 4096 + router + shared + 18 * expert
    assert mixer + each == 291_333_760
    assert attention + each == 230_989_824
    embedding = 25088 * 4096
    held = 9 * (mixer + each) + attention + each + embedding + 4096
    assert granite.parameters_held(CFG) == held == 2_955_758_208
    assert CFG["parameters_as_built"] == held
    c = granite.param_counts(CFG)
    assert c["mixer_matmuls"] + c["mixer_rest"] == mixer
    # a token's own: in balance 10 x 18 / 72 = two and a half experts
    per_token = 9 * c["mixer_matmuls"] + attention \
        + 10 * (router + shared + 2.5 * expert) + embedding
    assert granite.matmul_params(CFG) == per_token == 1_492_451_328
    assert CFG["matmul_parameters_a_token"] == per_token
    assert 4 * granite.state_row_numbers(CFG) == ROW == 4_329_472
    # 9 layers of it a slot: 38.97 MB, 3.74 GB at 96 slots
    assert 9 * ROW == 38_965_248


COUNTERS = {
    "paddle_generation_decode_steps_total": 10,
    "paddle_generation_tokens_total": 960,
    "paddle_generation_context_tokens_total": 10 * 96 * 930,
    "paddle_generation_state_rows_updated_total": 10 * 96 * 9,
    "paddle_generation_experts_touched_total": 10 * 10 * 18,
    "paddle_generation_expert_assignments_total": 10 * 10 * 240,
    "paddle_generation_expert_max_load_total": 10 * 10 * 22,
    "paddle_generation_routed_pairs_total": 10 * 10 * 960,
    "paddle_generation_moe_layer_steps_total": 100,
}


def test_decode_ops_and_bytes_by_hand():
    """10 steps of 96 tokens at contexts of 930: every held expert touched,
    a quarter of the pairs computed, 864 state rows rewritten a step."""
    mixer_mm = 4096 * 16768 + 8192 * 4096
    mixer_rest = 4 * 8448 + 8448 + 3 * 128 + 8192
    attention, expert, shared, router = (41_943_040, 9_437_184, 18_874_368,
                                         294_912)
    matmuls = 9 * mixer_mm + attention + 10 * shared + 25088 * 4096
    small = 9 * mixer_rest + 10 * (router + 8192) + 4096
    always = (2 * matmuls + 4 * small) * 10
    experts = 2 * expert * 1800
    rows = 10 * 96 * 9
    state = 2 * ROW * rows
    context = 10 * 96 * 930
    kv = 2 * 8 * 128 * 4 * context
    flops = 2 * (matmuls + small) * 960 + 2 * expert * 24000 \
        + 4 * 128 * 64 * 128 * rows + 2 * 2048 * 4 * context
    got = granite.decode_ops_and_bytes(CFG, COUNTERS, weight_bytes=4,
                                       kv_bytes=4)
    assert got == (flops, always + experts + state + kv)
    # weight_bytes is ignored, as afmoe and kimi_k2 ignore it
    assert granite.decode_ops_and_bytes(CFG, COUNTERS, 2, 4) == got
    # a step: ISSUE 33's 2.51 GB always, 3.40 GB of experts, 7.48 GB of
    # state, 0.73 GB of keys and values
    assert 2.50e9 < always / 10 < 2.53e9
    assert 3.39e9 < experts / 10 < 3.41e9
    assert 7.47e9 < state / 10 < 7.49e9
    assert 0.72e9 < kv / 10 < 0.74e9
    b = granite.decode_breakdown(CFG, COUNTERS, 4)
    assert (b["always_bytes"], b["expert_bytes"], b["state_bytes"],
            b["kv_bytes"], b["flops"]) == (always, experts, state, kv, flops)


def test_the_mixers_counts_by_hand():
    assert granite.ssm_decode_ops_and_bytes(CFG, 96) == (
        4 * 128 * 64 * 128 * 96, 2 * ROW * 96)
    flops, nbytes = granite.ssd_prefill_ops_and_bytes(CFG, 2048)
    assert flops == 2 * 2048 * 256 * 128 + 2 * 128 * 2048 * 256 * 64 \
        + 4 * 2048 * 128 * 64 * 128
    assert nbytes == 4 * (2 * 2048 * 8192 + 2 * 2048 * 128 + 2048 * 128
                          + 128 * 64 * 128)
    # one chunk shorter than 256 rows
    assert granite.ssd_prefill_ops_and_bytes(CFG, 64)[0] == \
        2 * 64 * 64 * 128 + 2 * 128 * 64 * 64 * 64 + 4 * 64 * 128 * 64 * 128


def test_the_new_reader_reads_the_counter():
    class Facts:
        cfg, hists, trace, counters = CFG, {}, None, COUNTERS
    share = readers.load_metric("state_cache_bytes_share")[1](Facts)
    b = granite.decode_breakdown(CFG, COUNTERS, 4)
    assert share == pytest.approx(100 * b["state_bytes"] / (
        b["always_bytes"] + b["expert_bytes"] + b["state_bytes"]
        + b["kv_bytes"]))
    assert 52 < share < 54
    assert readers.load_metric("held_expert_pairs_ratio")[1](Facts) == 0.25
    assert readers.load_metric("experts_touched_per_layer_step")[1](Facts) \
        == 18.0


def test_a_program_without_the_counter_gives_the_readers_nothing():
    """The parent's program under this PR's benchmark files: no
    ``state_rows_updated_total``; and architectures with no state kind."""
    old = {k: v for k, v in COUNTERS.items() if "state_rows" not in k}

    class Facts:
        cfg, counters, hists, trace = CFG, old, {
            "paddle_request_decode_step_ms": (10, 250.0)}, None
        device_kind = "TPU v5 lite"
    assert granite.decode_ops_and_bytes(CFG, old, 4, 4) is None
    for name in ("state_cache_bytes_share", "decode_step_roofline_share"):
        assert readers.load_metric(name)[1](Facts) is None, name

    class Latent(Facts):
        cfg = lm.load_config("kimi-k2.7-code-l6")
        counters = dict(COUNTERS, **{
            "paddle_generation_latent_rows_attended_total": 10 * 32 * 3300})

    class Dense(Facts):
        cfg, counters = lm.load_config("cerebras-gpt-1.3b"), COUNTERS
    for facts in (Latent, Dense):
        assert readers.load_metric("state_cache_bytes_share")[1](facts) \
            is None
    # the held experts' imbalance asks the configuration for the held
    # count under ``n_routed_experts`` (PERF.md section 7), so the file
    # gives it there too: the busiest of the 18 over their mean load
    Facts.counters = COUNTERS
    assert CFG["n_routed_experts"] == CFG["num_local_experts"] == 18
    assert readers.load_metric("held_expert_load_imbalance")[1](Facts) == \
        pytest.approx(
            COUNTERS["paddle_generation_expert_max_load_total"] * 18
            / COUNTERS["paddle_generation_expert_assignments_total"])
    # with the counter, the step's share of its roofline at 25 ms a step
    share = readers.load_metric("decode_step_roofline_share")[1](Facts)
    assert 60 < share < 75


def test_training_entry_points_say_why_they_are_not_there():
    for fn in (granite.train_program, granite.train_feed, granite.strategy,
               granite.train_flops_per_token):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            fn(CFG, {}, 0)
    with pytest.raises(KeyError):
        granite.kernels("train")
    assert granite.kernels("serve") == ("decode_attention_paged",
                                        "moe_grouped_matmul")


def test_what_the_module_does_not_build_is_refused_with_a_sentence():
    for other in ({"mamba_n_groups": 8}, {"mamba_proj_bias": True},
                  {"position_embedding_type": "rope"},
                  {"tie_word_embeddings": False}, {"mamba_expand": 4},
                  {"layer_types": CFG["layer_types"][:9] + ["moe"]},
                  {"num_hidden_layers": 9}):
        with pytest.raises(ValueError,
                           match="the granitemoehybrid module builds"):
            granite.sizes(dict(CFG, **other))
    with pytest.raises(ValueError, match="held in float32"):
        granite.serve_spec(CFG, dict(CFG["deployment"]["serving"],
                                     state_dtype="bfloat16"), (256,))


def test_sizes_and_tiny_keep_every_mechanism():
    s = granite.sizes(CFG)
    assert (s["num_experts"], s["experts_held"], s["top_k"]) == (72, 18, 10)
    assert s["scoring"] == "softmax_topk" and s["tie_embeddings"]
    assert not (s["qk_norm"] or s["attn_gate"] or s["post_norms"])
    assert (s["embed_scale"], s["attn_scale"], s["residual_scale"],
            s["logit_scale"]) == (12.0, 1 / 128, 0.22, 1 / 16)
    assert s["layer_types"] == ["mamba"] * 5 + ["full_attention"] \
        + ["mamba"] * 4
    assert s["mamba"] == dict(num_heads=128, head_dim=64, state_dim=128,
                              conv_width=4, chunk=256)
    assert (s["moe_d_ff"], s["shared_d_ff"], s["num_dense_layers"]) == \
        (768, 1536, 0)
    assert granite.vocab(CFG) == 25088
    assert granite.max_positions(CFG) == 4096
    tiny = granite.tiny(CFG)
    t = granite.sizes(tiny)
    assert t["num_experts"] > t["experts_held"] > t["top_k"] >= 2
    assert set(t["layer_types"]) == {"mamba", "full_attention"}
    # a 16-row bucket crosses a chunk's edge
    assert t["mamba"]["chunk"] == 8
    assert architectures.load(tiny) is granite


def test_no_file_that_was_under_benchmarks_changed():
    """PR 33 added a configuration and a cell as files: every file that was
    under ``benchmarks/`` at its parent (f624f71) has the hash it had."""
    with open(os.path.join(DATA, "files_at_pr32.json")) as f:
        was = json.load(f)
    assert len(was) > 90
    for rel, digest in was.items():
        with open(os.path.join(lm.BENCH_DIR, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, rel


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 32 left it: every list starts with
    what it held, an entry that was there differs at most by cells appended
    to its ``workloads`` (this cell first), and what follows the old
    entries starts with PR 33's one configuration, one cell on one chip and
    one per-layer metric. Later PRs append after them: nothing here counts
    the lists."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr32.json")) as f:
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
    assert added["configs"][0]["name"] == "granite-4.0-h-small-l10"
    assert (added["workloads"][0]["name"],
            added["workloads"][0]["chips"]) == (CELL, 1)
    assert added["per_layer"][0]["name"] == "state_cache_bytes_share"
    assert added["per_layer"][0]["workloads"][0] == CELL
    # every metric that lists kimi-serve-offline but the one whose reader
    # wants a latent kind
    kimi = [m["name"] for m in was["end_to_end"] + was["per_layer"]
            if "kimi-serve-offline" in m.get("workloads", [])]
    assert [n for n in grew if n in kimi] == [
        n for n in kimi if n != "latent_cache_bytes_share"]
    assert len(kimi) == 20 and {"output_tokens_per_s", "itl_p50_ms",
                                "decode_step_roofline_share",
                                "held_expert_load_imbalance"} <= set(grew)
    assert [w["name"] for w in now["workloads"][:len(was["workloads"]) + 1]
            if w["chips"] == 4] == ["lm-train-4chip"]
    cell = lm.load_json("workloads", CELL + ".json")
    assert cell["traffic"]["name"] == added["workloads"][0]["traffic"] == \
        "closed-144-chat"
    assert cell["why"] == added["workloads"][0]["why"]
    assert len(cell["why"]) <= 200 and len(added["configs"][0]["why"]) <= 200
    t = cell["traffic"]
    assert (t["clients"], t["ramp_requests"], t["schedule_seed"]) == \
        (144, 96, 33)
    assert cell["prompt_buckets"] == [256, 512, 1024, 2048]
    assert t["prompt_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.6, "lo": 64, "hi": 2000}
    assert t["output_len"] == {"dist": "lognormal", "median": 768,
                               "sigma": 0.5, "lo": 192, "hi": 2048}
