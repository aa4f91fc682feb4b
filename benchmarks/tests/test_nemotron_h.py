"""The ``nemotron_h`` architecture module: its counts by hand at the
published widths, the readers that hold for its cell (the new
``expert_stream_roofline_share`` on a small ``.xplane.pb`` written here),
what ``published`` refuses, the cell's rehearsal at ``tiny(cfg)``, that PR
46 edited no file the cell runs through, and that ``BENCHMARK.json`` grew by
appended entries and appended names only (the cell
``nemotron-serve-offline`` is rehearsed beside the others by
``test_rehearsal.py`` too). Everything runs on the CPU: none of its numbers
is a device number."""

import copy
import hashlib
import json
import os
import types

import pytest

from benchmarks import architectures, run as bench_run
from benchmarks.architectures import nemotron_h
from benchmarks.harness import lm, readers, trace_reduce as tr
from benchmarks.layer_metrics import expert_stream_roofline_share as esr

CFG = lm.load_config("nemotron-3-nano-30b-a3b-l13")
CELL = "nemotron-serve-offline"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]


def test_the_configuration_is_the_catalogs_row_but_for_its_four_cuts():
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(row):
        pytest.skip("no catalog here")
    with open(row) as f:
        entry = next(json.loads(line) for line in f if
                     '"name": "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
    assert CFG["source"] == entry["source_url"]
    differs = [k for k in CFG["reduced"]
               if CFG[k] != entry["config"][k]]
    assert sorted(k for k, v in entry["config"].items() if CFG[k] != v) == \
        sorted(differs)
    assert differs == CFG["reduced"] == REDUCED
    assert {k: CFG["published"][k] for k in REDUCED} == \
        {k: entry["config"][k] for k in REDUCED}
    # no width cut
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"],
            CFG["mamba_num_heads"], CFG["mamba_head_dim"],
            CFG["ssm_state_size"], CFG["n_groups"], CFG["conv_kernel"],
            CFG["chunk_size"], CFG["moe_intermediate_size"],
            CFG["moe_shared_expert_intermediate_size"],
            CFG["num_experts_per_tok"], CFG["n_routed_experts_published"],
            CFG["routed_scaling_factor"]) == \
        (2688, 32, 2, 128, 64, 64, 128, 8, 4, 128, 1856, 3712, 6, 128, 2.5)
    # the cut: the pattern's first two runs to an attention layer, half the
    # experts and half the vocabulary, within the guide's floors
    assert CFG["hybrid_override_pattern"] == "MEMEM*EMEMEM*" == \
        entry["config"]["hybrid_override_pattern"][:13]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"], CFG["expert_offset"]) == (13, 64, 65536, 0)
    assert CFG["n_routed_experts"] >= 8 and \
        CFG["vocab_size"] * 8 >= entry["config"]["vocab_size"]
    dep = CFG["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["stages"]) == (2, 4)
    assert dep["stages"] * CFG["num_hidden_layers"] == 52
    assert "6 pairs a decode step" in dep["expert_load"] and \
        "against 12" in dep["expert_load"]
    assert dep["serving"] == dict(slots=128, cache_len=4096, block_size=16,
                                  num_blocks=32768, kv_dtype="float32",
                                  state_dtype="float32")
    for key in ("assumed", "departures", "sizing", "published"):
        assert CFG[key]
    for item in ("no positions in attention", "split order of in_proj",
                 "the grouped gated norm", "initial values",
                 "rescale_prenorm_residual"):
        assert item in CFG["assumed"], item
    assert any("held [1856, 2688]" in d for d in CFG["departures"])
    assert CFG["sizing"]["serve_decode_128slots"]["live_bytes"] < 16.91e9


def test_parameters_by_hand():
    mixer = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    mixer_rest = 4 * 6144 + 6144 + 3 * 64 + 4096
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256
    expert, shared = 2 * 2688 * 1856, 2 * 2688 * 3712
    router = 2688 * 128 + 128
    c = nemotron_h.param_counts(CFG)
    assert (c["mixer_matmuls"], c["mixer_rest"], c["attention"],
            c["expert"], c["shared"], c["router"] + c["router_bias"]) == \
        (mixer, mixer_rest, attention, expert, shared, router)
    assert (c["mixer_layers"], c["expert_layers"],
            c["attention_layers"]) == (6, 5, 2)
    # ISSUE 46: 38.74 M, 23.40 M, 658.9 M, 352.3 M: 3,926 M in all
    assert round((mixer + mixer_rest + 2688) / 1e6, 2) == 38.74
    assert round((attention + 2688) / 1e6, 2) == 23.40
    layer = 64 * expert + shared + router + 2688
    assert round(layer / 1e6, 1) == 658.9
    total = 6 * (mixer + mixer_rest + 2688) + 2 * (attention + 2688) \
        + 5 * layer + 2 * 2688 * 65536 + 2688
    assert nemotron_h.parameters_held(CFG) == total == \
        CFG["parameters_as_built"] == 3926018560
    # a token's matmuls here: 6 x 64 / 128 = 3 experts' worth
    assert nemotron_h.matmul_params(CFG) == CFG["matmul_parameters_a_token"] \
        == 6 * mixer + 2 * attention + 5 * (
            2688 * 128 + shared + 3 * expert) + 2688 * 65536
    assert nemotron_h.state_row_numbers(CFG) * 4 + 4 == 2195460


# ten decode steps of 128 slots at a mean context of 1,500: all 64 held
# experts touched in 5 layers but two, half the routed pairs held
COUNTERS = {
    "paddle_generation_decode_steps_total": 10,
    "paddle_generation_tokens_total": 1280,
    "paddle_generation_context_tokens_total": 1280 * 1500,
    "paddle_generation_experts_touched_total": 10 * 5 * 64 - 2,
    "paddle_generation_expert_assignments_total": 10 * 128 * 6 * 5 // 2,
    "paddle_generation_expert_max_load_total": 50 * 14,
    "paddle_generation_moe_layer_steps_total": 50,
    "paddle_generation_routed_pairs_total": 10 * 128 * 6 * 5,
    "paddle_generation_state_rows_updated_total": 10 * 6 * 128,
}


def test_decode_breakdown_by_hand():
    c = nemotron_h.param_counts(CFG)
    matmuls = 6 * c["mixer_matmuls"] + 2 * c["attention"] \
        + 5 * c["shared"] + 2688 * 65536
    small = 6 * c["mixer_rest"] + 5 * (2688 * 128 + 128) + 14 * 2688
    b = nemotron_h.decode_breakdown(CFG, COUNTERS, 4)
    assert b["always_bytes"] == 10 * (2 * matmuls + 4 * small) \
        + 2 * 2688 * 1280
    assert b["expert_bytes"] == 2 * c["expert"] * 3198
    assert b["state_bytes"] == 2 * 2195456 * 7680
    assert b["kv_bytes"] == 4096 * 1280 * 1500
    flops = 2 * (matmuls + small) * 1280 + 2 * c["expert"] * 19200 \
        + 4 * 4096 * 128 * 7680 + 2 * 512 * 16 * 2 * 1280 * 1500
    assert b["flops"] == flops
    total = sum(b[k] for k in ("always_bytes", "expert_bytes",
                               "state_bytes", "kv_bytes"))
    got = nemotron_h.decode_ops_and_bytes(CFG, COUNTERS, weight_bytes=4,
                                          kv_bytes=4)
    assert got == (flops, total)
    assert nemotron_h.decode_ops_and_bytes(CFG, COUNTERS, 2, 4) == got
    # ISSUE 46's step: 1.11e9 always, 6.39e9 experts, 3.37e9 state, 0.79e9
    # keys and values = 11.65e9 B, 14.2 ms at 819 GB/s
    assert 1.11e9 < b["always_bytes"] / 10 < 1.12e9
    assert 6.37e9 < b["expert_bytes"] / 10 < 6.39e9
    assert 3.37e9 < b["state_bytes"] / 10 < 3.38e9
    assert 0.78e9 < b["kv_bytes"] / 10 < 0.79e9
    assert 14.1e-3 < total / 10 / 819e9 < 14.3e-3
    # the kernels alone, the groups taken
    assert nemotron_h.grouped_matmul_ops_and_bytes(CFG, 768, 64) == (
        2 * 2 * 2688 * 1856 * 768,
        2 * 2 * 2688 * 1856 * 64 + 4 * 768 * 2 * (2688 + 1856))
    assert nemotron_h.ssm_decode_ops_and_bytes(CFG, 128) == (
        4 * 4096 * 128 * 128, 2 * 2195456 * 128)
    ops, nbytes = nemotron_h.ssd_prefill_ops_and_bytes(CFG, 2048)
    assert ops == 2 * 8 * 2048 * 128 * 128 + 2 * 64 * 2048 * 128 * 64 \
        + 4 * 2048 * 4096 * 128
    assert nbytes == 4 * (2 * 2048 * 4096 + 2 * 2048 * 8 * 128 + 2048 * 64
                          + 4096 * 128)


def _facts(**more):
    return types.SimpleNamespace(
        cfg=CFG, cell={"name": CELL}, counters=COUNTERS, trace=None,
        hists={"paddle_request_decode_step_ms": (10, 200.0)},
        device_kind="TPU v5 lite", **more)


def test_the_accepted_readers_read_the_cells_counters():
    b = nemotron_h.decode_breakdown(CFG, COUNTERS, 4)
    total = sum(v for k, v in b.items() if k.endswith("_bytes"))
    facts = _facts()

    def read(name):
        return readers.load_metric(name)[1](facts)
    assert read("state_cache_bytes_share") == \
        pytest.approx(100 * b["state_bytes"] / total)
    assert 28 < read("state_cache_bytes_share") < 30
    # 14.2 ms of a 20 ms step
    assert read("decode_step_roofline_share") == \
        pytest.approx(100 * total / 819e9 / 0.2)
    assert read("experts_touched_per_layer_step") == \
        pytest.approx(3198 / 50)
    assert read("held_expert_pairs_ratio") == pytest.approx(0.5)
    # the busiest of the 64 held (``n_routed_experts``) over the mean 6
    assert read("held_expert_load_imbalance") == pytest.approx(14 / 6)
    # its breakdown has four parts: the reader of three has nothing to read
    assert read("expert_bytes_share") is None
    assert read("expert_stream_roofline_share") is None     # --trace 0


# -- expert_stream_roofline_share ---------------------------------------------

MS = 1_000_000      # ns
FIRST = ("%moe_grouped_matmul.2 = f32[768,1856]{1,0:T(8,128)S(1)} custom-call("
         "s32[96]{0} %a, f32[768,2688]{1,0} %x, bf16[64,1856,2688]{2,1,0} "
         "%wu), custom_call_target=\"tpu_custom_call\"")
DOWN = ("%moe_grouped_matmul.3 = f32[768,2688]{1,0:T(8,128)} custom-call("
        "s32[96]{0} %a, f32[768,1856]{1,0} %moe_grouped_matmul.2, "
        "bf16[64,1856,2688]{2,1,0} %wd), "
        "custom_call_target=\"tpu_custom_call\"")
PASS = FIRST.replace("768", "2048").replace("matmul.2", "matmul.7")
OTHER = ("%fusion.9 = f32[128,10304]{1,0} fusion(f32[128,2688]{1,0} %p.3), "
         "kind=kOutput, calls=%fused_computation.9")


def _xplane(where, planes):
    """An ``.xplane.pb`` where ``find_xplane`` looks for it, from
    ``{plane name: {line name: [(event name, start_ns, dur_ns), ...]}}``."""
    from jax.profiler import ProfileData
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        meta = {n: i for i, n in enumerate(names, 1)}
        body = ["id: %d name: %s" % (pid, json.dumps(plane))]
        for lid, (line, evs) in enumerate(lines.items(), 1):
            events = " ".join(
                "events { metadata_id: %d offset_ps: %d duration_ps: %d }"
                % (meta[n], s * 1000, d * 1000) for n, s, d in evs)
            body.append("lines { id: %d name: %s timestamp_ns: 0 %s }"
                        % (lid, json.dumps(line), events))
        body += ["event_metadata { key: %d value { id: %d name: %s } }"
                 % (i, i, json.dumps(n)) for n, i in meta.items()]
        out.append("planes { %s }" % " ".join(body))
    where = where / "trace" / "plugins" / "profile" / "t0"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(out)))


# chip 0: two whole runs of the decode module, [10, 30) and [40, 60), each
# with the kernel's two calls of 2 + 1 ms in each of its five expert layers
# (one layer drawn: the rest are left to the division) and a fusion; a run
# the window [0, 80) cuts; a prefill with a longer pass
def _planes():
    decode = [("jit_decode(77)", 10 * MS, 20 * MS),
              ("jit_decode(77)", 40 * MS, 20 * MS),
              ("jit_decode(77)", 75 * MS, 20 * MS),
              ("jit_prefill_512(78)", 62 * MS, 10 * MS)]
    ops = []
    for start in (10, 40, 75):
        ops += [(OTHER, start * MS, MS), (FIRST, (start + 1) * MS, 2 * MS),
                (DOWN, (start + 3) * MS, MS)]
    ops += [(PASS, 63 * MS, 8 * MS)]
    return {"/device:TPU:1": {"XLA Ops": [(FIRST, 0, 80 * MS)],
                              "XLA Modules": [("jit_decode(77)", 0, 80 * MS)]},
            "/device:TPU:0": {"XLA Ops": sorted(ops, key=lambda e: e[1]),
                              "XLA Modules": decode},
            "/host:CPU": {"python": [(tr.WINDOW_SPAN, 0, 80 * MS)]}}


def test_the_new_reader_takes_the_decode_steps_kernel_calls(
        tmp_path, monkeypatch):
    monkeypatch.setattr(lm, "CHECKOUT", str(tmp_path))
    read = readers.load_metric("expert_stream_roofline_share")[1]
    traced = dict(trace={"window_s": 0.080})
    facts = _facts()
    facts.trace = traced["trace"]
    assert read(facts) is None                      # no trace was written
    _xplane(tmp_path / ".bench_out" / CELL, _planes())
    window, calls, runs = esr.load(tr.find_xplane(
        str(tmp_path / ".bench_out" / CELL / "trace")))
    assert window == (0, 80 * MS) and len(calls) == 7 and len(runs) == 3
    # 3 ms of the kernel in each whole run, the cut run and the prefill's
    # pass left out
    assert esr.kernel_seconds_a_run(calls, runs, window) == \
        pytest.approx(3e-3)
    # 3,198 touched experts in 50 layer steps of 19.96 MB at 819 GB/s,
    # over 3 ms a run in five layers
    least = 3198 / 50 * 2 * 2 * 2688 * 1856 / 819e9
    assert read(facts) == pytest.approx(100 * least / (3e-3 / 5))
    assert 0 < read(facts) < 100 or least > 3e-3 / 5
    facts.trace = {"window_s": 3.0}
    assert read(facts) is None                      # another run's trace
    facts.trace = traced["trace"]
    facts.counters = {k: v for k, v in COUNTERS.items()
                      if "experts_touched" not in k}
    assert read(facts) is None                      # before the counters
    facts.counters = COUNTERS
    facts.cfg = lm.load_config("cerebras-gpt-1.3b")
    assert read(facts) is None                      # no such kernel counted
    spec = readers.load_metric("expert_stream_roofline_share")[0]
    assert (spec["layer"], spec["moves"], spec["unit"], spec["source"]) == \
        ("expert FFN op (ops/moe_ops.py moe_ffn)", "itl_p50_ms", "%",
         "device_trace")


def test_a_trace_without_the_kernel_or_a_device_has_nothing_to_read(
        tmp_path, monkeypatch):
    monkeypatch.setattr(lm, "CHECKOUT", str(tmp_path))
    facts = _facts()
    facts.trace = {"window_s": 0.080}
    planes = _planes()
    planes["/device:TPU:0"]["XLA Ops"] = [(OTHER, 10 * MS, MS)]
    _xplane(tmp_path / ".bench_out" / CELL, planes)
    read = readers.load_metric("expert_stream_roofline_share")[1]
    assert read(facts) is None
    # the CPU rehearsal: host planes only
    facts.cell = {"name": "rehearsal"}
    _xplane(tmp_path / ".bench_out" / "rehearsal",
            {"/host:CPU": planes["/host:CPU"]})
    assert read(facts) is None


# -- what the module refuses and keeps ----------------------------------------

def test_published_refuses_a_cut_width():
    pub = nemotron_h.published(CFG)
    assert set(pub["reducible"]) == set(REDUCED)
    for key, value in pub["widths"].items():
        assert CFG[key] == value, key
    for what, (built, value) in pub["as_built"].items():
        assert built == value, what
    for key in ("hidden_size", "head_dim", "mamba_num_heads",
                "mamba_head_dim", "ssm_state_size", "n_groups",
                "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "num_experts_per_tok", "n_routed_experts_published"):
        cut = dict(CFG, **{key: CFG[key] // 2})
        assert cut[key] != nemotron_h.published(cut)["widths"][key], key
    fewer = nemotron_h.published(dict(CFG, n_routed_experts=32))
    assert fewer["as_built"]["experts_a_chip"] == (32, 64)
    other = nemotron_h.published(dict(CFG, hybrid_override_pattern="M" * 13))
    assert other["as_built"]["pattern"][0] != other["as_built"]["pattern"][1]
    with pytest.raises(KeyError):
        nemotron_h.published(dict(CFG, source="https://example.com/other"))


def test_training_entry_points_say_why_they_are_not_there():
    for fn in (nemotron_h.train_program, nemotron_h.train_feed,
               nemotron_h.strategy, nemotron_h.train_flops_per_token):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            fn(CFG, {}, 0)
    with pytest.raises(KeyError):
        nemotron_h.kernels("train")
    assert nemotron_h.kernels("serve") == ("decode_attention_paged",
                                           "moe_grouped_matmul")


def test_sizes_and_tiny_keep_every_mechanism():
    s = nemotron_h.sizes(CFG)
    assert s["block"] == dict(sublayers=1) and s["expert_act"] == "relu2"
    assert s["layer_types"] == [
        {"M": "mamba", "E": "experts", "*": "full_attention"}[c]
        for c in "MEMEM*EMEMEM*"]
    assert s["mamba"] == dict(num_heads=64, head_dim=64, state_dim=128,
                              conv_width=4, chunk=128, n_groups=8)
    assert (s["scoring"], s["route_norm"], s["route_scale"], s["top_k"],
            s["num_experts"], s["experts_held"], s["expert_offset"]) == \
        ("sigmoid", True, 2.5, 6, 128, 64, 0)
    assert (s["d_model"], s["num_heads"], s["num_kv_heads"], s["head_dim"],
            s["moe_d_ff"], s["shared_d_ff"]) == (2688, 32, 2, 128, 1856, 3712)
    assert (s["embed_scale"], s["post_norms"], s["qk_norm"], s["attn_gate"],
            s["param_dtype"]) == (None, False, False, False, "bfloat16")
    assert "tie_embeddings" not in s and "rope_theta" not in s
    assert nemotron_h.vocab(CFG) == 65536
    assert nemotron_h.max_positions(CFG) == 4096
    for other in ({"mlp_hidden_act": "silu"}, {"n_groups": 7},
                  {"tie_word_embeddings": True}, {"n_group": 2},
                  {"hybrid_override_pattern": "MEMEM-EMEMEM*"},
                  {"hybrid_override_pattern": "MEMEM"},
                  {"use_conv_bias": False}, {"mlp_bias": True}):
        with pytest.raises(ValueError, match="the nemotron_h module builds"):
            nemotron_h.sizes(dict(CFG, **other))
    tiny = nemotron_h.tiny(CFG)
    t = nemotron_h.sizes(tiny)
    assert set(t["layer_types"]) == {"mamba", "experts", "full_attention"}
    assert t["mamba"]["n_groups"] > 1 and t["moe_d_ff"] % 128
    assert t["mamba"]["num_heads"] * t["mamba"]["head_dim"] != \
        tiny["expand"] * tiny["hidden_size"]
    assert t["experts_held"] < t["num_experts"]
    assert architectures.load(tiny) is nemotron_h
    assert tiny["deployment"]["chips_sharing_a_layer"] == 2


def test_the_cells_rehearsal_at_tiny(tmp_path, monkeypatch):
    """``run_cell`` on the cell's own files with the sizes of ``tiny(cfg)``
    and the traffic shrunk: the traced line holds the accepted metrics that
    hold for the cell, the held experts' matmuls went through the kernels
    (interpreted) at a width of a lane tile and a half, and the logits
    agree with the reference."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for part in ("configs", "workloads"):
        os.makedirs(tmp_path / part)
    os.symlink(os.path.join(lm.BENCH_DIR, "layer_metrics"),
               tmp_path / "layer_metrics")
    tiny = nemotron_h.tiny(CFG)
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
        bench, CELL, seed=2**31 + 46, seconds=3.0, trace=True,
        require_tpu=False, out_root=str(tmp_path / "out"))
    assert result["correct"] is True, notes["problems"]
    assert result["failed"] == 0 and result["attempted"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    _, layer = bench_run.cell_metrics(bench, CELL)
    # no device plane off the chip: nothing for the trace's two to read
    assert {m["name"] for m in layer} - set(got) <= {
        "expert_stream_roofline_share"}
    assert 0 < got["state_cache_bytes_share"] < 100
    assert 0 < got["experts_touched_per_layer_step"] <= 4
    assert 0 < got["held_expert_pairs_ratio"] < 1
    assert got["held_expert_load_imbalance"] >= 1
    assert got["compiles_in_window"] == 0
    assert set(notes["kernel_paths"]["moe_grouped_matmul"]) == {"interpret"}
    # exact products: the float32 sums' order, far inside the limit
    assert 0 < notes["reference_check"]["worst_rel_err"] < 1e-3


# -- the benchmark's files ----------------------------------------------------

def test_the_files_the_cell_runs_through_are_the_parents():
    """PR 46 added a configuration, a cell and a metric as files and edited
    nothing the cell runs through: the entry point, the harness, the two
    packages' contracts, the readers of the metrics the cell reports and
    the cell whose traffic it shares have the hash they had at its parent
    (d820494). Only those: a later ``benchmark`` PR that edits another
    file leaves this test alone, and one that edits these knows from it
    that this cell's numbers are on another yardstick."""
    with open(os.path.join(DATA, "files_at_pr45.json")) as f:
        was = json.load(f)
    assert {"run.py", "harness/serve.py", "harness/trace_reduce.py",
            "architectures/__init__.py", "reference/__init__.py",
            "workloads/granite-serve-offline.json"} <= set(was)
    assert {"layer_metrics/%s.json" % name for name in JOINED
            if name not in ("output_tokens_per_s", "itl_p50_ms")} <= set(was)
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
          "held_expert_pairs_ratio", "held_expert_load_imbalance",
          "state_cache_bytes_share", "setup_infer_shape_s",
          "setup_trace_lower_s", "setup_compile_s", "setup_cache_read_s",
          "setup_cache_misses"]


def test_benchmark_json_grew_by_appended_entries_only():
    """Against ``BENCHMARK.json`` as PR 45 left it: every list starts with
    what it held, an entry that was there differs at most by cells appended
    to its ``workloads`` (this cell first), and what follows the old
    entries starts with PR 46's one configuration, one cell on one chip and
    one per-layer metric. Later PRs append after them: nothing here counts
    the lists."""
    with open(os.path.join(lm.CHECKOUT, "BENCHMARK.json")) as f:
        now = json.load(f)
    with open(os.path.join(DATA, "benchmark_at_pr45.json")) as f:
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
                # appended names only; this cell's first where it joined
                # (a later PR's cell may join a list this one did not)
                assert lists[1][:len(lists[0])] == lists[0], old["name"]
                if CELL in lists[1]:
                    assert lists[1][len(lists[0])] == CELL, old["name"]
                    grew.append(old["name"])
        added[key] = now[key][len(was[key]):]
    assert grew == [m["name"] for m in was["end_to_end"] + was["per_layer"]
                    if m["name"] in JOINED] and len(grew) == len(JOINED)
    assert added["end_to_end"] == []
    config = added["configs"][0]
    assert (config["name"], config["reduced"]) == (CFG["name"], REDUCED)
    assert config["source"] == CFG["source"] and config["file"] == \
        "benchmarks/configs/nemotron-3-nano-30b-a3b-l13.json"
    entry = added["workloads"][0]
    assert (entry["name"], entry["config"], entry["chips"]) == \
        (CELL, CFG["name"], 1)
    assert added["per_layer"][0] == {
        "name": "expert_stream_roofline_share", "unit": "%",
        "better": "higher", "source": "device_trace",
        "layer": "expert FFN op (ops/moe_ops.py moe_ffn)",
        "moves": "itl_p50_ms", "workloads": [CELL]}
    assert [w["name"] for w in now["workloads"][:len(was["workloads"]) + 1]
            if w["chips"] == 4] == ["lm-train-4chip"]
    cell = lm.load_json("workloads", CELL + ".json")
    assert cell["traffic"]["name"] == entry["traffic"] == "closed-192-chat"
    assert cell["why"] == entry["why"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    t = cell["traffic"]
    assert (t["loop"], t["clients"], t["ramp_requests"], t["schedule_seed"],
            t["lead_in_s"], t["max_requests"], cell["trace_seconds"]) == \
        ("closed", 192, 128, 46, 8.0, 4096, 3.0)
    assert cell["prompt_buckets"] == [256, 512, 1024, 2048]
    assert t["prompt_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.6, "lo": 64, "hi": 2000}
    assert t["output_len"] == {"dist": "lognormal", "median": 768,
                               "sigma": 0.5, "lo": 192, "hi": 2048}
    # the chat mix of granite-serve-offline at the same clients a slot
    granite = lm.load_json("workloads", "granite-serve-offline.json")
    for key in ("prompt_len", "output_len", "lead_in_s", "max_requests"):
        assert t[key] == granite["traffic"][key], key
    assert t["clients"] * 96 == granite["traffic"]["clients"] * 128
    # the longest prompt and the longest output fit the cache
    assert t["prompt_len"]["hi"] + t["output_len"]["hi"] < \
        CFG["deployment"]["serving"]["cache_len"]
