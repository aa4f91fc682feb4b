"""The four per-layer metrics of the decode round (PR 23) on hand-made
facts, and that the traced rehearsal attributes idle gaps to the program's
own spans. The readers are data files but for the roofline share, which
brings ``layer_metrics/decode_step_roofline_share.py``."""

import numpy as np
import pytest

from benchmarks import architectures, run as bench_run
from benchmarks.harness import common, flops, lm, peaks, readers
from test_rehearsal import BENCH, tiny_bench  # noqa: F401 — the fixture

CFG = lm.load_config("cerebras-gpt-1.3b")
ARCH = architectures.load(CFG)
HOST = "paddle_generation_host_ms_total{phase=%s}"
STEPS = "paddle_generation_decode_steps_total"
WAIT = "paddle_generation_device_wait_ms_total"
TOKENS = "paddle_generation_tokens_total"
PREFILLS = "paddle_generation_prefills_total{bucket=128}"
CONTEXT = "paddle_generation_context_tokens_total"
STEP_MS = "paddle_request_decode_step_ms"


class _Device:
    device_kind = "TPU v5 lite"


def _facts(counters, hists=None):
    facts = common.Facts({}, CFG, [_Device()], 45.0)
    facts.counters, facts.hists = dict(counters), dict(hists or {})
    return facts


def _read(name, facts):
    return readers.load_metric(name)[1](facts)


# one window by hand: 10 decode steps; 3.0 + 1.5 + 0.5 + 1.0 ms of host
# turn and 40 ms of admissions; 2,700 ms blocked on the device
WINDOW = {STEPS: 10.0, HOST % "deliver": 3.0, HOST % "prepare": 1.5,
          HOST % "dispatch": 0.5, HOST % "other": 1.0, HOST % "admit": 40.0,
          WAIT: 2700.0,
          "paddle_generation_prompt_tokens_total": 700.0,
          "paddle_generation_prefill_padded_tokens_total": 1024.0}


@pytest.mark.parametrize("name,want", [
    ("decode_host_ms_per_step", 0.6),         # admit is kept apart
    ("decode_device_wait_ms_per_step", 270.0),
    ("prefill_useful_token_share", 700.0 / 1024.0),
])
def test_counter_readers_on_a_window_by_hand(name, want):
    assert _read(name, _facts(WINDOW)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "decode_host_ms_per_step", "decode_device_wait_ms_per_step",
    "decode_step_roofline_share", "prefill_useful_token_share"])
def test_a_window_without_decode_steps_or_prefills_reads_nothing(name):
    assert _read(name, _facts({STEPS: 0.0, TOKENS: 0.0})) is None
    assert _read(name, _facts({})) is None


def test_roofline_reader_reads_nothing_from_a_program_without_the_counter():
    """The parent of PR 23 counts steps and tokens and times the step, but
    not the context attended: the line then leaves the metric out."""
    facts = _facts({STEPS: 10.0, TOKENS: 330.0, PREFILLS: 10.0},
                   {STEP_MS: (10, 2770.0)})
    assert _read("decode_step_roofline_share", facts) is None


def test_roofline_share_of_one_step_by_hand():
    """32 sequences at 450 cached tokens each, one step of 277 ms: float32
    weights once, bf16 keys and values of 14,400 tokens, at 819 GB/s."""
    facts = _facts({STEPS: 1.0, TOKENS: 32.0, CONTEXT: 32 * 450.0},
                   {STEP_MS: (1, 277.0)})
    weights = 4 * ARCH.matmul_params(CFG)
    kv = 32 * 450 * 2 * 2048 * 24 * 2      # tokens x (k, v) x d x L x bf16
    least_ms = (weights + kv) / 819e9 * 1e3
    assert 9.0 < least_ms < 11.0        # PERF.md's "9.9 ms" step
    assert _read("decode_step_roofline_share", facts) == pytest.approx(
        100.0 * least_ms / 277.0)


@pytest.mark.parametrize("seed", range(6))
def test_roofline_share_cannot_pass_100_on_a_chip_at_its_roofline(seed):
    """Any window whose every step took at least its own least time (no
    chip does better) reads at most 100, and exactly 100 only where every
    step ran at its roofline under one bound: the window's totals are
    linear in the counters, and the least time of the totals is at most
    the sum of the steps' least times."""
    rs = np.random.RandomState(seed)
    pk = peaks.peaks_for(_Device.device_kind)
    steps = int(rs.randint(1, 40))
    tokens = context = 0
    took_s = 0.0
    slack = rs.choice([0.0, 0.5])       # at the roofline, or half over it
    for _ in range(steps):
        lens = rs.randint(1, 2049, size=rs.randint(1, 33)).tolist()
        least, _ = flops.roofline_seconds(*ARCH.decode_ops_and_bytes(
            CFG, {STEPS: 1.0, TOKENS: float(len(lens)),
                  CONTEXT: float(sum(lens))}, weight_bytes=4, kv_bytes=2), pk)
        took_s += least * (1.0 + slack)
        tokens += len(lens)
        context += sum(lens)
    # C's invariants: one prefill per finished request, tokens counted with
    # the prefill's; prepare + dispatch + wait is the step's time
    facts = _facts({STEPS: float(steps), TOKENS: tokens + 7.0, PREFILLS: 7.0,
                    CONTEXT: float(context), WAIT: took_s * 1e3},
                   {STEP_MS: (steps, took_s * 1e3)})
    share = _read("decode_step_roofline_share", facts)
    assert share <= 100.0 * (1 + 1e-9)
    assert share == pytest.approx(100.0 / (1.0 + slack), rel=1e-6)


@pytest.mark.parametrize("name", ["lm-serve-offline", "lm-serve-online"])
def test_rehearsals_idle_gaps_hold_a_span_of_the_program(tiny_bench, name):
    """A CPU walk of the traced cell: the dispatcher's spans are on the
    profiler's clock, so idle gaps go to them (their seconds are the CPU's
    and stand for nothing)."""
    result, _, _ = bench_run.run_cell(
        BENCH, name, seed=5, seconds=3.0, trace=True, require_tpu=False,
        out_root=str(tiny_bench / "out"))
    owners = [owner for owner, _ in result["breakdown"]["idle_gaps"]]
    assert any(o.startswith(("scheduler:", "session:")) for o in owners), \
        owners
    got = result["metrics"]
    for metric in ("decode_host_ms_per_step",
                   "decode_device_wait_ms_per_step",
                   "decode_step_roofline_share"):
        assert got[metric]["value"] > 0
    # host + wait: the step's time and the turn's remainder, less admits
    assert got["decode_host_ms_per_step"]["value"] + \
        got["decode_device_wait_ms_per_step"]["value"] >= \
        got["decode_step_mean_ms"]["value"]
    assert ("prefill_useful_token_share" in got) == ("online" in name)
    if "online" in name:
        assert 0 < got["prefill_useful_token_share"]["value"] <= 1
