"""The GPT-2 block's counts (``architectures/gpt2_block.py``) and
``flops.py``'s arithmetic against cases computed by hand."""

import pytest

from benchmarks.architectures import gpt2_block
from benchmarks.harness import flops, peaks

# a model small enough to count on paper
TOY = dict(n_embd=4, n_head=2, n_inner=16, n_layer=2, n_positions=8,
           vocab_size=10)
# the repo's own 12-layer LM at vocab 32768 (chip_smoke.py), and the full
# configuration of the benchmark
SMOKE = dict(n_embd=2048, n_head=16, n_inner=8192, n_layer=12,
             n_positions=1024, vocab_size=32768)
FULL = dict(SMOKE, n_layer=24, n_positions=2048, vocab_size=50257)

STEPS = "paddle_generation_decode_steps_total"
TOKENS = "paddle_generation_tokens_total"
PREFILLS = "paddle_generation_prefills_total{bucket=128}"
CONTEXT = "paddle_generation_context_tokens_total"


def test_matmul_params_by_hand():
    # per layer 4*4*4 + 2*4*16 = 192; two layers 384; head 4*10 = 40
    assert gpt2_block.matmul_params(TOY) == 424
    # 12 * (4*2048^2 + 2*2048*8192) + 2048*32768
    assert gpt2_block.matmul_params(SMOKE) == 12 * 50331648 + 67108864
    assert gpt2_block.matmul_params(FULL) == 24 * 50331648 + 2048 * 50257


def test_embedding_tables_are_not_in_n():
    # the program builds 740,519,936 parameters at SMOKE's sizes with a
    # 1,024-row position table; the matmul parameters are 671,088,640: the
    # two tables (67,108,864 + 2,097,152) and the small vectors are out
    assert gpt2_block.matmul_params(SMOKE) == 671088640


def test_train_flops_per_token_by_hand():
    # 6*N + 6*L*T*d with T = 8: 6*424 + 6*2*8*4 = 2544 + 384
    assert gpt2_block.train_flops_per_token(TOY, 8) == 2928
    # PR 21's step: 4.18 GFLOP a token by the corrected N
    got = gpt2_block.train_flops_per_token(SMOKE, 1024)
    assert got == 6 * 671088640 + 6 * 12 * 1024 * 2048
    assert round(got / 1e9, 2) == 4.18


def test_mfu_by_hand():
    # 2928 FLOP a token at 1e6 tokens/s on 2 chips of 1e10 FLOP/s
    assert flops.mfu(gpt2_block.train_flops_per_token(TOY, 8), 1e6, 2,
                     1e10) == pytest.approx(0.1464)


def test_decode_ops_and_bytes_by_hand():
    # one step of two sequences at contexts 3 and 5, after one prefill
    one = {STEPS: 1.0, TOKENS: 3.0, PREFILLS: 1.0, CONTEXT: 8.0}
    nflops, nbytes = gpt2_block.decode_ops_and_bytes(
        TOY, one, weight_bytes=4, kv_bytes=2)
    # 2*424 per sequence, two sequences; attention 4*L*d*sum = 4*2*4*8
    assert nflops == 1696 + 256
    # weights 424 * 4 bytes; KV of 8 tokens: 8 * 2 * 4 * 2 * 2 bytes
    assert nbytes == 1696 + 256
    # the weights are read once a step: two more steps with nothing cached
    # would add twice their bytes and no operation
    three = dict(one, **{STEPS: 3.0})
    assert gpt2_block.decode_ops_and_bytes(
        TOY, three, weight_bytes=4, kv_bytes=2) == (nflops, nbytes + 2 * 1696)
    # no decode step, or a program that does not count the context
    assert gpt2_block.decode_ops_and_bytes(TOY, {}, 4, 2) is None
    assert gpt2_block.decode_ops_and_bytes(
        TOY, {STEPS: 1.0, TOKENS: 3.0, PREFILLS: 1.0}, 4, 2) is None


def test_roofline_names_its_bound():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, p) == (10.0, "compute")
    assert flops.roofline_seconds(10.0, 1000.0, p) == (100.0, "memory")


def test_peaks_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
