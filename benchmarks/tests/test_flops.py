"""flops.py against cases computed by hand."""

import pytest

from benchmarks.harness import flops, peaks

# a model small enough to count on paper
TOY = dict(n_embd=4, n_inner=16, n_layer=2, vocab_size=10)
# the repo's own 12-layer LM at vocab 32768 (chip_smoke.py), and the two
# configurations of the benchmark
SMOKE = dict(n_embd=2048, n_inner=8192, n_layer=12, vocab_size=32768)
FULL = dict(n_embd=2048, n_inner=8192, n_layer=24, vocab_size=50257)


def test_matmul_params_by_hand():
    # per layer 4*4*4 + 2*4*16 = 192; two layers 384; head 4*10 = 40
    assert flops.matmul_params(TOY) == 424
    # 12 * (4*2048^2 + 2*2048*8192) + 2048*32768
    assert flops.matmul_params(SMOKE) == 12 * 50331648 + 67108864
    assert flops.matmul_params(FULL) == 24 * 50331648 + 2048 * 50257


def test_embedding_tables_are_not_in_n():
    # the program builds 740,519,936 parameters at SMOKE's sizes with a
    # 1,024-row position table; the matmul parameters are 671,088,640: the
    # two tables (67,108,864 + 2,097,152) and the small vectors are out
    assert flops.matmul_params(SMOKE) == 671088640


def test_train_flops_per_token_by_hand():
    # 6*N + 6*L*T*d with T = 8: 6*424 + 6*2*8*4 = 2544 + 384
    assert flops.train_flops_per_token(TOY, 8) == 2928
    # PR 21's step: 4.18 GFLOP a token by the corrected N
    got = flops.train_flops_per_token(SMOKE, 1024)
    assert got == 6 * 671088640 + 6 * 12 * 1024 * 2048
    assert round(got / 1e9, 2) == 4.18


def test_mfu_by_hand():
    # 2928 FLOP a token at 1e6 tokens/s on 2 chips of 1e10 FLOP/s
    assert flops.mfu(TOY, 8, 1e6, 2, 1e10) == pytest.approx(0.1464)


def test_decode_step_bytes_and_flops_by_hand():
    # weights 424 * 4 bytes; KV of contexts 3 and 5: 8 * 2 * 4 * 2 * 2 bytes
    assert flops.decode_step_bytes(TOY, [3, 5]) == 1696 + 256
    # 2*424 per sequence, two sequences; attention 4*L*d*sum = 4*2*4*8
    assert flops.decode_step_flops(TOY, [3, 5]) == 1696 + 256


def test_roofline_names_its_bound():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, p) == (10.0, "compute")
    assert flops.roofline_seconds(10.0, 1000.0, p) == (100.0, "memory")


def test_peaks_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
