"""chip_smoke.py off the chip: it refuses to run, and its phase functions
walk their whole control flow at a tiny width on the CPU (guide
on-chip-measurement §2, rehearsals 1 and 2). The phases' own checks are
the assertions: finite falling losses, every request answered, kernel
logits against the XLA reference, sharded against one-device losses.
Only the ``tpu_custom_call`` HLO check is skipped off-TPU; the kernels
run in interpret mode and are counted as such."""

import json
import os
import subprocess
import sys

import pytest

import paddle_tpu as ptpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY_LM = dict(vocab=128, d_model=64, num_heads=2, d_ff=128, num_layers=2)


def _run(code_or_script, env_extra, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, code_or_script, *args],
                          cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=120)


@pytest.mark.parametrize("args", [(), ("--chips", "4")],
                         ids=["one_chip", "four_chips"])
def test_off_chip_exits_nonzero_before_any_model(args):
    proc = _run(os.path.join(REPO, "chip_smoke.py"), {}, *args)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and "not a TPU" in last["error"]
    assert len(lines) == 1      # no phase ran, no model was built


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"],
                         ids=["fixed_path", "from_environment"])
def test_jax_cache_dir_is_placed_from_outside(env_dir):
    code = ("from paddle_tpu.core.compile_cache import enable_jax_cache;"
            "print(enable_jax_cache('/fixed/in/checkout'))")
    env = {} if env_dir is None else {"JAX_COMPILATION_CACHE_DIR": env_dir}
    proc = _run("-c", env, code)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == (env_dir or "/fixed/in/checkout")


def test_phase_train_tiny():
    # a path this process took before the phase (here: another test's call
    # that its gate handed to XLA) is not one the phase took
    from paddle_tpu.ops import kernel_path
    kernel_path.record("flash_attention")
    # a length of whole lane tiles: shorter ones take the backward's gate
    line = chip_smoke.phase_train(batch=2, seq_len=128, steps=3, **TINY_LM)
    assert line["ok"] and line["loss"][-1] < line["loss"][0]
    assert set(line["kernels"]["flash_attention"]) == {"interpret"}
    assert set(line["kernels"]["flash_attention_bwd"]) == {"interpret"}


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float32"])
def test_phase_serve_tiny(kv_dtype):
    line = chip_smoke.phase_serve(
        max_len=64, slots=4, prompt_buckets=(8, 32),
        prompt_lens=(3, 7, 20, 30), new_tokens=4, kv_dtype=kv_dtype,
        logit_rtol=2e-2, **TINY_LM)
    assert line["ok"] and line["requests"] == 4 and line["tokens"] == 16
    # two prompt buckets + one decode step: the shape set stays closed
    assert line["compiles"] == 3
    assert line["kernels"]["decode_attention_paged"]["interpret"] > 0


def test_phase_resnet_tiny():
    line = chip_smoke.phase_resnet(depth=18, batch=16, res=32,
                                   class_dim=10, steps=3)
    assert line["ok"] and line["loss"][-1] < line["loss"][0]


def test_phase_cross_chip_on_four_virtual_devices():
    line = chip_smoke.phase_cross_chip(
        4, TINY_LM, lm_batch=8, lm_seq_len=128, lm_steps=3, wd_vocab=4096,
        wd_slots=4, wd_emb_dim=8, wd_batch=64, wd_steps=3, loss_rtol=2e-2)
    assert line["ok"]


@pytest.mark.parametrize("place", [ptpu.TPUPlace, ptpu.CUDAPlace])
def test_tpu_place_raises_without_a_tpu(place):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        place().jax_device()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ptpu.Executor(place())
    assert ptpu.is_compiled_with_tpu() is False
    ptpu.Executor(ptpu.CPUPlace())      # the host is always there
