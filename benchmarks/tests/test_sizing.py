"""The sizing tool (``benchmarks/sweeps/sizing.py``) compiles the executor's
own step for a *described* v5e. Every described-topology compile of the
benchmark's tests sits in this one file, inside a fixture, as the
``on-chip-measurement`` guide says (only one process may load the TPU
library, and only after a test of this file has started).

The compiles here are two-layer cuts, to stay quick; the sizes that decided
the configurations are recorded in ``benchmarks/configs/*.json``.
"""

import copy

import pytest

from benchmarks import architectures
from benchmarks.harness import lm


@pytest.fixture(scope="module")
def sizing():
    """The sizing module, steered like a TPU backend, where a v5e topology
    can be described; its steering is undone afterwards."""
    import jax
    import paddle_tpu as ptpu
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops import kernel_path
    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no libtpu
        pytest.skip("cannot describe a v5e topology here: %r" % (e,))
    from benchmarks.sweeps import sizing as mod
    interpret = kernel_path.interpret_mode
    precision = ptpu.config.get_flag("matmul_precision")
    cache_on = jax.config.jax_enable_compilation_cache
    mod._steer_like_tpu()
    yield mod
    kernel_path.interpret_mode = interpret
    ptpu.config.set_flags(matmul_precision=precision)
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cfg():
    c = copy.deepcopy(lm.load_config("cerebras-gpt-1.3b"))
    for key in architectures.load(c).published(c)["reducible"]:
        c[key] = 2      # a two-layer cut at the published widths
    return c


def test_train_step_compiles_with_the_flash_kernel(sizing, cfg):
    rec = sizing.size_train(cfg, {}, batch=4, seq=2048)
    assert rec["tpu_custom_calls"] == 2         # one flash forward a layer
    # 12 bytes a parameter: float32 weights and two Adam moments
    assert rec["per_device"]["argument_bytes"] == pytest.approx(
        12 * rec["n_params"], rel=1e-3)
    assert rec["per_device"]["live_bytes"] < 16.0e9


def test_sharded_train_step_has_its_collectives(sizing, cfg):
    rec = sizing.size_train(cfg, {"data": 2, "model": 2}, batch=4, seq=2048)
    assert rec["all_reduce"] > 0 and rec["tpu_custom_calls"] == 2
    one = sizing.size_train(cfg, {}, batch=4, seq=2048)
    # the blocks' weights are halved over the model axis; the odd vocab
    # leaves the head and the embedding whole
    assert rec["per_device"]["argument_bytes"] < \
        one["per_device"]["argument_bytes"]


def test_serving_programs_compile_with_the_paged_kernel(sizing, cfg):
    rec = sizing.size_serve(cfg, [128])
    assert rec["programs"]["decode"]["tpu_custom_calls"] == 2
    pool = 2 * 2 * 2048 * 16 * 2048 * 2     # layers x (k, v) x pool x bf16
    assert rec["programs"]["decode"]["argument_bytes"] > pool
    assert "prefill_128" in rec["programs"]
