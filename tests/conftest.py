"""Test config: force a CPU backend with 8 virtual devices, so
sharding/mesh tests run anywhere (SURVEY §4: the analog of the reference's
CPU-stub strategy that lets all code paths test without accelerators).
Pallas kernels run in interpret mode here; tests/test_tpu_compile.py is
where the chip's compiler sees them, and ``python chip_smoke.py`` on the
chip is where they run.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) >= 8

# Exact f32 matmuls/convs for numeric checks (prod keeps the fast bf16-MXU
# default; this mirrors the reference comparing against CPU math).
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs and a fresh scope."""
    import paddle_tpu as ptpu
    from paddle_tpu.core import framework, scope
    prev_main = framework.switch_main_program(ptpu.Program())
    prev_startup = framework.switch_startup_program(ptpu.Program())
    prev_scope = scope._global_scope
    scope._global_scope = scope.Scope()
    yield
    framework.switch_main_program(prev_main)
    framework.switch_startup_program(prev_startup)
    scope._global_scope = prev_scope
