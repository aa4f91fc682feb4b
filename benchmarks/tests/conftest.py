"""The benchmark's own tests: run from the repo root with

    python -m pytest benchmarks/tests -q

They are not part of the repo's tier-1 suite (``tests/``). Everything here
runs on the CPU: four virtual devices for the mesh cell, Pallas kernels in
the interpreter. Nothing measured here is a device number.
"""

import os
import sys

# before JAX is imported anywhere: the CPU backend with four devices
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
