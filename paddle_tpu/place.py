"""Places — device identities (reference ``paddle/platform/place.h:24-53``:
CPUPlace/CUDAPlace variant). TPU-native: TPUPlace is first-class and names
a real TPU device or raises; CUDAPlace is kept as an API-compat alias of
it.
"""

import jax

__all__ = ["CPUPlace", "TPUPlace", "CUDAPlace", "is_compiled_with_tpu"]


class _Place:
    def __repr__(self):
        return self.__class__.__name__ + "()"

    def __eq__(self, other):
        return type(self) is type(other) and \
            getattr(self, "device_id", 0) == getattr(other, "device_id", 0)

    def __hash__(self):
        return hash((type(self).__name__, getattr(self, "device_id", 0)))


class CPUPlace(_Place):
    def jax_device(self):
        return jax.devices("cpu")[0]


class TPUPlace(_Place):
    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise RuntimeError(
                "%r needs a TPU, but JAX's default backend is %r (%d "
                "device(s)); use CPUPlace() to run on the host"
                % (self, devs[0].platform, len(devs)))
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                "%s(%d): this host has %d TPU device(s)"
                % (type(self).__name__, self.device_id, len(devs)))
        return devs[self.device_id]


class CUDAPlace(TPUPlace):
    """Compat alias: scripts written against the reference's CUDAPlace run
    on the TPU."""


def is_compiled_with_tpu():
    return any(d.platform == "tpu" for d in jax.devices())
