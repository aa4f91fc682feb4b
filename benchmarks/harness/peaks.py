"""Published peaks of the accelerators the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error, not a
default: a share of an unknown peak means nothing.

Source for the TPU v5e row: Google Cloud documentation, "TPU v5e" system
architecture page (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e
at 819 GB/s, 1,600 Gbit/s inter-chip interconnect).
"""

PEAKS = {
    # jax.devices()[0].device_kind on a v5e is "TPU v5 lite"
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e, per-chip figures",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device kind %r; add a row "
                       "with its source to benchmarks/harness/peaks.py"
                       % (device_kind,)) from None
