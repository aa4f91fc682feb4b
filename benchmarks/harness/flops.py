"""The arithmetic of utilization and of the roofline. The operations and
bytes themselves are each architecture's own count
(``benchmarks/architectures/<name>.py``: ``train_flops_per_token``,
``decode_ops_and_bytes``); the peaks are ``peaks.py``'s.
"""


def mfu(flops_per_token, tokens_per_s, chips, peak_flops):
    """Model FLOP/s utilization of a training run: required operations per
    token times tokens per second over chips times the bf16 peak. An
    end-to-end utilization, not a kernel's roofline share."""
    return flops_per_token * tokens_per_s / (chips * peak_flops)


def roofline_seconds(flops, nbytes, peaks, flops_key="bf16_flops"):
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peaks[flops_key], nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
