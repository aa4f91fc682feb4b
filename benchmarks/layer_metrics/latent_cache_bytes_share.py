"""``latent_cache_bytes_share``: the latent rows a window's decode steps
attended, in bytes as they are stored, over all the bytes the steps must
read (the architecture module's ``decode_breakdown``: the weights read every
step, the held experts that took a token, the latent rows), in percent. An
architecture without a latent cache, or a program without
``paddle_generation_latent_rows_attended_total`` (before PR 31), has nothing
to read.
"""

from benchmarks import architectures
from benchmarks.layer_metrics.decode_step_roofline_share import BYTES


def read(facts):
    cfg = facts.cfg
    breakdown = getattr(architectures.load(cfg), "decode_breakdown", None)
    if breakdown is None:
        return None
    b = breakdown(cfg, facts.counters,
                  BYTES[cfg["deployment"]["serving"]["kv_dtype"]])
    if b is None:
        return None
    total = b["always_bytes"] + b["expert_bytes"] + b["latent_bytes"]
    return 100.0 * b["latent_bytes"] / total if total else None
