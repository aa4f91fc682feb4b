"""``expert_bytes_share``: the held experts that took a token in a window's
decode steps, in bytes as they are held, over all the bytes the steps must
read (the architecture module's ``decode_breakdown``: the weights read every
step, the touched experts, the latent rows), in percent. An architecture
whose module has no ``decode_breakdown`` of exactly these three parts, or a
program without the routing counters, has nothing to read.
"""

from benchmarks import architectures
from benchmarks.layer_metrics.decode_step_roofline_share import BYTES

PARTS = {"always_bytes", "expert_bytes", "latent_bytes"}


def read(facts):
    cfg = facts.cfg
    breakdown = getattr(architectures.load(cfg), "decode_breakdown", None)
    if breakdown is None:
        return None
    b = breakdown(cfg, facts.counters,
                  BYTES[cfg["deployment"]["serving"]["kv_dtype"]])
    if b is None or {k for k in b if k.endswith("_bytes")} != PARTS:
        return None
    total = sum(b[part] for part in PARTS)
    return 100.0 * b["expert_bytes"] / total if total else None
