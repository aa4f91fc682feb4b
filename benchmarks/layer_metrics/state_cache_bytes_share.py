"""``state_cache_bytes_share``: the state rows a window's decode steps
advanced, each read and written whole as it is stored, over all the bytes
the steps must move (the architecture module's ``decode_breakdown``: the
weights read every step, the held experts that took a token, the state
rows, the keys and values attended), in percent. An architecture without a
state kind of layer cache, or a program without
``paddle_generation_state_rows_updated_total`` (before PR 33), has nothing
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
    if b is None or "state_bytes" not in b:
        return None
    total = sum(v for k, v in b.items() if k.endswith("_bytes"))
    return 100.0 * b["state_bytes"] / total if total else None
