"""``borrowed_kv_bytes_share``: the cached rows a window's decode steps
walked in a pool the walking layer does not own (cross layers that compute a
query only and read another layer's keys and values), in bytes as they are
stored, over all the bytes the steps must move (the architecture module's
``decode_breakdown``), in percent. An architecture none of whose layers
borrows, or a program without
``paddle_generation_borrowed_context_tokens_total`` (before PR 49), has
nothing to read.
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
    if b is None or "borrowed_kv_bytes" not in b:
        return None
    total = sum(v for k, v in b.items() if k.endswith("_bytes"))
    return 100.0 * b["borrowed_kv_bytes"] / total if total else None
