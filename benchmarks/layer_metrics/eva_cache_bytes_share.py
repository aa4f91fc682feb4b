"""``eva_cache_bytes_share``: the bytes a window's decode steps read and
wrote in the two pools of EVA attention (the window's rows and the
summaries attended, the summaries written and the blocks they were pooled
from, as stored) over all the bytes the steps must move (the architecture
module's ``decode_breakdown``: those and every weight once a step), in
percent. An architecture whose module breaks a step's bytes down otherwise,
or a program without the ``paddle_generation_eva_*`` counters (before PR
42), has nothing to read.
"""

from benchmarks import architectures
from benchmarks.layer_metrics.decode_step_roofline_share import BYTES

POOLS = ("window_bytes", "chunk_bytes", "written_bytes")


def read(facts):
    cfg = facts.cfg
    breakdown = getattr(architectures.load(cfg), "decode_breakdown", None)
    if breakdown is None:
        return None
    b = breakdown(cfg, facts.counters,
                  BYTES[cfg["deployment"]["serving"]["kv_dtype"]])
    if b is None or any(key not in b for key in POOLS):
        return None
    pools = sum(b[key] for key in POOLS)
    return 100.0 * pools / (pools + b["always_bytes"])
