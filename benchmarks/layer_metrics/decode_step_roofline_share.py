"""``decode_step_roofline_share``: the least time the chip could take for
the window's decode steps, as a share of the time they took.

The operations and bytes are the architecture's own count
(``decode_ops_and_bytes``), over the whole window from the program's
counters: for the GPT-2 block the decode steps, the decode tokens (all
tokens less one per prefill) and the cached tokens those attended. Both are
linear in the counters, so the window's totals are exact; the least time is
taken of the totals, which is at most the sum of the steps' own least
times. A program without the context counter (before PR 23) has nothing to
read.
"""

from benchmarks import architectures
from benchmarks.harness import flops, peaks

BYTES = {"bfloat16": 2, "float32": 4}


def read(facts):
    _, step_ms = facts.hists.get("paddle_request_decode_step_ms", (0, 0.0))
    if step_ms <= 0:
        return None
    cfg = facts.cfg
    # the weights once a step, as the program holds them (float32, the
    # default); the keys and values of every attended token once, in the
    # pool's dtype
    counted = architectures.load(cfg).decode_ops_and_bytes(
        cfg, facts.counters, weight_bytes=BYTES["float32"],
        kv_bytes=BYTES[cfg["deployment"]["serving"]["kv_dtype"]])
    if counted is None:
        return None
    least_s, _ = flops.roofline_seconds(
        *counted, peaks.peaks_for(facts.device_kind))
    return 100.0 * least_s / (step_ms / 1e3)
