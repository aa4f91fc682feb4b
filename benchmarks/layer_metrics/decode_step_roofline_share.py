"""``decode_step_roofline_share``: the least time the chip could take for
the window's decode steps, as a share of the time they took.

The operations and bytes are ``harness/flops.py``'s, over the whole window
from three counters of the program: the decode steps, the decode tokens
(all tokens less one per prefill) and the cached tokens those attended.
Both are linear in the counters, so the window's totals are exact; the
least time is taken of the totals, which is at most the sum of the steps'
own least times. A program without the context counter (before PR 23) has
nothing to read.
"""

from benchmarks.harness import flops, peaks

BYTES = {"bfloat16": 2, "float32": 4}


def read(facts):
    c = facts.counters
    steps = c.get("paddle_generation_decode_steps_total", 0)
    context = c.get("paddle_generation_context_tokens_total")
    _, step_ms = facts.hists.get("paddle_request_decode_step_ms", (0, 0.0))
    tokens = int(c.get("paddle_generation_tokens_total", 0) - sum(
        v for k, v in c.items()
        if k.startswith("paddle_generation_prefills_total")))
    if context is None or not steps or tokens <= 0 or step_ms <= 0:
        return None
    cfg = facts.cfg
    kv = BYTES[cfg["deployment"]["serving"]["kv_dtype"]]
    # the window's decode tokens, each at the mean context: the totals of
    # functions that are linear in the number of tokens and in their sum
    lens = [context / tokens] * tokens
    nflops = flops.decode_step_flops(cfg, lens)
    # the weights once a step, as the program holds them (float32, the
    # default); the keys and values of every attended token once
    nbytes = flops.decode_step_bytes(cfg, lens, kv_bytes=kv) + \
        (steps - 1) * flops.decode_step_bytes(cfg, [], kv_bytes=kv)
    least_s, _ = flops.roofline_seconds(
        nflops, nbytes, peaks.peaks_for(facts.device_kind))
    return 100.0 * least_s / (step_ms / 1e3)
