"""``eva_attended_share``: what the decode steps of EVA attention read of
what a full attention would have read, in percent: the window pool's rows
from each query's own window's first position on plus one summary for every
chunk before it (both summed over the layers by the program), over the
whole contexts once a layer. A program without the counters (before PR 42),
or a model without the two kinds of layer cache, has nothing to read.
"""


def read(facts):
    context = facts.counters.get("paddle_generation_context_tokens_total")
    rows = facts.counters.get("paddle_generation_eva_window_rows_total")
    summaries = facts.counters.get("paddle_generation_eva_chunk_rows_total")
    layers = facts.cfg.get("num_hidden_layers")
    if not context or not rows or summaries is None or not layers:
        return None
    return 100.0 * (rows + summaries) / (layers * context)
