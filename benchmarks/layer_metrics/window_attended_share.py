"""``window_attended_share``: what the window layers' decode attention
reads of what it would read without a window, in percent: the tokens they
attended (each layer's min(context, window), summed by the program) over
the whole contexts once per window layer. A program without the counter
(before PR 27), or a model without window layers, has nothing to read.
"""


def read(facts):
    context = facts.counters.get("paddle_generation_context_tokens_total")
    attended = facts.counters.get(
        "paddle_generation_window_context_tokens_total")
    layers = list(facts.cfg.get("layer_types", ())).count(
        "sliding_attention")
    if not context or not attended or not layers:
        return None
    return 100.0 * attended / (layers * context)
