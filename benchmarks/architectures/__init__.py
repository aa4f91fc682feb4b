"""One module per architecture: everything the benchmark knows about the
shape of a model. A configuration file names its module under
``architecture``; the drivers, readers, sweeps and tests call the module and
read no size key themselves, so a second architecture is files only:
``architectures/<name>.py``, ``reference/<name>.py`` and its data files.

A module offers, each taking the configuration dict:

- the programs, through the entry points a user of paddle_tpu calls:
  ``train_program(cfg, traffic, seed)`` -> (main, startup, loss);
  ``train_feed(rs, cfg, traffic)`` -> ``{"feed", "units_per_step",
  "reference_rows"}``: one step's feed drawn from ``rs``, the units
  (tokens, images) the step trains on, and the arrays, one row per row of
  the batch, that the reference's ``loss`` takes after the weights;
  ``serve_startup(cfg, seed)`` -> the startup program that makes the weights
  a session reads; ``serve_spec(cfg, geometry, prompt_buckets)``;
  ``strategy(cfg, mesh_axes, devices)``;
- what the traffic and the checks need: ``vocab(cfg)`` (the ids the traffic
  draws from: a sliced vocabulary is a smaller one), ``max_positions(cfg)``,
  ``kernels(kind)`` (the kernels a ``train`` or ``serve`` cell must find
  compiled);
- the counts: ``matmul_params(cfg)``, ``train_flops_per_token(cfg,
  seq_len)``, and ``decode_ops_and_bytes(cfg, counters, weight_bytes,
  kv_bytes)`` -> (operations, bytes) of a window's decode steps from the
  program's counters, or None where they hold nothing to read;
- what the tests need: ``published(cfg)`` -> ``{"widths", "reducible",
  "as_built"}`` (keys that may never be cut and the keys ``reduced`` may
  list, each with its published value; derived sizes as (built, published))
  and ``tiny(cfg)`` -> the same configuration at the rehearsal's CPU size.

The plain reference, ``reference/<name>.py``, has one signature for all:
``gather_weights(find_var, cfg)``, ``logits_at(w, tokens, positions, cfg)``,
``loss(w, tokens, labels, cfg)``.
"""

import importlib


def load(cfg):
    """The module of a configuration's architecture."""
    return importlib.import_module(
        "benchmarks.architectures." + cfg["architecture"])


def reference(cfg):
    """The plain reference of a configuration's architecture."""
    return importlib.import_module(
        "benchmarks.reference." + cfg["architecture"])


def decode_window(counters):
    """{"steps", "tokens", "context"} of a window's decode steps from the
    generation counters' deltas: the steps, the decode tokens (all tokens
    less one per prefill) and the cached tokens those attended, the new
    token included. None where a program does not count them (before PR 23)
    or the window held no decode step."""
    steps = counters.get("paddle_generation_decode_steps_total", 0)
    context = counters.get("paddle_generation_context_tokens_total")
    tokens = int(counters.get("paddle_generation_tokens_total", 0) - sum(
        v for k, v in counters.items()
        if k.startswith("paddle_generation_prefills_total")))
    if context is None or not steps or tokens <= 0:
        return None
    return {"steps": steps, "tokens": tokens, "context": context}
