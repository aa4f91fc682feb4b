"""The ``evabyte`` decoder (EvaByte 6.5B: a byte-level Llama-style dense
block whose attention is EVA: an exact aligned window and one learned
summary for every chunk of the windows before it, under one softmax; a
head of ``num_pred_heads`` x ``vocab_size`` columns) through the entry
points a user of paddle_tpu calls: ``models.moe_lm.moe_lm`` with the
``eva`` attention for the startup program that makes the weights,
``moe_lm_session`` for a serving cell; with its counts of operations and
bytes, and what the tests hold its configurations to. A configuration file
carries the catalog's own keys.

Serving only: the training entry points say why they are not there.

The counts are of what the *algorithm* requires. A decode step reads every
weight once (as held: 2 bytes), the rows of the window pool from each
query's window's first position to the query
(``paddle_generation_eva_window_rows_total``), a summary for every chunk
before that window (``paddle_generation_eva_chunk_rows_total``), each row
its keys and values as stored, and writes the summaries that the step
completes (``paddle_generation_eva_chunks_written_total``).
"""

import copy

from . import decode_window

PUBLISHED = {
    "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json": {
        "widths": dict(hidden_size=4096, num_attention_heads=32,
                       num_key_value_heads=32, intermediate_size=11008,
                       window_size=2048, chunk_size=16, vocab_size=320,
                       num_pred_heads=8, rope_theta=100000),
        "reducible": dict(num_hidden_layers=32)},
}

# the rehearsal's CPU size: every mechanism, nothing wide. A window of 8
# chunks of 4, so that a sequence of the rehearsal crosses windows
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=96, window_size=32, chunk_size=4,
            num_hidden_layers=3, vocab_size=64, num_pred_heads=3)
TINY_SERVING = dict(slots=4, cache_len=128, block_size=4, num_blocks=32,
                    chunk_num_blocks=32, kv_dtype="float32")
TINY_DTYPE = "float32"

BYTES = {"bfloat16": 2, "float32": 4}


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def sizes(cfg):
    """``models.moe_lm.MoeLM``'s arguments for a configuration."""
    if cfg["attention_class"] != "eva" or cfg["hidden_act"] != "silu" or \
            cfg["attention_bias"] or cfg["tie_word_embeddings"] or \
            cfg["rope_scaling"] or cfg["num_chunks"] or \
            cfg["num_key_value_heads"] != cfg["num_attention_heads"] or \
            cfg["window_size"] % cfg["chunk_size"]:
        raise ValueError("the evabyte module builds EVA attention with a "
                         "KV head a head and windows of whole chunks, "
                         "SwiGLU, no bias, an untied head and unscaled "
                         "rotary positions")
    layers = cfg["num_hidden_layers"]
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"], moe_d_ff=0, num_experts=0, top_k=0,
        layer_types=["full_attention"] * layers, num_dense_layers=layers,
        sliding_window=None, rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], embed_scale=None,
        param_dtype=cfg["torch_dtype"], init_std=cfg["init_std"],
        attention="eva", post_norms=False,
        eva=dict(window=cfg["window_size"], chunk=cfg["chunk_size"]),
        norm_offset=1.0 if cfg["norm_add_unit_offset"] else 0.0,
        pred_heads=cfg["num_pred_heads"])


def _serving_only(*_args, **_kw):
    raise NotImplementedError(
        "evabyte is served, not trained: the pooled attention has no "
        "backward, and at this repo's 12 bytes a parameter plus float32 "
        "gradients the four layers that fit a chip leave 3.9e9 bytes for "
        "sequences of 8,192 positions and more, below which most queries "
        "see no summary (ISSUE 42)")


train_program = train_feed = strategy = train_flops_per_token = _serving_only


def vocab(cfg):
    """The 320 ids of a byte-level vocabulary: the traffic draws bytes."""
    return cfg["vocab_size"]


def max_positions(cfg):
    """Rotary positions need no table: what bounds a sequence is the
    deployment's cache."""
    return min(cfg["max_position_embeddings"],
               cfg["deployment"]["serving"]["cache_len"])


def kernels(kind):
    """The kernel a cell of this kind must find compiled on the chip: the
    paged decode walk, over the window pool and over the chunk pool."""
    return {"serve": ("decode_attention_paged",)}[kind]


def serve_startup(cfg, seed):
    """The startup program of the whole-sequence forward: it makes every
    weight a session reads by name."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.moe_lm import moe_lm
    main, startup = ptpu.Program(), ptpu.Program()
    # the scope's RNG is seeded from the program: 0 would mean "unseeded"
    main.random_seed = startup.random_seed = int(seed) + 1
    with ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[8], dtype="int64")
        lbls = layers.data("lbls", shape=[8], dtype="int64")
        moe_lm(toks, lbls, **sizes(cfg))
    return startup


def serve_spec(cfg, geometry, prompt_buckets):
    """The paged generation spec of a configuration's deployment geometry
    (slots, cache length, block size = one chunk, the blocks of the window
    kind and of the chunk kind) with a cell's prompt buckets, each a whole
    number of windows. Greedy over the first head's logits."""
    from paddle_tpu.models.moe_lm import moe_lm_session
    return moe_lm_session(
        slots=geometry["slots"], cache_len=geometry["cache_len"],
        prompt_buckets=tuple(prompt_buckets),
        block_size=geometry["block_size"], num_blocks=geometry["num_blocks"],
        chunk_num_blocks=geometry["chunk_num_blocks"],
        kv_dtype=geometry["kv_dtype"], **sizes(cfg))


def param_counts(cfg):
    """Parameters by where they sit: an attention's four projections and
    its two pooling vectors, the feed-forward, the head over every
    prediction head, the embedding; and the layers."""
    d = cfg["hidden_size"]
    return {"attention": 4 * d * d, "pooling": 2 * d,
            "ffn": 3 * d * cfg["intermediate_size"],
            "head": d * cfg["vocab_size"] * cfg["num_pred_heads"],
            "embedding": d * cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def parameters_held(cfg):
    """Every parameter of the stage: layers with their two norms, the
    embedding, the final norm and the head."""
    c = param_counts(cfg)
    d = cfg["hidden_size"]
    return c["layers"] * (c["attention"] + c["pooling"] + c["ffn"] + 2 * d) \
        + c["embedding"] + d + c["head"]


def matmul_params(cfg):
    """Parameters that are multiplied with every token: the projections
    and the feed-forward of each layer, and the head (all its prediction
    heads). Norms, pooling vectors and the embedding table are left out."""
    c = param_counts(cfg)
    return c["layers"] * (c["attention"] + c["ffn"]) + c["head"]


def row_bytes(cfg, kv_bytes):
    """A cached row of either pool, a layer: keys and values of every
    head."""
    return 2 * cfg["hidden_size"] * kv_bytes


def decode_breakdown(cfg, counters, kv_bytes):
    """{"flops", "always_bytes", "window_bytes", "chunk_bytes",
    "written_bytes"} of a window's decode steps, or None where the program
    does not count the rows. FLOPs: 2 a matmul parameter a decode token,
    each attended row (of either pool) against every head's query, score
    and sum, and the poolings of the chunks completed. Bytes as held:
    every weight once a step (``torch_dtype``; norms float32), the window
    rows and the summaries attended and the summaries written as stored
    (``kv_bytes`` a number). The counters hold rows a layer already."""
    window = decode_window(counters)
    rows = counters.get("paddle_generation_eva_window_rows_total")
    summaries = counters.get("paddle_generation_eva_chunk_rows_total")
    written = counters.get("paddle_generation_eva_chunks_written_total")
    if window is None or rows is None or summaries is None or written is None:
        return None
    c = param_counts(cfg)
    d = cfg["hidden_size"]
    held = BYTES[cfg["torch_dtype"]]
    weights = held * (c["layers"] * (c["attention"] + c["pooling"] + c["ffn"])
                      + c["head"]) + 4 * (2 * c["layers"] + 1) * d
    row = row_bytes(cfg, kv_bytes)
    return {
        "flops": 2 * matmul_params(cfg) * window["tokens"]
        + 4 * d * (rows + summaries) + 8 * d * cfg["chunk_size"] * written,
        "always_bytes": weights * window["steps"],
        "window_bytes": row * rows,
        "chunk_bytes": row * summaries,
        # a summary is made from its block's rows, read once more
        "written_bytes": row * (1 + cfg["chunk_size"]) * written}


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps (:func:`decode_breakdown`).

    **``weight_bytes`` is ignored**, as the other modules of
    ``models/moe_lm.py`` ignore it: ``layer_metrics/
    decode_step_roofline_share.py`` passes 4, what the GPT-2 block's
    program holds; this program holds a weight in the configuration's
    ``torch_dtype`` (2 bytes)."""
    del weight_bytes
    b = decode_breakdown(cfg, counters, kv_bytes)
    if b is None:
        return None
    return b["flops"], b["always_bytes"] + b["window_bytes"] \
        + b["chunk_bytes"] + b["written_bytes"]


def eva_decode_ops_and_bytes(cfg, window_rows, chunk_rows, kv_bytes):
    """(FLOPs, bytes) of one layer's decode attention alone (the two walks
    and their merge) over ``window_rows`` and ``chunk_rows`` attended rows,
    all slots together."""
    rows = window_rows + chunk_rows
    return 4 * cfg["hidden_size"] * rows, row_bytes(cfg, kv_bytes) * rows


def eva_prefill_ops_and_bytes(cfg, tokens, kv_bytes):
    """(FLOPs, bytes) of one layer's prefill attention alone over a prompt
    of ``tokens`` positions (a whole number of windows): the poolings, each
    window's causal half of ``W x W`` scores and sums, each query against
    the summaries of the windows before it; q, k, v read and the output
    written once, the summaries written."""
    d, w, c = cfg["hidden_size"], cfg["window_size"], cfg["chunk_size"]
    windows = tokens // w
    exact = windows * w * (w + 1) // 2
    summed = sum(w * i * (w // c) for i in range(windows))
    return 4 * d * (exact + summed) + 8 * d * tokens, \
        d * kv_bytes * (3 * tokens + 2 * tokens // c) + 4 * d * tokens


def published(cfg):
    pub = copy.deepcopy(PUBLISHED[cfg["source"]])
    return dict(pub, as_built={
        "head_dim": (head_dim(cfg), 128),
        "chunks_a_window": (cfg["window_size"] // cfg["chunk_size"], 128),
        "a_block_is_a_chunk": (cfg["deployment"]["serving"]["block_size"],
                               cfg["chunk_size"])})


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, torch_dtype=TINY_DTYPE)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
