"""The ``afmoe`` decoder (Arcee Trinity: grouped-query attention that is
windowed or full layer by layer, sparse experts with a shared expert) through
the entry points a user of paddle_tpu calls: ``models.moe_lm.moe_lm`` for the
startup program that makes the weights, ``moe_lm_session`` for a serving
cell; with its counts of operations and bytes, and what the tests hold its
configurations to. A configuration file carries the catalog's own keys.

Serving only: the training entry points say why they are not there.

The counts are of what the *algorithm* requires. A decode step reads every
weight outside the routed experts once, **the routed experts that took a
token** once each (``paddle_generation_experts_touched_total``, counted by
the program from what the step routed), and the keys and values each layer
attends: the whole context in the full layers
(``paddle_generation_context_tokens_total``), the window's share of it in
the window layers (``paddle_generation_window_context_tokens_total``).
"""

import copy
import math

from . import decode_window

SLIDING, FULL = "sliding_attention", "full_attention"

# by source: the keys that may never be cut (the widths, the expert counts,
# the whole vocabulary) and what ``reduced`` may list, as published
_PERIOD = [SLIDING, SLIDING, SLIDING, FULL]
PUBLISHED = {
    "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json": {
        "widths": dict(hidden_size=2048, num_attention_heads=32,
                       num_key_value_heads=4, head_dim=128,
                       intermediate_size=6144, moe_intermediate_size=1024,
                       num_experts=128, num_experts_per_tok=8,
                       num_shared_experts=1, sliding_window=2048,
                       vocab_size=200192),
        "reducible": dict(num_hidden_layers=32, num_dense_layers=2,
                          layer_types=_PERIOD * 8)},
}

# the rehearsal's CPU size: every mechanism, nothing wide
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=2, sliding_window=8,
            vocab_size=128)
TINY_SERVING = dict(slots=4, cache_len=64, block_size=4, num_blocks=64,
                    window_num_blocks=48, kv_dtype="float32")
TINY_DTYPE = "float32"


def sizes(cfg):
    """``models.moe_lm.MoeLM``'s arguments for a configuration."""
    if cfg["num_shared_experts"] != 1 or cfg["score_func"] != "sigmoid" or \
            cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] or \
            max(cfg["n_group"], cfg["topk_group"], cfg["num_expert_groups"],
                cfg["num_limited_groups"]) != 1:
        raise ValueError("the afmoe module builds one shared expert, sigmoid "
                         "scores, SwiGLU, an untied head and no groups")
    if cfg.get("expert_offset", 0) or \
            cfg.get("experts_held", cfg["num_experts"]) != cfg["num_experts"]:
        raise ValueError("a deployment that holds a share of a layer's "
                         "experts needs the exchange that combines the "
                         "shares and counts of its own (decode_ops_and_bytes "
                         "and the routing metrics take every expert as "
                         "held): neither is written")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers, num_hidden_layers %d"
                         % (len(cfg["layer_types"]),
                            cfg["num_hidden_layers"]))
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], moe_d_ff=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        layer_types=list(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        embed_scale=math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"]
        else 1.0,
        param_dtype=cfg["torch_dtype"],
        init_std=cfg["initializer_range"])


def _serving_only(*_args, **_kw):
    raise NotImplementedError(
        "afmoe is served, not trained: at this repo's 12-16 bytes a trained "
        "parameter only a sixteenth of the experts fits a chip (ISSUE 27)")


train_program = train_feed = strategy = train_flops_per_token = _serving_only


def vocab(cfg):
    return cfg["vocab_size"]


def max_positions(cfg):
    """Rotary positions need no table: what bounds a sequence is the
    deployment's cache."""
    return min(cfg["max_position_embeddings"],
               cfg["deployment"]["serving"]["cache_len"])


def kernels(kind):
    """The kernels a cell of this kind must find compiled on the chip."""
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
    (slots, cache length, block size, the blocks of the full kind and of
    the window kind) with a cell's prompt buckets. Greedy."""
    from paddle_tpu.models.moe_lm import moe_lm_session
    return moe_lm_session(
        slots=geometry["slots"], cache_len=geometry["cache_len"],
        prompt_buckets=tuple(prompt_buckets),
        block_size=geometry["block_size"], num_blocks=geometry["num_blocks"],
        window_num_blocks=geometry.get("window_num_blocks"),
        kv_dtype=geometry["kv_dtype"], **sizes(cfg))


def param_counts(cfg):
    """Parameters by where they sit: an attention layer's five projections,
    the dense feed-forward, one expert (routed or shared), a router, the
    head; and how many layers are of each kind."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    types = cfg["layer_types"]
    return {
        # q, the output gate and o; k and v
        "attention": 3 * d * nh * hd + 2 * d * nkv * hd,
        "dense_ffn": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "router": d * cfg["num_experts"],
        "head": d * cfg["vocab_size"],
        "layers": len(types),
        "dense_layers": cfg["num_dense_layers"],
        "expert_layers": len(types) - cfg["num_dense_layers"],
        "full_layers": types.count(FULL),
        "window_layers": types.count(SLIDING),
    }


def matmul_params(cfg):
    """Parameters that are multiplied with every token: attention's five
    projections in each layer, the dense feed-forward, in each expert layer
    the router, the shared expert and the token's own
    ``num_experts_per_tok`` experts, and the head. Norms and the embedding
    table are left out."""
    c = param_counts(cfg)
    active = c["router"] + (1 + cfg["num_experts_per_tok"]) * c["expert"]
    return (c["layers"] * c["attention"] + c["dense_layers"] * c["dense_ffn"]
            + c["expert_layers"] * active + c["head"])


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps. FLOPs: 2 a matmul
    parameter a decode token, and each token's 32 query heads against what
    its layer attends. Bytes: every weight outside the routed experts once a
    step, each routed expert that took a token once, and the keys and
    values attended (4 KV heads, ``kv_bytes`` each).

    **``weight_bytes`` is ignored**: ``layer_metrics/
    decode_step_roofline_share.py`` passes 4, what the GPT-2 block's
    program holds; this program holds a matmul weight in the
    configuration's ``torch_dtype`` (2 bytes) and its routers in float32,
    and a count at 4 would read twice too high and past 100%."""
    del weight_bytes
    window = decode_window(counters)
    touched = counters.get("paddle_generation_experts_touched_total")
    window_context = counters.get(
        "paddle_generation_window_context_tokens_total", 0)
    if window is None or touched is None:
        return None
    c = param_counts(cfg)
    held = {"bfloat16": 2, "float32": 4}[cfg["torch_dtype"]]
    d_q = cfg["num_attention_heads"] * cfg["head_dim"]
    d_kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attended = c["full_layers"] * window["context"] + window_context
    nflops = 2 * matmul_params(cfg) * window["tokens"] + 4 * d_q * attended
    always = held * (c["layers"] * c["attention"]
                     + c["dense_layers"] * c["dense_ffn"]
                     + c["expert_layers"] * c["expert"] + c["head"]) \
        + 4 * c["expert_layers"] * c["router"]
    nbytes = always * window["steps"] + held * c["expert"] * touched \
        + 2 * d_kv * kv_bytes * attended
    return nflops, nbytes


def published(cfg):
    pub = copy.deepcopy(PUBLISHED[cfg["source"]])
    return dict(pub, as_built={
        "experts_held": (cfg["num_experts"], pub["widths"]["num_experts"]),
        "whole_periods": (cfg["layer_types"][cfg["num_dense_layers"]:],
                          _PERIOD * ((cfg["num_hidden_layers"]
                                      - cfg["num_dense_layers"]) // 4))})


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, torch_dtype=TINY_DTYPE)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
