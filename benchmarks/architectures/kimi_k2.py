"""The ``kimi_k2`` decoder (Moonshot Kimi-K2, DeepSeek-V3's block: latent
attention, sparse experts under a sigmoid router with a shared expert)
through the entry points a user of paddle_tpu calls:
``models.moe_lm.moe_lm`` with the latent block for the startup program that
makes the weights, ``moe_lm_session`` for a serving cell; with its counts of
operations and bytes, and what the tests hold its configurations to. A
configuration file carries the catalog's own keys.

Serving only: the training entry points say why they are not there.

**A share of each layer.** ``n_routed_experts`` is how many experts are held
here, ``[expert_offset, expert_offset + n_routed_experts)`` of the
``n_routed_experts_published`` the router scores; ``vocab_size`` is the
slice of the vocabulary held here. The program computes the held experts'
part of an expert layer and nothing stands in for the rest.

The counts are of what the *algorithm* requires. A decode step reads every
weight outside the routed experts once, **the held routed experts that took
a token** once each (``paddle_generation_experts_touched_total``), and the
latent rows its queries attend
(``paddle_generation_latent_rows_attended_total``) as they are stored: the
row's 576 numbers padded to whole lane tiles, in the pool's dtype.
"""

import copy

from . import decode_window

PUBLISHED = {
    "https://huggingface.co/moonshotai/Kimi-K2.7-Code/blob/main/config.json": {
        "widths": dict(hidden_size=7168, num_attention_heads=64,
                       q_lora_rank=1536, kv_lora_rank=512,
                       qk_nope_head_dim=128, qk_rope_head_dim=64,
                       v_head_dim=128, intermediate_size=18432,
                       moe_intermediate_size=2048, num_experts_per_tok=8,
                       n_shared_experts=1, first_k_dense_replace=1,
                       n_routed_experts_published=384),
        "reducible": dict(num_hidden_layers=61, n_routed_experts=384,
                          vocab_size=163840)},
}

# the rehearsal's CPU size: every mechanism, nothing wide. The model and
# expert widths are one lane tile and the weights bfloat16, so that the held
# experts' matmuls take ``pallas_moe``'s kernels (interpreted) as on the chip
TINY = dict(hidden_size=128, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=256, moe_intermediate_size=128,
            n_routed_experts_published=16, n_routed_experts=4,
            num_experts_per_tok=2, num_hidden_layers=3, vocab_size=128)
TINY_SERVING = dict(slots=4, cache_len=64, block_size=8, num_blocks=32,
                    kv_dtype="float32")
TINY_DTYPE = "bfloat16"

BYTES = {"bfloat16": 2, "float32": 4}


def row_width(cfg):
    """Numbers a cached row holds as stored: ``(c, k_r)`` and zeros up to
    whole lane tiles (``models/moe_lm.py``)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def sizes(cfg):
    """``models.moe_lm.MoeLM``'s arguments for a configuration."""
    scaling = cfg["rope_scaling"]
    if cfg["n_shared_experts"] != 1 or cfg["scoring_func"] != "sigmoid" or \
            cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] or \
            cfg["attention_bias"] or cfg["moe_layer_freq"] != 1 or \
            cfg["num_nextn_predict_layers"] or \
            max(cfg["n_group"], cfg["topk_group"]) != 1 or \
            (scaling and (scaling["type"] != "yarn" or
                          scaling["mscale"] != scaling["mscale_all_dim"])):
        raise ValueError("the kimi_k2 module builds one shared expert, "
                         "sigmoid scores, SwiGLU, an untied head, no groups, "
                         "no bias, an expert layer every layer after the "
                         "dense ones and YaRN with mscale = mscale_all_dim")
    layers = cfg["num_hidden_layers"]
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=1,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["intermediate_size"], moe_d_ff=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts_published"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        top_k=cfg["num_experts_per_tok"],
        layer_types=["full_attention"] * layers,
        num_dense_layers=cfg["first_k_dense_replace"], sliding_window=None,
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"], embed_scale=None,
        param_dtype=cfg["torch_dtype"], init_std=cfg["initializer_range"],
        attention="latent", post_norms=False,
        latent=dict(q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
                    nope_dim=cfg["qk_nope_head_dim"],
                    rope_dim=cfg["qk_rope_head_dim"],
                    v_dim=cfg["v_head_dim"]),
        rope_scaling=dict(scaling) if scaling else None)


def _serving_only(*_args, **_kw):
    raise NotImplementedError(
        "kimi_k2 is served, not trained: at this repo's 16 bytes a trained "
        "parameter no cut within the guide's floors fits a chip (ISSUE 31: "
        "the floor's routed experts alone are 22.5 GB)")


train_program = train_feed = strategy = train_flops_per_token = _serving_only


def vocab(cfg):
    """The slice of the vocabulary held here: the traffic draws from it."""
    return cfg["vocab_size"]


def max_positions(cfg):
    """Rotary positions need no table: what bounds a sequence is the
    deployment's cache."""
    return min(cfg["max_position_embeddings"],
               cfg["deployment"]["serving"]["cache_len"])


def kernels(kind):
    """The kernels a cell of this kind must find compiled on the chip, at
    every call site: the paged decode over the latent pool, and the held
    experts' grouped matmuls in the decode step and in every prefill."""
    return {"serve": ("decode_attention_paged", "moe_grouped_matmul")}[kind]


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
    (slots, cache length, block size, the blocks of the latent kind) with a
    cell's prompt buckets. Greedy."""
    from paddle_tpu.models.moe_lm import moe_lm_session
    return moe_lm_session(
        slots=geometry["slots"], cache_len=geometry["cache_len"],
        prompt_buckets=tuple(prompt_buckets),
        block_size=geometry["block_size"], num_blocks=geometry["num_blocks"],
        kv_dtype=geometry["kv_dtype"], **sizes(cfg))


def param_counts(cfg):
    """Parameters by where they sit: an attention layer's five projections
    (``W_dq``, ``W_uq``, ``W_dkv``, ``W_ukv``, ``W_o``), the dense
    feed-forward, one expert (routed or shared), a router over the published
    experts, the head and the embedding over the slice of the vocabulary;
    and how many layers are of each kind."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {
        "attention": d * q_rank + q_rank * nh * (nope + rope)
        + d * (rank + rope) + rank * nh * (nope + dv) + nh * dv * d,
        "dense_ffn": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "router": d * cfg["n_routed_experts_published"],
        "head": d * cfg["vocab_size"],
        "layers": layers, "dense_layers": dense,
        "expert_layers": layers - dense,
    }


def parameters_held(cfg):
    """Matmul parameters this chip holds: the embedding and the head over
    its slice, attention, the dense feed-forward, and in each expert layer
    the router, the shared expert and the held routed experts."""
    c = param_counts(cfg)
    return 2 * c["head"] + c["layers"] * c["attention"] \
        + c["dense_layers"] * c["dense_ffn"] + c["expert_layers"] * (
            c["router"] + (1 + cfg["n_routed_experts"]) * c["expert"])


def matmul_params(cfg):
    """Parameters that are multiplied with every token HERE: attention's
    projections in each layer (absorbed or expanded, ``W_ukv`` is
    multiplied once a token), the dense feed-forward, in each expert layer
    the router, the shared expert and the token's share of its
    ``num_experts_per_tok`` experts that a holder of ``n_routed_experts``
    of the published ones takes in balance, and the head."""
    c = param_counts(cfg)
    share = cfg["n_routed_experts"] / cfg["n_routed_experts_published"]
    active = c["router"] + \
        (1 + cfg["num_experts_per_tok"] * share) * c["expert"]
    return (c["layers"] * c["attention"] + c["dense_layers"] * c["dense_ffn"]
            + c["expert_layers"] * active + c["head"])


def latent_row_flops(cfg):
    """FLOPs of one cached row attended by one decode query, absorbed: each
    head's score over the row's ``kv_rank + rope`` numbers and its sum
    over the ``kv_rank``."""
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def decode_breakdown(cfg, counters, kv_bytes):
    """{"flops", "always_bytes", "expert_bytes", "latent_bytes"} of a
    window's decode steps, or None. FLOPs counted once (not the passes
    exact products take): 2 a parameter outside the routed experts a decode
    token, 2 an expert parameter a pair computed here, and each attended
    latent row against 64 heads. Bytes as held: every weight outside the
    routed experts once a step (bfloat16; routers float32), each held
    expert that took a token once, and the latent rows attended as stored
    (padding included, ``kv_bytes`` a number)."""
    window = decode_window(counters)
    touched = counters.get("paddle_generation_experts_touched_total")
    pairs = counters.get("paddle_generation_expert_assignments_total")
    rows = counters.get("paddle_generation_latent_rows_attended_total")
    if window is None or touched is None or pairs is None or rows is None:
        return None
    c = param_counts(cfg)
    held = BYTES[cfg["torch_dtype"]]
    outside = c["layers"] * c["attention"] \
        + c["dense_layers"] * c["dense_ffn"] \
        + c["expert_layers"] * c["expert"] + c["head"]
    routers = c["expert_layers"] * c["router"]
    return {
        "flops": 2 * (outside + routers) * window["tokens"]
        + 2 * c["expert"] * pairs + latent_row_flops(cfg) * rows,
        "always_bytes": (held * outside + 4 * routers) * window["steps"],
        "expert_bytes": held * c["expert"] * touched,
        "latent_bytes": row_width(cfg) * kv_bytes * rows}


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps (:func:`decode_breakdown`).

    **``weight_bytes`` is ignored**, as ``afmoe`` ignores it:
    ``layer_metrics/decode_step_roofline_share.py`` passes 4, what the
    GPT-2 block's program holds; this program holds a matmul weight in the
    configuration's ``torch_dtype`` (2 bytes) and its routers in float32."""
    del weight_bytes
    b = decode_breakdown(cfg, counters, kv_bytes)
    if b is None:
        return None
    return b["flops"], \
        b["always_bytes"] + b["expert_bytes"] + b["latent_bytes"]


def grouped_matmul_ops_and_bytes(cfg, pairs, touched):
    """(FLOPs, bytes) of one call of the held experts' three grouped
    matmuls alone: ``pairs`` rows over ``touched`` experts. The rows in
    float32 in and out, the inner activations once each way."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2 * 3 * d * f * pairs, \
        2 * 3 * d * f * touched + 4 * pairs * (2 * d + 2 * f)


def latent_decode_ops_and_bytes(cfg, rows, kv_bytes):
    """(FLOPs, bytes) of the paged latent decode kernel alone over ``rows``
    attended rows (all slots of one layer)."""
    return latent_row_flops(cfg) * rows, row_width(cfg) * kv_bytes * rows


def published(cfg):
    pub = copy.deepcopy(PUBLISHED[cfg["source"]])
    return dict(pub, as_built={
        "router_width": (cfg["n_routed_experts_published"],
                         pub["reducible"]["n_routed_experts"]),
        "experts_a_chip": (cfg["n_routed_experts"],
                           pub["reducible"]["n_routed_experts"]
                           // cfg["deployment"]["chips_sharing_a_layer"])})


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, torch_dtype=TINY_DTYPE)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
