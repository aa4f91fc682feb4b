"""The ``longcat_flash`` decoder (Meituan LongCat-Flash: a layer of two
latent-attention halves with the expert layer laid across them, the
shortcut-connected MoE, under a softmax router wider than its experts whose
last ``zero_expert_num`` outputs are identity experts) through the entry
points a user of paddle_tpu calls: ``models.moe_lm.moe_lm`` with the latent
block and a block of halves for the startup program that makes the weights,
``moe_lm_session`` for a serving cell; with its counts of operations and
bytes, and what the tests hold its configurations to. A configuration file
carries the catalog's own keys.

Serving only: the training entry points say why they are not there.

**A share of each layer.** ``n_routed_experts`` is how many real experts
are held here, ``[expert_offset, expert_offset + n_routed_experts)`` of the
``n_routed_experts_published`` the router scores beside its
``zero_expert_num`` identity outputs; ``vocab_size`` is the slice of the
vocabulary held here. The program computes the held experts' part of an
expert layer and, for its own rows, the identity experts' (every chip
does: over the shares it counts once); nothing stands in for the rest.

The counts are of what the *algorithm* requires. A decode step reads every
weight outside the routed experts once (both halves' attention and dense
feed-forward, the router, the head), **the held routed experts that took a
token** once each (``paddle_generation_experts_touched_total``), and the
latent rows its queries attend at both halves of every layer
(``paddle_generation_latent_rows_attended_total``, which counts attention
sites) as they are stored: the row's 576 numbers padded to whole lane
tiles, in the pool's dtype. An identity pair reads nothing.
"""

import copy
import math

from . import decode_window

PUBLISHED = {
    "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/"
    "config.json": {
        "widths": dict(hidden_size=6144, num_attention_heads=64,
                       q_lora_rank=1536, kv_lora_rank=512,
                       qk_nope_head_dim=128, qk_rope_head_dim=64,
                       v_head_dim=128, ffn_hidden_size=12288,
                       expert_ffn_hidden_size=2048, moe_topk=12,
                       zero_expert_num=256,
                       n_routed_experts_published=512),
        "reducible": dict(num_layers=28, n_routed_experts=512,
                          vocab_size=131072)},
}

HALVES = 2

# the rehearsal's CPU size: every mechanism, nothing wide. The model and
# expert widths are one lane tile and the weights bfloat16, so that the held
# experts' matmuls take ``pallas_moe``'s kernels (interpreted) as on the
# chip; a third of the router's outputs are identity experts, as published
TINY = dict(hidden_size=128, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, ffn_hidden_size=256, expert_ffn_hidden_size=128,
            n_routed_experts_published=16, n_routed_experts=4,
            zero_expert_num=8, moe_topk=3, num_layers=2, vocab_size=128)
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
    if cfg["attention_method"] != "MLA" or cfg["attention_bias"] or \
            cfg["zero_expert_type"] != "identity":
        raise ValueError("the longcat_flash module builds latent attention "
                         "without bias and identity zero-computation "
                         "experts")
    d = cfg["hidden_size"]
    return dict(
        vocab_size=cfg["vocab_size"], d_model=d,
        num_heads=cfg["num_attention_heads"], num_kv_heads=1,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["ffn_hidden_size"], moe_d_ff=cfg["expert_ffn_hidden_size"],
        num_experts=cfg["n_routed_experts_published"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        zero_experts=cfg["zero_expert_num"], top_k=cfg["moe_topk"],
        layer_types=["full_attention"] * cfg["num_layers"],
        num_dense_layers=0, sliding_window=None,
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        route_norm=False, route_scale=float(cfg["routed_scaling_factor"]),
        scoring="softmax_bias", embed_scale=None,
        param_dtype=cfg["torch_dtype"], init_std=cfg["initializer_range"],
        attention="latent", post_norms=False,
        latent=dict(q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
                    nope_dim=cfg["qk_nope_head_dim"],
                    rope_dim=cfg["qk_rope_head_dim"],
                    v_dim=cfg["v_head_dim"],
                    q_scale=math.sqrt(d / cfg["q_lora_rank"])
                    if cfg["mla_scale_q_lora"] else None,
                    kv_scale=math.sqrt(d / cfg["kv_lora_rank"])
                    if cfg["mla_scale_kv_lora"] else None),
        # the expert layer reads the first half's post-attention norm and
        # joins the stream after the second half's feed-forward
        block=dict(halves=HALVES, experts_read=0, experts_join=HALVES - 1))


def _serving_only(*_args, **_kw):
    raise NotImplementedError(
        "longcat_flash is served, not trained: at this repo's 12 bytes a "
        "trained parameter the smallest cut within the guide's floors (4 "
        "layers, 8 experts, an eighth of the vocabulary) is 47.6 GB (ISSUE "
        "40)")


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
    every call site: the paged decode over each half's latent pool, and the
    held experts' grouped matmuls in the decode step and in every prefill."""
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
    """Parameters by where they sit: a half's attention (``W_dq``, ``W_uq``,
    ``W_dkv``, ``W_ukv``, ``W_o``) and its dense feed-forward, one expert,
    the router over the published experts and the identity outputs, the head
    and the embedding over the slice of the vocabulary; the float32 vectors
    (a half's four norms, the router's bias); and how many layers."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    outputs = cfg["n_routed_experts_published"] + cfg["zero_expert_num"]
    return {
        "attention": d * q_rank + q_rank * nh * (nope + rope)
        + d * (rank + rope) + rank * nh * (nope + dv) + nh * dv * d,
        "dense_ffn": 3 * d * cfg["ffn_hidden_size"],
        "expert": 3 * d * cfg["expert_ffn_hidden_size"],
        "router": d * outputs,
        "half_vectors": q_rank + rank + 2 * d,
        "router_bias": outputs,
        "head": d * cfg["vocab_size"],
        "layers": cfg["num_layers"],
    }


def parameters_held(cfg):
    """Every parameter this chip holds: the embedding and the head over its
    slice and the final norm; in each layer two halves (attention, dense
    feed-forward, norms), the router with its bias and the held experts."""
    c = param_counts(cfg)
    half = c["attention"] + c["dense_ffn"] + c["half_vectors"]
    return 2 * c["head"] + cfg["hidden_size"] + c["layers"] * (
        HALVES * half + c["router"] + c["router_bias"]
        + cfg["n_routed_experts"] * c["expert"])


def matmul_params(cfg):
    """Parameters that are multiplied with every token HERE: both halves'
    attention and dense feed-forward in each layer, the router, the token's
    share of its ``moe_topk`` choices that a holder of ``n_routed_experts``
    of the router's outputs takes in balance, and the head."""
    c = param_counts(cfg)
    outputs = cfg["n_routed_experts_published"] + cfg["zero_expert_num"]
    held = cfg["moe_topk"] * cfg["n_routed_experts"] / outputs
    return c["layers"] * (HALVES * (c["attention"] + c["dense_ffn"])
                          + c["router"] + held * c["expert"]) + c["head"]


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
    token, 2 an expert parameter a pair computed here, 2 a lane an identity
    pair, and each attended latent row against 64 heads. Bytes as held:
    every weight outside the routed experts once a step (bfloat16; routers
    float32), each held expert that took a token once, and the latent rows
    attended as stored (padding included, ``kv_bytes`` a number)."""
    window = decode_window(counters)
    touched = counters.get("paddle_generation_experts_touched_total")
    pairs = counters.get("paddle_generation_expert_assignments_total")
    rows = counters.get("paddle_generation_latent_rows_attended_total")
    if window is None or touched is None or pairs is None or rows is None:
        return None
    c = param_counts(cfg)
    held = BYTES[cfg["torch_dtype"]]
    outside = c["layers"] * HALVES * (c["attention"] + c["dense_ffn"]) \
        + c["head"]
    routers = c["layers"] * c["router"]
    zero_pairs = counters.get("paddle_generation_zero_expert_pairs_total", 0)
    return {
        "flops": 2 * (outside + routers) * window["tokens"]
        + 2 * c["expert"] * pairs + 2 * cfg["hidden_size"] * zero_pairs
        + latent_row_flops(cfg) * rows,
        "always_bytes": (held * outside + 4 * routers) * window["steps"],
        "expert_bytes": held * c["expert"] * touched,
        "latent_bytes": row_width(cfg) * kv_bytes * rows}


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps (:func:`decode_breakdown`).

    **``weight_bytes`` is ignored**, as the other sparse modules ignore it:
    this program holds a matmul weight in the configuration's
    ``torch_dtype`` (2 bytes) and its routers in float32."""
    del weight_bytes
    b = decode_breakdown(cfg, counters, kv_bytes)
    if b is None:
        return None
    return b["flops"], \
        b["always_bytes"] + b["expert_bytes"] + b["latent_bytes"]


def published(cfg):
    pub = copy.deepcopy(PUBLISHED[cfg["source"]])
    real = pub["reducible"]["n_routed_experts"]
    return dict(pub, as_built={
        "router_width": (cfg["n_routed_experts_published"]
                         + cfg["zero_expert_num"],
                         real + pub["widths"]["zero_expert_num"]),
        "experts_a_chip": (cfg["n_routed_experts"],
                           real
                           // cfg["deployment"]["chips_sharing_a_layer"])})


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, torch_dtype=TINY_DTYPE)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
