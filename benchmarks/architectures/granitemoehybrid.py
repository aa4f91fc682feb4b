"""The ``granitemoehybrid`` decoder (IBM Granite 4.0-H: Mamba-2 state-space
layers beside one plain grouped-query attention layer in ten, sparse experts
under a softmax-of-the-chosen router with a shared expert in every layer)
through the entry points a user of paddle_tpu calls:
``models.moe_lm.moe_lm`` for the startup program that makes the weights,
``moe_lm_session`` for a serving cell; with its counts of operations and
bytes, and what the tests hold its configurations to. A configuration file
carries the catalog's own keys.

Serving only: the training entry points say why they are not there.

**A share of each layer.** ``num_local_experts`` is how many experts are held
here, ``[expert_offset, expert_offset + num_local_experts)`` of the
``num_local_experts_published`` the router scores; ``vocab_size`` is the
slice of the vocabulary held here. The program computes the held experts'
part of an expert layer and nothing stands in for the rest.

The counts are of what the *algorithm* requires. A decode step reads every
weight outside the routed experts once, **the held routed experts that took
a token** once each (``paddle_generation_experts_touched_total``), the keys
and values its queries attend in the attention layers, and **reads and
writes the whole state row of every slot it advances in every state-space
layer** (``paddle_generation_state_rows_updated_total``): a row's size does
not follow the sequence.
"""

import copy

from . import decode_window

PUBLISHED = {
    "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
    "config.json": {
        "widths": dict(hidden_size=4096, num_attention_heads=32,
                       num_key_value_heads=8, mamba_n_heads=128,
                       mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
                       mamba_expand=2, mamba_n_groups=1,
                       mamba_chunk_size=256, intermediate_size=768,
                       shared_intermediate_size=1536,
                       num_experts_per_tok=10, embedding_multiplier=12,
                       attention_multiplier=0.0078125,
                       residual_multiplier=0.22, logits_scaling=16,
                       num_local_experts_published=72),
        "reducible": dict(num_hidden_layers=40, num_local_experts=72,
                          vocab_size=100352,
                          layer_types=(["mamba"] * 5 + ["attention"]
                                       + ["mamba"] * 4) * 4)},
}

# the rehearsal's CPU size: every mechanism, nothing wide. The model and
# expert widths are one lane tile and the weights bfloat16, so that the held
# experts' matmuls take ``pallas_moe``'s kernels (interpreted) as on the
# chip; chunks of 8 rows, so that a 16-row bucket crosses one
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
            mamba_chunk_size=8, intermediate_size=128,
            shared_intermediate_size=256, num_local_experts_published=8,
            num_local_experts=4, n_routed_experts=4, num_experts_per_tok=2,
            attention_multiplier=0.125, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"], vocab_size=128)
TINY_SERVING = dict(slots=4, cache_len=64, block_size=8, num_blocks=32,
                    kv_dtype="float32", state_dtype="float32")
TINY_DTYPE = "bfloat16"

BYTES = {"bfloat16": 2, "float32": 4}


def sizes(cfg):
    """``models.moe_lm.MoeLM``'s arguments for a configuration."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["mamba_n_groups"] != 1 or cfg["mamba_proj_bias"] or \
            not cfg["mamba_conv_bias"] or cfg["attention_bias"] or \
            cfg["hidden_act"] != "silu" or \
            cfg["position_embedding_type"] != "nope" or \
            cfg["normalization_function"] != "rmsnorm" or \
            not cfg["tie_word_embeddings"] or \
            cfg["mamba_n_heads"] * cfg["mamba_d_head"] != \
            cfg["mamba_expand"] * d or \
            set(cfg["layer_types"]) - {"mamba", "attention"} or \
            len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError(
            "the granitemoehybrid module builds one group of B and C, a "
            "convolution with a bias and projections without, SwiGLU, "
            "RMSNorm, attention without positions or bias, a tied head, "
            "mamba_n_heads x mamba_d_head = mamba_expand x hidden_size and "
            "a layer type, mamba or attention, for every layer")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=d, num_heads=nh,
        num_kv_heads=cfg["num_key_value_heads"], head_dim=d // nh,
        d_ff=0, moe_d_ff=cfg["intermediate_size"],
        shared_d_ff=cfg["shared_intermediate_size"],
        num_experts=cfg["num_local_experts_published"],
        experts_held=cfg["num_local_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        top_k=cfg["num_experts_per_tok"],
        layer_types=["mamba" if t == "mamba" else "full_attention"
                     for t in cfg["layer_types"]],
        num_dense_layers=0, sliding_window=None,
        rms_eps=cfg["rms_norm_eps"], scoring="softmax_topk",
        embed_scale=float(cfg["embedding_multiplier"]),
        attn_scale=cfg["attention_multiplier"],
        residual_scale=cfg["residual_multiplier"],
        logit_scale=1.0 / cfg["logits_scaling"], tie_embeddings=True,
        qk_norm=False, attn_gate=False, post_norms=False,
        param_dtype=cfg["torch_dtype"], init_std=cfg["initializer_range"],
        mamba=dict(num_heads=cfg["mamba_n_heads"],
                   head_dim=cfg["mamba_d_head"],
                   state_dim=cfg["mamba_d_state"],
                   conv_width=cfg["mamba_d_conv"],
                   chunk=cfg["mamba_chunk_size"]))


def _serving_only(*_args, **_kw):
    raise NotImplementedError(
        "granitemoehybrid is served, not trained: the scan has no backward "
        "here, and at this repo's 12 bytes a trained parameter the smallest "
        "cut within the guide's floors is 23.5 GB (ISSUE 33)")


train_program = train_feed = strategy = train_flops_per_token = _serving_only


def vocab(cfg):
    """The slice of the vocabulary held here: the traffic draws from it."""
    return cfg["vocab_size"]


def max_positions(cfg):
    """No positions at all: what bounds a sequence is the deployment's
    cache."""
    return min(cfg["max_position_embeddings"],
               cfg["deployment"]["serving"]["cache_len"])


def kernels(kind):
    """The kernels a cell of this kind must find compiled on the chip, at
    every call site: the paged decode of the attention layers, and the held
    experts' grouped matmuls in the decode step and in every prefill. The
    mixer's scan and state update are XLA's (``ops/ssm_ops.py``)."""
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
    """The generation spec of a configuration's deployment geometry (slots,
    cache length, block size, the blocks of the attention layers' paged
    kind; the state kind has one row a slot) with a cell's prompt buckets.
    Greedy."""
    from paddle_tpu.models.moe_lm import moe_lm_session
    if geometry["state_dtype"] != "float32":
        raise ValueError("the state is held in float32")
    return moe_lm_session(
        slots=geometry["slots"], cache_len=geometry["cache_len"],
        prompt_buckets=tuple(prompt_buckets),
        block_size=geometry["block_size"], num_blocks=geometry["num_blocks"],
        kv_dtype=geometry["kv_dtype"], **sizes(cfg))


def param_counts(cfg):
    """Parameters by where they sit: a Mamba-2 mixer (``W_in``, the
    convolution and its bias, ``dt_bias``, ``A_log``, ``D``, the gated norm,
    ``W_out``), an attention layer's four projections, one routed expert,
    the shared expert, a router over the published experts, a layer's two
    norms, the embedding (= the head) over the slice of the vocabulary; and
    how many layers are of each kind."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, nkv = d // nh, cfg["num_key_value_heads"]
    h, di = cfg["mamba_n_heads"], cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    lanes = di + 2 * cfg["mamba_d_state"]
    mamba = sum(t == "mamba" for t in cfg["layer_types"])
    return {
        "mixer_matmuls": d * (di + lanes + h) + di * d,
        "mixer_rest": cfg["mamba_d_conv"] * lanes + lanes + 3 * h + di,
        "attention": 2 * d * nh * hd + 2 * d * nkv * hd,
        "expert": 3 * d * cfg["intermediate_size"],
        "shared": 3 * d * cfg["shared_intermediate_size"],
        "router": d * cfg["num_local_experts_published"],
        "norms": 2 * d,
        "embedding": d * cfg["vocab_size"],
        "layers": len(cfg["layer_types"]), "mamba_layers": mamba,
        "attention_layers": len(cfg["layer_types"]) - mamba,
    }


def parameters_held(cfg):
    """Every parameter this chip holds: the embedding once (the head is the
    same rows), the final norm, and in each layer its mixer or attention,
    its two norms, the router, the shared expert and the held routed
    experts."""
    c = param_counts(cfg)
    each = c["norms"] + c["router"] + c["shared"] \
        + cfg["num_local_experts"] * c["expert"]
    return c["embedding"] + cfg["hidden_size"] \
        + c["mamba_layers"] * (c["mixer_matmuls"] + c["mixer_rest"] + each) \
        + c["attention_layers"] * (c["attention"] + each)


def matmul_params(cfg):
    """Parameters that are multiplied with every token HERE: the mixer's or
    attention's projections in each layer, the router, the shared expert
    and the token's share of its ``num_experts_per_tok`` experts that a
    holder of ``num_local_experts`` of the published ones takes in balance,
    and the head."""
    c = param_counts(cfg)
    share = cfg["num_local_experts"] / cfg["num_local_experts_published"]
    active = c["router"] + c["shared"] \
        + cfg["num_experts_per_tok"] * share * c["expert"]
    return (c["mamba_layers"] * c["mixer_matmuls"]
            + c["attention_layers"] * c["attention"]
            + c["layers"] * active + c["embedding"])


def state_row_numbers(cfg):
    """Numbers a slot's row holds in one state-space layer: the scan's state
    ``[H, P, N]`` and the convolution's last ``K`` inputs."""
    di = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return di * cfg["mamba_d_state"] + \
        cfg["mamba_d_conv"] * (di + 2 * cfg["mamba_d_state"])


def ssm_decode_ops_and_bytes(cfg, rows):
    """(FLOPs, bytes) of the decode update alone over ``rows`` state rows
    (all slots of one layer): a multiply-add into every number of the
    scan's state and another out of it, the row read and written as it is
    stored (float32). The projections are not in it."""
    di = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return 4 * di * cfg["mamba_d_state"] * rows, \
        2 * 4 * state_row_numbers(cfg) * rows


def ssd_prefill_ops_and_bytes(cfg, tokens):
    """(FLOPs, bytes) of the chunked scan alone over one sequence of
    ``tokens`` rows (whole chunks of ``mamba_chunk_size`` or one shorter):
    per chunk the scores ``C B^T`` [Q, Q, N], the masked product with
    ``dt x`` [H, Q, Q, P], the chunk's end state and the entering state's
    part of the output [Q, H, P, N] each; bytes: x and y [T, H, P], B, C
    [T, N] and dt [T, H] once each and the end state, float32. The
    decay's exponentials are not counted."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    q = min(cfg["mamba_chunk_size"], tokens)
    flops = 2 * tokens * q * n + 2 * h * tokens * q * p \
        + 2 * 2 * tokens * h * p * n
    return flops, 4 * (2 * tokens * h * p + 2 * tokens * n + tokens * h
                       + h * p * n)


def decode_breakdown(cfg, counters, kv_bytes):
    """{"flops", "always_bytes", "expert_bytes", "state_bytes", "kv_bytes"}
    of a window's decode steps, or None. FLOPs counted once (not the passes
    exact products take): 2 a parameter outside the routed experts a decode
    token, 2 an expert parameter a pair computed here, the state update of
    every row advanced and 4 a cached number attended in the attention
    layers. Bytes as held: every weight outside the routed experts once a
    step (bfloat16; routers, norms and the mixer's small vectors float32),
    each held expert that took a token once, **every state row advanced
    read and written**, and the keys and values attended (``kv_bytes`` a
    number)."""
    window = decode_window(counters)
    touched = counters.get("paddle_generation_experts_touched_total")
    pairs = counters.get("paddle_generation_expert_assignments_total")
    rows = counters.get("paddle_generation_state_rows_updated_total")
    if window is None or touched is None or pairs is None or rows is None:
        return None
    c = param_counts(cfg)
    held = BYTES[cfg["torch_dtype"]]
    kv_width = 2 * cfg["num_key_value_heads"] * (
        cfg["hidden_size"] // cfg["num_attention_heads"])
    matmuls = c["mamba_layers"] * c["mixer_matmuls"] \
        + c["attention_layers"] * c["attention"] \
        + c["layers"] * c["shared"] + c["embedding"]
    small = c["mamba_layers"] * c["mixer_rest"] \
        + c["layers"] * (c["router"] + c["norms"]) + cfg["hidden_size"]
    update_flops, update_bytes = ssm_decode_ops_and_bytes(cfg, rows)
    return {
        "flops": 2 * (matmuls + small) * window["tokens"]
        + 2 * c["expert"] * pairs + update_flops
        + 2 * kv_width * (cfg["num_attention_heads"]
                          // cfg["num_key_value_heads"])
        * c["attention_layers"] * window["context"],
        "always_bytes": (held * matmuls + 4 * small) * window["steps"],
        "expert_bytes": held * c["expert"] * touched,
        "state_bytes": update_bytes,
        "kv_bytes": kv_width * kv_bytes * c["attention_layers"]
        * window["context"]}


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps (:func:`decode_breakdown`).

    **``weight_bytes`` is ignored**, as ``afmoe`` and ``kimi_k2`` ignore it:
    ``layer_metrics/decode_step_roofline_share.py`` passes 4, what the
    GPT-2 block's program holds; this program holds a matmul weight in the
    configuration's ``torch_dtype`` (2 bytes) and the rest in float32."""
    del weight_bytes
    b = decode_breakdown(cfg, counters, kv_bytes)
    if b is None:
        return None
    return b["flops"], b["always_bytes"] + b["expert_bytes"] \
        + b["state_bytes"] + b["kv_bytes"]


def published(cfg):
    pub = copy.deepcopy(PUBLISHED[cfg["source"]])
    return dict(pub, as_built={
        "router_width": (cfg["num_local_experts_published"],
                         pub["reducible"]["num_local_experts"]),
        "experts_a_chip": (cfg["num_local_experts"],
                           pub["reducible"]["num_local_experts"]
                           // cfg["deployment"]["chips_sharing_a_layer"]),
        "layer_types": (list(cfg["layer_types"]),
                        pub["reducible"]["layer_types"][
                            :cfg["num_hidden_layers"]])})


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, torch_dtype=TINY_DTYPE)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
