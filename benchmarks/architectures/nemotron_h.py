"""The ``nemotron_h`` decoder (NVIDIA Nemotron 3 Nano: **a layer is one
sublayer**, by its letter in ``hybrid_override_pattern`` a Mamba-2 mixer
with ``n_groups`` groups of B and C (``M``), an expert layer of two-matrix
relu² experts under a sigmoid router with one shared expert (``E``) or
grouped-query attention without positions (``*``)) through the entry points
a user of paddle_tpu calls: ``models.moe_lm.moe_lm`` with a block of one
sublayer for the startup program that makes the weights,
``moe_lm_session`` for a serving cell; with its counts of operations and
bytes, and what the tests hold its configurations to. A configuration file
carries the catalog's own keys.

Serving only: the training entry points say why they are not there.

**A share of each layer.** ``n_routed_experts`` is how many experts are held
here, ``[expert_offset, expert_offset + n_routed_experts)`` of the
``n_routed_experts_published`` the router scores; ``vocab_size`` is the
slice of the vocabulary held here, embedding and head alike. The program
computes the held experts' part of an expert layer and nothing stands in
for the rest.

The counts are of what the *algorithm* requires, at the published widths:
an expert is its two matrices of ``moe_intermediate_size`` columns however
the program tiles them. A decode step reads every weight outside the routed
experts once (of the embedding the rows of its tokens), **the held routed
experts that took a token** once each
(``paddle_generation_experts_touched_total``), the keys and values its
queries attend in the attention layers, and **reads and writes the whole
state row of every slot it advances in every mixer layer**
(``paddle_generation_state_rows_updated_total``): a row's size does not
follow the sequence.
"""

import copy

from . import decode_window

PUBLISHED = {
    "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/"
    "main/config.json": {
        "widths": dict(hidden_size=2688, num_attention_heads=32,
                       num_key_value_heads=2, head_dim=128,
                       mamba_num_heads=64, mamba_head_dim=64,
                       ssm_state_size=128, n_groups=8, conv_kernel=4,
                       chunk_size=128, expand=2, intermediate_size=1856,
                       moe_intermediate_size=1856,
                       moe_shared_expert_intermediate_size=3712,
                       num_experts_per_tok=6, n_shared_experts=1,
                       routed_scaling_factor=2.5,
                       n_routed_experts_published=128),
        "reducible": dict(
            num_hidden_layers=52, n_routed_experts=128, vocab_size=131072,
            hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                    "EMEMEMEM*EMEMEMEME")},
}

# the letters of ``hybrid_override_pattern`` as ``models.moe_lm`` names them
LAYER_TYPES = {"M": "mamba", "E": "experts", "*": "full_attention"}

# the rehearsal's CPU size: every mechanism, nothing wide. The model's width
# is one lane tile and an expert's 192 is one and a half, as the published
# 1856 is fourteen and a half, and the weights bfloat16, so that the held
# experts' matmuls take ``pallas_moe``'s kernels (interpreted) as on the
# chip; chunks of 8 rows, so that a 16-row bucket crosses one; four heads a
# group
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, mamba_num_heads=8, mamba_head_dim=16,
            ssm_state_size=16, n_groups=2, chunk_size=8,
            intermediate_size=192, moe_intermediate_size=192,
            moe_shared_expert_intermediate_size=256,
            n_routed_experts_published=8, n_routed_experts=4,
            num_experts_per_tok=2, num_hidden_layers=5,
            hybrid_override_pattern="MEM*E", vocab_size=128)
TINY_SERVING = dict(slots=4, cache_len=64, block_size=8, num_blocks=32,
                    kv_dtype="float32", state_dtype="float32")
TINY_DTYPE = "bfloat16"

BYTES = {"bfloat16": 2, "float32": 4}


def sizes(cfg):
    """``models.moe_lm.MoeLM``'s arguments for a configuration."""
    pattern = cfg["hybrid_override_pattern"]
    if cfg["mlp_hidden_act"] != "relu2" or \
            cfg["mamba_hidden_act"] != "silu" or cfg["mamba_proj_bias"] or \
            cfg["mlp_bias"] or cfg["use_bias"] or cfg["attention_bias"] or \
            not cfg["use_conv_bias"] or cfg["tie_word_embeddings"] or \
            cfg["n_shared_experts"] != 1 or \
            max(cfg["n_group"], cfg["topk_group"]) != 1 or \
            cfg["mamba_num_heads"] % cfg["n_groups"] or \
            set(pattern) - set(LAYER_TYPES) or "*" not in pattern or \
            len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(
            "the nemotron_h module builds two-matrix relu2 experts with one "
            "shared expert under a router without groups, a silu mixer "
            "whose heads are whole groups of B and C, a convolution with a "
            "bias and nothing else with one, an untied head and a letter "
            "M, E or * for every layer, one of them *")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=0, moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["moe_shared_expert_intermediate_size"],
        num_experts=cfg["n_routed_experts_published"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        top_k=cfg["num_experts_per_tok"],
        layer_types=[LAYER_TYPES[c] for c in pattern],
        num_dense_layers=0, sliding_window=None,
        rms_eps=cfg["layer_norm_epsilon"], scoring="sigmoid",
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"], embed_scale=None,
        qk_norm=False, attn_gate=False, post_norms=False,
        param_dtype=cfg["torch_dtype"], init_std=cfg["initializer_range"],
        block=dict(sublayers=1), expert_act="relu2",
        mamba=dict(num_heads=cfg["mamba_num_heads"],
                   head_dim=cfg["mamba_head_dim"],
                   state_dim=cfg["ssm_state_size"],
                   conv_width=cfg["conv_kernel"], chunk=cfg["chunk_size"],
                   n_groups=cfg["n_groups"]))


def _serving_only(*_args, **_kw):
    raise NotImplementedError(
        "nemotron_h is served, not trained: the scan and moe_ffn have no "
        "backward here, and at 16 bytes a trained parameter (weights, "
        "gradients, Adam's state) the 3,926 M parameters of the smallest "
        "cut within the guide's floors are 62.8 GB: one chip of 16, which "
        "is the driver's own cut for training (ISSUE 46)")


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
    experts' grouped matmuls (two a layer: ``relu(x up)^2`` and ``down``)
    in the decode step and in every prefill. The mixer's scan and state
    update are XLA's (``ops/ssm_ops.py``)."""
    return {"serve": ("decode_attention_paged", "moe_grouped_matmul")}[kind]


def centre_second_matrices(startup):
    """Append to a startup program, behind the draws, what takes the mean
    over its rows out of every relu² feed-forward's second matrix (the
    routed stacks ``*.experts.down.w`` [E, f, d] and the shared
    ``*.shared.down.w`` [f, d]; float32 arithmetic, the result in the
    dtype the matrix is held in).

    These are the seeded stand-ins' initial values, this module's to
    choose (the configuration's ``assumed`` says so), not the layers': a
    user of ``layers.ffn`` or ``moe_ffn`` gets Normal(0, std) as written.
    What relu(.)^2 hands a second matrix is nonnegative, about ``0.5
    sigma^2`` in every lane for every token, so a matrix whose rows add up
    to a vector adds that one vector to every token's residual stream: with
    drawn weights a third of the stream, under which a batch's tokens route
    to the same few experts, how few by the seed (PERF.md, PR 46). The
    published model has a trained correction bias for that; nothing trained
    is held here."""
    block = startup.global_block()
    for name in [n for n in block.vars if n.endswith(".down.w")]:
        var = block.var(name)
        wide, mean, less = (block.create_var(
            name="%s.%s" % (name, part), dtype="float32", shape=shape)
            for part, shape in (
                ("f32", var.shape),
                ("row_mean", var.shape[:-2] + (1,) + var.shape[-1:]),
                ("centred", var.shape)))
        for op, ins, outs, attrs in (
                ("cast", {"X": [name]}, wide, {"out_dtype": "float32"}),
                ("reduce_mean", {"X": [wide.name]}, mean,
                 {"dim": -2, "keep_dim": True}),
                ("elementwise_sub", {"X": [wide.name], "Y": [mean.name]},
                 less, {}),
                ("cast", {"X": [less.name]}, var,
                 {"out_dtype": var.dtype})):
            block.append_op(op, inputs=ins, outputs={"Out": [outs.name]},
                            attrs=attrs, infer_shape=False)


def serve_startup(cfg, seed):
    """The startup program of the whole-sequence forward: it makes every
    weight a session reads by name, the feed-forwards' second matrices
    centred over their rows (:func:`centre_second_matrices`)."""
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
    centre_second_matrices(startup)
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
    ``W_out``), an attention layer's four projections, one routed expert
    (two matrices), the shared expert, a router over the published experts
    with its correction bias, a layer's one norm, the embedding and the
    head over the slice of the vocabulary (each); and how many layers are
    of each kind."""
    d = cfg["hidden_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    h, di = cfg["mamba_num_heads"], \
        cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    lanes = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    pattern = cfg["hybrid_override_pattern"]
    return {
        "mixer_matmuls": d * (di + lanes + h) + di * d,
        "mixer_rest": cfg["conv_kernel"] * lanes + lanes + 3 * h + di,
        "attention": 2 * d * width + 2 * d * kv,
        "expert": 2 * d * cfg["moe_intermediate_size"],
        "shared": 2 * d * cfg["moe_shared_expert_intermediate_size"],
        "router": d * cfg["n_routed_experts_published"],
        "router_bias": cfg["n_routed_experts_published"],
        "norm": d,
        "embedding": d * cfg["vocab_size"],
        "mixer_layers": pattern.count("M"),
        "expert_layers": pattern.count("E"),
        "attention_layers": pattern.count("*"),
    }


def parameters_held(cfg):
    """Every parameter this chip holds: the embedding, the head, the final
    norm, and in each layer its one norm and its mixer, its attention or
    its router, shared expert and held routed experts."""
    c = param_counts(cfg)
    return 2 * c["embedding"] + cfg["hidden_size"] \
        + c["mixer_layers"] * (c["mixer_matmuls"] + c["mixer_rest"]
                               + c["norm"]) \
        + c["attention_layers"] * (c["attention"] + c["norm"]) \
        + c["expert_layers"] * (
            c["router"] + c["router_bias"] + c["shared"] + c["norm"]
            + cfg["n_routed_experts"] * c["expert"])


def matmul_params(cfg):
    """Parameters that are multiplied with every token HERE: the mixers' and
    the attention layers' projections, in each expert layer the router, the
    shared expert and the token's share of its ``num_experts_per_tok``
    experts that a holder of ``n_routed_experts`` of the published ones
    takes in balance, and the head."""
    c = param_counts(cfg)
    share = cfg["n_routed_experts"] / cfg["n_routed_experts_published"]
    return (c["mixer_layers"] * c["mixer_matmuls"]
            + c["attention_layers"] * c["attention"]
            + c["expert_layers"] * (
                c["router"] + c["shared"]
                + cfg["num_experts_per_tok"] * share * c["expert"])
            + c["embedding"])


def state_row_numbers(cfg):
    """Numbers a slot's row holds in one mixer layer: the scan's state
    ``[H, P, N]`` and the convolution's last ``K`` inputs over the lanes of
    ``xBC``, every group's B and C among them."""
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return di * cfg["ssm_state_size"] + cfg["conv_kernel"] * (
        di + 2 * cfg["n_groups"] * cfg["ssm_state_size"])


def ssm_decode_ops_and_bytes(cfg, rows):
    """(FLOPs, bytes) of the decode update alone over ``rows`` state rows
    (all slots of one layer): a multiply-add into every number of the
    scan's state and another out of it (a head against its own group's B
    and C: the groups change which, not how many), the row read and written
    as it is stored (float32). The projections are not in it."""
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return 4 * di * cfg["ssm_state_size"] * rows, \
        2 * 4 * state_row_numbers(cfg) * rows


def ssd_prefill_ops_and_bytes(cfg, tokens):
    """(FLOPs, bytes) of the chunked scan alone over one sequence of
    ``tokens`` rows (whole chunks of ``chunk_size`` or one shorter): per
    chunk the scores ``C B^T`` [G, Q, Q, N] of every group, the masked
    product with ``dt x`` [H, Q, Q, P], the chunk's end state and the
    entering state's part of the output [Q, H, P, N] each; bytes: x and y
    [T, H, P], B, C [T, G, N] and dt [T, H] once each and the end state,
    float32. The decay's exponentials are not counted."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    q = min(cfg["chunk_size"], tokens)
    flops = 2 * g * tokens * q * n + 2 * h * tokens * q * p \
        + 2 * 2 * tokens * h * p * n
    return flops, 4 * (2 * tokens * h * p + 2 * tokens * g * n + tokens * h
                       + h * p * n)


def grouped_matmul_ops_and_bytes(cfg, pairs, touched):
    """(FLOPs, bytes) of one call of the held experts' two grouped matmuls
    alone: ``pairs`` rows over ``touched`` experts. Each touched expert's
    two published matrices once in bfloat16 (no padding of the width is
    counted, whatever the kernel's tiles hold); the rows in float32 in and
    out and the inner activations once each way."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2 * 2 * d * f * pairs, \
        2 * 2 * d * f * touched + 4 * pairs * (2 * d + 2 * f)


def decode_breakdown(cfg, counters, kv_bytes):
    """{"flops", "always_bytes", "expert_bytes", "state_bytes", "kv_bytes"}
    of a window's decode steps, or None. FLOPs counted once (not the passes
    exact products take): 2 a parameter outside the routed experts a decode
    token, 2 an expert parameter a pair computed here, the state update of
    every row advanced and 4 a cached number attended in the attention
    layers. Bytes as held: every weight outside the routed experts and the
    embedding once a step (bfloat16; routers, norms and the mixer's small
    vectors float32) and a row of the embedding a token, each held expert
    that took a token once, **every state row advanced read and written**,
    and the keys and values attended (``kv_bytes`` a number)."""
    window = decode_window(counters)
    touched = counters.get("paddle_generation_experts_touched_total")
    pairs = counters.get("paddle_generation_expert_assignments_total")
    rows = counters.get("paddle_generation_state_rows_updated_total")
    if window is None or touched is None or pairs is None or rows is None:
        return None
    c = param_counts(cfg)
    held = BYTES[cfg["torch_dtype"]]
    kv_width = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    # the head is a matmul over every row of it; the embedding is a gather
    matmuls = c["mixer_layers"] * c["mixer_matmuls"] \
        + c["attention_layers"] * c["attention"] \
        + c["expert_layers"] * c["shared"] + c["embedding"]
    small = c["mixer_layers"] * c["mixer_rest"] \
        + c["expert_layers"] * (c["router"] + c["router_bias"]) \
        + (c["mixer_layers"] + c["expert_layers"] + c["attention_layers"]
           + 1) * c["norm"]
    update_flops, update_bytes = ssm_decode_ops_and_bytes(cfg, rows)
    return {
        "flops": 2 * (matmuls + small) * window["tokens"]
        + 2 * c["expert"] * pairs + update_flops
        + 2 * kv_width * (cfg["num_attention_heads"]
                          // cfg["num_key_value_heads"])
        * c["attention_layers"] * window["context"],
        "always_bytes": (held * matmuls + 4 * small) * window["steps"]
        + held * cfg["hidden_size"] * window["tokens"],
        "expert_bytes": grouped_matmul_ops_and_bytes(cfg, 0, touched)[1],
        "state_bytes": update_bytes,
        "kv_bytes": kv_width * kv_bytes * c["attention_layers"]
        * window["context"]}


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps (:func:`decode_breakdown`).

    **``weight_bytes`` is ignored**, as the other sparse modules ignore it:
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
        "router_width": (cfg["n_routed_experts_published"],
                         pub["reducible"]["n_routed_experts"]),
        "experts_a_chip": (cfg["n_routed_experts"],
                           pub["reducible"]["n_routed_experts"]
                           // cfg["deployment"]["chips_sharing_a_layer"]),
        "vocabulary_a_chip": (cfg["vocab_size"],
                              pub["reducible"]["vocab_size"]
                              // cfg["deployment"]["chips_sharing_a_layer"]),
        "pattern": (cfg["hybrid_override_pattern"],
                    pub["reducible"]["hybrid_override_pattern"][
                        :cfg["num_hidden_layers"]])})


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, torch_dtype=TINY_DTYPE)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
