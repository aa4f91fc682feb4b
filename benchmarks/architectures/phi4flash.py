"""The ``phi4flash`` decoder (Microsoft Phi-4-mini-flash-reasoning: the
SambaY decoder-hybrid-decoder, arXiv:2507.06607; a self-decoder of Mamba-1
mixers and differential window attention, and a cross-decoder one layer of
which keeps the model's only full rows of keys and values while seven more
walk them with a query of their own and seven gated memory units read one
layer's scan output) through the entry points a user of paddle_tpu calls:
``models.moe_lm.moe_lm`` for the startup program that makes the weights,
``moe_lm_session`` for a serving cell; with its counts of operations and
bytes, and what the tests hold its configurations to. A configuration file
carries the catalog's own keys and, marked as assumed, the sizes the catalog
does not give.

Serving only: the training entry points say why they are not there. Whole:
nothing of the model is cut, one chip holds it.

The counts are of what the *algorithm* requires. A decode step reads every
weight once (as held: 2 bytes a matmul weight, the embedding once as the
tied head), **the rows of the one full pool once for every layer that walks
it**: the layer that owns it (``paddle_generation_context_tokens_total``)
and each of the cross layers that borrow it
(``paddle_generation_borrowed_context_tokens_total``), the window layers'
rows (``paddle_generation_window_context_tokens_total``), and reads and
writes the whole state row of every slot it advances in every Mamba layer
(``paddle_generation_state_rows_updated_total``).
"""

import copy

from . import decode_window

PUBLISHED = {
    "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/"
    "config.json": {
        "widths": dict(hidden_size=2560, num_attention_heads=40,
                       num_key_value_heads=20, intermediate_size=10240,
                       sliding_window=512, mb_per_layer=2, mamba_d_state=16,
                       mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160),
        "reducible": dict(num_hidden_layers=32, vocab_size=200064)},
}

# the rehearsal's CPU size: every mechanism, nothing wide. Eight layers are
# the plan's smallest with both borrowing types; a window of 8 rows in a
# block of 4, so that a 16-row bucket crosses both
TINY = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
            intermediate_size=96, sliding_window=8, mamba_d_state=4,
            mamba_dt_rank=4, num_hidden_layers=8, vocab_size=128,
            initializer_range=0.1, kv_from=5, memory_from=4)
TINY_SERVING = dict(slots=4, cache_len=64, block_size=4, num_blocks=64,
                    window_num_blocks=48, kv_dtype="float32",
                    state_dtype="float32")
TINY_DTYPE = "float32"

BYTES = {"bfloat16": 2, "float32": 4}


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def layer_plan(cfg):
    """The mixer of every layer, as ``models.moe_lm.MoeLM`` names them:
    with ``mb_per_layer`` 2 every second layer is of the Mamba class and
    the others attend; the second half is the cross-decoder, whose first
    Mamba layer is a Mamba-1 mixer that hands on its scan output and whose
    first attention layer is the one full layer; after them the Mamba class
    is a gated memory unit and attention is cross-attention."""
    n, every = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    half = n // 2
    plan = []
    for i in range(n):
        state_space = i % every == 0
        if i < half:
            plan.append("mamba" if state_space else "sliding_attention")
        elif i < half + every:
            plan.append("mamba" if state_space else "full_attention")
        else:
            plan.append("gmu" if state_space else "cross_attention")
    return plan


def sizes(cfg):
    """``models.moe_lm.MoeLM``'s arguments for a configuration."""
    n = cfg["num_hidden_layers"]
    if cfg["hidden_act"] != "silu" or not cfg["tie_word_embeddings"] or \
            cfg["mlp_bias"] or cfg["lm_head_bias"] or \
            cfg["mb_per_layer"] != 2 or n % 4 or \
            not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"] or \
            not cfg["attention_bias"] or \
            cfg["layer_types"] != layer_plan(cfg) or \
            (cfg["memory_from"], cfg["kv_from"]) != (n // 2, n // 2 + 1):
        raise ValueError(
            "the phi4flash module builds SwiGLU without a bias, a tied "
            "head without one, a convolution with a bias and Mamba "
            "projections without, attention projections with, a Mamba "
            "class in every second layer of a number of layers that four "
            "divides, and the layer plan that follows from them "
            "(layer_types, memory_from, kv_from)")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"], moe_d_ff=0, num_experts=0, top_k=0,
        layer_types=list(cfg["layer_types"]), num_dense_layers=n,
        sliding_window=cfg["sliding_window"], rms_eps=cfg["layer_norm_eps"],
        embed_scale=None, param_dtype=cfg["torch_dtype"],
        init_std=cfg["initializer_range"], post_norms=False, qk_norm=False,
        attn_gate=False, tie_embeddings=True, norm="layer", attn_bias=True,
        differential=True, window_rotary=False, kv_from=cfg["kv_from"],
        memory_from=cfg["memory_from"],
        mamba=dict(scan="s6", d_inner=d_inner(cfg),
                   state_dim=cfg["mamba_d_state"],
                   conv_width=cfg["mamba_d_conv"],
                   dt_rank=cfg["mamba_dt_rank"],
                   bc_std=cfg["mamba_bc_init_factor"]
                   * cfg["initializer_range"]))


def _serving_only(*_args, **_kw):
    raise NotImplementedError(
        "phi4flash is served, not trained: whole, the model is 61.6e9 bytes "
        "at this repo's 16 bytes a trained parameter and no cut within the "
        "guide's floors fits a chip, the selective scan has no backward "
        "here, and what the model is for (caches that are not one a layer) "
        "exists only in serving (ISSUE 49)")


train_program = train_feed = strategy = train_flops_per_token = _serving_only


def vocab(cfg):
    """The whole vocabulary: the traffic draws from all of it."""
    return cfg["vocab_size"]


def max_positions(cfg):
    """No positions at all: what bounds a sequence is the deployment's
    cache."""
    return min(cfg["max_position_embeddings"],
               cfg["deployment"]["serving"]["cache_len"])


def kernels(kind):
    """The kernel a cell of this kind must find compiled on the chip, at
    every call site: the paged decode walk, of the window layers, of the
    full layer and of the seven cross layers (and the one-row walks of a
    prefill's cross layers). The scan and the state update are XLA's
    (``ops/ssm_ops.py``)."""
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
    """The generation spec of a configuration's deployment geometry (slots,
    cache length, block size, the blocks of the full layer's paged kind and
    of the window layers'; the state kind has one row a slot) with a cell's
    prompt buckets. Greedy."""
    from paddle_tpu.models.moe_lm import moe_lm_session
    if geometry["state_dtype"] != "float32":
        raise ValueError("the state is held in float32")
    return moe_lm_session(
        slots=geometry["slots"], cache_len=geometry["cache_len"],
        prompt_buckets=tuple(prompt_buckets),
        block_size=geometry["block_size"], num_blocks=geometry["num_blocks"],
        window_num_blocks=geometry["window_num_blocks"],
        kv_dtype=geometry["kv_dtype"], **sizes(cfg))


def param_counts(cfg):
    """Parameters by where they sit: a Mamba-1 mixer's matrices (``W_in``,
    ``x_proj``, ``W_dt``, ``W_out``) and its small vectors (the convolution
    and its bias, ``dt_bias``, ``A_log``, ``D``), a differential attention
    (``Wqkv`` and ``out_proj``) and its biases, four lambda vectors and
    sub-norm, a cross layer's ``Wq`` and ``out_proj`` and theirs, a gated
    memory unit's two matrices, the feed-forward, a layer's two LayerNorms,
    the embedding (= the head); and how many layers are of each type."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    di, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    qkv = (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * hd
    types = list(cfg["layer_types"])
    return {
        "mixer_matmuls": d * 2 * di + di * (r + 2 * n) + r * di + di * d,
        "mixer_rest": (cfg["mamba_d_conv"] + 1) * di + di + n * di + di,
        "attention": d * qkv + d * d, "attention_rest": qkv + d + 6 * hd,
        "cross": 2 * d * d, "cross_rest": 2 * d + 6 * hd,
        "gmu": 2 * d * di,
        "ffn": 3 * d * cfg["intermediate_size"],
        "norms": 4 * d,
        "embedding": d * cfg["vocab_size"],
        "layers": len(types), "mamba_layers": types.count("mamba"),
        "window_layers": types.count("sliding_attention"),
        "full_layers": types.count("full_attention"),
        "cross_layers": types.count("cross_attention"),
        "gmu_layers": types.count("gmu")}


def _sums(cfg):
    """(matmul parameters outside the embedding, float32 parameters) of
    the whole model."""
    c = param_counts(cfg)
    attending = c["window_layers"] + c["full_layers"]
    matmuls = c["mamba_layers"] * c["mixer_matmuls"] \
        + attending * c["attention"] + c["cross_layers"] * c["cross"] \
        + c["gmu_layers"] * c["gmu"] + c["layers"] * c["ffn"]
    small = c["mamba_layers"] * c["mixer_rest"] \
        + attending * c["attention_rest"] \
        + c["cross_layers"] * c["cross_rest"] + c["layers"] * c["norms"] \
        + 2 * cfg["hidden_size"]
    return matmuls, small


def parameters_held(cfg):
    """Every parameter: the embedding once (the head is the same rows), the
    final LayerNorm and the layers."""
    matmuls, small = _sums(cfg)
    return matmuls + small + param_counts(cfg)["embedding"]


def matmul_params(cfg):
    """Parameters that are multiplied with every token: the layers'
    matrices and the head."""
    return _sums(cfg)[0] + param_counts(cfg)["embedding"]


def row_bytes(cfg, kv_bytes):
    """A cached position of one attention layer: keys and values of every
    KV head."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * kv_bytes


def state_row_numbers(cfg):
    """Numbers a slot's row holds in one Mamba layer: the scan's state
    ``[N, D]`` and the convolution's last ``K`` inputs ``[K, D]``."""
    return (cfg["mamba_d_state"] + cfg["mamba_d_conv"]) * d_inner(cfg)


def s6_decode_ops_and_bytes(cfg, rows):
    """(FLOPs, bytes) of the decode update alone over ``rows`` state rows:
    a decay's product, a multiply-add into every number of the scan's
    state and another out of it, the row read and written as it is stored
    (float32). The projections and the exponentials are not in it."""
    return 6 * d_inner(cfg) * cfg["mamba_d_state"] * rows, \
        2 * 4 * state_row_numbers(cfg) * rows


def s6_prefill_ops_and_bytes(cfg, tokens):
    """(FLOPs, bytes) of the selective scan alone over one sequence of
    ``tokens`` rows: the same six operations a number of state a row; x, dt
    and y [T, D] and B, C [T, N] once each and the end state, float32."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    return 6 * di * n * tokens, 4 * (3 * tokens * di + 2 * tokens * n
                                     + n * di)


def decode_breakdown(cfg, counters, kv_bytes):
    """{"flops", "always_bytes", "kv_bytes", "borrowed_kv_bytes",
    "window_bytes", "state_bytes"} of a window's decode steps, or None
    where the program does not count them. Bytes as held: every weight once
    a step (``torch_dtype``; norms, biases and the mixer's small vectors
    float32; the embedding once, as the head) and a token's row of it,
    **the full pool's rows once a walk**: the owning layer's walks
    (``kv_bytes``) and the cross layers' (``borrowed_kv_bytes``), the
    window layers' rows, and every state row advanced read and written.
    FLOPs: 2 a matmul parameter a decode token, each attended row against
    40 query heads of 64 lanes and summed over 40 heads of 128 (a pair's
    value), and the state update."""
    window = decode_window(counters)
    borrowed = counters.get("paddle_generation_borrowed_context_tokens_total")
    in_window = counters.get("paddle_generation_window_context_tokens_total")
    rows = counters.get("paddle_generation_state_rows_updated_total")
    if window is None or borrowed is None or in_window is None or \
            rows is None:
        return None
    c = param_counts(cfg)
    held = BYTES[cfg["torch_dtype"]]
    matmuls, small = _sums(cfg)
    row = row_bytes(cfg, kv_bytes)
    owned = c["full_layers"] * window["context"]
    update_flops, update_bytes = s6_decode_ops_and_bytes(cfg, rows)
    # q . k over a head's lanes, p . v over a pair's
    attend = 2 * cfg["num_attention_heads"] * 3 * head_dim(cfg)
    return {
        "flops": 2 * (matmuls + c["embedding"]) * window["tokens"]
        + attend * (owned + borrowed + in_window) + update_flops,
        "always_bytes": (held * (matmuls + c["embedding"]) + 4 * small)
        * window["steps"] + held * cfg["hidden_size"] * window["tokens"],
        "kv_bytes": row * owned,
        "borrowed_kv_bytes": row * borrowed,
        "window_bytes": row * in_window,
        "state_bytes": update_bytes}


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps (:func:`decode_breakdown`).

    **``weight_bytes`` is ignored**, as the other modules of
    ``models/moe_lm.py`` ignore it: ``layer_metrics/
    decode_step_roofline_share.py`` passes 4, what the GPT-2 block's
    program holds; this program holds a matmul weight in the
    configuration's ``torch_dtype`` (2 bytes) and the rest in float32."""
    del weight_bytes
    b = decode_breakdown(cfg, counters, kv_bytes)
    if b is None:
        return None
    return b["flops"], sum(v for k, v in b.items() if k.endswith("_bytes"))


def published(cfg):
    pub = copy.deepcopy(PUBLISHED[cfg["source"]])
    return dict(pub, as_built={
        "head_dim": (head_dim(cfg), 64),
        "d_inner": (d_inner(cfg), 5120),
        "layer_types": (list(cfg["layer_types"]), layer_plan(cfg)),
        "a_pair_is_a_lane_tile": (2 * head_dim(cfg), 128)})


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, torch_dtype=TINY_DTYPE)
    cfg["layer_types"] = layer_plan(cfg)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
