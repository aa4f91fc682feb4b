"""The GPT-2 block LM (``layers/attention.py``, the one LM the repo has)
through the entry points a user of paddle_tpu calls: ``transformer_lm`` +
``Adam.minimize`` for a training cell, ``transformer_lm_session`` for a
serving cell; with its counts of operations and bytes, and what the tests
hold its configurations to.

A configuration file carries the published sizes under the publisher's own
keys (``n_embd``, ``n_head``, ...); ``SIZE_KEYS`` maps the program's
argument names to them in one place.

The counts are of what the *algorithm* requires, not what a kernel happens
to execute: recomputation in a backward pass does not count, and causal
attention counts the half of the score matrix it needs. The token and
position embedding tables are gathers and do no matmul work, so they are
not in N (``bench.py:338-341`` counted them, which made its "mfu" about
9% too high at vocab 32768).
"""

import copy

from . import decode_window

# the program's argument -> the configuration's key
SIZE_KEYS = {"vocab": "vocab_size", "d_model": "n_embd",
             "num_heads": "n_head", "d_ff": "n_inner",
             "num_layers": "n_layer", "max_len": "n_positions"}

# by source: the keys that may never be cut and the depth ``reduced`` may
# list, as published; the head size they give
PUBLISHED = {
    "https://huggingface.co/cerebras/Cerebras-GPT-1.3B": {
        "widths": dict(n_embd=2048, n_head=16, n_inner=8192,
                       n_positions=2048, vocab_size=50257),
        "reducible": dict(n_layer=24),
        "head_size": 128},
}

# the rehearsal's CPU size; head size 128 is the only geometry the paged
# decode kernel takes
TINY = dict(n_embd=256, n_head=2, n_inner=512, n_positions=64, vocab_size=128,
            n_layer=2)
TINY_SERVING = dict(slots=4, cache_len=64, num_blocks=16)


def sizes(cfg):
    """The program's size arguments for a configuration."""
    s = {arg: cfg[key] for arg, key in SIZE_KEYS.items()}
    if s["d_model"] % s["num_heads"]:
        raise ValueError("n_embd %d is not a multiple of n_head %d"
                         % (s["d_model"], s["num_heads"]))
    return s


def vocab(cfg):
    return sizes(cfg)["vocab"]


def max_positions(cfg):
    return sizes(cfg)["max_len"]


def kernels(kind):
    """The kernels a cell of this kind must find compiled on the chip."""
    return {"train": ("flash_attention",),
            "serve": ("decode_attention_paged",)}[kind]


def _program(cfg, seq_len, seed, train, learning_rate=1e-4):
    """(main, startup, loss) of the seeded LM at the configuration's sizes:
    with Adam when ``train``, else the bare forward whose startup program
    makes the weights a serving session reads by name."""
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm
    s = sizes(cfg)
    main, startup = ptpu.Program(), ptpu.Program()
    # the scope's RNG is seeded from the program: 0 would mean "unseeded"
    main.random_seed = startup.random_seed = int(seed) + 1
    with ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[seq_len], dtype="int64")
        lbls = layers.data("lbls", shape=[seq_len], dtype="int64")
        loss, _ = transformer_lm(
            toks, lbls, vocab_size=s["vocab"], d_model=s["d_model"],
            num_heads=s["num_heads"], d_ff=s["d_ff"],
            num_layers=s["num_layers"], is_test=not train)
        if train:
            ptpu.optimizer.Adam(learning_rate=learning_rate).minimize(
                loss, startup_program=startup)
    return main, startup, loss


def train_program(cfg, traffic, seed):
    return _program(cfg, int(traffic["seq_len"]), seed, train=True,
                    learning_rate=float(traffic["learning_rate"]))


def train_feed(rs, cfg, traffic):
    """One step's batch of seeded token ids; the labels are the next ids."""
    import numpy as np
    batch, seq_len = int(traffic["batch"]), int(traffic["seq_len"])
    ids = rs.randint(2, vocab(cfg), (batch, seq_len)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    return {"feed": {"toks": ids, "lbls": labels},
            "units_per_step": batch * seq_len,
            "reference_rows": (ids, labels)}


def serve_startup(cfg, seed):
    return _program(cfg, max_positions(cfg), seed, train=False)[1]


def serve_spec(cfg, geometry, prompt_buckets):
    """The paged generation spec of a configuration's deployment geometry
    (slots, cache length, block size, pool blocks) with a cell's prompt
    buckets. Greedy: ``decode_policy=None`` whatever the flags say."""
    from paddle_tpu.models.transformer import transformer_lm_session
    s = sizes(cfg)
    return transformer_lm_session(
        s["vocab"], d_model=s["d_model"], num_heads=s["num_heads"],
        d_ff=s["d_ff"], num_layers=s["num_layers"],
        max_len=s["max_len"], slots=geometry["slots"],
        cache_len=geometry["cache_len"],
        prompt_buckets=tuple(prompt_buckets), paged=True,
        block_size=geometry["block_size"],
        num_blocks=geometry["num_blocks"], prefix_cache=False,
        decode_policy=None)


def strategy(cfg, mesh_axes, devices):
    """DistStrategy of a cell's mesh (``{"data": 2, "model": 2}``) with the
    transformer's tensor-parallel rules where the mesh has a model axis."""
    from paddle_tpu import parallel
    from paddle_tpu.models.transformer import transformer_tp_rules
    rules = transformer_tp_rules("model") if mesh_axes.get("model", 1) > 1 \
        else None
    return parallel.DistStrategy(parallel.make_mesh(dict(mesh_axes), devices),
                                 param_rules=rules)


def matmul_params(cfg):
    """Parameters that are multiplied with every token: the four attention
    projections and the two feed-forward matrices of each layer, and the
    LM head. Biases, LayerNorm and the two embedding tables are left out."""
    s = sizes(cfg)
    d, dff = s["d_model"], s["d_ff"]
    per_layer = 4 * d * d + 2 * d * dff
    return s["num_layers"] * per_layer + d * s["vocab"]


def train_flops_per_token(cfg, seq_len):
    """Forward plus backward, three times the forward (6*N + 6*L*T*d). The
    forward of one token of a sequence of ``seq_len``: 2*N, and causal
    attention's QK^T and PV, 2*T*d each against the full square, half of
    which the mask needs."""
    s = sizes(cfg)
    attention = 2 * s["num_layers"] * seq_len * s["d_model"]
    return 3 * (2 * matmul_params(cfg) + attention)


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    """(FLOPs, bytes) of a window's decode steps. FLOPs: 2*N a decode token
    and each token against its whole cached context (no causal halving: one
    query row). Bytes: every matmul parameter once a step (``weight_bytes``
    each, as the program holds them) and the keys and values of every
    attended token (``kv_bytes`` each). Both are linear in the counters, so
    the window's totals are exact."""
    window = decode_window(counters)
    if window is None:
        return None
    s = sizes(cfg)
    n, layers_by_width = matmul_params(cfg), s["num_layers"] * s["d_model"]
    nflops = 2 * n * window["tokens"] + 4 * layers_by_width * window["context"]
    nbytes = n * weight_bytes * window["steps"] + \
        window["context"] * 2 * layers_by_width * kv_bytes
    return nflops, nbytes


def published(cfg):
    pub = PUBLISHED[cfg["source"]]
    s = sizes(cfg)
    return {"widths": dict(pub["widths"]), "reducible": dict(pub["reducible"]),
            "as_built": {"head_size": (s["d_model"] // s["num_heads"],
                                       pub["head_size"])}}


def tiny(cfg):
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    if "serving" in cfg.get("deployment", {}):
        cfg["deployment"]["serving"].update(TINY_SERVING)
    return cfg
