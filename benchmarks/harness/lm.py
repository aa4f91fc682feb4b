"""Builds the LM programs of a configuration file through the entry points
a user of paddle_tpu calls: ``transformer_lm`` + ``Adam.minimize`` for a
training cell, ``transformer_lm_session`` for a serving cell.

A configuration file carries the published sizes under the publisher's own
keys (``n_embd``, ``n_head``, ...); ``sizes`` maps them to the program's
argument names in one place.
"""

import contextlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    """A data file of the benchmark, by its path under ``benchmarks/``."""
    path = os.path.join(BENCH_DIR, *parts)
    with open(path) as f:
        return json.load(f)


def load_config(name):
    cfg = load_json("configs", name + ".json")
    if cfg["name"] != name:
        raise ValueError("configs/%s.json names itself %r" % (name, cfg["name"]))
    return cfg


def sizes(cfg):
    """The program's size arguments for a configuration."""
    if cfg["n_embd"] % cfg["n_head"]:
        raise ValueError("n_embd %d is not a multiple of n_head %d"
                         % (cfg["n_embd"], cfg["n_head"]))
    return dict(vocab=cfg["vocab_size"], d_model=cfg["n_embd"],
                num_heads=cfg["n_head"], d_ff=cfg["n_inner"],
                num_layers=cfg["n_layer"])


@contextlib.contextmanager
def flags(**kw):
    """Set paddle_tpu config flags for a block and restore them after."""
    import paddle_tpu as ptpu
    prev = {k: ptpu.config.get_flag(k) for k in kw}
    ptpu.config.set_flags(**kw)
    try:
        yield
    finally:
        ptpu.config.set_flags(**prev)


def lm_program(cfg, seq_len, seed, train, learning_rate=1e-4):
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


def serve_spec(cfg, geometry, prompt_buckets):
    """The paged generation spec of a configuration's deployment geometry
    (slots, cache length, block size, pool blocks) with a cell's prompt
    buckets. Greedy: ``decode_policy=None`` whatever the flags say."""
    from paddle_tpu.models.transformer import transformer_lm_session
    s = sizes(cfg)
    return transformer_lm_session(
        s["vocab"], d_model=s["d_model"], num_heads=s["num_heads"],
        d_ff=s["d_ff"], num_layers=s["num_layers"],
        max_len=cfg["n_positions"], slots=geometry["slots"],
        cache_len=geometry["cache_len"],
        prompt_buckets=tuple(prompt_buckets), paged=True,
        block_size=geometry["block_size"],
        num_blocks=geometry["num_blocks"], prefix_cache=False,
        decode_policy=None)


def logits_var(program, fetch_name):
    """Name of the logits row the program's greedy epilogue (argmax) reads."""
    for op in program.global_block().ops:
        if op.type == "arg_max" and fetch_name in sum(op.outputs.values(), []):
            return op.inputs["X"][0]
    raise LookupError("no argmax produces %r" % fetch_name)


def make_strategy(mesh_axes, devices):
    """DistStrategy of a cell's mesh (``{"data": 2, "model": 2}``) with the
    transformer's tensor-parallel rules where the mesh has a model axis."""
    from paddle_tpu import parallel
    from paddle_tpu.models.transformer import transformer_tp_rules
    rules = transformer_tp_rules("model") if mesh_axes.get("model", 1) > 1 \
        else None
    return parallel.DistStrategy(parallel.make_mesh(dict(mesh_axes), devices),
                                 param_rules=rules)
