"""A second architecture as files only (``tests/test_second_architecture``
drops this directory's files into a copy of the benchmark): a configuration
that speaks the catalog's keys (``hidden_size``, ``num_hidden_layers``, ...)
and has none of the GPT-2 block's. The repo has one LM to build, so the
programs and the counts are the block's own, handed the same configuration
under the block's keys; what the toy proves is that nothing outside an
architecture's module reads a size key. A real architecture writes its own
programs, counts and reference here (``architectures/__init__.py`` lists
what a module offers).
"""

import copy

from benchmarks.architectures import gpt2_block as _block

# the program's argument -> the configuration's key
SIZE_KEYS = {"vocab": "vocab_size", "d_model": "hidden_size",
             "num_heads": "num_attention_heads", "d_ff": "intermediate_size",
             "num_layers": "num_hidden_layers",
             "max_len": "max_position_embeddings"}
PUBLISHED = {"widths": dict(hidden_size=256, num_attention_heads=2,
                            intermediate_size=512, vocab_size=128,
                            max_position_embeddings=64),
             "reducible": dict(num_hidden_layers=4)}


def block_cfg(cfg):
    """The same configuration under the GPT-2 block's keys."""
    return dict(cfg, **{_block.SIZE_KEYS[arg]: cfg[key]
                        for arg, key in SIZE_KEYS.items()})


def train_program(cfg, traffic, seed):
    return _block.train_program(block_cfg(cfg), traffic, seed)


def train_feed(rs, cfg, traffic):
    return _block.train_feed(rs, block_cfg(cfg), traffic)


def serve_startup(cfg, seed):
    return _block.serve_startup(block_cfg(cfg), seed)


def serve_spec(cfg, geometry, prompt_buckets):
    return _block.serve_spec(block_cfg(cfg), geometry, prompt_buckets)


def strategy(cfg, mesh_axes, devices):
    return _block.strategy(block_cfg(cfg), mesh_axes, devices)


def vocab(cfg):
    return cfg["vocab_size"]


def max_positions(cfg):
    return cfg["max_position_embeddings"]


kernels = _block.kernels


def matmul_params(cfg):
    return _block.matmul_params(block_cfg(cfg))


def train_flops_per_token(cfg, seq_len):
    return _block.train_flops_per_token(block_cfg(cfg), seq_len)


def decode_ops_and_bytes(cfg, counters, weight_bytes, kv_bytes):
    return _block.decode_ops_and_bytes(block_cfg(cfg), counters, weight_bytes,
                                       kv_bytes)


def published(cfg):
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(copy.deepcopy(PUBLISHED), as_built={"head_size": (head, 128)})


def tiny(cfg):
    """The toy is published at the rehearsal's size."""
    return copy.deepcopy(cfg)
