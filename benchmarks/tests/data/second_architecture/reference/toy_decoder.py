"""The toy's plain reference: the GPT-2 block's mathematics
(``reference/gpt2_block.py``) over the same configuration under the block's
keys. A real architecture writes its own forward pass here."""

from benchmarks.architectures.toy_decoder import block_cfg
from benchmarks.reference import gpt2_block as _block


def gather_weights(find_var, cfg):
    return _block.gather_weights(find_var, block_cfg(cfg))


def logits_at(w, tokens, positions, cfg):
    return _block.logits_at(w, tokens, positions, block_cfg(cfg))


def loss(w, tokens, labels, cfg):
    return _block.loss(w, tokens, labels, block_cfg(cfg))
