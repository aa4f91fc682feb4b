"""Operations and bytes the GPT-2-block LM needs, computed from its shapes.

These count what the *algorithm* requires, not what a kernel happens to
execute: recomputation in a backward pass does not count, and causal
attention counts the half of the score matrix it needs. The token and
position embedding tables are gathers and do no matmul work, so they are
not in N (``bench.py:338-341`` counted them, which made its "mfu" about
9% too high at vocab 32768).
"""


def matmul_params(cfg):
    """Parameters that are multiplied with every token: the four attention
    projections and the two feed-forward matrices of each layer, and the
    LM head. Biases, LayerNorm and the two embedding tables are left out."""
    d, dff = cfg["n_embd"], cfg["n_inner"]
    per_layer = 4 * d * d + 2 * d * dff
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def attention_flops_per_token(cfg, context):
    """Forward FLOPs of causal attention for one token, averaged over a
    sequence of ``context`` tokens: QK^T and PV are 2*context*d each
    against the full square, half of which the causal mask needs."""
    return 2 * cfg["n_layer"] * context * cfg["n_embd"]


def forward_flops_per_token(cfg, context):
    """Forward FLOPs for one token of a sequence of ``context`` tokens."""
    return 2 * matmul_params(cfg) + attention_flops_per_token(cfg, context)


def train_flops_per_token(cfg, seq_len):
    """Forward plus backward: three times the forward (6*N + 6*L*T*d)."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def mfu(cfg, seq_len, tokens_per_s, chips, peak_flops):
    """Model FLOP/s utilization of a training run: required operations per
    token times tokens per second over chips times the bf16 peak. An
    end-to-end utilization, not a kernel's roofline share."""
    return train_flops_per_token(cfg, seq_len) * tokens_per_s / (
        chips * peak_flops)


def decode_step_bytes(cfg, context_lens, weight_bytes=4, kv_bytes=2):
    """Bytes one decode step must read: every matmul parameter once
    (``weight_bytes`` each, f32 as the program holds them) and each live
    sequence's cached keys and values (``kv_bytes`` each, bf16 blocks)."""
    weights = matmul_params(cfg) * weight_bytes
    kv = sum(context_lens) * 2 * cfg["n_embd"] * cfg["n_layer"] * kv_bytes
    return weights + kv


def decode_step_flops(cfg, context_lens):
    """FLOPs of one decode step: one token per live sequence, each against
    its whole cached context (no causal halving: one query row)."""
    n = len(context_lens)
    attn = 4 * cfg["n_layer"] * cfg["n_embd"] * sum(context_lens)
    return 2 * matmul_params(cfg) * n + attn


def roofline_seconds(flops, nbytes, peaks, flops_key="bf16_flops"):
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peaks[flops_key], nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
