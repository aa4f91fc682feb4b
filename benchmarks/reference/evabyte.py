"""Plain reference of the ``evabyte`` decoder (EvaByte 6.5B; EVA is Zheng,
Yuan, Wang, Kong, *Efficient Attention via Control Variates*, ICLR 2023,
arXiv:2302.04542): a byte-level Llama-style dense block (RMSNorm with a
unit offset before each half, no bias, SwiGLU, rotary positions in every
layer, every head its own KV head) whose attention is EVA. Per layer, ``h``
[T, d] the residual stream, W the window, C the chunk, ``s = D^-1/2``::

    a = RMSNorm(h) * (1 + w_in)
    q, k, v = a W_q, a W_k, a W_v ;  q, k = RoPE(q), RoPE(k)
    per head, learned mu, phi [D]:
      chunk c = positions [C c, C c + C):
        kbar_c = sum_j softmax_j(mu  . k_j) k_j
        vbar_c = sum_j softmax_j(phi . k_j) v_j
      query i:  E_i = { j : j // W == i // W, j <= i }
                R_i = { c : C (c + 1) <= W (i // W) }
      o_i = ( sum_E exp(s q_i.k_j) v_j + sum_R exp(s q_i.kbar_c) vbar_c )
          / ( sum_E exp(s q_i.k_j)     + sum_R exp(s q_i.kbar_c) )
    h = h + concat(o) W_o
    b = RMSNorm(h) * (1 + w_post);  h = h + (silu(b W_gate) * (b W_up)) W_down
    logits = (RMSNorm(h_last) * (1 + w_f)) W_head

``W_head`` is [d, num_pred_heads x V], head-major: head p's V columns
predict byte t + 1 + p. Like every reference it takes the configuration
dict (``gather_weights(find_var, cfg)``, ``logits_at(w, tokens, positions,
cfg)``: head 0's logits, what chooses the next byte; ``loss(w, tokens,
labels, cfg)``), and ``all_heads_at`` gives every head's. Straight
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernels, no cache, no batching, one sequence at a time; attention is
taken a window of queries and a head at a time (scores ``[W, W + T / C]``)
and the feed-forward ``ROWS`` rows at a time, so that 8,192 positions fit
beside a serving session.

It is fed the program's own weights by name and keeps them as they are held
(bfloat16 upcast exactly). Departures and what is assumed of the released
modelling code are in the configuration file.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 2048         # rows of the feed-forward taken at a time


def weight_names(cfg):
    """The program's parameter names (``models/moe_lm.py``), in the
    reference's own terms."""
    names = {"embed": "moe_lm.embed.w", "norm_final": "moe_lm.norm_final.w",
             "head": "moe_lm.lm_head.w"}
    for i in range(cfg["num_hidden_layers"]):
        p, q = "l%d." % i, "moe_lm.l%d." % i
        for part in ("q", "k", "v", "o"):
            names[p + part] = q + "attn.%s.w" % part
        for part in ("mu", "phi"):
            names[p + part] = q + "attn." + part
        for part in ("gate", "up", "down"):
            names[p + part] = q + "mlp.%s.w" % part
        for norm in ("norm_in", "norm_pre_mlp"):
            names[p + norm] = q + norm + ".w"
    return names


def gather_weights(find_var, cfg):
    """{reference name: array} from the program's scope (``find_var`` is
    ``scope.find_var``). No copy: the arrays are the program's own."""
    return {k: find_var(v) for k, v in weight_names(cfg).items()}


def _rms_norm(x, w, cfg):
    gain = w.astype(F32) + (1.0 if cfg["norm_add_unit_offset"] else 0.0)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + cfg["rms_norm_eps"]) * gain


def _rotary(x, theta):
    """x [T, H, D] at positions 0..T-1: lane i of a head turns with lane
    i + D/2 by position * theta^(-2i/D)."""
    t, _, d = x.shape
    half = d // 2
    ang = jnp.arange(t, dtype=F32)[:, None, None] * \
        theta ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def summaries(k, v, mu, phi, chunk):
    """k, v [T, H, D], mu, phi [H, D] -> (kbar, vbar) [T // chunk, H, D]:
    each whole chunk's keys pooled under softmax(mu . k) and its values
    under softmax(phi . k), over the chunk's positions, unscaled."""
    n = k.shape[0] // chunk
    kc = k[:n * chunk].reshape((n, chunk) + k.shape[1:])
    vc = v[:n * chunk].reshape((n, chunk) + v.shape[1:])
    wk = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, mu), axis=1)
    wv = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (jnp.einsum("nch,nchd->nhd", wk, kc),
            jnp.einsum("nch,nchd->nhd", wv, vc))


def _eva(q, k, v, mu, phi, cfg):
    """q, k, v [T, H, D] (rotated) -> o [T, H, D]: a window of queries at a
    time against its own rows and every summary of the windows before."""
    t, _, hd = q.shape
    win, chunk = cfg["window_size"], cfg["chunk_size"]
    kbar, vbar = summaries(k, v, mu, phi, chunk)
    scale = hd ** -0.5
    out = []
    for lo in range(0, t, win):
        n = min(win, t - lo)
        causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
        # the chunks of the earlier windows: all of them whole
        behind = lo // chunk

        def head(x):
            qh, kh, vh, kb, vb = x          # [n, D] x3, [behind, D] x2
            s = jnp.concatenate(
                [jnp.where(causal, qh @ kh.T * scale, -jnp.inf),
                 qh @ kb.T * scale], axis=-1)
            p = jax.nn.softmax(s, axis=-1)
            return p[:, :n] @ vh + p[:, n:] @ vb

        by_head = [x.transpose(1, 0, 2) for x in (
            q[lo:lo + n], k[lo:lo + n], v[lo:lo + n], kbar[:behind],
            vbar[:behind])]
        out.append(jax.lax.map(head, tuple(by_head)).transpose(1, 0, 2))
    return jnp.concatenate(out, axis=0)


def _swiglu(b, gate, up, down):
    """(silu(b W_gate) * (b W_up)) W_down, ``ROWS`` rows at a time."""
    def rows(x):
        return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) \
            @ down.astype(F32)
    t = b.shape[0]
    if t <= ROWS or t % ROWS:
        return rows(b)
    return jax.lax.map(rows, b.reshape(t // ROWS, ROWS, -1)).reshape(t, -1)


def hidden(w, tokens, cfg):
    """tokens [T] -> final hidden states [T, d], after the last RMSNorm."""
    nh = cfg["num_attention_heads"]
    if cfg["num_key_value_heads"] != nh:
        raise ValueError("every head of this model has its own KV head")
    h = w["embed"][tokens].astype(F32)
    t = h.shape[0]
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        a = _rms_norm(h, w[p + "norm_in"], cfg)
        q, k, v = ((a @ w[p + x].astype(F32)).reshape(t, nh, -1)
                   for x in "qkv")
        o = _eva(_rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"]),
                 v, w[p + "mu"].astype(F32).reshape(nh, -1),
                 w[p + "phi"].astype(F32).reshape(nh, -1), cfg)
        h = h + o.reshape(t, -1) @ w[p + "o"].astype(F32)
        b = _rms_norm(h, w[p + "norm_pre_mlp"], cfg)
        h = h + _swiglu(b, w[p + "gate"], w[p + "up"], w[p + "down"])
    return _rms_norm(h, w["norm_final"], cfg)


def all_heads_at(w, tokens, positions, cfg):
    """Logits [len(positions), num_pred_heads, V] of one sequence: head p
    predicts the byte p + 1 places on."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, tokens, cfg)[positions] @ w["head"].astype(F32)
        return x.reshape(x.shape[0], cfg["num_pred_heads"], -1)


def logits_at(w, tokens, positions, cfg):
    """Logits [len(positions), V] of one sequence at the given positions:
    the head that predicts the next byte."""
    return all_heads_at(w, tokens, positions, cfg)[:, 0]


def loss(w, tokens, labels, cfg):
    """Mean next-byte cross-entropy of one sequence (labels [T])."""
    logits = logits_at(w, tokens, jnp.arange(tokens.shape[0]), cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
