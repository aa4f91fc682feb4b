"""Plain reference of the ``granitemoehybrid`` decoder (IBM Granite 4.0-H;
the layer equations as ISSUE 33 wrote them down from the published
``config.json``): RMSNorm before each half of a block only, a mixer that is
layer by layer a Mamba-2 state-space mixer or plain grouped-query attention
without positions, and in every layer a shared expert plus the
``num_experts_per_tok`` best of ``num_local_experts_published`` routed
experts, weighed by a softmax over the chosen logits; four multipliers; the
head tied to the embedding.

    h0 = embedding_multiplier * embed[token]
    h  = h + residual_multiplier * mix(RMSNorm(h; w_in))
    h  = h + residual_multiplier * (routed(m) + shared(m)),  m = RMSNorm(h; w_pre)
    logits = RMSNorm(h; w_final) embed^T / logits_scaling

    mamba:  [z, xBC, dt] = a W_in
            xBC_t = silu(sum_{k<K} w[k] * xBC_{t-K+1+k} + b)   zeros before row 0
            [x, B, C] = split(xBC_t);  dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
            S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t
            y_t[h] = S_t[h] C_t + D[h] x_t[h]
            mix = (RMSNorm(y * silu(z); w_norm)) W_out
    attention:  o_h,i = sum_{j<=i} softmax_j(q_h,i . k_g(h),j * attention_multiplier) v_g(h),j
            mix = concat_h(o_h) W_o                      no positions, no bias
    routed: l = m W_r;  top-k of l;  w = softmax over the chosen k

The scan is the **sequential recurrence**, one row after the other by
``lax.scan`` (the program computes it in chunks: the two check each other);
the convolution is a plain sum over its shifted rows. No cache, no chunks,
no sorting: routing is a dense top-k mask over all published experts and the
held experts are a slice of it. Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time.

It is fed the program's own weights by name and keeps them as they are held
(bfloat16 matmul weights upcast exactly), one matrix, one expert, one block
of query rows and one slice of the vocabulary at a time, so that it fits
beside a serving session on one chip.

Departures from the published description, which the program makes and this
file follows (each is in the configuration file): the experts held are
``[expert_offset, expert_offset + num_local_experts)`` of
``num_local_experts_published`` and what the others would add is left out;
the vocabulary is its first ``vocab_size`` rows; what the keys do not settle
is under ``assumed``.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
# query rows attended at once: [H, rows, T] float32 scores
QUERY_ROWS = 256


def weight_names(cfg):
    """The program's parameter names (``models/moe_lm.py``), in the
    reference's own terms. The head is the embedding."""
    names = {"embed": "moe_lm.embed.w", "norm_final": "moe_lm.norm_final.w"}
    for i, kind in enumerate(cfg["layer_types"]):
        p, q = "l%d." % i, "moe_lm.l%d." % i
        if kind == "mamba":
            for mine, theirs in (
                    ("in", "in.w"), ("conv_w", "conv.w"),
                    ("conv_b", "conv.b"), ("dt_bias", "dt_bias"),
                    ("a_log", "a_log"), ("d", "d"), ("norm", "norm.w"),
                    ("out", "out.w")):
                names[p + "mamba." + mine] = q + "mamba." + theirs
        else:
            for part in ("q", "k", "v", "o"):
                names[p + "attn." + part] = q + "attn.%s.w" % part
        for norm in ("norm_in", "norm_pre_mlp"):
            names[p + norm] = q + norm + ".w"
        names[p + "router"] = q + "moe.router.w"
        for mine, theirs in (("shared", "moe.shared"),
                             ("experts", "moe.experts")):
            for part in ("gate", "up", "down"):
                names["%s%s.%s" % (p, mine, part)] = \
                    "%s%s.%s.w" % (q, theirs, part)
    return names


def gather_weights(find_var, cfg):
    """{reference name: array} from the program's scope (``find_var`` is
    ``scope.find_var``). No copy: the arrays are the program's own."""
    return {k: find_var(v) for k, v in weight_names(cfg).items()}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) \
        @ down.astype(F32)


def mamba_scan(x, dt, a, b, c, d):
    """The recurrence, one row after the other from a zero state: x
    [T, H, P], dt [T, H] (after softplus), a [H] (negative), b, c [T, N],
    d [H] -> (y [T, H, P], the state after the last row [H, P, N])."""
    def row(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.einsum("hpn,n->hp", s, c_t) + d[:, None] * x_t

    h, p = x.shape[1:]
    last, y = jax.lax.scan(row, jnp.zeros((h, p, b.shape[1]), F32),
                           (x, dt, b, c))
    return y, last


def mamba_inputs(a, w, p, cfg):
    """a [T, d] -> (z [T, HP], the convolution's inputs xBC [T, HP + 2N],
    the convolution's output after silu, dt [T, H] after softplus)."""
    h, hp = cfg["mamba_n_heads"], cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    lanes = hp + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    zxd = a @ w[p + "in"].astype(F32)
    z, raw, dt = zxd[:, :hp], zxd[:, hp:hp + lanes], zxd[:, hp + lanes:]
    assert dt.shape[1] == h
    t, k = a.shape[0], cfg["mamba_d_conv"]
    conv_w = w[p + "conv_w"].astype(F32)                    # [K, lanes]
    padded = jnp.pad(raw, ((k - 1, 0), (0, 0)))
    act = sum(conv_w[j] * padded[j:j + t] for j in range(k))
    act = jax.nn.silu(act + w[p + "conv_b"].astype(F32))
    return z, raw, act, jax.nn.softplus(dt + w[p + "dt_bias"].astype(F32))


def _mamba(a, w, p, cfg):
    h, hd, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    t, hp = a.shape[0], h * hd
    z, _, act, dt = mamba_inputs(a, w, p, cfg)
    y, _ = mamba_scan(act[:, :hp].reshape(t, h, hd), dt,
                      -jnp.exp(w[p + "a_log"].astype(F32)),
                      act[:, hp:hp + n], act[:, hp + n:],
                      w[p + "d"].astype(F32))
    g = _rms_norm(y.reshape(t, hp) * jax.nn.silu(z), w[p + "norm"],
                  cfg["rms_norm_eps"])
    return g @ w[p + "out"].astype(F32)


def _attention(a, w, p, cfg):
    t = a.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    q = (a @ w[p + "q"].astype(F32)).reshape(t, nkv, nh // nkv, hd)
    k = (a @ w[p + "k"].astype(F32)).reshape(t, nkv, hd)
    v = (a @ w[p + "v"].astype(F32)).reshape(t, nkv, hd)
    rows = next(r for r in range(min(t, QUERY_ROWS), 0, -1) if t % r == 0)
    cols = jnp.arange(t)[None, :]

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * rows, rows, axis=0)
        s = jnp.einsum("qkgd,ckd->kgqc", qb, k) * cfg["attention_multiplier"]
        visible = cols <= (b * rows + jnp.arange(rows))[:, None]
        s = jnp.where(visible, s, -jnp.inf)
        return jnp.einsum("kgqc,ckd->qkgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(t // rows)).reshape(t, nh * hd)
    return o @ w[p + "o"].astype(F32)


def _experts(m, w, p, cfg):
    """The routed experts' part of the feed-forward: a dense [T, E] mask of
    the chosen experts' softmax weights over all published experts, then
    one held expert after the other over every token."""
    logits = m @ w[p + "router"].astype(F32)
    top, sel = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    dense = jnp.zeros_like(logits).at[
        jnp.arange(m.shape[0])[:, None], sel].set(
            jax.nn.softmax(top, axis=-1))
    held = w[p + "experts.gate"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(dense, cfg.get("expert_offset", 0),
                                        held, axis=1)

    def add_expert(f, e):
        gate, up, down, weight = e
        return f + weight[:, None] * _swiglu(m, gate, up, down), None

    f, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (w[p + "experts.gate"], w[p + "experts.up"], w[p + "experts.down"],
         mine.T))
    return f


def hidden(w, tokens, cfg):
    """tokens [T] -> final hidden states [T, d], after the last RMSNorm."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = w["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d." % i
        a = _rms_norm(h, w[p + "norm_in"], eps)
        mix = _mamba(a, w, p + "mamba.", cfg) if kind == "mamba" \
            else _attention(a, w, p + "attn.", cfg)
        h = h + res * mix
        m = _rms_norm(h, w[p + "norm_pre_mlp"], eps)
        f = _swiglu(m, w[p + "shared.gate"], w[p + "shared.up"],
                    w[p + "shared.down"]) + _experts(m, w, p, cfg)
        h = h + res * f
    return _rms_norm(h, w["norm_final"], eps)


def _head(x, embed, scaling):
    """x [n, d] @ embed^T [d, V] / scaling, a slice of the vocabulary at a
    time."""
    v = embed.shape[0]
    width = next(c for c in range(min(v, 16384), 0, -1) if v % c == 0)

    def part(i):
        rows = jax.lax.dynamic_slice_in_dim(embed, i * width, width, axis=0)
        return x @ rows.astype(F32).T

    out = jax.lax.map(part, jnp.arange(v // width))     # [V/width, n, width]
    return out.transpose(1, 0, 2).reshape(x.shape[0], v) / scaling


def logits_at(w, tokens, positions, cfg):
    """Logits [len(positions), V] of one sequence at the given positions."""
    with jax.default_matmul_precision("highest"):
        return _head(hidden(w, tokens, cfg)[positions], w["embed"],
                     cfg["logits_scaling"])


def loss(w, tokens, labels, cfg):
    """Mean next-token cross-entropy of one sequence (labels [T])."""
    with jax.default_matmul_precision("highest"):
        logits = _head(hidden(w, tokens, cfg), w["embed"],
                       cfg["logits_scaling"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
