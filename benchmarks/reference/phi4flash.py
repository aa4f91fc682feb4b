"""Plain reference of the ``phi4flash`` decoder (Microsoft
Phi-4-mini-flash-reasoning; the SambaY decoder-hybrid-decoder of
arXiv:2507.06607, on Samba arXiv:2406.07522, YOCO arXiv:2405.05254 and the
Differential Transformer arXiv:2410.05258; the layer equations as ISSUE 49
wrote them down from the published ``config.json`` and the family's
modelling code as it remembers it). Layer i of 0..n-1, ``h`` float32:

    a = LayerNorm_in(h);  h = h + mixer_i(a)
    u = LayerNorm_post(h);  h = h + down(silu(gate(u)) * up(u))
    logits = LayerNorm_final(h) E^T          (E the embedding, tied; no bias)

``mixer_i`` by the plan (``mb_per_layer`` 2: even layers are of the Mamba
class, odd layers attend; from layer n/2 on it is the cross-decoder):

    i < n/2, even     Mamba-1
    i < n/2, odd      differential attention, window 512 (this position
                      and the 511 before it)
    i = n/2           Mamba-1, which also hands on m = its scan output
    i = n/2 + 1       differential attention, full, causal: the one layer
                      whose keys and values the cross layers read
    later, even       gated memory unit on m
    later, odd        differential cross-attention: its own query, layer
                      n/2 + 1's keys and values

    Mamba-1:  [x | z] = a W_in;  x_t = silu(sum_k w[k] x_{t-K+1+k} + b)
              [r | B | C] = x_t W_x;  dt_t = softplus(r W_dt + b_dt);  A = -exp(A_log)
              S_t = exp(dt_t (outer) A) . S_{t-1} + (dt_t x_t) (outer) B_t
              y_t = S_t C_t + D x_t;  m = y;  out = (y silu(z)) W_out
    GMU:      out = (silu(a W_in') * m) W_out'        m of the same token
    diff attention:  qkv = a W_qkv + b;  pairs (q1_p, q2_p) = heads (2p, 2p+1),
              (k1_g, k2_g) likewise, V_g = (v_2g | v_2g+1), g = p // (pairs / KV pairs)
              A1 = softmax(q1 k1^T / sqrt(D)), A2 = softmax(q2 k2^T / sqrt(D))   under the mask
              o_p = A1 V_g - lambda A2 V_g
              lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,  lambda_init = 0.8 - 0.6 exp(-0.3 i)
              o_p = RMSNorm_2D(o_p; w) (1 - lambda_init);  out = concat_p(o_p) W_o + b_o
    cross:    q = a W_q + b alone; keys and values are layer n/2 + 1's

No positional encoding anywhere. The scan is the **sequential recurrence**,
one row after the other by ``lax.scan``, each row's decay taken by hand
(``nemotron_h.step_decay`` says why); the convolution is a plain sum over
its shifted rows; a pair's two softmaxes are computed apart, on heads of D
lanes. No cache, no kernels, no batching: one sequence at a time, straight
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``.

It is fed the program's own weights by name and keeps them as they are held
(bfloat16 matmul weights upcast exactly), one matrix, one block of query
rows and one slice of the vocabulary at a time, so that it fits beside a
serving session on one chip.

Departures from the published description, which the program makes and this
file follows (each is in the configuration file): ``x_proj`` is held as two
matrices, ``[D, R]`` and ``[D, 2N]`` (its columns, regrouped); ``A_log`` is
held ``[N, D]``, the transpose of the published ``[D, N]``; weights are
random from the seed; what the catalog's keys do not settle is under
``assumed``.
"""

import math

import jax
import jax.numpy as jnp

from .nemotron_h import step_decay

F32 = jnp.float32
# query rows attended at once: [pairs, rows, T] float32 scores, twice
QUERY_ROWS = 256
# rows of the vocabulary multiplied at once
VOCAB_ROWS = 8192

_MIXER = ("in", "in.w"), ("x_dt", "x_dt.w"), ("x_bc", "x_bc.w"), \
    ("dt", "dt.w"), ("out", "out.w"), ("conv_w", "conv.w"), \
    ("conv_b", "conv.b"), ("dt_bias", "dt_bias"), ("a_log", "a_log"), \
    ("d", "d")
_LAMBDAS = "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"


def weight_names(cfg):
    """The program's parameter names (``models/moe_lm.py``), in the
    reference's own terms. The head is the embedding."""
    names = {"embed": "moe_lm.embed.w", "norm_final.w": "moe_lm.norm_final.w",
             "norm_final.b": "moe_lm.norm_final.b"}
    for i, kind in enumerate(cfg["layer_types"]):
        p, q = "l%d." % i, "moe_lm.l%d." % i
        if kind == "mamba":
            for mine, theirs in _MIXER:
                names[p + "mamba." + mine] = q + "mamba." + theirs
        elif kind == "gmu":
            for part in ("in", "out"):
                names[p + "gmu." + part] = q + "gmu.%s.w" % part
        else:
            first = "q" if kind == "cross_attention" else "qkv"
            for part in (first, "o"):
                names[p + "attn." + part] = q + "attn.%s.w" % part
                names[p + "attn.%s_b" % part] = q + "attn.%s.b" % part
            for part in _LAMBDAS:
                names[p + "attn." + part] = q + "attn." + part
            names[p + "attn.subln"] = q + "attn.subln.w"
        for norm in ("norm_in", "norm_pre_mlp"):
            for part in "wb":
                names["%s%s.%s" % (p, norm, part)] = \
                    "%s%s.%s" % (q, norm, part)
        for part in ("gate", "up", "down"):
            names[p + "mlp." + part] = q + "mlp.%s.w" % part
    return names


def gather_weights(find_var, cfg):
    """{reference name: array} from the program's scope (``find_var`` is
    ``scope.find_var``). No copy: the arrays are the program's own."""
    return {k: find_var(v) for k, v in weight_names(cfg).items()}


def _layer_norm(x, w, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w[p + ".w"].astype(F32) \
        + w[p + ".b"].astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) \
        @ down.astype(F32)


def mamba_inputs(a, w, p):
    """a [T, d] -> (the convolution's inputs raw [T, D], the gate z, the
    convolved x after silu, dt [T, D] after softplus, B, C [T, N])."""
    xz = a @ w[p + "in"].astype(F32)
    di = xz.shape[1] // 2
    raw, z = xz[:, :di], xz[:, di:]
    conv_w = w[p + "conv_w"].astype(F32)                    # [K, D]
    t, k = a.shape[0], conv_w.shape[0]
    padded = jnp.pad(raw, ((k - 1, 0), (0, 0)))
    x = sum(conv_w[j] * padded[j:j + t] for j in range(k))
    x = jax.nn.silu(x + w[p + "conv_b"].astype(F32))
    r = x @ w[p + "x_dt"].astype(F32)
    bc = x @ w[p + "x_bc"].astype(F32)
    n = bc.shape[1] // 2
    dt = jax.nn.softplus(r @ w[p + "dt"].astype(F32)
                         + w[p + "dt_bias"].astype(F32))
    return raw, z, x, dt, bc[:, :n], bc[:, n:]


def mamba_scan(x, dt, a, b, c):
    """The recurrence, one row after the other from a zero state: x, dt
    [T, D] (dt after softplus), a [N, D] (negative), b, c [T, N] -> (y
    [T, D] without the ``D x`` term, the state after the last row
    [N, D])."""
    def row(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = step_decay(dt_t[None, :] * a) * s \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, c_t @ s

    last, y = jax.lax.scan(row, jnp.zeros(a.shape, F32), (x, dt, b, c))
    return y, last


def _mamba(a, w, p):
    """-> (the mixer's output [T, d], m = y [T, D] before the gate)."""
    _, z, x, dt, b, c = mamba_inputs(a, w, p)
    y, _ = mamba_scan(x, dt, -jnp.exp(w[p + "a_log"].astype(F32)), b, c)
    y = y + w[p + "d"].astype(F32) * x
    return (y * jax.nn.silu(z)) @ w[p + "out"].astype(F32), y


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def keys_and_values(a, w, p, cfg):
    """a [T, d] -> (q [T, H, D], k [T, Hkv, D], v [T, Hkv, D]) of an
    attention layer's one projection with its bias."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    qkv = a @ w[p + "qkv"].astype(F32) + w[p + "qkv_b"].astype(F32)
    t = a.shape[0]
    return (qkv[:, :nh * hd].reshape(t, nh, hd),
            qkv[:, nh * hd:(nh + nkv) * hd].reshape(t, nkv, hd),
            qkv[:, (nh + nkv) * hd:].reshape(t, nkv, hd))


def diff_attention(q, k, v, w, p, i, cfg, window=None):
    """q [T, H, D] on k, v [T, Hkv, D] -> [T, d]: the pairs' two softmaxes
    apart, ``A1 V - lambda A2 V``, the norm over a pair's 2D lanes and the
    output projection with its bias."""
    t, nh, hd = q.shape
    pairs, kv_pairs = nh // 2, k.shape[1] // 2
    q = q.reshape(t, kv_pairs, pairs // kv_pairs, 2, hd)
    k = k.reshape(t, kv_pairs, 2, hd)
    v = v.reshape(t, kv_pairs, 2 * hd)
    rows = next(r for r in range(min(t, QUERY_ROWS), 0, -1) if t % r == 0)
    cols = jnp.arange(t)[None, :]

    def dot(x, y):
        return jnp.sum(w[p + x].astype(F32) * w[p + y].astype(F32))
    init = lambda_init(i)
    lam = jnp.exp(dot("lambda_q1", "lambda_k1")) \
        - jnp.exp(dot("lambda_q2", "lambda_k2")) + init

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * rows, rows, axis=0)
        at = (b * rows + jnp.arange(rows))[:, None]
        visible = cols <= at
        if window:
            visible = visible & (at - cols < window)
        s = jnp.einsum("qgrsd,cgsd->gsrqc", qb, k) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        o = jnp.einsum("gsrqc,cgl->sqgrl", prob, v)
        return o[0] - lam * o[1]                    # [rows, G, R, 2D]

    o = jax.lax.map(block, jnp.arange(t // rows)).reshape(t, pairs, 2 * hd)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["layer_norm_eps"]) \
        * w[p + "subln"].astype(F32) * (1.0 - init)
    return o.reshape(t, nh * hd) @ w[p + "o"].astype(F32) \
        + w[p + "o_b"].astype(F32)


def hidden(w, tokens, cfg):
    """tokens [T] -> h [T, d] float32 before the final norm."""
    eps = cfg["layer_norm_eps"]
    nh = cfg["num_attention_heads"]
    h = w["embed"][tokens].astype(F32)
    handed = {}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d." % i
        a = _layer_norm(h, w, p + "norm_in", eps)
        if kind == "mamba":
            o, m = _mamba(a, w, p + "mamba.")
            if i == cfg["memory_from"]:
                handed["m"] = m
        elif kind == "gmu":
            o = (jax.nn.silu(a @ w[p + "gmu.in"].astype(F32)) * handed["m"]) \
                @ w[p + "gmu.out"].astype(F32)
        elif kind == "cross_attention":
            q = a @ w[p + "attn.q"].astype(F32) + w[p + "attn.q_b"].astype(F32)
            o = diff_attention(q.reshape(q.shape[0], nh, -1), *handed["kv"],
                               w, p + "attn.", i, cfg)
        else:
            q, k, v = keys_and_values(a, w, p + "attn.", cfg)
            if i == cfg["kv_from"]:
                handed["kv"] = (k, v)
            o = diff_attention(
                q, k, v, w, p + "attn.", i, cfg,
                cfg["sliding_window"] if kind == "sliding_attention"
                else None)
        h = h + o
        u = _layer_norm(h, w, p + "norm_pre_mlp", eps)
        h = h + _swiglu(u, w[p + "mlp.gate"], w[p + "mlp.up"],
                        w[p + "mlp.down"])
    return h


def _head(x, w, cfg):
    """x [n, d] -> logits [n, V] float32 over the tied embedding, a slice
    of the vocabulary at a time."""
    x = _layer_norm(x, w, "norm_final", cfg["layer_norm_eps"])
    embed = w["embed"]
    v = embed.shape[0]
    size = next(r for r in range(min(v, VOCAB_ROWS), 0, -1) if v % r == 0)
    parts = jax.lax.map(lambda e: x @ e.astype(F32).T,
                        embed.reshape(v // size, size, -1))
    return jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], v)


def logits_at(w, tokens, positions, cfg):
    """Logits [len(positions), V] of one sequence at the given positions."""
    with jax.default_matmul_precision("highest"):
        return _head(hidden(w, tokens, cfg)[positions], w, cfg)


def loss(w, tokens, labels, cfg):
    """Mean next-token cross-entropy of one sequence (labels [T])."""
    with jax.default_matmul_precision("highest"):
        logits = _head(hidden(w, tokens, cfg), w, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
