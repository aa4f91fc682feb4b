"""Plain reference of the ``afmoe`` decoder (Arcee Trinity; the layer
equations as ISSUE 27 wrote them down from the published ``config.json`` and
modeling code): RMSNorm before and after each half of a block, grouped-query
attention with a per-head RMSNorm of queries and keys, rotary positions and
a causal window in the ``sliding_attention`` layers and no positions at all
in the ``full_attention`` ones, a sigmoid gate on the attention output, a
SwiGLU feed-forward that is dense in the first ``num_dense_layers`` layers
and, in the rest, a shared expert plus the ``num_experts_per_tok`` best of
``num_experts`` routed experts (sigmoid scores, normalised, times
``route_scale``). Like every reference it takes the configuration dict
(``gather_weights(find_var, cfg)``, ``logits_at(w, tokens, positions, cfg)``,
``loss(w, tokens, labels, cfg)``). Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no sorting or grouping of tokens, one sequence at a time; routing
is a dense mask of top-k weights over all experts.

It is fed the program's own weights by name and keeps them as they are held
(bfloat16 matmul weights upcast exactly): the experts are upcast one at a
time, the head a slice of the vocabulary at a time and attention is taken a
KV head at a time, so that it fits beside a serving session on one chip.

Departures from the published description, which the program makes and this
file follows (each is in the configuration file): what the published keys do
not settle (ISSUE 27's dagger items: the output gate, the per-head norms,
no positions in full layers, the norm after each half, the selection bias)
is under ``assumed``; ``expert_bias`` is zeros; the experts held are
``[expert_offset, expert_offset + experts held)`` and what the others would
add is left out, in the program and here alike.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
SLIDING = "sliding_attention"


def weight_names(cfg):
    """The program's parameter names (``models/moe_lm.py``), in the
    reference's own terms."""
    names = {"embed": "moe_lm.embed.w", "norm_final": "moe_lm.norm_final.w",
             "head": "moe_lm.lm_head.w"}
    for i in range(cfg["num_hidden_layers"]):
        p, q = "l%d." % i, "moe_lm.l%d." % i
        for part in ("q", "k", "v", "gate", "o", "q_norm", "k_norm"):
            names[p + "attn." + part] = q + "attn.%s.w" % part
        for norm in ("norm_in", "norm_post_attn", "norm_pre_mlp",
                     "norm_post_mlp"):
            names[p + norm] = q + norm + ".w"
        if i < cfg["num_dense_layers"]:
            kinds = {"mlp": "mlp"}
        else:
            kinds = {"shared": "moe.shared", "experts": "moe.experts"}
            names[p + "router"] = q + "moe.router.w"
            names[p + "expert_bias"] = q + "moe.expert_bias"
        for mine, theirs in kinds.items():
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


def _attention(a, w, p, cfg, windowed):
    t = a.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = (a @ w[p + "q"].astype(F32)).reshape(t, nh, hd)
    k = (a @ w[p + "k"].astype(F32)).reshape(t, nkv, hd)
    v = (a @ w[p + "v"].astype(F32)).reshape(t, nkv, hd)
    gate = a @ w[p + "gate"].astype(F32)
    q = _rms_norm(q, w[p + "q_norm"], eps)
    k = _rms_norm(k, w[p + "k_norm"], eps)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    visible = j <= i
    if windowed:
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
        visible = visible & (i - j < cfg["sliding_window"])

    def one_kv_head(qkv):
        qg, kg, vg = qkv            # [G, T, D], [T, D], [T, D]
        s = jnp.einsum("gqd,kd->gqk", qg, kg) / jnp.sqrt(float(hd))
        s = jnp.where(visible, s, -jnp.inf)
        return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(s, axis=-1), vg)

    qg = q.transpose(1, 0, 2).reshape(nkv, nh // nkv, t, hd)
    o = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))
    o = o.reshape(nh, t, hd).transpose(1, 0, 2).reshape(t, nh * hd)
    return (o * jax.nn.sigmoid(gate)) @ w[p + "o"].astype(F32)


def _experts(m, w, p, cfg, forced=None, scores=None):
    """The routed experts' part of the feed-forward: a dense [T, E] mask
    of the top-k weights, then one held expert after the other over every
    token. ``forced`` (rows [T] bool, ids [T, k]) replaces the selection of
    its rows (see :func:`routed`); ``scores``, a list, takes the scores."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ w[p + "router"].astype(F32))
    _, sel = jax.lax.top_k(s + w[p + "expert_bias"].astype(F32), k)
    if forced is not None:
        sel = jnp.where(forced[0][:, None], forced[1], sel)
    if scores is not None:
        scores.append(s)
    top = jnp.take_along_axis(s, sel, axis=1)
    if cfg["route_norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], sel].set(
        top * cfg["route_scale"])
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


def hidden(w, tokens, cfg, forced=None, scores=None):
    """tokens [T] -> final hidden states [T, d], after the last RMSNorm.
    ``forced``: per expert layer a (rows, ids) pair or None; ``scores``: a
    list that takes each expert layer's [T, E] scores (:func:`routed`)."""
    eps = cfg["rms_norm_eps"]
    h = w["embed"][tokens].astype(F32)
    if cfg["mup_enabled"]:
        h = h * jnp.sqrt(float(cfg["hidden_size"]))
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        a = _rms_norm(h, w[p + "norm_in"], eps)
        o = _attention(a, w, p + "attn.", cfg,
                       cfg["layer_types"][i] == SLIDING)
        h = h + _rms_norm(o, w[p + "norm_post_attn"], eps)
        m = _rms_norm(h, w[p + "norm_pre_mlp"], eps)
        if i < cfg["num_dense_layers"]:
            f = _swiglu(m, w[p + "mlp.gate"], w[p + "mlp.up"],
                        w[p + "mlp.down"])
        else:
            f = _swiglu(m, w[p + "shared.gate"], w[p + "shared.up"],
                        w[p + "shared.down"]) + _experts(
                m, w, p, cfg,
                forced and forced[i - cfg["num_dense_layers"]], scores)
        h = h + _rms_norm(f, w[p + "norm_post_mlp"], eps)
    return _rms_norm(h, w["norm_final"], eps)


def _head(x, head):
    """x [n, d] @ head [d, V], a slice of the vocabulary at a time."""
    v = head.shape[1]
    width = next(c for c in range(min(v, 16384), 0, -1) if v % c == 0)

    def part(i):
        cols = jax.lax.dynamic_slice_in_dim(head, i * width, width, axis=1)
        return x @ cols.astype(F32)

    out = jax.lax.map(part, jnp.arange(v // width))     # [V/width, n, width]
    return out.transpose(1, 0, 2).reshape(x.shape[0], v)


def logits_at(w, tokens, positions, cfg):
    """Logits [len(positions), V] of one sequence at the given positions."""
    with jax.default_matmul_precision("highest"):
        return _head(hidden(w, tokens, cfg)[positions], w["head"])


def routed(w, tokens, positions, cfg, forced=None):
    """(logits at ``positions``, scores [expert layers, T, E]) of one
    sequence, with the selection of some rows **given**: ``forced`` holds
    per expert layer ``(rows [T] bool, ids [T, k])``. Top-k of scores is a
    discontinuous choice, so a program in another precision picks another
    expert wherever two scores lie closer than its noise, and its logits
    then differ from :func:`logits_at`'s by far more than rounding. Handed
    the program's choices, the rest is compared exactly, and each choice
    is judged by the margin the scores returned here give it
    (``benchmarks/sweeps/routing_agreement.py`` does both)."""
    scores = []
    with jax.default_matmul_precision("highest"):
        h = hidden(w, tokens, cfg, forced, scores)
        return _head(h[positions], w["head"]), jnp.stack(scores)


def loss(w, tokens, labels, cfg):
    """Mean next-token cross-entropy of one sequence (labels [T])."""
    with jax.default_matmul_precision("highest"):
        logits = _head(hidden(w, tokens, cfg), w["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
