"""Plain reference of the ``kimi_k2`` decoder (Moonshot Kimi-K2; the layer
equations as ISSUE 31 wrote them down from the published ``config.json`` and
the ``DeepseekV3``-style modeling code the family follows): RMSNorm before
each half of a block, latent attention (MLA) in its **expanded** form only,
a SwiGLU feed-forward that is dense in the first ``first_k_dense_replace``
layers and, in the rest, a shared expert plus the ``num_experts_per_tok``
best of ``n_routed_experts_published`` routed experts (sigmoid scores,
normalised, times ``routed_scaling_factor``), an untied head.

    a   = RMSNorm(h; w_in)
    c_q = RMSNorm(a W_dq; w_q);  q = c_q W_uq -> [T, H, nope + rope]
    (c, k_r) = a W_dkv;  c = RMSNorm(c; w_kv)
    q_rope, k_r = rope(q_rope, pos), rope(k_r, pos)      one k_r for all heads
    (k_nope, v) = c W_ukv -> [T, H, nope], [T, H, v];  k_h = (k_nope_h, k_r)
    o_h,i = sum_{j<=i} softmax_j(q_h,i . k_h,j * s) v_h,j
    h = h + concat_h(o_h) W_o;  m = RMSNorm(h; w_post);  h = h + FFN(m)

with ``s = (nope + rope)^-1/2 * (0.1 mscale_all_dim ln(factor) + 1)^2`` and
YaRN rotary frequencies. No absorption, no cache, no sorting: routing is a
dense top-k mask over all published experts and the held experts are a slice
of it. Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time.

It is fed the program's own weights by name and keeps them as they are held
(bfloat16 matmul weights upcast exactly), one matrix, one expert, one block
of query rows and one slice of the vocabulary at a time, so that it fits
beside a serving session on one chip.

Departures from the published description, which the program makes and this
file follows (each is in the configuration file): no vision tower; the
experts held are ``[expert_offset, expert_offset + n_routed_experts)`` of
``n_routed_experts_published`` and what the others would add is left out;
the vocabulary is its first ``vocab_size`` rows; the selection bias
``e_score_correction_bias`` is zeros; rotary lanes pair ``i`` with
``i + rope/2`` (the published code interleaves: a fixed permutation of the
rope columns of ``W_uq`` and ``W_dkv``); what the keys do not settle (the
dagger items) is under ``assumed``.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
# query rows attended at once: [H, rows, T] float32 scores
QUERY_ROWS = 256


def weight_names(cfg):
    """The program's parameter names (``models/moe_lm.py``), in the
    reference's own terms."""
    names = {"embed": "moe_lm.embed.w", "norm_final": "moe_lm.norm_final.w",
             "head": "moe_lm.lm_head.w"}
    for i in range(cfg["num_hidden_layers"]):
        p, q = "l%d." % i, "moe_lm.l%d." % i
        for part in ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
                     "o"):
            names[p + "attn." + part] = q + "attn.%s.w" % part
        for norm in ("norm_in", "norm_pre_mlp"):
            names[p + norm] = q + norm + ".w"
        if i < cfg["first_k_dense_replace"]:
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


def yarn_frequencies(cfg):
    """The rope lanes' ``rope/2`` frequencies under the configuration's
    ``rope_scaling`` (YaRN), as ISSUE 31 states them."""
    rot, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = jnp.arange(rot // 2, dtype=F32)
    f = theta ** (-2.0 * i / rot)
    y = cfg["rope_scaling"]
    if not y:
        return f

    def corr(n):
        return rot * math.log(y["original_max_position_embeddings"]
                              / (2 * math.pi * n)) / (2 * math.log(theta))
    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), rot - 1)
    r = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return f * (1.0 - r) + f / y["factor"] * r


def softmax_scale(cfg):
    y = cfg["rope_scaling"]
    m = 1.0
    if y and y.get("mscale_all_dim"):
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, freq):
    """x [T, .., rope] at positions 0..T-1: lane i turns with lane
    i + rope/2."""
    t, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=F32).reshape((t,) + (1,) * (x.ndim - 1)) * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(a, w, p, cfg):
    t = a.shape[0]
    nh, nope, rope, dv = (cfg["num_attention_heads"],
                          cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    freq = yarn_frequencies(cfg)
    c_q = _rms_norm(a @ w[p + "q_a"].astype(F32), w[p + "q_a_norm"], eps)
    q = (c_q @ w[p + "q_b"].astype(F32)).reshape(t, nh, nope + rope)
    ckr = a @ w[p + "kv_a"].astype(F32)
    c = _rms_norm(ckr[:, :rank], w[p + "kv_a_norm"], eps)
    k_r = _rope(ckr[:, rank:], freq)                         # [T, rope]
    kv = (c @ w[p + "kv_b"].astype(F32)).reshape(t, nh, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (t, nh, rope))], -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freq)], -1)
    v = kv[..., nope:]
    rows = next(r for r in range(min(t, QUERY_ROWS), 0, -1) if t % r == 0)
    cols = jnp.arange(t)[None, :]

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * rows, rows, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * softmax_scale(cfg)
        visible = cols <= (b * rows + jnp.arange(rows))[:, None]
        s = jnp.where(visible, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(t // rows)).reshape(t, nh * dv)
    return o @ w[p + "o"].astype(F32)


def _experts(m, w, p, cfg):
    """The routed experts' part of the feed-forward: a dense [T, E] mask of
    the top-k weights over all published experts, then one held expert
    after the other over every token."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ w[p + "router"].astype(F32))
    _, sel = jax.lax.top_k(s + w[p + "expert_bias"].astype(F32), k)
    top = jnp.take_along_axis(s, sel, axis=1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], sel].set(
        top * cfg["routed_scaling_factor"])
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
    eps = cfg["rms_norm_eps"]
    h = w["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        a = _rms_norm(h, w[p + "norm_in"], eps)
        h = h + _attention(a, w, p + "attn.", cfg)
        m = _rms_norm(h, w[p + "norm_pre_mlp"], eps)
        if i < cfg["first_k_dense_replace"]:
            f = _swiglu(m, w[p + "mlp.gate"], w[p + "mlp.up"],
                        w[p + "mlp.down"])
        else:
            f = _swiglu(m, w[p + "shared.gate"], w[p + "shared.up"],
                        w[p + "shared.down"]) + _experts(m, w, p, cfg)
        h = h + f
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


def loss(w, tokens, labels, cfg):
    """Mean next-token cross-entropy of one sequence (labels [T])."""
    with jax.default_matmul_precision("highest"):
        logits = _head(hidden(w, tokens, cfg), w["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
