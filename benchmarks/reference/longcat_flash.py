"""Plain reference of the ``longcat_flash`` decoder (Meituan LongCat-Flash;
the layer equations as ISSUE 40 wrote them down from the published
``config.json`` and the ``LongcatFlash`` modeling code as recalled): a layer
is **two halves**, each a latent attention (MLA, **expanded** form only) and
a dense SwiGLU with their own RMSNorms, and ONE expert layer laid across
them, the shortcut-connected MoE: it reads the first half's post-attention
norm and its result joins the residual stream only after the second half's
feed-forward. With ``h`` the layer's input:

    for i in (0, 1):
        a = h + MLA_i(RMSNorm(h; w_in_i))
        u = RMSNorm(a; w_post_i)
        if i == 0:  s = MoE(u)
        h = a + (silu(u Wg_i) * (u Wu_i)) Wd_i
        if i == 1:  h = h + s

    MLA(x):  c_q = RMSNorm(x W_dq; w_q) * sqrt(d / q_rank);  q = c_q W_uq
             (c, k_r) = x W_dkv;  c = RMSNorm(c; w_kv) * sqrt(d / kv_rank)
             q_rope, k_r = rope(q_rope, pos), rope(k_r, pos)
             (k_nope, v) = c W_ukv;  k_h = (k_nope_h, k_r)
             o_h,i = sum_{j<=i} softmax_j(q_h,i . k_h,j (nope + rope)^-1/2) v_h,j
             -> concat_h(o_h) W_o

    MoE(u):  p = softmax(u W_r) over E + Z outputs;  sel = top_k(p + b)
             w_e = routed_scaling_factor p_e,  e in sel   (no renormalising)
             s = sum_{e in sel, e < E} w_e SwiGLU_e(u)
               + (sum_{e in sel, e >= E} w_e) u           identity experts

No shared expert, no absorption, no cache, no sorting: routing is a dense
top-k mask over all ``n_routed_experts_published + zero_expert_num`` router
outputs, the held experts are a slice of the first part of it and the
identity experts the whole of the second. Straight ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``, one sequence at a time.

It is fed the program's own weights by name and keeps them as they are held
(bfloat16 matmul weights upcast exactly), one matrix, one expert, one block
of query rows and one slice of the vocabulary at a time, so that it fits
beside a serving session on one chip.

Departures, which the program makes and this file follows (each is in the
configuration file): the experts held are ``[expert_offset, expert_offset +
n_routed_experts)`` of ``n_routed_experts_published`` and what the others
would add is left out (the identity experts' part is every chip's own and
stays whole); the vocabulary is its first ``vocab_size`` rows; the
selection bias is zeros; rotary lanes pair ``i`` with ``i + rope/2``.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
# query rows attended at once: [H, rows, T] float32 scores
QUERY_ROWS = 256
HALVES = 2


def weight_names(cfg):
    """The program's parameter names (``models/moe_lm.py`` with a block of
    halves), in the reference's own terms."""
    names = {"embed": "moe_lm.embed.w", "norm_final": "moe_lm.norm_final.w",
             "head": "moe_lm.lm_head.w"}
    for i in range(cfg["num_layers"]):
        for j in range(HALVES):
            p, q = "l%d.h%d." % (i, j), "moe_lm.l%d.h%d." % (i, j)
            for part in ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                         "kv_b", "o"):
                names[p + "attn." + part] = q + "attn.%s.w" % part
            for norm in ("norm_in", "norm_pre_mlp"):
                names[p + norm] = q + norm + ".w"
            for part in ("gate", "up", "down"):
                names["%smlp.%s" % (p, part)] = "%smlp.%s.w" % (q, part)
        p, q = "l%d." % i, "moe_lm.l%d.moe." % i
        names[p + "router"] = q + "router.w"
        names[p + "expert_bias"] = q + "expert_bias"
        for part in ("gate", "up", "down"):
            names["%sexperts.%s" % (p, part)] = "%sexperts.%s.w" % (q, part)
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


def _rope(x, freq):
    """x [T, .., rope] at positions 0..T-1: lane i turns with lane
    i + rope/2."""
    t, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=F32).reshape((t,) + (1,) * (x.ndim - 1)) * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def latent_scales(cfg):
    """(q, kv): what the two latents are multiplied by after their norms,
    ``sqrt(hidden / rank)`` where ``mla_scale_*_lora`` says so."""
    d = cfg["hidden_size"]
    return (math.sqrt(d / cfg["q_lora_rank"])
            if cfg["mla_scale_q_lora"] else 1.0,
            math.sqrt(d / cfg["kv_lora_rank"])
            if cfg["mla_scale_kv_lora"] else 1.0)


def _attention(a, w, p, cfg):
    t = a.shape[0]
    nh, nope, rope, dv = (cfg["num_attention_heads"],
                          cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    q_scale, kv_scale = latent_scales(cfg)
    freq = float(cfg["rope_theta"]) ** (
        -2.0 * jnp.arange(rope // 2, dtype=F32) / rope)
    c_q = _rms_norm(a @ w[p + "q_a"].astype(F32), w[p + "q_a_norm"], eps) \
        * q_scale
    q = (c_q @ w[p + "q_b"].astype(F32)).reshape(t, nh, nope + rope)
    ckr = a @ w[p + "kv_a"].astype(F32)
    c = _rms_norm(ckr[:, :rank], w[p + "kv_a_norm"], eps) * kv_scale
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
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (nope + rope) ** -0.5
        visible = cols <= (b * rows + jnp.arange(rows))[:, None]
        s = jnp.where(visible, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(t // rows)).reshape(t, nh * dv)
    return o @ w[p + "o"].astype(F32)


def route(u, w, p, cfg):
    """-> (sel [T, k] router outputs, weights [T, k]): the best k of the
    softmax over every output plus the selection bias, weighed by the
    softmax itself times ``routed_scaling_factor``."""
    prob = jax.nn.softmax(u @ w[p + "router"].astype(F32), axis=-1)
    _, sel = jax.lax.top_k(prob + w[p + "expert_bias"].astype(F32),
                           cfg["moe_topk"])
    return sel, jnp.take_along_axis(prob, sel, axis=1) \
        * cfg["routed_scaling_factor"]


def shortcut(u, w, p, cfg):
    """The expert layer over u [T, d]: a dense [T, E] mask of the chosen
    weights over the published real experts, then one held expert after the
    other over every token; and the identity experts' part, the sum of the
    weights chosen past the real experts times u itself."""
    sel, top = route(u, w, p, cfg)
    real = cfg["n_routed_experts_published"]
    dense = jnp.zeros((u.shape[0], real + cfg["zero_expert_num"]), F32).at[
        jnp.arange(u.shape[0])[:, None], sel].set(top)
    held = w[p + "experts.gate"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(dense, cfg.get("expert_offset", 0),
                                        held, axis=1)

    def add_expert(f, e):
        gate, up, down, weight = e
        return f + weight[:, None] * _swiglu(u, gate, up, down), None

    s, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (w[p + "experts.gate"], w[p + "experts.up"], w[p + "experts.down"],
         mine.T))
    identity = jnp.sum(jnp.where(sel >= real, top, 0.0), axis=1,
                       keepdims=True)
    return s + identity * u


def hidden(w, tokens, cfg):
    """tokens [T] -> final hidden states [T, d], after the last RMSNorm."""
    eps = cfg["rms_norm_eps"]
    h = w["embed"][tokens].astype(F32)
    for i in range(cfg["num_layers"]):
        for j in range(HALVES):
            p = "l%d.h%d." % (i, j)
            a = h + _attention(_rms_norm(h, w[p + "norm_in"], eps), w,
                               p + "attn.", cfg)
            u = _rms_norm(a, w[p + "norm_pre_mlp"], eps)
            if j == 0:
                s = shortcut(u, w, "l%d." % i, cfg)
            h = a + _swiglu(u, w[p + "mlp.gate"], w[p + "mlp.up"],
                            w[p + "mlp.down"])
        h = h + s
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
