"""Plain reference of the ``nemotron_h`` decoder (NVIDIA Nemotron 3 Nano;
the layer equations as ISSUE 46 wrote them down from the published
``config.json`` and the family's modelling code as remembered): **a layer is
one sublayer**, a Mamba-2 mixer (``M``), an expert layer (``E``) or
grouped-query attention without positions (``*``) by its letter in
``hybrid_override_pattern``, each behind one RMSNorm and added to the
residual stream; no multiplier anywhere; a final RMSNorm; an untied head.

    h0 = embed[token]
    h  = h + f_i(RMSNorm(h; w_i)),   f_i by the pattern's letter
    logits = RMSNorm(h; w_final) W_head

    M:  [z, xBC, dt] = a W_in                xBC = [x (HP), B (G N), C (G N)]
        xBC_t = silu(sum_{k<K} w[k] * xBC_{t-K+1+k} + b)   zeros before row 0
        dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log);  g(h) = h // (H / G)
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h]
                 + dt_t[h] x_t[h] (outer) B_t[g(h)]
        y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
        f = (RMSNorm_grouped(y * silu(z)) * w_norm) W_out   the mean of squares
                                           within each group's HP / G lanes
    E:  s = sigmoid(a W_r);  the top k of s + b;  w = s[chosen] / sum * scaling
        f = sum_chosen w_e down_e(relu(up_e(a))^2) + down_s(relu(up_s(a))^2)
        an expert is TWO matrices: no gate
    *:  o_h,i = sum_{j<=i} softmax_j(q_h,i . k_g(h),j / sqrt(D)) v_g(h),j
        f = concat_h(o_h) W_o                       no positions, no bias

The scan is the **sequential recurrence**, one row after the other by
``lax.scan`` (the program computes it in chunks: the two check each other),
each row's decay taken by hand (:func:`step_decay` says why);
the convolution is a plain sum over its shifted rows. No cache, no chunks,
no sorting, no kernels: routing is a dense top-k mask over all published
experts and the held experts are a slice of it. Straight ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``, one sequence at
a time.

It is fed the program's own weights by name and keeps them as they are held
(bfloat16 matmul weights upcast exactly; an expert's ``up`` matrix is held
``[f, d]`` as its ``down`` matrix is and is transposed here), one matrix,
one expert, one block of query rows and one slice of the vocabulary at a
time, so that it fits beside a serving session on one chip.

Departures from the published description, which the program makes and this
file follows (each is in the configuration file): the experts held are
``[expert_offset, expert_offset + n_routed_experts)`` of
``n_routed_experts_published`` and what the others would add is left out;
the vocabulary is its first ``vocab_size`` rows, embedding and head alike;
the correction bias is zeros; what the keys do not settle is under
``assumed``.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
# query rows attended at once: [H, rows, T] float32 scores
QUERY_ROWS = 256

_MIXER = ("in", "in.w"), ("conv_w", "conv.w"), ("conv_b", "conv.b"), \
    ("dt_bias", "dt_bias"), ("a_log", "a_log"), ("d", "d"), \
    ("norm", "norm.w"), ("out", "out.w")


def weight_names(cfg):
    """The program's parameter names (``models/moe_lm.py``), in the
    reference's own terms."""
    names = {"embed": "moe_lm.embed.w", "norm_final": "moe_lm.norm_final.w",
             "head": "moe_lm.lm_head.w"}
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        p, q = "l%d." % i, "moe_lm.l%d." % i
        names[p + "norm"] = q + "norm.w"
        if letter == "M":
            for mine, theirs in _MIXER:
                names[p + "mamba." + mine] = q + "mamba." + theirs
        elif letter == "*":
            for part in "qkvo":
                names[p + "attn." + part] = q + "attn.%s.w" % part
        else:
            names[p + "router"] = q + "moe.router.w"
            names[p + "bias"] = q + "moe.expert_bias"
            for mine in ("shared", "experts"):
                for part in ("up", "down"):
                    names["%s%s.%s" % (p, mine, part)] = \
                        "%smoe.%s.%s.w" % (q, mine, part)
    return names


def gather_weights(find_var, cfg):
    """{reference name: array} from the program's scope (``find_var`` is
    ``scope.find_var``). No copy: the arrays are the program's own."""
    return {k: find_var(v) for k, v in weight_names(cfg).items()}


def _rms_norm(x, w, eps, groups=1):
    """RMSNorm over the last axis, the mean of squares taken within each of
    ``groups`` equal runs of lanes; one weight a lane."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * w.astype(F32)


def _relu2_mlp(x, up, down):
    """``relu(x up)^2 down``: up [d, f], down [f, d]."""
    return jnp.square(jax.nn.relu(x @ up.astype(F32))) @ down.astype(F32)


def step_decay(z):
    """``exp(z)`` of a step's ``dt A <= 0`` to two float32 roundings, by
    hand: ``z = k ln 2 + r`` with ``ln 2`` in two parts, ``exp(r)`` on
    ``|r| <= ln 2 / 2`` as its series to the 7th power (the rest is under
    6e-9), the power of two set in the exponent's bits.

    Why not ``jnp.exp``: the recurrence multiplies one decay a row into
    the state, so a head that remembers a thousand rows carries a thousand
    decays' errors, and the TPU's ``exp`` reads 1.0e-6 LOW in the mean
    (5e-6 at most; the CPU's 2e-8): on the chip this recurrence with
    ``jnp.exp`` lay 1.7e-5 of its outputs by root mean square from a
    float64 recurrence, twelve times as far as the program's chunked scan
    (1.4e-6), which takes one ``exp`` of a sum (my chip run, PR 46:
    PERF.md section 6). A reference has to be the nearer of the two to the
    truth: a router behind the mixer chose another expert than the
    program's at near ties of its own making."""
    k = jnp.maximum(jnp.round(z * 1.4426950408889634), -126.0)
    r = (z - k * 0.693359375) - k * -2.12194440e-4
    series = 1.0 / 5040
    for term in (1.0 / 720, 1.0 / 120, 1.0 / 24, 1.0 / 6, 0.5, 1.0, 1.0):
        series = series * r + term
    two_k = jax.lax.bitcast_convert_type(
        (k.astype(jnp.int32) + 127) << 23, F32)
    return series * two_k


def mamba_scan(x, dt, a, b, c, d):
    """The recurrence, one row after the other from a zero state: x
    [T, H, P], dt [T, H] (after softplus), a [H] (negative), b, c
    [T, G, N], d [H] -> (y [T, H, P], the state after the last row
    [H, P, N]). Head ``h`` reads group ``h // (H / G)``."""
    h, p = x.shape[1:]
    per_group = h // b.shape[1]

    def row(s, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, per_group, axis=0)            # [H, N]
        c_h = jnp.repeat(c_t, per_group, axis=0)
        s = step_decay(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_h) + d[:, None] * x_t

    last, y = jax.lax.scan(row, jnp.zeros((h, p, b.shape[2]), F32),
                           (x, dt, b, c))
    return y, last


def mamba_inputs(a, w, p, cfg):
    """a [T, d] -> (z [T, HP], the convolution's inputs xBC [T, HP + 2GN],
    the convolution's output after silu, dt [T, H] after softplus)."""
    h = cfg["mamba_num_heads"]
    hp = h * cfg["mamba_head_dim"]
    lanes = hp + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    zxd = a @ w[p + "in"].astype(F32)
    z, raw, dt = zxd[:, :hp], zxd[:, hp:hp + lanes], zxd[:, hp + lanes:]
    assert dt.shape[1] == h
    t, k = a.shape[0], cfg["conv_kernel"]
    conv_w = w[p + "conv_w"].astype(F32)                    # [K, lanes]
    padded = jnp.pad(raw, ((k - 1, 0), (0, 0)))
    act = sum(conv_w[j] * padded[j:j + t] for j in range(k))
    act = jax.nn.silu(act + w[p + "conv_b"].astype(F32))
    return z, raw, act, jax.nn.softplus(dt + w[p + "dt_bias"].astype(F32))


def _mamba(a, w, p, cfg):
    h, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    t, hp = a.shape[0], h * hd
    z, _, act, dt = mamba_inputs(a, w, p, cfg)
    y, _ = mamba_scan(act[:, :hp].reshape(t, h, hd), dt,
                      -jnp.exp(w[p + "a_log"].astype(F32)),
                      act[:, hp:hp + g * n].reshape(t, g, n),
                      act[:, hp + g * n:].reshape(t, g, n),
                      w[p + "d"].astype(F32))
    gated = _rms_norm(y.reshape(t, hp) * jax.nn.silu(z), w[p + "norm"],
                      cfg["layer_norm_epsilon"], groups=g)
    return gated @ w[p + "out"].astype(F32)


def _attention(a, w, p, cfg):
    t = a.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = (a @ w[p + "q"].astype(F32)).reshape(t, nkv, nh // nkv, hd)
    k = (a @ w[p + "k"].astype(F32)).reshape(t, nkv, hd)
    v = (a @ w[p + "v"].astype(F32)).reshape(t, nkv, hd)
    rows = next(r for r in range(min(t, QUERY_ROWS), 0, -1) if t % r == 0)
    cols = jnp.arange(t)[None, :]

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * rows, rows, axis=0)
        s = jnp.einsum("qkgd,ckd->kgqc", qb, k) * hd ** -0.5
        visible = cols <= (b * rows + jnp.arange(rows))[:, None]
        s = jnp.where(visible, s, -jnp.inf)
        return jnp.einsum("kgqc,ckd->qkgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(t // rows)).reshape(t, nh * hd)
    return o @ w[p + "o"].astype(F32)


def route(m, w, p, cfg, forced=None):
    """m [T, d] -> the dense [T, E] weights of the router over all
    published experts: the chosen experts' normalised, scaled sigmoid
    scores, zeros elsewhere. ``forced`` [T, k] int32: a row that holds
    expert ids (none negative) is chosen as it says, whatever the scores'
    order; the weights are still this router's own scores of those
    experts."""
    s = jax.nn.sigmoid(m @ w[p + "router"].astype(F32))
    _, sel = jax.lax.top_k(s + w[p + "bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    if forced is not None:
        sel = jnp.where(jnp.all(forced >= 0, axis=-1, keepdims=True),
                        forced, sel)
    chosen = jnp.take_along_axis(s, sel, axis=1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], sel].set(
        chosen * cfg["routed_scaling_factor"])


def routed_experts(m, w, p, cfg, forced=None):
    """The held routed experts' part of an expert layer, one held expert
    after the other over every token."""
    held = w[p + "experts.up"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(
        route(m, w, p, cfg, forced), cfg.get("expert_offset", 0), held,
        axis=1)

    def add_expert(f, e):
        up, down, weight = e                # up is held [f, d], as down is
        return f + weight[:, None] * _relu2_mlp(m, up.T, down), None

    f, _ = jax.lax.scan(add_expert, jnp.zeros_like(m),
                        (w[p + "experts.up"], w[p + "experts.down"], mine.T))
    return f


def _experts(m, w, p, cfg, forced=None):
    return routed_experts(m, w, p, cfg, forced) + _relu2_mlp(
        m, w[p + "shared.up"], w[p + "shared.down"])


_SUBLAYER = {"M": ("mamba.", _mamba), "*": ("attn.", _attention),
             "E": ("", _experts)}


def hidden(w, tokens, cfg, forced=None, seen=None):
    """tokens [T] -> final hidden states [T, d], after the last RMSNorm.
    ``forced`` {layer: [T, k] int32}: the experts an expert layer's router
    is told to choose (:func:`route`), for a check that hands the
    reference the program's own choices: a top-k at a near tie is a
    choice, not a fault. ``seen``: a dict that takes each expert layer's
    input, ``{layer: [T, d]}``, what its router scores."""
    eps = cfg["layer_norm_epsilon"]
    h = w["embed"][tokens].astype(F32)
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        p = "l%d." % i
        part, f = _SUBLAYER[letter]
        a = _rms_norm(h, w[p + "norm"], eps)
        if letter == "E":
            if seen is not None:
                seen[i] = a
            h = h + f(a, w, p + part, cfg, (forced or {}).get(i))
        else:
            h = h + f(a, w, p + part, cfg)
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


def logits_at(w, tokens, positions, cfg, forced=None):
    """Logits [len(positions), V] of one sequence at the given positions
    (``forced``: :func:`hidden`)."""
    with jax.default_matmul_precision("highest"):
        return _head(hidden(w, tokens, cfg, forced)[positions], w["head"])


def logits_and_router_inputs(w, tokens, positions, cfg, forced=None):
    """:func:`logits_at` and, beside it, what each expert layer's router
    scored, every row of the sequence: ``{layer: [T, d]}``."""
    with jax.default_matmul_precision("highest"):
        seen = {}
        h = hidden(w, tokens, cfg, forced, seen)
        return _head(h[positions], w["head"]), seen


def loss(w, tokens, labels, cfg):
    """Mean next-token cross-entropy of one sequence (labels [T])."""
    with jax.default_matmul_precision("highest"):
        logits = _head(hidden(w, tokens, cfg), w["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
