"""The Mamba-2 mixer (a state-space layer; Dao & Gu 2024, "Transformers are
SSMs"), twice over as ``mla_ops`` has latent attention twice over. With
``H`` heads of ``P`` lanes, a state of ``N`` numbers a lane, ``G`` groups of
``B`` and ``C`` (head ``h`` reads group ``h // (H / G)``; one group: every
head the same), ``d_inner = H P`` and a depthwise causal convolution of
width ``K`` over the ``d_inner + 2GN`` lanes of ``xBC``:

    [z, xBC, dt] = u W_in                     (no bias)
    xBC_t = silu(sum_k w[k] * xBC_{t-K+1+k} + b)      zeros before row 0
    [x, B, C] = split(xBC_t);  dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
    out = (rmsnorm(y * silu(z)) * w_norm) W_out     the mean of squares
                                  within each group's d_inner / G lanes

* ``mamba2_mixer`` — whole rows of sequences from a zero state, in the
  **chunked** form (SSD): inside a chunk of ``chunk`` rows the recurrence
  is the masked product ``(C B^T . L)(dt x)`` with ``L[i, j] =
  exp(sum_{j<m<=i} dt_m A)``, across chunks it is the same recurrence on
  the chunks' end states. With ``Len`` it is a prompt's prefill inside a
  padded bucket: rows at or past ``Len`` have ``dt = 0``, so they move
  nothing, and what is left in the state row ``Table[0]`` is the state
  after row ``Len - 1``, the last ``K`` inputs of the convolution before
  ``Len`` and ``Len`` itself, the tokens the row has absorbed.
* ``mamba2_mixer_decode`` — one row a slot against the state of every
  slot: the recurrence's one step over the whole pool, in place.

**The state row of a slot is the row of its own index**, so a step updates
the pool elementwise where it lies (read once, written once) and gathers
nothing. ``Table[s]`` names the row batch row ``s`` may write: ``s`` itself
where the slot advances, anything else (the session feeds the pool's size,
a dead entry) where it does not, and then nothing of the row moves. Beside
each row the pool keeps ``At``, the tokens it has absorbed: a step at
position ``p`` advances the row only if ``At == p``; a row that already
holds ``p + 1`` tokens (the step is run a second time on the same feeds)
gives its output from the row as stored. A position is absorbed once
however often it is stepped, as a row of keys and values overwritten in
place is written once.

Products with ``W_in`` and ``W_out`` are exact (``moe_ops.exact_dot``) and
everything between them is float32 at the highest precision; the state is
float32. Plain ``jax.numpy``: no kernel of this repo's own.

**The Mamba-1 mixer** (Gu & Dao 2023, the S6 selective scan) is the same
two ops over again, ``mamba1_mixer`` and ``mamba1_mixer_decode``, and not
Mamba-2 at other numbers. With ``D`` inner channels, ``N`` numbers of state
a channel and a step size through a bottleneck of ``R``:

    [x | z] = u W_in                      (a ``linear`` before the op)
    x_t = silu(sum_k w[k] * x_{t-K+1+k} + b)          over x alone
    [r | B | C] = x_t [W_r | W_bc];  dt_t = softplus(r W_dt + dt_bias)
    S_t = exp(dt_t (outer) A) . S_{t-1} + (dt_t x_t) (outer) B_t     A = -exp(A_log)
    y_t = S_t C_t + D x_t;   out = (y_t silu(z_t)) W_out   (a ``linear`` after)

The decay is a **matrix** ``A [N, D]``, so ``exp(dt_t A)`` is ``[N, D]`` a
token and no scalar a head factors out of a chunk: the chunked form does not
apply. A prompt's scan is the recurrence itself, a ``lax.scan`` over the
rows whose carry is the one state: nothing of ``[T, N, D]`` is ever held.
The state lies ``[N, D]``, channels along the lanes (the published
``[D, N]`` would fill 16 of a tile's 128 lanes). B and C are one for all
channels, there is no norm, and the ops hand back ``y`` itself beside the
gated ``y silu(z)``: a later layer's gated memory unit reads it. The two
small products inside the op (``W_r | W_bc`` and ``W_dt``) are exact on the
float32 ``x``; the large ones are ``linear`` ops and take ``amp``'s one
pass.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .moe_ops import exact_dot

_HIGHEST = jax.lax.Precision.HIGHEST


def _groups_of(b, h):
    """(G, H / G) of B and C ``[.., G, N]`` under ``h`` heads."""
    g = b.shape[-2]
    if h % g:
        raise ValueError("%d heads in %d groups of B and C" % (h, g))
    return g, h // g


def ssd_chunked(x, dt, a, b, c, chunk):
    """The selective scan of one sequence from a zero state, chunked:
    x [T, H, P], dt [T, H] (after softplus; 0 in rows that move nothing),
    a [H] (negative), b, c [T, N]; ``chunk`` divides T. Returns
    (y [T, H, P] without the ``D x`` term, the state after row T-1
    [H, P, N]). Every exponent is of a number <= 0. With b, c [T, G, N]
    each group's H / G heads scan under their own B and C."""
    t, h, p = x.shape
    if b.ndim == 3:
        g, hg = _groups_of(b, h)
        y, last = jax.vmap(
            lambda *one: ssd_chunked(*one, chunk), in_axes=(1, 1, 0, 1, 1),
            out_axes=(1, 0))(x.reshape(t, g, hg, p), dt.reshape(t, g, hg),
                             a.reshape(g, hg), b, c)
        return y.reshape(t, h, p), last.reshape(h, p, -1)
    n, q, nc = b.shape[1], chunk, t // chunk
    xc, dtc = x.reshape(nc, q, h, p), dt.reshape(nc, q, h)
    bc, cc = b.reshape(nc, q, n), c.reshape(nc, q, n)
    cs = jnp.cumsum(dtc * a, axis=1)                    # [nc, q, H]
    # inside a chunk: row i takes dt_j x_j B_j of rows j <= i, decayed
    # by the steps between them
    csh = cs.transpose(0, 2, 1)                         # [nc, H, q]
    rows = jnp.arange(q)
    seen = rows[None, :] <= rows[:, None]               # [i, j]
    decay = jnp.exp(jnp.where(seen, csh[..., :, None] - csh[..., None, :],
                              -jnp.inf))                # [nc, H, i, j]
    scores = jnp.einsum("cin,cjn->cij", cc, bc, precision=_HIGHEST)
    m = scores[:, None] * decay * dtc.transpose(0, 2, 1)[:, :, None, :]
    y = jnp.einsum("chij,cjhp->cihp", m, xc, precision=_HIGHEST)
    # a chunk's own end state, and the recurrence over the chunks
    to_end = jnp.exp(cs[:, -1:, :] - cs) * dtc          # [nc, q, H]
    ends = jnp.einsum("cqhp,cqn->chpn", xc * to_end[..., None], bc,
                      precision=_HIGHEST)               # [nc, H, P, N]

    def next_chunk(s, inp):
        whole, end = inp
        return whole[:, None, None] * s + end, s

    last, entering = jax.lax.scan(
        next_chunk, jnp.zeros((h, p, n), jnp.float32),
        (jnp.exp(cs[:, -1, :]), ends))
    y = y + jnp.einsum("cqn,chpn->cqhp", cc, entering, precision=_HIGHEST) \
        * jnp.exp(cs)[..., None]
    return y.reshape(t, h, p), last


def ssm_step(ssm, dt, a, x, b, c, fresh):
    """One step of the recurrence over a whole pool where it lies: ssm
    [S, H, P, N], dt [S, H] (after softplus), a [H], x [S, H, P], b, c
    [S, N], ``fresh`` [S] bool. -> (the pool with the rows of ``fresh``
    advanced and the others as they were, y [S, H, P] = ``S_new C``
    without the ``D x`` term): elementwise, the pool read once and
    written once. With b, c [S, G, N] each group's H / G heads take
    their own B and C: spread over the heads first (a few MB), so that
    the update stays the one elementwise pass over the pool (over a
    leading group axis XLA made it three: a transposed update and a
    second read for ``y``; PERF.md, PR 46)."""
    if b.ndim == 3:
        per_group = _groups_of(b, ssm.shape[1])[1]
        b, c = (jnp.repeat(v, per_group, axis=1)[:, :, None, :]
                for v in (b, c))
    else:
        b, c = b[:, None, None, :], c[:, None, None, :]
    step = jnp.exp(dt * a)[..., None, None] * ssm + \
        (dt[..., None] * x)[..., None] * b
    new = jnp.where(fresh[:, None, None, None], step, ssm)
    return new, jnp.sum(new * c, axis=-1)


def _sizes(ctx):
    return ctx.attr("num_heads"), ctx.attr("head_dim"), ctx.attr("state_dim")


def _groups(ctx):
    return ctx.attr("n_groups") or 1


def _split_in(ctx, x2):
    """x2 [n, d] -> (z [n, HP], xBC [n, HP + 2GN], dt [n, H]) = ``x W_in``."""
    h, p, n = _sizes(ctx)
    di, gn = h * p, _groups(ctx) * n
    zxd = exact_dot(x2, ctx.input("WIn"))
    if zxd.shape[1] != 2 * di + 2 * gn + h:
        raise ValueError("WIn has %d columns, [z, xBC, dt] needs %d"
                         % (zxd.shape[1], 2 * di + 2 * gn + h))
    return zxd[:, :di], zxd[:, di:2 * di + 2 * gn], zxd[:, 2 * di + 2 * gn:]


def _split_bc(ctx, act, di):
    """The lanes of the convolved ``xBC`` past ``x`` [.., 2GN] -> (B, C),
    each [.., N] for one group and [.., G, N] for more."""
    g, n = _groups(ctx), ctx.attr("state_dim")
    b, c = act[..., di:di + g * n], act[..., di + g * n:]
    if g == 1:
        return b, c
    return (b.reshape(b.shape[:-1] + (g, n)),
            c.reshape(c.shape[:-1] + (g, n)))


def _dt_a(ctx, dt):
    return jax.nn.softplus(dt + ctx.input("DtBias").astype(jnp.float32)), \
        -jnp.exp(ctx.input("ALog").astype(jnp.float32))


def _gate_and_out(ctx, y, z):
    """y, z [n, HP] -> ``(rmsnorm(y silu(z)) w_norm) W_out`` [n, d], the
    norm's mean of squares within each group's ``HP / G`` lanes."""
    g = y * jax.nn.silu(z)
    shape = g.shape
    if _groups(ctx) > 1:
        g = g.reshape(shape[0], _groups(ctx), -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + ctx.attr("epsilon", 1e-5))
    return exact_dot(g.reshape(shape)
                     * ctx.input("NormW").astype(jnp.float32),
                     ctx.input("WOut"))


@register_op("mamba2_mixer")
def _mamba2_mixer(ctx):
    """X [B, T, d]; WIn [d, 2HP + 2GN + H], ConvW [K, HP + 2GN], ConvB,
    DtBias [H], ALog [H], D [H], NormW [HP], WOut [HP, d]; attrs num_heads,
    head_dim, state_dim, chunk, epsilon and, past one group of B and C,
    n_groups. Out [B, T, d] float32: every
    sequence from a zero state. With a state pool (B = 1: a prefill) also
    Ssm [R, H, P, N], Conv [R, K, HP + 2N], At [R] int32, Len [1] and
    Table [1]: row ``Table[0]`` is left holding the state after row
    ``Len - 1`` (the module's docstring), a dead entry drops the write."""
    x = ctx.input("X").astype(jnp.float32)
    h, p, _ = _sizes(ctx)
    bsz, t, d = x.shape
    di = h * p
    z, xbc, dt = _split_in(ctx, x.reshape(-1, d))
    conv_w = ctx.input("ConvW").astype(jnp.float32)
    k = conv_w.shape[0]
    dt, a = _dt_a(ctx, dt)
    dt = dt.reshape(bsz, t, h)
    pooled = ctx.has_input("Ssm")
    if pooled:
        if bsz != 1:
            raise ValueError("a state row takes one sequence, not %d" % bsz)
        length = ctx.input("Len").reshape(-1)[0].astype(jnp.int32)
        dt = jnp.where((jnp.arange(t) < length)[None, :, None], dt, 0.0)
    # K zero rows before the sequence: the convolution's K - 1, and one
    # more so that the last K inputs before any length are a slice
    raw = jnp.pad(xbc.reshape(bsz, t, -1), ((0, 0), (k, 0), (0, 0)))
    act = sum(conv_w[j] * raw[:, j + 1:j + 1 + t] for j in range(k))
    act = jax.nn.silu(act + ctx.input("ConvB").astype(jnp.float32))
    xs = act[..., :di].reshape(bsz, t, h, p)
    q = next(r for r in range(min(t, ctx.attr("chunk")), 0, -1)
             if t % r == 0)
    y, last = jax.vmap(lambda x1, dt1, b1, c1: ssd_chunked(
        x1, dt1, a, b1, c1, q))(xs, dt, *_split_bc(ctx, act, di))
    y = y + ctx.input("D").astype(jnp.float32)[:, None] * xs
    out = {"Out": _gate_and_out(ctx, y.reshape(-1, di), z)
           .reshape(bsz, t, d)}
    if pooled:
        row = ctx.input("Table").reshape(-1)[0].astype(jnp.int32)
        tail = jax.lax.dynamic_slice_in_dim(raw[0], length, k, axis=0)
        out["SsmOut"] = ctx.input("Ssm").at[row].set(last[0], mode="drop")
        out["ConvOut"] = ctx.input("Conv").at[row].set(tail, mode="drop")
        out["AtOut"] = ctx.input("At").at[row].set(length, mode="drop")
    return out


@register_op("mamba2_mixer_decode")
def _mamba2_mixer_decode(ctx):
    """X [S, 1, d] and the weights of ``mamba2_mixer``; Ssm [S, H, P, N],
    Conv [S, K, HP + 2N], At [S] int32 (the state pool: row s is slot s's),
    Pos [S] int (the position of each slot's token), Table [S, 1] int.
    Out [S, 1, d] float32. Row s advances, and ``At[s]`` becomes
    ``Pos[s] + 1``, only where ``Table[s] == s`` and ``At[s] == Pos[s]``;
    where it already holds ``Pos[s] + 1`` tokens the output comes from the
    row as stored; a row that is not the slot's to write gives numbers
    nobody reads."""
    x = ctx.input("X").astype(jnp.float32)
    h, p, _ = _sizes(ctx)
    s, _, d = x.shape
    di = h * p
    ssm, conv, at = ctx.input("Ssm"), ctx.input("Conv"), ctx.input("At")
    if ssm.shape[0] != s:
        raise ValueError("a state pool of %d rows under %d slots: a slot's "
                         "row is the row of its own index"
                         % (ssm.shape[0], s))
    pos = ctx.input("Pos").reshape(-1).astype(jnp.int32)
    own = ctx.input("Table").reshape(s, -1)[:, 0] == jnp.arange(s)
    fresh = own & (at == pos)
    z, xbc, dt = _split_in(ctx, x.reshape(s, d))
    dt, a = _dt_a(ctx, dt)                              # [S, H], [H]
    # the convolution's window: the stored inputs moved up by the new one,
    # or as stored where the row has taken this input already
    window = jnp.where(fresh[:, None, None],
                       jnp.concatenate([conv[:, 1:], xbc[:, None]], axis=1),
                       conv)
    act = jnp.sum(window * ctx.input("ConvW").astype(jnp.float32), axis=1)
    act = jax.nn.silu(act + ctx.input("ConvB").astype(jnp.float32))
    xs = act[:, :di].reshape(s, h, p)
    new, y = ssm_step(ssm, dt, a, xs, *_split_bc(ctx, act, di), fresh)
    y = y + ctx.input("D").astype(jnp.float32)[:, None] * xs
    return {"Out": _gate_and_out(ctx, y.reshape(s, di), z).reshape(s, 1, d),
            "SsmOut": new, "ConvOut": window,
            "AtOut": jnp.where(fresh, pos + 1, at)}


def s6_scan(x, dt, a, b, c):
    """Mamba-1's selective scan of one sequence from a zero state, a row
    after the other: x, dt [T, D] (dt after softplus; 0 in rows that move
    nothing), a [N, D] (negative), b, c [T, N] -> (y [T, D] without the
    ``D x`` term, the state after row T-1 [N, D]). The carry is the one
    state; eight rows a trip of the loop."""
    def row(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a) * s + (dt_t * x_t) * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    last, y = jax.lax.scan(row, jnp.zeros(a.shape, jnp.float32),
                           (x, dt, b, c), unroll=min(8, x.shape[0]))
    return y, last


def s6_step(ssm, dt, a, x, b, c, fresh):
    """One step of Mamba-1's recurrence over a whole pool where it lies:
    ssm [S, N, D], dt, x [S, D], a [N, D], b, c [S, N], ``fresh`` [S] bool
    -> (the pool with the rows of ``fresh`` advanced, y [S, D] = ``S_new
    C`` without the ``D x`` term): one elementwise pass, as
    :func:`ssm_step` is."""
    step = jnp.exp(dt[:, None, :] * a) * ssm + \
        (dt * x)[:, None, :] * b[:, :, None]
    new = jnp.where(fresh[:, None, None], step, ssm)
    return new, jnp.sum(new * c[:, :, None], axis=1)


def _s6_inputs(ctx, x):
    """The convolved x [.., D] -> (dt [.., D] after softplus, A [N, D],
    B, C [.., N])."""
    flat = x.reshape(-1, x.shape[-1])
    r = exact_dot(flat, ctx.input("WR"))
    bc = exact_dot(flat, ctx.input("WBC")).reshape(x.shape[:-1] + (-1,))
    n = bc.shape[-1] // 2
    dt = exact_dot(r, ctx.input("WDt")).reshape(x.shape)
    dt, a = _dt_a(ctx, dt)
    return dt, a, bc[..., :n], bc[..., n:]


@register_op("mamba1_mixer")
def _mamba1_mixer(ctx):
    """XZ [B, T, 2D] (``u W_in``: x then the gate z); ConvW [K, D], ConvB
    [D], WR [D, R], WBC [D, 2N], WDt [R, D], DtBias [D], ALog [N, D], D [D].
    Out [B, T, D] float32 = ``y silu(z)`` and M [B, T, D] = ``y``, the
    scan's output with its ``D x`` term before the gate: every sequence
    from a zero state. With a state pool (B = 1: a prefill) also Ssm
    [R, N, D], Conv [R, K, D], At [R] int32, Len [1] and Table [1], as
    ``mamba2_mixer`` takes them: row ``Table[0]`` is left holding the state
    after row ``Len - 1``, a dead entry drops the write."""
    xz = ctx.input("XZ").astype(jnp.float32)
    bsz, t, d2 = xz.shape
    di = d2 // 2
    raw, z = xz[..., :di], xz[..., di:]
    conv_w = ctx.input("ConvW").astype(jnp.float32)
    k = conv_w.shape[0]
    # K zero rows before the sequence, as the Mamba-2 op pads them
    raw = jnp.pad(raw, ((0, 0), (k, 0), (0, 0)))
    x = sum(conv_w[j] * raw[:, j + 1:j + 1 + t] for j in range(k))
    x = jax.nn.silu(x + ctx.input("ConvB").astype(jnp.float32))
    dt, a, b, c = _s6_inputs(ctx, x)
    pooled = ctx.has_input("Ssm")
    if pooled:
        if bsz != 1:
            raise ValueError("a state row takes one sequence, not %d" % bsz)
        length = ctx.input("Len").reshape(-1)[0].astype(jnp.int32)
        dt = jnp.where((jnp.arange(t) < length)[None, :, None], dt, 0.0)
    y, last = jax.vmap(lambda *one: s6_scan(*one[:2], a, *one[2:]))(
        x, dt, b, c)
    y = y + ctx.input("D").astype(jnp.float32) * x
    out = {"Out": y * jax.nn.silu(z), "M": y}
    if pooled:
        row = ctx.input("Table").reshape(-1)[0].astype(jnp.int32)
        tail = jax.lax.dynamic_slice_in_dim(raw[0], length, k, axis=0)
        out["SsmOut"] = ctx.input("Ssm").at[row].set(last[0], mode="drop")
        out["ConvOut"] = ctx.input("Conv").at[row].set(tail, mode="drop")
        out["AtOut"] = ctx.input("At").at[row].set(length, mode="drop")
    return out


@register_op("mamba1_mixer_decode")
def _mamba1_mixer_decode(ctx):
    """XZ [S, 1, 2D] and the weights of ``mamba1_mixer``; Ssm [S, N, D],
    Conv [S, K, D], At [S] int32, Pos [S], Table [S, 1]: the state pool's
    books as ``mamba2_mixer_decode`` keeps them (a row advances only where
    ``Table[s] == s`` and ``At[s] == Pos[s]``; a row that already holds
    ``Pos[s] + 1`` tokens gives its output as stored). Out, M [S, 1, D]."""
    xz = ctx.input("XZ").astype(jnp.float32)
    s, _, d2 = xz.shape
    di = d2 // 2
    raw, z = xz[:, 0, :di], xz[:, 0, di:]
    ssm, conv, at = ctx.input("Ssm"), ctx.input("Conv"), ctx.input("At")
    if ssm.shape[0] != s:
        raise ValueError("a state pool of %d rows under %d slots: a slot's "
                         "row is the row of its own index"
                         % (ssm.shape[0], s))
    pos = ctx.input("Pos").reshape(-1).astype(jnp.int32)
    own = ctx.input("Table").reshape(s, -1)[:, 0] == jnp.arange(s)
    fresh = own & (at == pos)
    window = jnp.where(fresh[:, None, None],
                       jnp.concatenate([conv[:, 1:], raw[:, None]], axis=1),
                       conv)
    x = jnp.sum(window * ctx.input("ConvW").astype(jnp.float32), axis=1)
    x = jax.nn.silu(x + ctx.input("ConvB").astype(jnp.float32))
    dt, a, b, c = _s6_inputs(ctx, x)
    new, y = s6_step(ssm, dt, a, x, b, c, fresh)
    y = y + ctx.input("D").astype(jnp.float32) * x
    return {"Out": (y * jax.nn.silu(z))[:, None], "M": y[:, None],
            "SsmOut": new, "ConvOut": window,
            "AtOut": jnp.where(fresh, pos + 1, at)}


@register_op("mamba2_param_init")
def _mamba2_param_init(ctx):
    """U, uniform in [0, 1) -> Mamba-2's published initial values: attr
    ``what`` ``a_log`` gives ``log(A)`` with A uniform in [1, 16];
    ``dt_bias`` gives the inverse softplus of a ``dt`` log-uniform in
    [1e-3, 1e-1]; ``a_log_rows`` gives Mamba-1's ``log(1..N)`` down the
    rows of U [N, D], the same for every channel (the draw is not read)."""
    u = ctx.input("U").astype(jnp.float32)
    if ctx.attr("what") == "a_log":
        return {"Out": jnp.log(1.0 + 15.0 * u)}
    if ctx.attr("what") == "a_log_rows":
        rows = jnp.arange(1, u.shape[0] + 1, dtype=jnp.float32)
        return {"Out": jnp.broadcast_to(jnp.log(rows)[:, None], u.shape)}
    dt = jnp.maximum(jnp.exp(jnp.log(1e-3) + u * jnp.log(1e2)), 1e-4)
    return {"Out": dt + jnp.log(-jnp.expm1(-dt))}
