"""A decoder LM driven by a per-layer spec and the shape of its block, given
as data: a feed-forward that is dense in the leading layers (where there
are any) and sparse experts with a shared expert in the rest, behind a
mixer that is layer by layer a Mamba-2 state-space mixer (``"mamba"``,
``ops/ssm_ops.py``) or an attention that is

* ``gqa``: grouped queries, full or windowed layer by layer, rotary
  positions in the window layers, RMSNorm before and after each half, a
  gated attention output and a scaled embedding (the ``afmoe`` family; the
  equations are in ``benchmarks/reference/afmoe.py``), each of which is
  data: without norms on q and k, the gate and positions, with a softmax
  scale, residual, embedding and logit multipliers of its own and the head
  tied to the embedding it is the ``granitemoehybrid`` family's attention
  layer (``benchmarks/reference/granitemoehybrid.py``); or
* ``latent``: MLA (``ops/mla_ops.py``) with low-rank queries, YaRN rotary
  positions on the rope lanes, RMSNorm before each half only, no gate and
  no embedding scale (the ``kimi_k2`` / DeepSeek-V3 family;
  ``benchmarks/reference/kimi_k2.py``); or
* ``eva``: EVA attention (``ops/eva_ops.py``): every head its own KV head,
  rotary positions in every layer, an exact aligned window and one learned
  summary for every chunk of the windows before it, in a dense block with
  RMSNorm before each half only, unit-offset norms (``norm_offset``) and a
  head of ``pred_heads`` x ``vocab_size`` columns of which the first
  ``vocab_size`` choose the next token (the ``evabyte`` family;
  ``benchmarks/reference/evabyte.py``). Served in the operands it is held
  in: under the ``amp`` flag its products take one pass.

The seventh family (``phi4flash``, the SambaY decoder-hybrid-decoder;
``benchmarks/reference/phi4flash.py``) is ``gqa`` again with more of it as
data, and two layer types that **borrow**: its mixer is Mamba-1's
(``mamba=dict(scan="s6", ..)``: a decay matrix, ``ops/ssm_ops.py``), its
attention is **differential** (``differential``: heads in pairs ``(2p,
2p+1)``, two softmaxes over one value of twice the head's lanes, ``attn1 -
lambda attn2`` under an RMSNorm over those lanes; held as ordinary grouped
queries of twice the head's width on half the KV heads, a query padded
with zeros on the half it does not score, so that the paged kernel serves
it as it stands and reads a cached page once for both softmaxes), with
biases on its projections (``attn_bias``), LayerNorm for RMSNorm
(``norm="layer"``) and no rotary turn anywhere (``window_rotary=False``).
``"cross_attention"`` is a layer that computes a query only and walks the
keys and values of layer ``kv_from``: it has no layer cache, writes
nothing and reads that layer's rows of this very step. ``"gmu"`` is a
gated memory unit, ``(silu(a W_in) * m) W_out`` with ``m`` the same token's
scan output of layer ``memory_from`` before its gate: an activation handed
from one layer to later ones, neither a weight nor a cache. Layers from the
first that hands something on are traced under ``cross_decoder``, those
before it under ``self_decoder``; and where the model ends in layers that
own no cache, a prefill runs them on the prompt's last row alone (nothing
reads their other rows).

The layer's own shape is data too. Without ``block`` a layer is one mixer
and then one feed-forward, each added to the residual stream where it was
read. With ``block`` (``halves``, ``experts_read``, ``experts_join``) a
layer is that many **halves**, each an attention and a dense feed-forward
with their own norms, and ONE expert layer (no shared expert) laid across
them: it reads the norm before half ``experts_read``'s feed-forward and
its result joins the residual stream only after half ``experts_join``'s
(the shortcut-connected MoE of the ``longcat_flash`` family,
``benchmarks/reference/longcat_flash.py``; its router is ``zero_experts``
wider than its experts, ``ops/moe_ops.py``). Such a layer has a layer
cache a half: the session counts attention **sites**, not layers.
With ``block`` ``dict(sublayers=1)`` a layer is ONE sublayer, ``h + f(norm
(h))`` with one norm and one residual sum, where ``f`` is by the layer's
type a Mamba-2 mixer, an attention or, the fourth entry of
``layer_types``, ``"experts"``: the expert layer with its shared expert
alone (the ``nemotron_h`` family, ``benchmarks/reference/nemotron_h.py``,
whose experts are two matrices, ``expert_act`` ``relu2``, and whose mixer
has ``n_groups`` groups of B and C). An ``"experts"`` layer has no layer
cache, so there too a cache goes by its **site**.

:func:`moe_lm` is the whole-sequence forward (its startup program makes the
weights); :func:`moe_lm_session` builds the paged prefill and decode
programs through ``transformer.lm_session``, with one kind of layer cache
for the full layers and one, which frees blocks behind the window, for the
window layers, or for latent attention the one **latent kind**: one pool a
layer whose row is a token's ``(c, k_r)`` padded to whole lane tiles. A
model with state-space layers has beside its paged kind a **state kind**:
a pool of one fixed-size float32 row a slot in each such layer (the scan's
state, the convolution's last inputs and the tokens absorbed), rewritten
whole every step; its prefill starts from a zero state, so it takes
neither a shared prefix nor speculation. EVA attention has two kinds a
layer: an **aligned window** kind (the rows of the sequence's current
window, all freed at the window's edge) and a **chunk kind** (one row for
every ``chunk`` positions: a chunk's summary, made from the window kind's
block when its last row is written).
Matmul weights, the embedding and the head are created and
held in ``param_dtype``; norms, router and expert bias are float32, and so
is every activation: the products are exact (ops/moe_ops.py says why), so
the layer caches should be float32 too.
"""

import contextlib
import math

from .. import layers
from ..core.framework import name_scope
from ..layer_helper import LayerHelper
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr
from .transformer import lm_session

__all__ = ["moe_lm", "moe_lm_session", "MoeLM"]

SLIDING, FULL, MAMBA = "sliding_attention", "full_attention", "mamba"
EXPERTS = "experts"
# the two types that borrow: another layer's keys and values, another
# layer's scan output
CROSS, GMU = "cross_attention", "gmu"
# what a trace's operations of a one-sublayer layer go under, by its type
_SUBLAYER_SCOPE = {MAMBA: "mamba2_mixer", EXPERTS: "moe_ffn",
                   FULL: "attention", SLIDING: "attention"}


class MoeLM:
    """The model as ``lm_session`` takes one, and as :func:`moe_lm` runs it
    over whole sequences. Parameter names are fixed (``moe_lm.l3.attn.q.w``)
    so that every program built from the same sizes shares the scope's
    weights."""

    def __init__(self, vocab_size, d_model, num_heads, num_kv_heads, head_dim,
                 d_ff, moe_d_ff, num_experts, top_k, layer_types,
                 num_dense_layers, sliding_window, rope_theta=10000.0,
                 rms_eps=1e-5, route_norm=True, route_scale=1.0,
                 embed_scale=1.0, param_dtype="float32", expert_offset=0,
                 experts_held=None, init_std=0.02, attention="gqa",
                 post_norms=True, latent=None, rope_scaling=None,
                 scoring="sigmoid", qk_norm=True, attn_gate=True,
                 attn_scale=None, residual_scale=None, logit_scale=None,
                 tie_embeddings=False, mamba=None, shared_d_ff=None,
                 block=None, zero_experts=0, eva=None, norm_offset=0.0,
                 pred_heads=1, expert_act=None, norm="rms", attn_bias=False,
                 differential=False, window_rotary=True, kv_from=None,
                 memory_from=None):
        self.block = dict(block) if block else None
        # a layer of one sublayer, among them the fourth type
        self.single = (self.block or {}).get("sublayers") == 1
        unknown = set(layer_types) - {SLIDING, FULL, MAMBA} \
            - ({EXPERTS} if self.single else {CROSS, GMU})
        if unknown:
            raise ValueError(
                "layer_types holds %s: a layer is %r, %r or %r, in a "
                "block of one sublayer also %r, and in a block of a mixer "
                "and a feed-forward also %r (reading the keys and values "
                "of layer kv_from) or %r (reading the scan output of layer "
                "memory_from)" % (sorted(unknown), SLIDING, FULL, MAMBA,
                                  EXPERTS, CROSS, GMU))
        self.kv_from, self.memory_from = kv_from, memory_from
        self._check_borrowed(layer_types, mamba or {}, attention)
        if attention not in ("gqa", "latent", "eva"):
            raise ValueError("attention is 'gqa', 'latent' or 'eva', not %r"
                             % (attention,))
        if attention == "gqa" and num_heads % num_kv_heads:
            raise ValueError("%d query heads on %d KV heads"
                             % (num_heads, num_kv_heads))
        self.vocab_size = vocab_size
        self.d, self.nh, self.nkv, self.hd = (d_model, num_heads,
                                               num_kv_heads, head_dim)
        self.d_ff, self.moe_d_ff = d_ff, moe_d_ff
        self.shared_d_ff = shared_d_ff or moe_d_ff
        self.num_experts, self.top_k = num_experts, top_k
        self.layer_types = tuple(layer_types)
        self.num_dense_layers = num_dense_layers
        self.window = sliding_window
        self.theta, self.eps = rope_theta, rms_eps
        self.route_norm, self.route_scale = route_norm, route_scale
        self.embed_scale, self.dtype = embed_scale, param_dtype
        self.expert_offset, self.experts_held = expert_offset, experts_held
        self.std = init_std
        self.attention, self.post_norms = attention, post_norms
        self.yarn = rope_scaling
        self.scoring, self.qk_norm, self.attn_gate = (scoring, qk_norm,
                                                      attn_gate)
        self.attn_scale = attn_scale
        self.residual_scale, self.logit_scale = residual_scale, logit_scale
        self.tie_embeddings = tie_embeddings
        self.zero_experts = zero_experts
        self.norm_offset, self.pred_heads = norm_offset, pred_heads
        self.expert_act = expert_act
        self.norm, self.attn_bias = norm, attn_bias
        self.differential, self.window_rotary = differential, window_rotary
        if norm not in ("rms", "layer") or (differential and (
                attention != "gqa" or qk_norm or attn_gate
                or num_heads % 2 or num_kv_heads % 2)):
            raise ValueError("norm is 'rms' or 'layer'; differential "
                             "attention is grouped-query attention's, with "
                             "heads and KV heads in pairs and neither a "
                             "norm on q and k nor a gate")
        if self.single:
            if attention != "gqa" or num_dense_layers or post_norms:
                raise ValueError("a block of one sublayer is grouped-query "
                                 "attention's, has no leading dense layer "
                                 "and one norm a layer")
        elif block and (attention != "latent" or num_dense_layers
                        or not 0 <= block["experts_read"]
                        <= block["experts_join"] < block["halves"]):
            raise ValueError("a block of halves is latent attention's, has "
                             "an expert layer in every layer and reads it "
                             "at a half no later than the one it joins")
        # expert pairs a row of a step routes, held here or not: every
        # layer but the leading dense ones has one expert layer, and of
        # one-sublayer layers those that are one
        self.pairs_per_row = top_k * (
            self.layer_types.count(EXPERTS) if self.single
            else len(self.layer_types) - num_dense_layers)
        if attention == "latent":
            self._latent_sizes(**latent)
            return
        if attention == "eva":
            self._eva_sizes(**eva)
            return
        # the kinds of layer cache, full first where the model has both;
        # per layer the width of a cached row and its kind. The kind of the
        # layer whose pool the cross layers walk counts them as borrowers
        present = [t for t in (FULL, SLIDING) if t in self.layer_types]
        self.kinds = tuple(("full", None) if t == FULL else
                           ("window", sliding_window) for t in present)
        walks = self.layer_types.count(CROSS)
        if walks:
            lent = present.index(self.layer_types[kv_from])
            self.kinds = tuple(
                kind + (dict(borrowers=walks),) if k == lent else kind
                for k, kind in enumerate(self.kinds))
        state_row = None
        if MAMBA in self.layer_types:
            if not present:
                raise ValueError("a model of state-space layers alone has "
                                 "no paged kind for the session to size")
            # a slot's row in a layer: the scan's state and the
            # convolution's inputs. Mamba-2: (num_heads, head_dim,
            # state_dim, conv_width, chunk); Mamba-1 (``scan`` "s6"):
            # (d_inner, state_dim, conv_width, dt_rank), its convolution
            # over x alone
            self.mamba = dict(mamba)
            if self.mamba.pop("scan", "ssd") == "s6":
                self.s6 = True
                state_row = ((mamba["state_dim"], mamba["d_inner"]),
                             (mamba["conv_width"], mamba["d_inner"]))
            else:
                lanes = mamba["num_heads"] * mamba["head_dim"] \
                    + 2 * mamba.get("n_groups", 1) * mamba["state_dim"]
                state_row = ((mamba["num_heads"], mamba["head_dim"],
                              mamba["state_dim"]),
                             (mamba["conv_width"], lanes))
            self.kinds += (("state", None),)
        # a layer's site among the layer caches: an expert layer has none,
        # nor has a layer that borrows
        cached = [i for i, t in enumerate(self.layer_types)
                  if t not in (EXPERTS, CROSS, GMU)]
        if walks or GMU in self.layer_types:
            # the cross-decoder: from the first layer that hands something
            # on; and its tail, the layers after the last that owns a cache
            self.cross_from = min(
                i for i in (kv_from, memory_from) if i is not None)
            self.tail_from = cached[-1] + 1
            if any(t in (CROSS, GMU)
                   for t in self.layer_types[:self.tail_from]):
                raise ValueError(
                    "a layer that owns a cache follows one that borrows: a "
                    "prefill runs the borrowing layers on the prompt's "
                    "last row alone, so they close the model")
        self.site = {i: at for at, i in enumerate(cached)}
        self.cache_layers = [
            (state_row, len(present)) if self.layer_types[i] == MAMBA else
            (num_kv_heads * head_dim, present.index(self.layer_types[i]))
            for i in cached]

    # a model none of whose layers borrows has no cross-decoder, no tail
    # that a prefill runs on one row, and Mamba-2's scan where it has one
    s6, cross_from, tail_from = False, None, None

    def _check_borrowed(self, layer_types, mamba, attention):
        """Refuse a ``kv_from`` / ``memory_from`` that is missing, points
        at a layer of the wrong type or at one no earlier than a layer that
        reads it."""
        for t, what, source, right in (
                (CROSS, "kv_from", self.kv_from, (FULL, SLIDING)),
                (GMU, "memory_from", self.memory_from, (MAMBA,))):
            readers = [i for i, u in enumerate(layer_types) if u == t]
            if not readers:
                if source is not None:
                    raise ValueError("%s is %r and no layer is %r"
                                     % (what, source, t))
                continue
            if attention != "gqa" or source is None or \
                    not 0 <= source < readers[0] or \
                    layer_types[source] not in right or \
                    (t == GMU and mamba.get("scan") != "s6"):
                raise ValueError(
                    "a %r layer reads layer %s = %r, which has to be an "
                    "earlier layer of type %s (grouped-query attention; a "
                    "memory is a Mamba-1 scan's output)"
                    % (t, what, source, " or ".join(map(repr, right))))

    def _latent_sizes(self, q_rank, kv_rank, nope_dim, rope_dim, v_dim,
                      q_scale=None, kv_scale=None):
        """Latent attention's widths, its one kind of layer cache and the
        scale of its scores: ``(nope + rope)^-1/2``, times the square of
        YaRN's ``0.1 mscale_all_dim ln(factor) + 1`` where the positions
        are scaled. ``q_scale`` / ``kv_scale`` (absent: none) multiply the
        two latents after their norms."""
        if set(self.layer_types) != {FULL}:
            raise ValueError("latent attention has no window layers")
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.q_scale, self.kv_scale = q_scale, kv_scale
        self.nope, self.rope, self.v_dim = nope_dim, rope_dim, v_dim
        m = 1.0
        if self.yarn and self.yarn.get("mscale_all_dim"):
            m = 0.1 * self.yarn["mscale_all_dim"] * \
                math.log(self.yarn["factor"]) + 1.0
        self.attn_scale = (nope_dim + rope_dim) ** -0.5 * m * m
        # a cached row is (c, k_r) and zeros up to whole lane tiles: one
        # pool an attention site (a layer, or each half of one), read once
        # a page as key and value
        self.row_width = -(-(kv_rank + rope_dim) // 128) * 128
        self.kinds = (("latent", None),)
        self.cache_pools = ("c",)
        self.cache_layers = [(self.row_width, 0)] * (
            len(self.layer_types) * (self.block or {}).get("halves", 1))

    def _eva_sizes(self, window, chunk):
        """EVA attention's two kinds of layer cache, the window's first: a
        layer has a site in each (``cache_layers`` 2i and 2i + 1), rows as
        wide as its keys."""
        if set(self.layer_types) != {FULL} or self.nh != self.nkv or \
                window % chunk:
            raise ValueError("EVA attention is every layer's, gives every "
                             "head a KV head of its own and has windows of "
                             "whole chunks")
        self.eva = dict(window=window, chunk=chunk)
        self.kinds = (("window", window, dict(aligned=True)),
                      ("chunk", None, dict(chunk=chunk)))
        self.cache_layers = [(self.nkv * self.hd, k) for _ in
                             self.layer_types for k in (0, 1)]

    # -- the block ---------------------------------------------------------
    def _norm(self, x, name, group_size=0):
        if self.norm == "layer":
            return layers.layer_norm(
                x, begin_norm_axis=len(x.shape) - 1, epsilon=self.eps,
                param_attr="moe_lm.%s.w" % name,
                bias_attr="moe_lm.%s.b" % name)
        return layers.rms_norm(x, epsilon=self.eps, group_size=group_size,
                               param_attr="moe_lm.%s.w" % name,
                               offset=self.norm_offset)

    def _linear(self, x, size, name, bias=False):
        y = layers.linear(x, size, "moe_lm.%s.w" % name, self.dtype,
                          self.std)
        if bias:
            y = layers.bias_add(y, "moe_lm.%s.b" % name, self.std)
        return y

    def _positions(self, ctx):
        """The rotary layer's position arguments for a program's mode."""
        if ctx is None:
            return {}
        decode = ctx["mode"] == "decode"
        return dict(pos=ctx["pos"] if decode else ctx["pos_idx"],
                    per_row=decode)

    def _latent_attention(self, a, p, site, ctx):
        """a [B, T, d] -> latent attention's output [B, T, H*v]: expanded
        over the rows' own latents (whole sequences, a prefill), absorbed
        over the site's paged latent pool (a decode step). ``p`` prefixes
        the site's parameters, ``site`` is its layer cache's index."""
        nh, nope, rope = self.nh, self.nope, self.rope
        c_q = self._norm(self._linear(a, self.q_rank, p + "q_a"),
                         p + "q_a_norm")
        if self.q_scale:
            c_q = layers.scale(c_q, self.q_scale)
        q = self._linear(c_q, nh * (nope + rope), p + "q_b")
        ckr = self._linear(a, self.kv_rank + rope, p + "kv_a")
        c = self._norm(layers.slice(ckr, [2], [0], [self.kv_rank]),
                       p + "kv_a_norm")
        if self.kv_scale:
            c = layers.scale(c, self.kv_scale)
        k_r = layers.slice(ckr, [2], [self.kv_rank], [self.kv_rank + rope])
        turn = dict(self._positions(ctx), theta=self.theta, yarn=self.yarn)
        q = layers.rotary_embedding(q, nope + rope,
                                    lanes=(nope, nope + rope), **turn)
        k_r = layers.rotary_embedding(k_r, rope, **turn)
        attend = dict(num_heads=nh, nope_dim=nope, rope_dim=rope,
                      v_dim=self.v_dim, scale=self.attn_scale,
                      param_attr="moe_lm.%skv_b.w" % p, dtype=self.dtype,
                      std=self.std)
        if ctx is not None:
            # the token's row into the site's pool: (c, k_r, zeros)
            pool, = ctx["caches"][site]
            row = layers.pad(layers.concat([c, k_r], axis=2),
                             [0, 0, 0, 0, 0,
                              self.row_width - self.kv_rank - rope])
            decode = ctx["mode"] == "decode"
            where = {"Pos": [ctx["pos"].name]} if decode else \
                {"Hist": [ctx["hist"].name], "Len": [ctx["key_length"].name]}
            LayerHelper("moe_lm_attention").append_op(
                type="kv_cache_append_paged" if decode
                else "kv_cache_write_paged",
                inputs=dict(where, Cache=[pool.name], New=[row.name],
                            Table=[ctx["table"].name]),
                outputs={"Out": [pool.name]})
            if decode:
                return layers.mla_attention(
                    q, c, k_r, cache=pool, pos=ctx["pos"],
                    table=ctx["table"], **attend)
        return layers.mla_attention(q, c, k_r, block_rows=512, **attend)

    def _eva_attention(self, a, p, i, ctx):
        """a [B, T, d] -> EVA attention's output [B, T, H*D]: over the
        rows' own window and summaries (whole sequences, a prefill, which
        also writes both pools) or over the layer's two paged pools (a
        decode step)."""
        rope = dict(self._positions(ctx), head_dim=self.hd, theta=self.theta)
        q, k, v = (self._linear(a, self.nh * self.hd, p + part)
                   for part in "qkv")
        where = {}
        if ctx is not None:
            where = dict(caches=ctx["caches"][2 * i:2 * i + 2],
                         tables=ctx["tables"])
            if ctx["mode"] == "decode":
                where["pos"] = ctx["pos"]
            else:
                where.update(hist=ctx["hist"], length=ctx["key_length"])
        return layers.eva_attention(
            layers.rotary_embedding(q, **rope),
            layers.rotary_embedding(k, **rope), v, self.nh,
            prefix="moe_lm." + p[:-1], dtype=self.dtype,
            **dict(self.eva, **where))

    def _mixer(self, a, i, ctx, handed=None):
        """a [B, T, d] -> the state-space mixer's output [B, T, d]: whole
        sequences, a prefill into the slot's state row, or a decode step
        over the layer's state pool. Mamba-1's scan output goes into
        ``handed`` where a later layer reads this one's."""
        where = {}
        if ctx is not None:
            at = self.site[i]
            where = dict(state=ctx["caches"][at],
                         table=ctx["tables"][self.cache_layers[at][1]])
            if ctx["mode"] == "decode":
                where["pos"] = ctx["pos"]
            else:
                where["length"] = ctx["key_length"]
        if self.s6:
            o, m = layers.mamba1_mixer(
                a, prefix="moe_lm.l%d.mamba" % i, dtype=self.dtype,
                std=self.std, **dict(self.mamba, **where))
            if i == self.memory_from:
                handed["memory"] = m
            return o
        return layers.mamba2_mixer(
            a, prefix="moe_lm.l%d.mamba" % i, epsilon=self.eps,
            dtype=self.dtype, std=self.std, **dict(self.mamba, **where))

    def _attention(self, a, i, ctx, handed=None):
        """a [B, T, d] -> the (gated) attention output [B, T, H*D], through
        the layer's paged cache where ``ctx`` has one; a cross layer's
        through the cache of the layer it reads. ``handed`` carries that
        layer's keys and values to the cross layers of a whole-sequence
        forward."""
        p = "l%d.attn." % i
        if self.attention == "latent":
            return self._latent_attention(a, p, i, ctx)
        if self.attention == "eva":
            return self._eva_attention(a, p, i, ctx)
        t = self.layer_types[i]
        source = self.kv_from if t == CROSS else i
        windowed = self.layer_types[source] == SLIDING
        # KV heads as the pools hold them: a differential pair's two keys
        # are one head of twice the lanes, and so are its two values
        nkv = self.nkv // 2 if self.differential else self.nkv
        if t == CROSS:
            q = self._linear(a, self.nh * self.hd, p + "q", self.attn_bias)
            k, v = handed["kv"]
        elif self.differential:
            # one matrix, as published: q, then k, then v
            qkv = self._linear(a, (self.nh + 2 * self.nkv) * self.hd,
                               p + "qkv", self.attn_bias)
            cuts = [0, self.nh * self.hd, (self.nh + self.nkv) * self.hd,
                    (self.nh + 2 * self.nkv) * self.hd]
            q, k, v = (layers.slice(qkv, [2], [lo], [hi])
                       for lo, hi in zip(cuts, cuts[1:]))
        else:
            q = self._linear(a, self.nh * self.hd, p + "q", self.attn_bias)
            k = self._linear(a, self.nkv * self.hd, p + "k", self.attn_bias)
            v = self._linear(a, self.nkv * self.hd, p + "v", self.attn_bias)
        if i == self.kv_from:
            handed["kv"] = (k, v)
        if self.attn_gate:
            gate = self._linear(a, self.nh * self.hd, p + "gate")
        if self.qk_norm:
            q = self._norm(q, p + "q_norm", self.hd)
            k = self._norm(k, p + "k_norm", self.hd)
        if windowed and self.window_rotary:
            # positions only where the window bounds what they span
            rope = dict(self._positions(ctx), head_dim=self.hd,
                        theta=self.theta)
            q = layers.rotary_embedding(q, **rope)
            k = layers.rotary_embedding(k, **rope)
        if self.differential:
            q = layers.diff_attention_queries(q, self.hd)
        helper = LayerHelper("moe_lm_attention")
        out = helper.create_tmp_variable(q.dtype)
        attrs = {"num_heads": self.nh, "num_kv_heads": nkv}
        if windowed:
            attrs["window"] = self.window
        if self.attn_scale is not None:
            attrs["scale"] = self.attn_scale
        elif self.differential:
            attrs["scale"] = self.hd ** -0.5    # of a head, not of a pair
        if ctx is None:
            helper.append_op(
                type="multihead_attention",
                inputs={"Q": [q.name], "K": [k.name], "V": [v.name]},
                outputs={"Out": [out.name]},
                attrs=dict(attrs, causal=True, ring_axis=None))
        else:
            at = self.site[source]
            ck, cv = ctx["caches"][at]
            table = ctx["tables"][self.cache_layers[at][1]]
            if ctx["mode"] == "decode":
                write, attend = ("kv_cache_append_paged",
                                 "multihead_attention_decode_paged")
                where = {"Pos": [ctx["pos"].name], "Table": [table.name]}
            elif t == CROSS:
                # a prefill's one row, the prompt's last, against the pool
                # its source layer has just written: a decode walk of one
                # slot
                attend = "multihead_attention_decode_paged"
                where = {
                    "Pos": [layers.elementwise_add(
                        ctx["hist"], ctx["last_pos"]).name],
                    "Table": [layers.reshape(
                        table, [1, table.shape[0]]).name]}
            else:
                write, attend = ("kv_cache_write_paged",
                                 "multihead_attention_prefill_paged")
                where = {"Table": [table.name], "Hist": [ctx["hist"].name],
                         "Len": [ctx["key_length"].name]}
                attrs["block_rows"] = 512
            for cvar, new in ((ck, k), (cv, v)) if t != CROSS else ():
                helper.append_op(type=write,
                                 inputs=dict(where, Cache=[cvar.name],
                                             New=[new.name]),
                                 outputs={"Out": [cvar.name]})
            helper.append_op(type=attend,
                             inputs=dict(where, Q=[q.name],
                                         CacheK=[ck.name],
                                         CacheV=[cv.name]),
                             outputs={"Out": [out.name]}, attrs=attrs)
        if self.differential:
            out = layers.diff_attention_combine(
                out, self.hd, 0.8 - 0.6 * math.exp(-0.3 * i),
                "moe_lm." + p[:-1], epsilon=self.eps)
        if not self.attn_gate:
            return out
        return layers.elementwise_mul(out, layers.sigmoid(gate))

    def _gmu(self, a, i, handed):
        """a [B, T, d] -> the gated memory unit's output [B, T, d]:
        ``(silu(a W_in) * m) W_out`` on the same rows' scan output of layer
        ``memory_from``."""
        m = handed["memory"]
        g = self._linear(a, m.shape[-1], "l%d.gmu.in" % i)
        return self._linear(layers.elementwise_mul(layers.silu(g), m),
                            self.d, "l%d.gmu.out" % i)

    def _feed_forward(self, m, i):
        """-> (f, the experts' pair counts or None)."""
        if i < self.num_dense_layers:
            return layers.ffn(m, self.d_ff, "moe_lm.l%d.mlp" % i,
                              self.dtype, act=self.expert_act), None
        p = "moe_lm.l%d.moe" % i
        shared = layers.ffn(m, self.shared_d_ff, p + ".shared", self.dtype,
                            act=self.expert_act)
        routed, counts = self._experts(m, i)
        return layers.elementwise_add(routed, shared), counts

    def _experts(self, m, i):
        """-> (the held routed experts' part of layer i's expert layer,
        its pair counts: the held experts' and, behind them, the identity
        experts' where the router has any)."""
        routed, *counts = layers.moe_ffn(
            m, self.num_experts, self.top_k, self.moe_d_ff,
            "moe_lm.l%d.moe" % i,
            route_norm=self.route_norm, route_scale=self.route_scale,
            expert_offset=self.expert_offset,
            experts_held=self.experts_held, dtype=self.dtype, std=self.std,
            scoring=self.scoring, zero_experts=self.zero_experts,
            act=self.expert_act)
        return routed, (layers.concat(counts, axis=0) if self.zero_experts
                        else counts[0])

    def _residual(self, h, o):
        if self.residual_scale is not None:
            o = layers.scale(o, self.residual_scale)
        return layers.elementwise_add(h, o)

    def _halves(self, h, i, ctx):
        """Layer i as a block of halves -> (h, the expert layer's counts).
        The expert layer is the shortcut: read at one half's norm, added
        after another's feed-forward, so that in a deployment its exchange
        runs beside the dense work in between."""
        b = self.block
        for j in range(b["halves"]):
            p = "l%d.h%d." % (i, j)
            o = self._latent_attention(self._norm(h, p + "norm_in"),
                                       p + "attn.", i * b["halves"] + j, ctx)
            h = self._residual(h, self._linear(o, self.d, p + "attn.o"))
            u = self._norm(h, p + "norm_pre_mlp")
            if j == b["experts_read"]:
                with name_scope("scmoe_shortcut"):
                    s, counts = self._experts(u, i)
            h = self._residual(h, layers.swiglu(
                u, self.d_ff, "moe_lm.%smlp" % p, self.dtype))
            if j == b["experts_join"]:
                with name_scope("scmoe_shortcut"):
                    h = self._residual(h, s)
        return h, counts

    def _sublayer(self, h, i, ctx):
        """Layer i as ONE sublayer -> (``h + f(norm(h))``, an expert
        layer's counts or None): ``f`` the mixer, the attention with its
        output projection or the expert layer with its shared expert, by
        the layer's type, whose name the layer's operations are traced
        under."""
        t = self.layer_types[i]
        with name_scope(_SUBLAYER_SCOPE[t]):
            a, counts = self._norm(h, "l%d.norm" % i), None
            if t == EXPERTS:
                o, counts = self._feed_forward(a, i)
            elif t == MAMBA:
                o = self._mixer(a, i, ctx)
            else:
                o = self._linear(self._attention(a, i, ctx), self.d,
                                 "l%d.attn.o" % i)
            return self._residual(h, o), counts

    def _last_row(self, x, last_pos):
        """x [1, P, w] -> [1, 1, w]: the row at ``last_pos`` [1]."""
        return layers.gather(layers.transpose(x, [1, 0, 2]), last_pos)

    def _scopes(self, i):
        """What a trace's operations of layer i's mixer go under, where the
        model has a cross-decoder: the decoder, and within it the mixer's
        type."""
        scopes = contextlib.ExitStack()
        if self.cross_from is not None:
            t = self.layer_types[i]
            scopes.enter_context(name_scope(
                "self_decoder" if i < self.cross_from else "cross_decoder"))
            scopes.enter_context(name_scope(
                {MAMBA: "mamba1_mixer" if self.s6 else "mamba2_mixer",
                 GMU: "gmu", CROSS: "cross_attention"}.get(
                     t, "diff_attention" if self.differential
                     else "attention")))
        return scopes

    def hidden(self, tokens, ctx=None):
        """tokens [B, T] -> (h [B, T, d] float32 before the final norm,
        [counts] of the expert layers in order). A prefill whose ``ctx``
        names the prompt's last row (``last_pos``) hands back that row
        alone, [1, 1, d]: cut out before the layers that own no cache,
        where the model ends in such layers, else at the end."""
        emb = layers.embedding(
            tokens, size=[self.vocab_size, self.d], dtype=self.dtype,
            param_attr=ParamAttr(
                name="moe_lm.embed.w",
                initializer=NormalInitializer(0.0, self.std)),
            keep_dims=True)
        h = layers.cast(emb, "float32")
        if self.embed_scale is not None:
            h = layers.scale(h, self.embed_scale)
        all_counts = []
        # what a layer hands to later ones beside the residual stream
        handed = {}
        last_pos = (ctx or {}).get("last_pos")
        for i in range(len(self.layer_types)):
            if self.block:
                h, counts = (self._sublayer if self.single
                             else self._halves)(h, i, ctx)
                if counts is not None:
                    all_counts.append(counts)
                continue
            if last_pos is not None and i == self.tail_from:
                h = self._last_row(h, last_pos)
                if "memory" in handed:
                    handed["memory"] = self._last_row(handed["memory"],
                                                      last_pos)
            t = self.layer_types[i]
            with self._scopes(i):
                a = self._norm(h, "l%d.norm_in" % i)
                if t == MAMBA:
                    o = self._mixer(a, i, ctx, handed)
                elif t == GMU:
                    o = self._gmu(a, i, handed)
                else:
                    o = self._linear(self._attention(a, i, ctx, handed),
                                     self.d, "l%d.attn.o" % i,
                                     self.attn_bias)
            if self.post_norms:
                o = self._norm(o, "l%d.norm_post_attn" % i)
            h = self._residual(h, o)
            f, counts = self._feed_forward(
                self._norm(h, "l%d.norm_pre_mlp" % i), i)
            if self.post_norms:
                f = self._norm(f, "l%d.norm_post_mlp" % i)
            h = self._residual(h, f)
            if counts is not None:
                all_counts.append(counts)
        if last_pos is not None and self.tail_from is None:
            h = self._last_row(h, last_pos)
        return h, all_counts

    def _head(self, h):
        h = self._norm(h, "norm_final")
        if self.tie_embeddings:
            # the embedding's own rows, read as they lie
            logits = layers.linear(h, self.vocab_size, "moe_lm.embed.w",
                                   self.dtype, self.std, transpose_w=True)
        else:
            logits = self._linear(h, self.vocab_size * self.pred_heads,
                                  "lm_head")
        if self.logit_scale is not None:
            logits = layers.scale(logits, self.logit_scale)
        return logits

    def _next_token_row(self, logits, rows):
        """[.., pred_heads * V] -> [rows, V]: the head that predicts the
        next token, the first of however many the model has."""
        if self.pred_heads > 1:
            logits = layers.slice(logits, [len(logits.shape) - 1], [0],
                                  [self.vocab_size])
        return layers.reshape(logits, [rows, self.vocab_size])

    # -- what lm_session calls ----------------------------------------------
    def logits(self, tokens, cache_ctx=None):
        return self._head(self.hidden(tokens, cache_ctx)[0])

    def prefill_row(self, tokens, last_pos, cache_ctx):
        # the last real row before the head: [1,P,d] -> [P,1,d] -> [1,1,d];
        # the head over every row of the bucket would be P x V logits
        at, _ = self.hidden(tokens, dict(cache_ctx, last_pos=last_pos))
        return self._next_token_row(self._head(at), 1)

    def decode_row(self, tokens, cache_ctx):
        h, counts = self.hidden(tokens, cache_ctx)
        row = self._next_token_row(self._head(h), tokens.shape[0])
        return row, (layers.stack(counts, axis=0) if counts else None)

    def draft(self, overrides):
        raise ValueError("moe_lm has no speculative draft (and neither a "
                         "window nor a state kind of layer cache takes "
                         "speculation)")


def moe_lm(tokens, labels, **sizes):
    """tokens/labels: [B, T] ids (labels = tokens shifted); ``sizes`` are
    :class:`MoeLM`'s. Returns (loss, logits)."""
    model = MoeLM(**sizes)
    logits = layers.cast(model.logits(tokens), "float32")
    t = tokens.shape[1]
    tok_loss = layers.softmax_with_cross_entropy(
        model._next_token_row(logits, -1),
        layers.reshape(labels, [-1, 1]))
    return layers.mean(layers.reshape(tok_loss, [-1, t])), logits


def moe_lm_session(slots, cache_len, prompt_buckets, block_size, num_blocks,
                   window_num_blocks=None, kv_dtype="float32", bos_id=0,
                   eos_id=1, cache_ns=None, chunk_num_blocks=None, **sizes):
    """The paged prefill and decode programs of :func:`moe_lm` (a
    ``GenerationSpec``): ``num_blocks`` sizes the first kind of layer
    cache (the full layers', where the model has any), and
    ``window_num_blocks`` the window layers' where it has both; a state
    kind has one row a slot; ``chunk_num_blocks`` sizes a chunk kind, whose
    block holds ``block_size`` summaries. Greedy; positions are rotary or
    none, so a sequence is bounded by ``cache_len`` alone."""
    model = MoeLM(**sizes)
    return lm_session(
        model, max_len=cache_len, slots=slots, cache_len=cache_len,
        prompt_buckets=prompt_buckets, bos_id=bos_id, eos_id=eos_id,
        cache_ns=cache_ns, dtype=kv_dtype,
        block_size=block_size, num_blocks=num_blocks, prefix_cache=False,
        decode_policy=None,
        kind_blocks={"window": window_num_blocks, "state": slots,
                     "chunk": chunk_num_blocks}
        if len(model.kinds) > 1 else None)
