"""Transformer language model / sequence classifier.

Flagship long-context model: causal LM over padded token batches, built
from layers/attention.py; with ``ring_axis`` + a 'sp'-bearing mesh the
attention sequence dimension shards across devices (ring attention).
"""

from .. import layers
from ..layers.attention import (transformer_encoder_layer,
                                positional_encoding,
                                positional_encoding_window)

__all__ = ["transformer_lm", "transformer_lm_generate", "lm_session",
           "transformer_lm_session", "transformer_tp_rules"]


def _lm_backbone(tokens, vocab_size, d_model, num_heads, d_ff, num_layers,
                 ring_axis=None, dropout_prob=0.0, is_test=False,
                 cache_ctx=None):
    """tokens [B,T] -> logits [B,T,V]; parameters named via the shared
    embedding/encoder param_attrs so train and generate programs share
    weights through the scope.

    ``cache_ctx`` (KV-cached generation, transformer_lm_session): dict
    with ``mode`` ('prefill'|'decode'), ``caches`` ([(k, v) block-pool
    Variable pairs per layer]), ``max_len`` (position-table length —
    must equal the table length of the program whose weights are
    served), ``table`` (the block-table feed) and the mode's index
    feeds: for prefill ``key_length``, ``hist`` (cached-prefix depth)
    and ``pos_idx`` (per-window-row position indices, hist +
    arange(P)); for decode ``pos``. Every
    parameter name is identical to the uncached build — cached
    programs serve a scope trained by the plain ones."""
    emb = layers.embedding(tokens, size=[vocab_size, d_model],
                           param_attr="tok_embedding",
                           keep_dims=cache_ctx is not None)
    if cache_ctx is None:
        x = positional_encoding(emb)
    elif cache_ctx.get("pos_idx") is not None:
        # suffix prefill: the window starts at cached depth
        # hist, so its position rows are gathered, not sliced from 0
        x = positional_encoding_window(emb, cache_ctx["max_len"],
                                       pos=cache_ctx["pos_idx"],
                                       window_rows=True)
    else:
        x = positional_encoding_window(emb, cache_ctx["max_len"],
                                       pos=cache_ctx.get("pos"))
    for i in range(num_layers):
        cache = None
        key_length = None
        if cache_ctx is not None:
            ck, cv = cache_ctx["caches"][i]
            cache = {"k": ck, "v": cv, "mode": cache_ctx["mode"],
                     "pos": cache_ctx.get("pos"),
                     "table": cache_ctx.get("table"),
                     "hist": cache_ctx.get("hist")}
            key_length = cache_ctx.get("key_length")
        x = transformer_encoder_layer(
            x, d_model, num_heads, d_ff, causal=True,
            key_length=key_length, ring_axis=ring_axis,
            dropout_prob=dropout_prob, is_test=is_test, cache=cache)
    x = layers.layer_norm(x, begin_norm_axis=2)
    return layers.fc(x, vocab_size, num_flatten_dims=2, bias_attr=False,
                     param_attr="lm_head.w")


def transformer_tp_rules(model_axis="model"):
    """Megatron-style tensor-parallel PartitionSpec rules for the
    transformer params (fed to parallel.DistStrategy): qkv + ffn1
    column-parallel, attention-out + ffn2 row-parallel, lm head and
    token embedding vocab-sharded. XLA inserts the all-reduces at the
    row-parallel seams (the scaling-book recipe)."""
    from .. import parallel
    P = parallel.P
    # UNANCHORED tails (like wide_deep.vocab_shard_rules): optimizer
    # accumulators extend the param name (<param>_moment1_acc_0) and
    # must inherit the sharding; state_sharding's shape-divisibility
    # guard drops the axes on scalars like beta-pow accumulators.
    return [
        (r"\.qkv_[qkv]\.w", P(None, model_axis)),
        (r"\.o\.w", P(model_axis, None)),
        (r"\.ffn1\.w", P(None, model_axis)),
        (r"\.ffn1\.b", P(model_axis)),
        (r"\.ffn2\.w", P(model_axis, None)),
        (r"^lm_head\.w", P(None, model_axis)),
        (r"^tok_embedding", P(model_axis, None)),
    ]


def transformer_lm(tokens, labels, vocab_size, d_model=128, num_heads=4,
                   d_ff=256, num_layers=2, ring_axis=None,
                   dropout_prob=0.0, is_test=False, length=None):
    """tokens/labels: [B, T] ids (labels = tokens shifted). Returns
    (loss, logits)."""
    logits = _lm_backbone(tokens, vocab_size, d_model, num_heads, d_ff,
                          num_layers, ring_axis=ring_axis,
                          dropout_prob=dropout_prob, is_test=is_test)
    t = tokens.shape[1]
    flat_logits = layers.reshape(logits, [-1, vocab_size])
    flat_labels = layers.reshape(labels, [-1, 1])
    tok_loss = layers.softmax_with_cross_entropy(flat_logits, flat_labels)
    tok_loss = layers.reshape(tok_loss, [-1, t])
    if length is not None:
        mask = layers.sequence_mask(length, maxlen=t)
        masked = layers.elementwise_mul(tok_loss, mask)
        loss = layers.elementwise_div(layers.reduce_sum(masked),
                                      layers.reduce_sum(mask))
    else:
        loss = layers.mean(tok_loss)
    return loss, logits


def transformer_lm_generate(batch_anchor, vocab_size, d_model=128,
                            num_heads=4, d_ff=256, num_layers=2,
                            max_len=16, beam_size=4, bos_id=0, eos_id=1,
                            return_all_beams=False, decode="beam",
                            sample_seed=0, temperature=1.0, top_k=0,
                            top_p=1.0):
    """Beam-search generation from the causal LM via the generic
    BeamSearchDecoder (reference beam_search_op composability demo: the
    same decode engine drives GRU NMT and this transformer).

    **Reference implementation** — the step re-runs the full backbone
    over the token history, O(L^2) per sequence: the simple exact
    formulation, kept as the golden oracle for the production path.
    The KV-cached decode (:func:`transformer_lm_session` +
    serving.generation) is O(L) and is tested token-for-token identical
    to this path's greedy (beam_size=1) output
    (tests/test_generation.py).

    ``decode="sample"`` is the stochastic reference path: beam_size is
    forced to 1 and each step samples under the SAME counter-key
    schedule the cached session uses — ``decoding_key(sample_seed,
    position)`` with temperature/top-k/top-p — so cached-vs-reference
    parity tests cover stochastic decode too (the token at sequence
    index *i* is keyed by (seed, i) on both paths; a session decoding
    from a ``[bos]`` prompt with the same seed reproduces this path's
    stream token-for-token).

    ``batch_anchor``: any [B, ...] variable sizing the batch (e.g. an
    int32 dummy [B, 1]). Returns (ids, lengths, scores).
    """
    if decode == "sample":
        beam_size = 1
    bs = layers.BeamSearchDecoder(beam_size=beam_size, max_len=max_len,
                                  bos_id=bos_id, eos_id=eos_id,
                                  decode=decode, sample_seed=sample_seed,
                                  temperature=temperature, top_k=top_k,
                                  top_p=top_p)
    with bs.step():
        bs.token()                       # advances via history
        anchor = bs.state(batch_anchor)  # sizes the batch; never updated
        del anchor
        hist = bs.history()              # [N, max_len] tokens so far
        pos = bs.position()              # [1] current step index
        logits_all = _lm_backbone(hist, vocab_size, d_model, num_heads,
                                  d_ff, num_layers, is_test=True)
        # take logits at the current position: [N,L,V] -> [L,N,V] -> [N,V]
        by_time = layers.transpose(logits_all, [1, 0, 2])
        at_pos = layers.gather(by_time, pos)
        bs.set_logits(layers.reshape(at_pos, [-1, vocab_size]))
    return bs(return_all_beams=return_all_beams)


def transformer_lm_session(vocab_size, d_model=128, num_heads=4,
                           d_ff=256, num_layers=2, max_len=16,
                           slots=None, cache_len=None,
                           prompt_buckets=None, bos_id=0, eos_id=1,
                           cache_ns=None, dtype="float32", paged=None,
                           block_size=None, num_blocks=None,
                           prefix_cache=None, decode_policy="flags"):
    """Build the KV-cached generation programs for the causal LM — the
    O(L)-per-token production decode path (the O(L^2) reference is
    :func:`transformer_lm_generate`).

    Three program families, all parameter names identical to
    :func:`transformer_lm` / the reference generate path (build each
    under ``unique_name.guard()`` to share a trained scope). Each
    layer's K/V storage is ONE [num_blocks, block_size, d_model] block
    pool, and the programs route writes and attention through a
    per-sequence block table feed (ops/generation_ops.py):

    * **prefill** (one per prompt bucket P), a suffix-WINDOW prefill:
      tokens [1, P] plus a ``hist`` feed — the first ``hist`` positions
      are already cached (prefix blocks shared from an earlier
      admission), the window's K/V rows are written through the table
      and its queries attend the cached prefix plus themselves
      causally; the next token at the last prompt position comes back.
      ``hist=0`` is a plain prefill; the shape set stays one program
      per prompt bucket regardless of hist.
    * **decode** (exactly one per (slots, cache_len) shape): one token
      per slot + per-slot positions + a [slots, max_blocks] table feed
      -> K/V appended in place, one single-query attention per layer
      over each slot's live blocks (the ``flash_attention`` flag arms
      the block-table-gather Pallas kernel; dense XLA shares the gather
      semantics), next token per slot.
    * a tiny **block-copy program** (one compile) backs copy-on-write.

    Cache variables are persistable (named under ``cache_ns``, unique
    per session so several sessions can share one scope/params) and
    ride the executor's donated state update — the cache never copies.
    ``max_len`` must equal the position-table length of the program
    whose weights are served. Defaults for ``slots`` /
    ``cache_len`` / ``prompt_buckets`` come from the
    ``generation_slots`` / ``generation_cache_buckets`` /
    ``generation_prompt_buckets`` config flags (read only here — with
    no session built, generation costs nothing anywhere).
    ``block_size`` / ``num_blocks`` / ``prefix_cache`` default to the
    ``generation_block_size`` / ``generation_pool_blocks`` /
    ``generation_prefix_cache`` flags; ``num_blocks=0`` gives every
    slot a whole table (slots x ceil(cache_len / block_size) blocks).
    Slots and pool bytes are DECOUPLED: a session can run more decode
    lanes than whole tables would afford, because a lane pins only its
    live blocks, not a worst-case row. ``paged`` takes None or True
    (benchmarks/architectures/gpt2_block.py:123 passes it).

    **Decode policy** (``decode_policy``, default ``"flags"``: resolve
    the ``decode_*`` config flags via ``DecodePolicy.from_flags`` —
    the ONLY place those flags are read): with a policy, the epilogues
    stop being a hardcoded argmax. Sampling adds per-request
    seed/position feeds and ends in the counter-keyed
    ``decode_sample`` op; a constraint adds an additive logit-mask
    feed; ``speculate_k > 0`` additionally builds a
    **verify program** — a suffix-window prefill at window W = k+1
    whose epilogue (``decode_verify``) re-decides every window
    position with the target's own logits and counts the accepted
    draft prefix — plus a nested greedy **draft spec** (same
    machinery, fresh cache namespace, by default a 1-layer truncation
    of this model so it shares weights through the same scope; pass
    ``decode_draft_model`` overrides and a separate draft scope for
    an independently trained draft). ``decode_policy=None`` forces
    plain greedy regardless of flags. The all-defaults flags resolve
    to None: spec.policy is None and every program ends in the plain
    argmax.

    Returns a :class:`paddle_tpu.serving.generation.GenerationSpec`
    consumed by ``GenerationSession`` / ``GenerationScheduler``.
    """
    return lm_session(
        _GptBlockLM(vocab_size, d_model, num_heads, d_ff, num_layers),
        max_len=max_len, slots=slots, cache_len=cache_len,
        prompt_buckets=prompt_buckets, bos_id=bos_id, eos_id=eos_id,
        cache_ns=cache_ns, dtype=dtype, paged=paged, block_size=block_size,
        num_blocks=num_blocks, prefix_cache=prefix_cache,
        decode_policy=decode_policy)


class _GptBlockLM:
    """The GPT-2 block LM as :func:`lm_session` takes a model: what its
    layers cache and the logits its programs end in."""

    kinds = (("full", None),)
    theta = None                # learned positions: bounded by the table

    def __init__(self, vocab_size, d_model, num_heads, d_ff, num_layers):
        self.vocab_size = vocab_size
        self.dims = dict(d_model=d_model, num_heads=num_heads, d_ff=d_ff,
                         num_layers=num_layers)
        # per layer: (width of a cached K or V row, index of its kind)
        self.cache_layers = [(d_model, 0)] * num_layers

    def logits(self, tokens, cache_ctx):
        return _lm_backbone(tokens, self.vocab_size, is_test=True,
                            cache_ctx=cache_ctx, **self.dims)

    def prefill_row(self, tokens, last_pos, cache_ctx):
        # logits at the last REAL prompt position (ppos = len-1):
        # [1,P,V] -> [P,1,V] -> [1,1,V] -> [1,V]
        by_time = layers.transpose(self.logits(tokens, cache_ctx),
                                   [1, 0, 2])
        at = layers.gather(by_time, last_pos)
        return layers.reshape(at, [1, self.vocab_size])

    def decode_row(self, tokens, cache_ctx):
        """-> (logits [slots, V], the step's expert counts or None)."""
        return layers.reshape(self.logits(tokens, cache_ctx),
                              [tokens.shape[0], self.vocab_size]), None

    def draft(self, overrides):
        """The speculative draft: by default a 1-layer truncation of the
        target — identical parameter names for the layers it keeps, so
        running it over the TARGET's scope shares embedding/head/layer-0
        weights, a free self-draft. ``decode_draft_model`` overrides
        the dims (then give the session a separate draft scope)."""
        dkw = dict(self.dims, num_layers=1)
        if overrides:
            unknown = set(overrides) - set(dkw)
            if unknown:
                raise ValueError("decode_draft_model keys %r not in "
                                 "%r" % (sorted(unknown),
                                         sorted(dkw)))
            dkw.update(overrides)
        return _GptBlockLM(self.vocab_size, **dkw)


def lm_session(model, max_len=16, slots=None, cache_len=None,
               prompt_buckets=None, bos_id=0, eos_id=1, cache_ns=None,
               dtype="float32", paged=None, block_size=None,
               num_blocks=None, prefix_cache=None, decode_policy="flags",
               kind_blocks=None):
    """The KV-cached generation programs of a causal LM, whatever its
    block: :func:`transformer_lm_session` (whose docstring describes the
    programs and every argument) with the model behind an object.
    ``model`` offers ``vocab_size``; ``kinds``, its kinds of layer cache
    as (name, window) pairs, the first the one ``num_blocks`` sizes (a
    third entry, a dict, holds what else ``paged_cache.CacheKind`` takes:
    ``aligned`` for a window that is freed whole at its edge, ``chunk``
    for a kind whose row stands for that many positions, ``borrowers`` for
    the further layers that walk this kind's pools and own none: such a
    layer is no entry of ``cache_layers`` and reads another's by its
    index);
    ``cache_layers``, per layer cache (one a layer, or one an attention
    site where a layer has several: the model reads
    ``cache_ctx["caches"]`` by the same index) the width of a cached row
    and the index of its kind; optionally ``cache_pools``, the pools a
    layer has (default ``("k", "v")``; a latent kind has one, ``("c",)``, whose row is key and
    value at once); whether a shared prefix or a speculative verify can
    be built on its prefill goes by its kinds' names
    (``paged_cache.refuse_sharing``);
    ``logits(tokens, cache_ctx)`` -> [B, T, V];
    ``prefill_row(tokens, last_pos, cache_ctx)`` -> [1, V];
    ``decode_row(tokens, cache_ctx)`` -> ([slots, V], expert counts or
    None; with counts the model offers ``pairs_per_row``, the expert pairs
    a row routes in a step, held here or not, and where its router has
    identity experts ``zero_experts``: each layer's counts then end in
    the step's identity pairs); ``draft(overrides)`` ->
    the speculative draft's model.
    ``kind_blocks`` sizes the pools of the kinds after the first, by
    name; their table feeds are ``gen.ptab.<name>`` / ``gen.dtab.<name>``
    and reach the model as ``cache_ctx["tables"]``, one per kind.
    A kind named ``state`` is no rows of keys and values: a layer of it
    names in ``cache_layers`` the shapes of a slot's row, ``(ssm, conv)``,
    and has the pools ``ssm`` and ``conv`` (float32 whatever ``dtype``) and
    ``at`` (int32, the tokens a row has absorbed), one row a slot; its
    table feeds hold one entry a sequence."""
    import numpy as np
    from .. import config as _config
    from ..core import unique_name as _un
    from ..core.framework import Program, program_guard
    from ..serving.generation import GenerationSpec
    from ..serving.decoding import DecodePolicy
    from ..serving.paged_cache import CacheKind, refuse_sharing

    if decode_policy == "flags":
        decode_policy = DecodePolicy.from_flags()
    policy = decode_policy
    vocab_size = model.vocab_size
    # (name, window, what else the kind's CacheKind takes)
    kinds = tuple((k[0], k[1], dict(k[2]) if len(k) > 2 else {})
                  for k in model.kinds)
    sampled = policy is not None and policy.sampled
    constraint = None if policy is None else policy.constraint
    spec_k = 0 if policy is None else policy.speculate_k
    pools = tuple(getattr(model, "cache_pools", ("k", "v")))

    if slots is None:
        slots = int(_config.get_flag("generation_slots"))
    if slots < 1:
        raise ValueError("slots must be >= 1, got %r" % (slots,))
    if cache_len is None:
        bucks = sorted(int(b) for b in
                       _config.get_flag("generation_cache_buckets"))
        cache_len = next((b for b in bucks if b >= max_len),
                         bucks[-1] if bucks else max_len)
    cache_len = max(int(cache_len), int(max_len))
    if prompt_buckets is None:
        prompt_buckets = _config.get_flag("generation_prompt_buckets")
    prompt_buckets = tuple(sorted({
        min(int(p), max_len) for p in prompt_buckets if int(p) >= 1}))
    if not prompt_buckets:
        raise ValueError("need at least one prompt bucket")
    if cache_ns is None:
        # generated OUTSIDE the guards below, so two sessions over the
        # same scope never collide on cache names while still sharing
        # every parameter name
        cache_ns = _un.generate("kv_session")
    if dtype == "float32":
        # bf16 (or other) K/V pools: resolved ONCE here at construction;
        # the resolved value rides the spec's cache_vars, the draft
        # spec, and _rebuild — no further flag reads. Params and
        # activations stay f32; only the cache storage narrows (the
        # decode kernels/references upcast at the contraction).
        kvd = _config.get_flag("generation_kv_dtype")
        if kvd:
            dtype = str(kvd)
    if paged is not None and not paged:
        # the keyword survives for benchmarks/architectures/gpt2_block.py:123,
        # which passes True
        raise ValueError(_config._DENSE_KV_REMOVED)
    if block_size is None:
        block_size = int(_config.get_flag("generation_block_size"))
    block_size = max(1, int(block_size))
    max_blocks = -(-cache_len // block_size)   # ceil
    if num_blocks is None:
        num_blocks = int(_config.get_flag("generation_pool_blocks"))
    # unsized: a whole table for every slot, so that no sequence starves
    num_blocks = int(num_blocks) or slots * max_blocks
    if prefix_cache is None:
        prefix_cache = bool(_config.get_flag("generation_prefix_cache"))
    rows = [num_blocks] + [int((kind_blocks or {})[name])
                           for name, _, _ in kinds[1:]]
    # the first kind's table feeds go by the bare names, a later kind's
    # end in the kind's
    cache_kinds = tuple(
        CacheKind(name, window, rows[k],
                  sum(1 for _, lk in model.cache_layers if lk == k),
                  "gen.ptab.%s" % name if k else "gen.ptab",
                  "gen.dtab.%s" % name if k else "gen.dtab", **more)
        for k, (name, window, more) in enumerate(kinds))
    if spec_k or prefix_cache:
        refuse_sharing(cache_kinds)

    def layer_pools(width, k):
        """(pool, shape, dtype) of a layer's cache variables."""
        if kinds[k][0] == "state":
            ssm, conv = width
            return (("ssm", (rows[k],) + tuple(ssm), "float32"),
                    ("conv", (rows[k],) + tuple(conv), "float32"),
                    ("at", (rows[k],), "int32"))
        return tuple((pool, (rows[k], block_size, width), dtype)
                     for pool in pools)

    # (name, shape, dtype) layer by layer, as the spec lists them
    cache_vars = [tuple(("%s.l%d.%s" % (cache_ns, i, pool), shape, held)
                        for pool, shape, held in layer_pools(width, k))
                  for i, (width, k) in enumerate(model.cache_layers)]

    def make_cache_vars(program):
        block = program.global_block()
        return [tuple(block.create_var(
            name=name, shape=shape, dtype=held, persistable=True,
            stop_gradient=True) for name, shape, held in layer)
            for layer in cache_vars]

    def table_feeds(names, lead):
        """One table feed a kind, as the model indexes them. A sequence's
        table is ``max_blocks`` wide, a state kind's one."""
        return [layers.data(feed, shape=lead + [
            1 if kind.name == "state" else max_blocks], dtype="int32",
            append_batch_size=False)
            for feed, kind in zip(names, cache_kinds)]

    def _policy_epilogue(row, seed=None, step=None, mask=None):
        """row [n, V] -> next token [n] under the resolved policy.
        The policy-off shape is the same argmax as ever; constraint
        masks are ADDED to the logits (0 legal / -inf banned) before
        whichever chooser runs."""
        if mask is not None:
            row = layers.elementwise_add(row, mask)
        if sampled:
            return layers.decode_sample(
                row, seed, step, temperature=policy.temperature,
                top_k=policy.top_k, top_p=policy.top_p)
        return layers.argmax(row, axis=-1)

    def _policy_feeds(prefix, n):
        """Declare the per-program policy feeds: seed [n] int64 +
        step [n] int32 when sampling (step = the generated token's
        sequence position, the counter in decoding_key), mask [n, V]
        when constrained. Returns (seed, step, mask) vars (None when
        unused) and the extra feed names in order."""
        seed = step = mask = None
        names = []
        if sampled:
            seed = layers.data(prefix + "seed", shape=[n],
                               dtype="int64", append_batch_size=False)
            step = layers.data(prefix + "step", shape=[n],
                               dtype="int32", append_batch_size=False)
            names += [prefix + "seed", prefix + "step"]
        if constraint is not None:
            mask = layers.data(prefix + "mask",
                               shape=[n, vocab_size], dtype="float32",
                               append_batch_size=False)
            names.append(prefix + "mask")
        return seed, step, mask, tuple(names)

    prefill_programs = {}
    prefill_fetch = None
    prefill_extra = ()
    for P in prompt_buckets:
        prog = Program(name="prefill_%d" % P)
        with _un.guard(), program_guard(prog, Program()):
            toks = layers.data("gen.ptok", shape=[1, P], dtype="int64",
                               append_batch_size=False)
            plen = layers.data("gen.plen", shape=[1], dtype="int32",
                               append_batch_size=False)
            ppos = layers.data("gen.ppos", shape=[1], dtype="int32",
                               append_batch_size=False)
            phist = layers.data("gen.phist", shape=[1], dtype="int32",
                                append_batch_size=False)
            ppix = layers.data("gen.ppix", shape=[P], dtype="int32",
                               append_batch_size=False)
            ptabs = table_feeds([k.prefill_table for k in cache_kinds], [])
            cache_ctx = {"mode": "prefill", "caches": None,
                         "table": ptabs[0], "tables": ptabs,
                         "hist": phist, "pos_idx": ppix,
                         "key_length": plen, "max_len": max_len}
            pseed, pstep, pmask, prefill_extra = _policy_feeds(
                "gen.p", 1)
            cache_ctx["caches"] = make_cache_vars(prog)
            # the row at the last REAL prompt position (ppos = len-1)
            row = model.prefill_row(toks, ppos, cache_ctx)
            nxt = _policy_epilogue(row, seed=pseed, step=pstep,
                                   mask=pmask)
        prefill_programs[P] = prog
        prefill_fetch = nxt.name

    decode_program = Program(name="decode")
    with _un.guard(), program_guard(decode_program, Program()):
        toks = layers.data("gen.dtok", shape=[slots, 1], dtype="int64",
                           append_batch_size=False)
        dpos = layers.data("gen.dpos", shape=[slots], dtype="int32",
                           append_batch_size=False)
        dtabs = table_feeds([k.decode_table for k in cache_kinds], [slots])
        cache_ctx = {"mode": "decode", "caches": None, "table": dtabs[0],
                     "tables": dtabs,
                     "pos": dpos, "max_len": max_len}
        dseed, dstep, dmask, decode_extra = _policy_feeds(
            "gen.d", slots)
        cache_ctx["caches"] = make_cache_vars(decode_program)
        row, stats = model.decode_row(toks, cache_ctx)
        nxt = _policy_epilogue(row, seed=dseed, step=dstep, mask=dmask)
    decode_fetch = nxt.name

    # copy-on-write primitive: block Src -> block Dst in EVERY
    # layer's K and V pool (one block id addresses the same row
    # range of all of them). One program, one compile, feeds only.
    copy_program = Program(name="copy")
    with _un.guard(), program_guard(copy_program, Program()):
        csrc = layers.data("gen.csrc", shape=[1], dtype="int32",
                           append_batch_size=False)
        cdst = layers.data("gen.cdst", shape=[1], dtype="int32",
                           append_batch_size=False)
        cblock = copy_program.global_block()
        # the first kind's layers: only its blocks are ever shared
        for cvars, (_, k) in zip(make_cache_vars(copy_program),
                                 model.cache_layers):
            for cvar in cvars if k == 0 else ():
                cblock.append_op(
                    type="kv_block_copy",
                    inputs={"Cache": [cvar.name],
                            "Src": [csrc.name],
                            "Dst": [cdst.name]},
                    outputs={"Out": [cvar.name]})

    verify_program = None
    verify_fetch = None
    verify_feeds = None
    draft_spec = None
    if spec_k:
        # speculative verify: ONE suffix-window prefill at window
        # W = k+1 ([pending_token, draft_1..draft_k]) whose epilogue
        # re-decides every window position with the TARGET's logits
        # under the counter keys and counts the accepted draft prefix.
        # Scoring row i sits at live length hist + i, so this is
        # exactly the window-prefill shape — batch 1, run
        # per speculating slot (the low-batch latency regime
        # speculation exists for).
        W = spec_k + 1
        verify_program = Program(name="verify")
        with _un.guard(), program_guard(verify_program, Program()):
            vtok = layers.data("gen.vtok", shape=[1, W], dtype="int64",
                               append_batch_size=False)
            vlen = layers.data("gen.vlen", shape=[1], dtype="int32",
                               append_batch_size=False)
            vhist = layers.data("gen.vhist", shape=[1], dtype="int32",
                                append_batch_size=False)
            vpix = layers.data("gen.vpix", shape=[W], dtype="int32",
                               append_batch_size=False)
            vtab = layers.data("gen.vtab", shape=[max_blocks],
                               dtype="int32", append_batch_size=False)
            vseed = layers.data("gen.vseed", shape=[1], dtype="int64",
                                append_batch_size=False)
            cache_ctx = {"mode": "prefill",
                         "caches": make_cache_vars(verify_program),
                         "table": vtab, "hist": vhist, "pos_idx": vpix,
                         "key_length": vlen, "max_len": max_len}
            logits = model.logits(vtok, cache_ctx)
            vtoks, vaccept = layers.decode_verify(
                logits, vtok, vseed, vhist, kind=policy.kind,
                temperature=policy.temperature, top_k=policy.top_k,
                top_p=policy.top_p)
        verify_feeds = ("gen.vtok", "gen.vlen", "gen.vhist",
                        "gen.vpix", "gen.vtab", "gen.vseed")
        verify_fetch = (vtoks.name, vaccept.name)
        # the draft: the same call, a pool with a whole table for every
        # slot (it runs ahead of the target and must never starve), no
        # prefix index, plain greedy policy (a deterministic draft
        # collapses modified rejection sampling to prefix matching; see
        # decoding_ops). Its rollback is a truncation of the session's
        # ``lengths``: rejected rows are overwritten in place, inside
        # blocks its table already holds.
        draft_spec = lm_session(
            model.draft(policy.draft), max_len=max_len, slots=slots,
            cache_len=cache_len, prompt_buckets=prompt_buckets,
            bos_id=bos_id, eos_id=eos_id, cache_ns=None, dtype=dtype,
            block_size=block_size, num_blocks=slots * max_blocks,
            prefix_cache=False, decode_policy=None)

    def _rebuild():
        # the session-rebuild factory (serving.generation): identical
        # programs/parameters, but cache_ns=None forces a FRESH cache
        # namespace — a wedged step leaked from the torn-down session
        # can only ever write to the old, orphaned names
        return lm_session(
            model, max_len=max_len, slots=slots, cache_len=cache_len,
            prompt_buckets=prompt_buckets, bos_id=bos_id,
            eos_id=eos_id, cache_ns=None, dtype=dtype,
            block_size=block_size, num_blocks=num_blocks,
            prefix_cache=prefix_cache, decode_policy=policy,
            kind_blocks=kind_blocks)

    # what a block (a state kind: a row) holds over each kind's layers
    kind_block_bytes = tuple(
        sum(int(np.prod(shape[1:])) * np.dtype(held).itemsize
            for layer, (_, lk) in zip(cache_vars, model.cache_layers)
            if lk == k for _, shape, held in layer)
        for k in range(len(kinds)))

    return GenerationSpec(
        slots=slots, cache_len=cache_len, max_len=max_len,
        prompt_buckets=prompt_buckets, bos_id=bos_id, eos_id=eos_id,
        cache_vars=tuple(var for layer in cache_vars for var in layer),
        prefill_programs=prefill_programs,
        prefill_feeds=("gen.ptok", "gen.plen", "gen.ppos", "gen.phist",
                       "gen.ppix", "gen.ptab") + prefill_extra,
        prefill_fetch=prefill_fetch,
        decode_program=decode_program,
        decode_feeds=("gen.dtok", "gen.dpos", "gen.dtab") + decode_extra,
        decode_fetch=decode_fetch,
        rebuild=_rebuild,
        block_size=block_size, num_blocks=num_blocks,
        max_blocks=max_blocks, prefix_cache=bool(prefix_cache),
        copy_program=copy_program, copy_feeds=("gen.csrc", "gen.cdst"),
        vocab_size=vocab_size, policy=policy,
        verify_program=verify_program, verify_feeds=verify_feeds,
        verify_fetch=verify_fetch, draft_spec=draft_spec,
        cache_kinds=cache_kinds,
        stats_fetch=None if stats is None else stats.name,
        routed_pairs=None if stats is None else slots * model.pairs_per_row,
        zero_experts=getattr(model, "zero_experts", 0),
        kind_block_bytes=kind_block_bytes)
