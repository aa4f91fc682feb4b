"""KV-cache bookkeeping: the block-pool allocator and the
content-hashed prefix index of a generation session.

A cache row for every slot ([slots, cache_len, d_model] per layer)
would pin the same HBM for a 64-token chat as for a 2048-token document
and cap concurrency by the most
pessimistic bucket. The paged layout (the PagedAttention insight)
stores each layer's K/V as ONE [num_blocks, block_size, d_model] pool;
a sequence owns a host-side *block table* — the list of physical block
ids backing its logical positions — and pins only ``ceil(len /
block_size)`` blocks, so concurrency becomes "pool bytes / live
tokens".

Two host-side objects, both single-threaded by contract (the
scheduler's dispatcher thread is the only caller, like the session):

* :class:`BlockPool` — free-list + per-block refcounts. A block with
  refcount 1 is exclusively owned and writable in place; refcount > 1
  means it is shared (another sequence, or the prefix index's pin) and
  a writer must copy-on-write first. ``check_invariant`` cross-checks
  the refcounts against every live table + the index pins — a leaked
  block is a test failure, not a slow OOM.
* :class:`PrefixIndex` — RadixAttention-style prompt caching at block
  granularity: prefill output blocks are registered under a running
  content hash of their token chunks (the chain hash makes a block's
  identity include its full left context), full-block hits are shared
  read-only across sequences via pool refcounts, and a partial tail
  block is shared up to the longest common token prefix (the sharer
  copies-on-write before extending it). Registered blocks hold one
  index pin each, so prompt K/V survives ``retire()`` and the next
  admission with the same prefix re-prefills only its unshared suffix;
  under pool pressure, pin-only (no live sequence) entries are evicted
  LRU.

Metrics (always-on, the serving discipline):
``paddle_generation_prefix_hits_total`` / ``_prefix_misses_total``
(admissions with/without a shared prefix),
``_prefix_shared_tokens_total`` (prompt tokens NOT re-prefilled),
``_kv_block_cows_total`` (copy-on-write block copies),
``_kv_pool_evictions_total`` (prefix blocks reclaimed under pressure),
``_kv_blocks_in_use`` (gauge per pool); and what a decode step reads and
writes of each kind of layer cache, kept by the kind at the step's launch
(``LayerCache.count_step``): ``_window_context_tokens_total``,
``_latent_rows_attended_total``, ``_state_rows_updated_total``,
``_eva_window_rows_total``, ``_eva_chunk_rows_total``,
``_eva_chunks_written_total``, ``_borrowed_context_tokens_total`` (the rows
walked by layers that own no pool, in the pool of a layer that does), and
``_paged_blocks_total{kind, fill}``, the blocks of pages its walks take.
"""

import collections
import hashlib
import itertools

import numpy as np

from ..observability import metrics as _metrics
from ..observability import request_trace as _rtrace
from ..ops.pallas_attention import _pages_in_buffers, paged_walk

__all__ = ["BlockPool", "PrefixIndex", "PoolExhausted", "CacheKind",
           "LayerCache", "refuse_sharing"]

PREFIX_HITS = _metrics.REGISTRY.counter(
    "paddle_generation_prefix_hits_total",
    "Admissions that reused at least one cached prefix block")
PREFIX_MISSES = _metrics.REGISTRY.counter(
    "paddle_generation_prefix_misses_total",
    "Admissions that found no cached prefix block")
PREFIX_SHARED_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_prefix_shared_tokens_total",
    "Prompt tokens served from cached prefix blocks instead of being "
    "re-prefilled")
BLOCK_COWS = _metrics.REGISTRY.counter(
    "paddle_generation_kv_block_cows_total",
    "Copy-on-write block copies (a sequence wrote into a shared "
    "block)")
POOL_EVICTIONS = _metrics.REGISTRY.counter(
    "paddle_generation_kv_pool_evictions_total",
    "Cached prefix blocks reclaimed under pool pressure (LRU, "
    "pin-only entries)")
BLOCKS_IN_USE = _metrics.REGISTRY.gauge(
    "paddle_generation_kv_blocks_in_use",
    "Referenced blocks in one session's pool (labelled per pool — "
    "sessions side by side must not overwrite each other)",
    labelnames=("pool",))
SPEC_ROLLBACKS = _metrics.REGISTRY.counter(
    "paddle_generation_kv_spec_rollback_blocks_total",
    "Blocks returned by speculative-decoding rollbacks (window rows "
    "past the accepted draft prefix)")

WINDOW_BLOCKS_FREED = _metrics.REGISTRY.counter(
    "paddle_generation_kv_window_blocks_freed_total",
    "Blocks a window kind of layer cache returned because every row of "
    "them lay behind the window")

# What a decode step reads and writes of a kind of layer cache, counted by
# the kind itself (``LayerCache.count_step``) at the step's launch.
WINDOW_CONTEXT_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_window_context_tokens_total",
    "Cached tokens attended by the window layers of decode steps: per "
    "step, the sum over the slots that advanced and over the window "
    "layers of min(context length, window)")
LATENT_ROWS_ATTENDED = _metrics.REGISTRY.counter(
    "paddle_generation_latent_rows_attended_total",
    "Cached latent rows attended by decode steps: per step, the sum over "
    "the slots that advanced and over the attention sites of a latent "
    "kind (one a layer, or one a half of a layer of two halves) of their "
    "context length, the new token included")
EVA_WINDOW_ROWS = _metrics.REGISTRY.counter(
    "paddle_generation_eva_window_rows_total",
    "Rows of an aligned window kind attended by decode steps: per step, "
    "the sum over the slots that advanced and over the kind's layers of "
    "the rows from the query's own window's first position to the query")
EVA_CHUNK_ROWS = _metrics.REGISTRY.counter(
    "paddle_generation_eva_chunk_rows_total",
    "Rows of a chunk kind (one summary a chunk of positions) attended by "
    "decode steps: per step, the sum over the slots that advanced and "
    "over the kind's layers of the chunks that lie before the query's "
    "own window")
EVA_CHUNKS_WRITTEN = _metrics.REGISTRY.counter(
    "paddle_generation_eva_chunks_written_total",
    "Summaries written into a chunk kind's pools, a layer each: by a "
    "decode step for every slot whose row completed a chunk, by a "
    "prefill for every whole chunk of its prompt")
STATE_ROWS_UPDATED = _metrics.REGISTRY.counter(
    "paddle_generation_state_rows_updated_total",
    "State rows advanced by decode steps: per step, the layers of a state "
    "kind times the slots that advanced (each such row is read and "
    "written whole)")

BORROWED_CONTEXT_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_borrowed_context_tokens_total",
    "Cached rows walked by layers in a pool they do not own (cross "
    "layers that compute a query only and read another layer's keys and "
    "values): per step, the sum over the slots that advanced of their "
    "context length, the new token included, times the kind's borrowers")

PAGED_BLOCKS = _metrics.REGISTRY.counter(
    "paddle_generation_paged_blocks_total",
    "Blocks of pages the paged decode kernel's walks take in decode steps "
    "(a block: as many pages of a pool as the kernel's buffers hold, "
    "fetched and attended together): per step, the sum over the slots that "
    "advanced and over the kind's layers. fill=full: every page of the "
    "block is live and its copies are awaited once a pool; fill=partial: "
    "a walk's last block, awaited page by page", labelnames=("kind", "fill"))

_POOL_SEQ = itertools.count()

# One kind of layer cache of a generation spec: the layers that share a
# block table because they keep the same rows. ``window`` None keeps every
# row; a window keeps the last ``window`` rows of a sequence and frees the
# blocks behind them. Each kind has its own pool of ``num_blocks`` and its
# own table feeds; ``layers`` is how many layers are of the kind. A kind
# named ``latent`` keeps every row like ``full``; what differs is on the
# device: one pool a layer, whose row is a token's latent, key and value at
# once (ops/mla_ops.py). A kind named ``state`` keeps no rows of tokens: its
# pool is one fixed-size row a slot in each of its layers (a state-space
# layer's state, ops/ssm_ops.py), a sequence's table is the one entry it is
# bound to from admission to retirement, and the row of a slot is the row of
# its own index, so that a step updates the pool where it lies.
# Two more behaviours are fields of their own. A window that is ``aligned``
# keeps the rows of the sequence's current block of ``window`` positions,
# from the last multiple of ``window`` on: nothing is freed until a
# sequence crosses that edge, and then every block behind it at once. And
# a kind with ``chunk`` > 1 keeps one row for every ``chunk`` positions (a
# summary of them, written when the chunk's last position is: the kind
# named ``chunk``, ops/eva_ops.py), so that its table grows a block every
# ``chunk * block_size`` positions and a sequence of n tokens holds
# ``n // chunk`` rows. ``borrowers`` is how many further layers walk the
# pools of this kind's layers without one of their own (a cross layer reads
# the keys and values another layer wrote): they hold no block and write no
# row, and a decode step's walks of the kind are theirs too.
CacheKind = collections.namedtuple(
    "CacheKind", "name window num_blocks layers prefill_table decode_table "
    "aligned chunk borrowers", defaults=(False, 1, 0))

# Why a kind of layer cache takes neither a shared prefix nor speculation:
# said here once, for ``lm_session`` and ``GenerationSession`` alike.
_NO_SHARING = {
    "state": "a slot's state is one row rewritten whole every step, which "
             "no prefix can share (nothing keeps it as it was at a block's "
             "edge) and no rejected draft can be rolled back from",
    "latent": "its prefill expands keys and values from the prompt's own "
              "latents and attends nothing cached before them",
    "chunk": "its prefill attends the prompt's own rows and summaries and "
             "nothing cached before them, and a summary written at a "
             "chunk's last position cannot be rolled back",
    "window": "blocks shared or rolled back behind a window are not "
              "implemented",
}


def refuse_sharing(kinds):
    """Raise ValueError if a spec with these kinds of layer cache (their
    names, or ``CacheKind``s) cannot serve ``prefix_cache`` or
    ``speculate_k``: the message names the kind that refuses and why."""
    names = [getattr(k, "name", k) for k in kinds]
    names += ["window" for k in kinds if getattr(k, "window", None)]
    for name in _NO_SHARING:
        if name in names:
            raise ValueError(
                "a %s kind of layer cache takes neither prefix_cache nor "
                "speculate_k: %s" % (name, _NO_SHARING[name]))
    if len(kinds) > 1:
        raise ValueError(
            "a spec with more than one kind of layer cache (%s) takes "
            "neither prefix_cache nor speculate_k: only the first kind's "
            "blocks are indexed or rolled back" % ", ".join(names))


class PoolExhausted(RuntimeError):
    """No free block and nothing evictable — the pool is at live
    capacity. Admission gates on ``admit_ok`` so clients normally
    never see this; mid-decode it means the growing sequence must
    finish at its current length (retired with reason 'capacity')."""


class BlockPool:
    """Fixed-size block allocator over one session's K/V pools.

    One block id indexes the same row range of EVERY per-layer K and V
    pool (all layers write the same logical positions), so the
    allocator is per-session, not per-layer. Refcounts, not ownership
    lists: a sequence's table holds one ref per entry, the prefix
    index holds one pin per registered block, and a block returns to
    the free list exactly when its count reaches zero.
    """

    def __init__(self, num_blocks, block_size, kind=None):
        if num_blocks < 1 or block_size < 1:
            raise ValueError("need num_blocks >= 1 and block_size >= 1,"
                             " got %r / %r" % (num_blocks, block_size))
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free = collections.deque(range(self.num_blocks))
        self._ref = [0] * self.num_blocks
        # a gauge child of its own: the pool's serial number, behind the
        # name of its kind of layer cache where it has one ("latent.p7")
        self._label = "p%d" % next(_POOL_SEQ)
        if kind is not None:
            self._label = "%s.%s" % (kind, self._label)
        self._gauge = BLOCKS_IN_USE.labels(pool=self._label)
        self._gauge.set(0)

    # -- accounting ------------------------------------------------------
    def free_count(self):
        return len(self._free)

    def used_count(self):
        return self.num_blocks - len(self._free)

    def refcount(self, block):
        return self._ref[block]

    def _update_gauge(self):
        self._gauge.set(self.used_count())

    # -- lifecycle -------------------------------------------------------
    def alloc(self):
        """One fresh block with refcount 1 (the caller's)."""
        if not self._free:
            # pool pressure is a per-request fate decision (starve /
            # preempt / park) — annotate the active request's trace
            _rtrace.global_event("poolExhausted",
                                 num_blocks=self.num_blocks,
                                 block_size=self.block_size)
            raise PoolExhausted(
                "all %d blocks referenced (%d-row blocks)"
                % (self.num_blocks, self.block_size))
        block = self._free.popleft()
        self._ref[block] = 1
        self._update_gauge()
        return block

    def take(self, block):
        """The free block ``block`` itself, with refcount 1: a state kind
        binds a slot to the row of its own index."""
        if self._ref[block]:
            raise RuntimeError("block %d is taken" % block)
        self._free.remove(block)
        self._ref[block] = 1
        self._update_gauge()
        return block

    def incref(self, block):
        if self._ref[block] < 1:
            raise RuntimeError("incref on free block %d" % block)
        self._ref[block] += 1

    def decref(self, block):
        """Drop one reference; returns True when the block was freed."""
        if self._ref[block] < 1:
            raise RuntimeError("decref on free block %d" % block)
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free.append(block)
            self._update_gauge()
            return True
        return False

    def truncate_table(self, table, n_blocks):
        """Trim a host block table IN PLACE to its first ``n_blocks``
        entries, decref'ing the dropped blocks — the speculative-
        decoding rollback (and the prepare-failure undo): window rows
        past the accepted prefix return their storage to the pool.
        Returns how many blocks were dropped."""
        surplus = table[n_blocks:]
        if not surplus:
            return 0
        del table[n_blocks:]
        for block in surplus:
            self.decref(block)
        return len(surplus)

    def close(self):
        """Retire this pool's gauge child (registry label hygiene on
        session teardown, the breaker-gauge discipline)."""
        BLOCKS_IN_USE.remove(pool=self._label)

    def check_invariant(self, tables, index=None):
        """Assert the pool books balance: every block's refcount equals
        the references the live ``tables`` (iterable of block-id lists)
        plus the ``index`` pins actually hold, free blocks carry zero
        references, and free + referenced covers the whole pool.
        Raises AssertionError with the discrepancy — tests assert this
        after retire/close/failover so a leaked block is a loud
        failure, not a slow OOM."""
        want = collections.Counter()
        for table in tables:
            want.update(int(b) for b in table)
        if index is not None:
            want.update(index.pinned_blocks())
        free = set(self._free)
        assert len(free) == len(self._free), \
            "free list holds duplicates: %r" % (self._free,)
        for block in range(self.num_blocks):
            assert self._ref[block] == want[block], (
                "block %d refcount %d but %d live references "
                "(tables + index pins)"
                % (block, self._ref[block], want[block]))
            assert (self._ref[block] == 0) == (block in free), (
                "block %d refcount %d vs free-list membership %s"
                % (block, self._ref[block], block in free))
        assert len(free) + sum(1 for r in self._ref if r > 0) == \
            self.num_blocks


class LayerCache:
    """The host books of one :class:`CacheKind` in one session: its pool
    and a block table per slot, indexed by logical block as ever. A window
    kind marks the entries it has freed dead (the pool's ``num_blocks``,
    what the table feeds hold for rows nobody owns) and remembers per slot
    the first live one, so that a feed row copies the live entries only. A
    state kind's table is the one row the slot is bound to: its own.
    An **aligned** window is never written behind its edge: an admission's
    table starts with dead entries up to it, and ``first_seen`` is that
    edge's block. A kind whose row is a **chunk** counts its rows as
    ``n_tokens // chunk`` (its table feeds are as wide as any, and mostly
    dead). What a decode step and a prefill read and write of the kind, the
    kind counts itself (``count_step``, ``count_prefill``): the session
    walks its kinds and asks none what it is."""

    @classmethod
    def of_kinds(cls, kinds, block_size, slots, max_blocks, block_bytes):
        """The books of a spec's kinds, in its order (``block_bytes``: what
        a block of each holds over its layers, the spec's
        ``kind_block_bytes``). A chunk kind is handed the aligned window
        kind whose blocks its rows are made from: a query's summaries end
        at that window's edge."""
        caches = [cls(k, block_size, slots, max_blocks, size)
                  for k, size in zip(kinds, block_bytes)]
        for cache in caches:
            if cache.chunk > 1:
                cache.made_from = next(c for c in caches if c.kind.aligned)
        return caches

    def __init__(self, kind, block_size, slots, max_blocks, block_bytes):
        self.kind = kind
        self.window = kind.window
        self.chunk = kind.chunk
        self.state = kind.name == "state"
        if self.state and kind.num_blocks != slots:
            raise ValueError("a state kind has one row a slot: %d rows, %d "
                             "slots" % (kind.num_blocks, slots))
        self.pool = BlockPool(kind.num_blocks, 1 if self.state
                              else block_size, kind.name)
        # where a fresh block comes from: the pool, unless the session puts
        # an allocator of its own in its place (one that evicts first)
        self.alloc = self.pool.alloc
        self.made_from = None
        # entries of a table feed's row
        self.width = 1 if self.state else max_blocks
        self.tables = [[] for _ in range(slots)]
        self.first = np.zeros(slots, np.int64)
        # pages the decode kernel fetches and attends at once of this
        # kind's pools, the kernel's own count from what a block holds
        # over the kind's layers (``block_bytes``); a state kind is not
        # walked
        self.walk_pages = 0 if self.state else _pages_in_buffers(
            block_bytes // kind.layers, max_blocks)

    def extend(self, table, n_tokens, slot):
        """Append to ``table`` what a sequence of ``n_tokens`` in ``slot``
        still lacks: fresh blocks (from ``self.alloc``), or a state kind's
        one row, the slot's own. An empty table of an aligned window
        starts with dead entries for the blocks behind the window's edge,
        which nothing will write. Raises PoolExhausted with the table as
        far as it got."""
        if self.state:
            if not table:
                table.append(self.pool.take(slot))
            return
        if self.kind.aligned and not table:
            table.extend([self.pool.num_blocks] * int(
                self.first_seen(n_tokens)))
        rows = n_tokens // self.chunk
        while len(table) * self.pool.block_size < rows:
            table.append(self.alloc())

    def blocks_for(self, n_tokens):
        """The blocks a sequence of ``n_tokens`` takes at its admission."""
        if self.state:
            return 1
        blocks = -(-(n_tokens // self.chunk) // self.pool.block_size)
        if self.kind.aligned:
            blocks -= int(self.first_seen(n_tokens))
        return blocks

    def drop(self, table):
        """Return the live blocks of a table that belongs to no slot yet
        (an admission undone)."""
        for block in table:
            if block < self.pool.num_blocks:
                self.pool.decref(block)

    def release(self, slot):
        """Return every block the slot still holds."""
        for block in self.tables[slot][self.first[slot]:]:
            self.pool.decref(block)
        self.tables[slot] = []
        self.first[slot] = 0

    def first_seen(self, lengths):
        """The first block the query at position ``lengths`` (the next
        row written) can see, the decode kernel's first live page: a
        window keeps the rows from ``length + 1 - window`` on, an aligned
        one those from the last multiple of ``window`` at or before the
        query."""
        lengths = np.asarray(lengths)
        edge = lengths // self.window * self.window if self.kind.aligned \
            else np.maximum(lengths + 1 - self.window, 0)
        return edge // self.pool.block_size

    def trim(self, slot, first):
        """Free the blocks of ``slot`` before block ``first``: those that
        lie wholly behind its window. Returns how many were freed."""
        table, old = self.tables[slot], int(self.first[slot])
        first = min(int(first), len(table))
        freed = 0
        for j in range(old, first):
            if table[j] < self.pool.num_blocks:     # never written: dead
                self.pool.decref(table[j])
                table[j] = self.pool.num_blocks
                freed += 1
        self.first[slot] = max(old, first)
        return freed

    def feed_row(self, row, slot):
        """Write the slot's live entries into a table-feed row that is
        dead everywhere else."""
        table, first = self.tables[slot], int(self.first[slot])
        row[first:len(table)] = table[first:]

    def table_row(self, table):
        """A table-feed row for ``table``: dead past its end."""
        row = np.full(self.width, self.pool.num_blocks, np.int32)
        row[:len(table)] = table
        return row

    def count_step(self, lengths):
        """Count what a decode step reads and writes of this kind, over its
        layers: ``lengths`` are those of the slots the step advances, the
        new row included."""
        layers = self.kind.layers
        if self.state:
            STATE_ROWS_UPDATED.inc(layers * int(lengths.size))
            return
        self._count_walk_blocks(lengths)
        if self.kind.name == "latent":
            LATENT_ROWS_ATTENDED.inc(layers * int(lengths.sum()))
        elif self.kind.aligned:
            # a query attends its own window's rows, from the edge on
            EVA_WINDOW_ROWS.inc(layers * int(
                (lengths - self._edge(lengths, self.window)).sum()))
        elif self.chunk > 1:
            # a query attends a summary for every chunk before its own
            # window's edge; a chunk is summed up by its last position
            edge = self._edge(lengths, self.made_from.window)
            EVA_CHUNK_ROWS.inc(layers * int(edge.sum()) // self.chunk)
            EVA_CHUNKS_WRITTEN.inc(layers * int(
                (lengths % self.chunk == 0).sum()))
        elif self.window:
            WINDOW_CONTEXT_TOKENS.inc(layers * int(
                np.minimum(lengths, self.window).sum()))
        if self.kind.borrowers:
            BORROWED_CONTEXT_TOKENS.inc(self.kind.borrowers * int(
                (np.minimum(lengths, self.window) if self.window
                 else lengths).sum()))

    def _count_walk_blocks(self, lengths):
        """The blocks of ``walk_pages`` pages the decode kernel's walk of
        this kind takes for sequences of ``lengths`` rows, full ones and
        each walk's last where it is partial, by the kernel's own
        arithmetic (``pallas_attention.paged_walk``). A chunk kind's walk
        is handed the summaries before its window's edge as its length."""
        if self.chunk > 1:
            lengths = self._edge(lengths, self.made_from.window) \
                // self.chunk
        _, n_pages = paged_walk(lengths, self.pool.block_size, self.width,
                                self.window, self.kind.aligned, np)
        for fill, blocks in (("full", n_pages // self.walk_pages),
                             ("partial", n_pages % self.walk_pages > 0)):
            PAGED_BLOCKS.labels(kind=self.kind.name, fill=fill).inc(
                (self.kind.layers + self.kind.borrowers)
                * int(blocks.sum()))

    def count_prefill(self, n_tokens):
        """Count what the prefill of a prompt of ``n_tokens`` writes of
        this kind beyond its rows: a chunk kind's summaries."""
        if self.chunk > 1:
            EVA_CHUNKS_WRITTEN.inc(self.kind.layers
                                   * (n_tokens // self.chunk))

    @staticmethod
    def _edge(lengths, window):
        """The first position of the aligned window that holds each
        sequence's newest row."""
        return (lengths - 1) // window * window

    def check_invariant(self, index=None):
        self.pool.check_invariant(
            (t[f:] for t, f in zip(self.tables, self.first)), index)


def _chain_digest(parent, chunk):
    """Content hash of one block-size token chunk, chained through its
    left context: the same tokens after a different prefix hash
    differently, so a block is only ever shared between sequences whose
    ENTIRE history up to that block matches."""
    h = hashlib.blake2b(parent, digest_size=16)
    h.update(np.ascontiguousarray(chunk, dtype=np.int64).tobytes())
    return h.digest()


class PrefixIndex:
    """Block-granular prompt cache over one :class:`BlockPool`.

    * ``match(tokens)`` — longest cached prefix: full-chunk chain-hash
      hits first, then the registered partial tail with the longest
      common token prefix. Returns ``(n_tokens, [block ids])`` without
      taking references (the admitting caller increfs what it keeps).
    * ``register(tokens, table)`` — after a prefill wrote the blocks,
      publish every full chunk (and the partial tail) of ``tokens``;
      newly registered blocks get one index pin (incref) so they
      outlive the sequence.
    * ``evict_one()`` — reclaim the LRU entry whose block no live
      sequence references (refcount == the pin alone); the allocator
      calls this under pressure before giving up.
    """

    def __init__(self, pool):
        self.pool = pool
        self.block_size = pool.block_size
        self._full = {}        # chain digest -> block id
        self._tails = {}       # chain digest -> {token tuple: block id}
        # LRU over every registered entry: key -> ("full", digest) or
        # ("tail", digest, tokens); move_to_end on every hit
        self._lru = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.shared_tokens = 0

    def __len__(self):
        return len(self._lru)

    def pinned_blocks(self):
        """Every block currently holding an index pin (one count per
        registered entry) — the invariant checker's view."""
        out = [b for b in self._full.values()]
        for tails in self._tails.values():
            out.extend(tails.values())
        return out

    def _touch(self, key):
        self._lru[key] = True
        self._lru.move_to_end(key)

    # -- lookup ----------------------------------------------------------
    def _walk(self, tokens, touch):
        """Longest cached prefix walk -> (n_matched, blocks). With
        ``touch`` the hit entries refresh their LRU position; without,
        the walk is completely side-effect-free (the placement probe's
        contract — a capacity poll must not rewrite eviction order)."""
        tokens = np.asarray(tokens, np.int64).reshape(-1)
        bs = self.block_size
        digest = b""
        blocks = []
        i = 0
        while (i + 1) * bs <= tokens.size:
            nxt = _chain_digest(digest, tokens[i * bs:(i + 1) * bs])
            block = self._full.get(nxt)
            if block is None:
                break
            digest = nxt
            blocks.append(block)
            if touch:
                self._touch(("full", nxt))
            i += 1
        matched = i * bs
        # partial tail: longest common token prefix with any tail
        # registered under this chain position (>= 1 token shares the
        # block's leading rows; the sharer copies-on-write before
        # writing past them)
        rest = tuple(int(t) for t in tokens[matched:matched + bs])
        best_m, best_blk, best_key = 0, None, None
        for tail, block in self._tails.get(digest, {}).items():
            m = 0
            for a, b in zip(tail, rest):
                if a != b:
                    break
                m += 1
            if m > best_m:
                best_m, best_blk = m, block
                best_key = ("tail", digest, tail)
        if best_blk is not None:
            blocks.append(best_blk)
            matched += best_m
            if touch:
                self._touch(best_key)
        return matched, blocks

    def peek(self, tokens):
        """Matched-prefix LENGTH only, with no side effects at all (no
        counters, no LRU touch): what scheduler placement consults to
        decide whether a long replay journal still fits a prompt
        bucket once its cached prefix is subtracted."""
        matched, _ = self._walk(tokens, touch=False)
        return matched

    def match(self, tokens):
        """Longest cached prefix of ``tokens`` -> (n_matched, blocks).
        The caller caps ``tokens`` (generation always re-prefills at
        least the final prompt token — logits come from hidden states,
        which are not cached). No references are taken here."""
        matched, blocks = self._walk(tokens, touch=True)
        if matched:
            self.hits += 1
            self.shared_tokens += matched
            PREFIX_HITS.inc()
            PREFIX_SHARED_TOKENS.inc(matched)
        else:
            self.misses += 1
            PREFIX_MISSES.inc()
        return matched, blocks

    # -- registration ----------------------------------------------------
    def register(self, tokens, table):
        """Publish the prompt ``tokens`` whose K/V rows live in
        ``table`` (block ids covering positions [0, len(tokens))).
        Chunks already registered are left as-is (the matching path
        shares the very blocks in ``table``); new entries pin their
        block. Returns the new entries' keys, for :meth:`withdraw`."""
        tokens = np.asarray(tokens, np.int64).reshape(-1)
        bs = self.block_size
        digest = b""
        nfull = tokens.size // bs
        new = []
        for i in range(min(nfull, len(table))):
            digest = _chain_digest(digest, tokens[i * bs:(i + 1) * bs])
            if digest not in self._full:
                self._full[digest] = table[i]
                self.pool.incref(table[i])
                new.append(("full", digest))
            self._touch(("full", digest))
        tail = tuple(int(t) for t in tokens[nfull * bs:])
        if tail and len(table) > nfull:
            tails = self._tails.setdefault(digest, {})
            if tail not in tails:
                tails[tail] = table[nfull]
                self.pool.incref(table[nfull])
                new.append(("tail", digest, tail))
            self._touch(("tail", digest, tail))
        return new

    def withdraw(self, keys):
        """Unpin the entries a ``register`` made (its return value) of a
        prefill that turned out to have failed; those evicted since are
        gone already."""
        for key in keys:
            if key in self._lru:
                self._drop(key)

    # -- eviction --------------------------------------------------------
    def _drop(self, key):
        if key[0] == "full":
            block = self._full.pop(key[1])
        else:
            tails = self._tails[key[1]]
            block = tails.pop(key[2])
            if not tails:
                del self._tails[key[1]]
        del self._lru[key]
        self.pool.decref(block)
        return block

    def evictable_count(self):
        """Entries whose block only the index keeps alive — what
        ``admit_ok`` may count as reclaimable capacity."""
        return sum(1 for b in self.pinned_blocks()
                   if self.pool.refcount(b) == 1)

    def evict_one(self):
        """Reclaim the LRU pin-only entry; True when a block was
        freed. Entries whose block a live sequence still references
        are skipped (dropping the pin would free nothing now and
        forfeit the share)."""
        for key in list(self._lru):
            block = (self._full.get(key[1]) if key[0] == "full"
                     else self._tails.get(key[1], {}).get(key[2]))
            if block is not None and self.pool.refcount(block) == 1:
                self._drop(key)
                POOL_EVICTIONS.inc()
                _rtrace.global_event("prefixEvict", block=int(block))
                return True
        return False

    def clear(self):
        """Unpin everything (session close): every registered block
        drops its index reference."""
        for key in list(self._lru):
            self._drop(key)

    def stats(self):
        return {"hits": self.hits, "misses": self.misses,
                "shared_tokens": self.shared_tokens,
                "entries": len(self._lru)}
