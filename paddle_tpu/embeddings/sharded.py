"""Row-sharded embedding tables — the pserver seam rebuilt for ICI.

The reference system's entire distributed runtime (the C++ pserver and
the Go pserver/master, PAPER.md §2) exists to serve one workload: sparse
embedding lookups against tables too big for any single worker, hashed
across shards by ``row_id % num_shards``
(``SparseParameterDistribution.cpp``). Here that seam is rebuilt as ICI
collectives inside the jitted step instead of parameter-server RPC:

* **Storage** — a distributed table of logical shape ``[vocab, dim]`` is
  materialized as one global ``[padded_vocab, dim]`` array in
  *mod-interleaved (shard-major) layout*: storage row ``s*rps + k``
  holds logical row ``k*n + s`` (``n`` shards, ``rps = padded_vocab/n``
  rows per shard). Under ``NamedSharding P(data_axis, None)`` shard
  ``s``'s contiguous block is then exactly the rows with
  ``id % n == s`` — the pserver hash rule expressed as a layout, so the
  mesh's block placement IS the mod placement. ``padded_vocab`` rounds
  the vocab up to a multiple of :data:`PAD_MULTIPLE` so the same static
  program shape serves any power-of-two shard count (elastic resizes
  re-permute, never reshape — see checkpoint.py).
* **Lookup** — a two-hop ``all_to_all`` inside ``shard_map``:
  each device hashes its batch's ids to owning
  shards, exchanges id buckets (hop 1, index wire width), gathers rows
  from its local shard, and exchanges the rows back (hop 2). Bucket
  capacity is the device's own id count, so the exchange is static-
  shaped and skew-proof (a device can never send one shard more ids
  than it has).
* **Gradient** — the backward op reverses the route: output-row
  gradients travel TO the owning shard, are merged per shard
  (``merge_duplicate_rows``), and surface as a SelectedRows-style
  (Rows, Values) pair in global shard-major coordinates — the
  optimizers' existing sparse scatter path applies them. A step never
  materializes a dense gradient the size of the table.

With no mesh (or ``embedding_shard_rows`` off, or a shard count that
doesn't divide the padded vocab) everything degrades to a single-shard
identity layout and a dense gather — numerically identical, zero
collectives. With ``embedding_a2a`` off but sharding on, the gather
goes through the mod layout as a global-view ``take`` and GSPMD picks
the collectives (the compiler-chosen baseline the probe compares
against).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..observability import metrics as _metrics

__all__ = ["PAD_MULTIPLE", "padded_vocab", "to_shard_major", "to_logical",
           "register_table", "dist_tables", "active_shards"]

# Vocab padding granularity: every power-of-two shard count up to 64
# divides it, so one static [padded_vocab, dim] program shape survives
# any elastic resize on a power-of-two mesh (resharding permutes rows,
# it never changes shapes — the executor compile cache keeps its
# entries and checkpoints stay shape-compatible).
PAD_MULTIPLE = 64

# -- always-registered telemetry (recording armed per trace by the
# ``telemetry`` flag; family creation is one-time and free) ------------
_LOOKUP_ROWS = _metrics.REGISTRY.counter(
    "paddle_embedding_lookup_rows_total",
    "Embedding rows looked up through distributed tables (ids per "
    "step, duplicates included)")
_A2A_BYTES = _metrics.REGISTRY.counter(
    "paddle_embedding_a2a_bytes_total",
    "Bytes exchanged over the embedding all_to_all, by payload: "
    "direction=ids (index hops) / direction=rows (row payload hops), "
    "forward and backward both counted",
    labelnames=("direction",))
_UNIQUE_RATIO = _metrics.REGISTRY.gauge(
    "paddle_embedding_unique_ratio",
    "Unique/total ids of the last distributed-lookup batch (duplicate "
    "merge leverage: low ratio = merge_duplicate_rows saves work)")


def padded_vocab(vocab):
    """Vocab rounded up to a multiple of :data:`PAD_MULTIPLE`."""
    v = int(vocab)
    return -(-v // PAD_MULTIPLE) * PAD_MULTIPLE


def to_shard_major(table, num_shards):
    """Logical row order -> mod-interleaved storage order (host numpy).

    Storage row ``s*rps + k`` receives logical row ``k*n + s``; with
    ``num_shards == 1`` the layout is the identity."""
    n = int(num_shards)
    t = np.asarray(table)
    if n <= 1:
        return t
    if t.shape[0] % n:
        raise ValueError("table rows %d not divisible by %d shards"
                         % (t.shape[0], n))
    return np.ascontiguousarray(
        t.reshape((t.shape[0] // n, n) + t.shape[1:])
        .swapaxes(0, 1).reshape(t.shape))


def to_logical(table, num_shards):
    """Inverse of :func:`to_shard_major`."""
    n = int(num_shards)
    t = np.asarray(table)
    if n <= 1:
        return t
    if t.shape[0] % n:
        raise ValueError("table rows %d not divisible by %d shards"
                         % (t.shape[0], n))
    return np.ascontiguousarray(
        t.reshape((n, t.shape[0] // n) + t.shape[1:])
        .swapaxes(0, 1).reshape(t.shape))


def register_table(program, name, vocab, padded, dim, slot_of=None):
    """Record a distributed table (or one of its optimizer slots) on
    its program — the registry DistStrategy placement, the executor
    cache key, and checkpoint reshard all read."""
    tables = getattr(program, "_dist_embeddings", None)
    if tables is None:
        tables = {}
        program._dist_embeddings = tables
    tables[name] = {"vocab": int(vocab), "padded": int(padded),
                    "dim": int(dim), "slot_of": slot_of}


def dist_tables(program):
    """The program's distributed-table registry (or None)."""
    return getattr(program, "_dist_embeddings", None)


def active_shards(strategy, padded):
    """(num_shards, mesh, axis) the mod layout splits into under this
    strategy — 1/None/None whenever row sharding cannot apply (no
    strategy, ``embedding_shard_rows`` off, no data axis, or a shard
    count that doesn't divide the padded vocab). Storage layout,
    placement, and the traced ops all derive from this one rule, so
    they can never disagree within a run."""
    if strategy is None:
        return 1, None, None
    from .. import config as _config
    if not _config.get_flag("embedding_shard_rows"):
        return 1, None, None
    axis = strategy.data_axis
    if axis is None:
        return 1, None, None
    n = strategy.data_shards()
    if n <= 1 or int(padded) % n:
        return 1, None, None
    return n, strategy.mesh, axis


# -- traced routes -----------------------------------------------------

def _bucketize(flat, local_rows, n, sentinel):
    """Static-shape id bucketing: stable-sort ids by owning shard and
    lay shard s's ids at ``bucket[s, :counts[s]]`` (rest = sentinel).
    Returns (bucket [n, m], order [m], idx [n, m], valid [n, m]) — the
    same (order, idx, valid) reassemble replies or gradients."""
    m = flat.shape[0]
    owner = flat % n
    order = jnp.argsort(owner)  # jnp.argsort is stable
    sorted_local = local_rows[order]
    counts = jnp.bincount(owner, length=n)
    offs = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    col = jnp.arange(m)
    idx = offs[:, None] + col[None, :]
    valid = col[None, :] < counts[:, None]
    bucket = jnp.where(valid, sorted_local[jnp.clip(idx, 0, m - 1)],
                       sentinel)
    return bucket, order, idx, valid


def _local_rows(flat, n, rps, pad):
    """Per-id local row within the owning shard; padding_idx ids are
    pushed to the out-of-range sentinel ``rps`` (their forward output
    is zeroed, their gradient dropped)."""
    local = flat // n
    if pad is not None:
        local = jnp.where(flat == pad, rps, local)
    return local


def _a2a_lookup(dim, mesh, axis, n, rps, wire=None):
    """Two-hop all_to_all lookup on the shard-major table. Local rows
    already carry the pad sentinel; sentinel/invalid slots come back
    as zero rows. ``wire="int8"`` quantizes rows SHARD-SIDE before the
    return hop (symmetric per-row amax/127 int8 + one f32 scale per
    row crosses the wire instead of f32 rows — ~3.9x fewer payload
    bytes at dim 128) and dequantizes after; zero/sentinel rows
    quantize to exactly zero, and the gradient route stays f32."""

    def f(w_loc, flat_loc, local_loc):
        m = flat_loc.shape[0]
        bucket, order, idx, valid = _bucketize(flat_loc, local_loc, n,
                                               rps)
        recv = jax.lax.all_to_all(bucket, axis, 0, 0)        # [n, m]
        rows = jnp.where((recv < rps)[..., None],
                         w_loc[jnp.clip(recv, 0, rps - 1)], 0.0)
        if wire == "int8":
            amax = jnp.max(jnp.abs(rows), axis=-1, keepdims=True)
            qscale = jnp.where(amax > 0.0, amax / 127.0,
                               jnp.ones_like(amax))
            qrows = jnp.clip(jnp.rint(rows / qscale), -127.0, 127.0) \
                .astype(jnp.int8)
            back = jax.lax.all_to_all(qrows, axis, 0, 0) \
                .astype(w_loc.dtype) \
                * jax.lax.all_to_all(qscale, axis, 0, 0)     # [n, m, D]
        else:
            back = jax.lax.all_to_all(rows, axis, 0, 0)      # [n, m, D]
        out_sorted = jnp.zeros((m + 1, dim), w_loc.dtype).at[
            jnp.where(valid, idx, m)].set(back, mode="drop")[:m]
        return jnp.zeros_like(out_sorted).at[order].set(out_sorted)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    return shard_map(
        f, mesh=mesh, in_specs=(P(axis, None), P(axis), P(axis)),
        out_specs=P(axis, None), check_vma=False)


def _a2a_grad(dim, axis, n, rps, vp):
    """Reverse route: output-row gradients travel to the owning shard,
    get merged per shard, and surface as (Rows, Values) in global
    shard-major coordinates (sentinels -> ``vp``, dropped by the
    optimizer scatter)."""
    from ..ops.sparse_ops import merge_duplicate_rows

    def f(g_loc, flat_loc, local_loc):
        m = flat_loc.shape[0]
        bucket, order, idx, valid = _bucketize(flat_loc, local_loc, n,
                                               rps)
        g_sorted = g_loc[order]
        bvals = jnp.where(valid[..., None],
                          g_sorted[jnp.clip(idx, 0, m - 1)], 0.0)
        rrows = jax.lax.all_to_all(bucket, axis, 0, 0)       # [n, m]
        rvals = jax.lax.all_to_all(bvals, axis, 0, 0)        # [n, m, D]
        s = jax.lax.axis_index(axis)
        grows = jnp.where(rrows < rps, rrows + s * rps, vp).reshape(-1)
        return merge_duplicate_rows(grows.astype(jnp.int32),
                                    rvals.reshape(-1, dim), vp)

    return f


def _trace_mode(flat_len, vp):
    """(n, mesh, axis, use_a2a, telemetry, wire) for the current trace
    — one place both ops read; with no strategy set (single device,
    program build-time shape inference) nothing reads any config flag.
    ``wire`` is the forward a2a payload dtype (embedding_wire_dtype,
    only consulted when the a2a route is live; gradients stay f32)."""
    from .. import parallel as _parallel
    strat = _parallel.current_strategy()
    if strat is None:
        return 1, None, None, False, False, None
    n, mesh, axis = active_shards(strat, vp)
    from .. import config as _config
    use_a2a = (n > 1 and bool(_config.get_flag("embedding_a2a"))
               and flat_len % n == 0)
    wire = None
    if use_a2a:
        w = _config.get_flag("embedding_wire_dtype")
        if w:
            wire = str(w)
    return (n, mesh, axis, use_a2a, bool(_config.get_flag("telemetry")),
            wire)


def _tel_record(unique, total=0, ids_bytes=0, rows_bytes=0,
                lookup=False):
    """Host callback target (jax.debug.callback): runs once per
    executed step, only when telemetry was armed at trace time."""
    if lookup:
        _LOOKUP_ROWS.inc(float(total))
        if total:
            _UNIQUE_RATIO.set(float(unique) / float(total))
    if ids_bytes:
        _A2A_BYTES.labels(direction="ids").inc(float(ids_bytes))
    if rows_bytes:
        _A2A_BYTES.labels(direction="rows").inc(float(rows_bytes))


def _unique_count(flat):
    if flat.shape[0] == 0:
        return jnp.zeros((), jnp.int32)
    s = jnp.sort(flat)
    return 1 + (s[1:] != s[:-1]).sum().astype(jnp.int32)


def a2a_step_bytes(total_ids, dim, n, itemsize=4, index_itemsize=4):
    """Static per-step exchange volume of one two-hop route, summed
    over devices: the index hop moves ``n * total_ids`` indices, the
    payload hop ``n * total_ids`` rows (bucket capacity = per-device id
    count, so each of the n devices ships n buckets of that size).
    Also the probe's printed comparison basis."""
    ids_b = n * total_ids * index_itemsize
    rows_b = n * total_ids * dim * itemsize
    return ids_b, rows_b


@register_op("lookup_table_dist")
def _lookup_table_dist_op(ctx):
    """Distributed embedding lookup on a mod-interleaved table."""
    w, ids = ctx.input("W"), ctx.input("Ids")
    vp = int(ctx.attr("padded_vocab"))
    pad = ctx.attr("padding_idx")
    squeeze = (not ctx.attr("keep_dims", False) and ids.shape
               and ids.shape[-1] == 1)
    ishape = tuple(ids.shape[:-1] if squeeze else ids.shape)
    dim = w.shape[1]
    flat = ids.reshape(-1).astype(jnp.int32)
    n, mesh, axis, use_a2a, telemetry, wire = _trace_mode(
        flat.shape[0], vp)
    rps = vp // n
    local = _local_rows(flat, n, rps, pad)
    if use_a2a:
        out = _a2a_lookup(dim, mesh, axis, n, rps, wire=wire)(
            w, flat, local)
    else:
        # identity layout (n == 1) or GSPMD-partitioned gather through
        # the mod layout (sharding on, a2a off)
        srow = jnp.clip((flat % n) * rps + local, 0, vp - 1)
        out = jnp.take(w, srow, axis=0)
        if pad is not None:
            out = jnp.where((flat == pad)[:, None], 0.0, out)
    if telemetry:
        total = int(flat.shape[0])
        if use_a2a:
            ids_b, rows_b = a2a_step_bytes(
                total, dim, n, itemsize=1 if wire == "int8" else 4)
            if wire == "int8":
                rows_b += n * total * 4  # per-row f32 scales, return hop
        else:
            ids_b, rows_b = 0, 0
        jax.debug.callback(
            functools.partial(_tel_record, total=total, ids_bytes=ids_b,
                              rows_bytes=rows_b, lookup=True),
            _unique_count(flat))
    return {"Out": out.reshape(ishape + (dim,))}


@register_op("lookup_table_dist_grad")
def _lookup_table_dist_grad_op(ctx):
    """d(lookup_table_dist)/dW as (Rows, Values) in global shard-major
    coordinates — never a dense [padded_vocab, dim] cotangent. In a2a
    mode each shard's received gradients are merged locally
    (merge_duplicate_rows) before the optimizer's global merge."""
    og, ids = ctx.input("OutGrad"), ctx.input("Ids")
    vp = int(ctx.attr("padded_vocab"))
    pad = ctx.attr("padding_idx")
    flat = ids.reshape(-1).astype(jnp.int32)
    dim = og.shape[-1]
    g = og.reshape(flat.shape[0], dim)
    n, mesh, axis, use_a2a, telemetry, _wire = _trace_mode(
        flat.shape[0], vp)
    rps = vp // n
    local = _local_rows(flat, n, rps, pad)
    if use_a2a:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        rows, vals = shard_map(
            _a2a_grad(dim, axis, n, rps, vp), mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis)),
            out_specs=(P(axis), P(axis, None)),
            check_vma=False)(g, flat, local)
    else:
        rows = jnp.where(local >= rps, vp,
                         (flat % n) * rps + local).astype(jnp.int32)
        vals = g
    if telemetry and use_a2a:
        ids_b, rows_b = a2a_step_bytes(int(flat.shape[0]), dim, n)
        jax.debug.callback(
            functools.partial(_tel_record, ids_bytes=ids_b,
                              rows_bytes=rows_b),
            jnp.zeros((), jnp.int32))
    return {"Rows": rows, "Values": vals}
