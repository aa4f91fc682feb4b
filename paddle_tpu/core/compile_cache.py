"""Persistent on-disk compile cache: restart = deserialize, not compile.

The executor's in-memory ``_CacheEntry`` table dies with the process, so
every replica start re-traces and re-compiles every bucket and every
trainer restart recompiles the step — fine for a lab, fatal for an
autoscaling fleet spinning replicas up under load. This module mirrors
that table onto disk (config flag ``compile_cache_dir``): each entry is
the ``jax.stages.Compiled`` executable serialized through
``jax.experimental.serialize_executable`` plus a per-entry JSON manifest
carrying its sha256 digest and the compile environment fingerprint.

**Key stability.** The in-memory key leads with ``program._uid`` — a
per-process counter, useless across restarts. The persistent key is a
sha256 over the *content*: the program's serialized dict
(core/serialization.py), the feed signature, fetch names, donation,
every trace-time flag that keys the in-memory cache, the ingest specs,
and the environment fingerprint (jax/jaxlib versions, backend platform,
device kind and count, XLA_FLAGS). Same source program + same shapes +
same flags + same machine shape ⇒ same digest; anything else is a clean
miss, never a wrong executable.

**Corruption tolerance** (the PR-3 checkpoint discipline): every load
digest-verifies the blob against its manifest; a truncated, bit-flipped,
or unpicklable entry — or one whose manifest is itself torn — is
quarantined to ``corrupt_*`` (bounded evidence, like checkpoint
quarantine) and the caller silently falls back to a normal compile. A
poisoned cache dir can cost a cold start its fast path, never a crash
and never a mis-executed step (the digest covers the whole blob; an
environment mismatch is a skip, not a quarantine). The chaos hook
``cache_corrupt`` (resilience/faults.py) injects exactly this failure.

Trust boundary: the serialized executable format pickles XLA-internal
objects, so (unlike the data-only ``__model__`` JSON) cache dirs and
``compiled/`` artifact members must come from a writer you trust.

**Size bound** (``compile_cache_max_bytes`` flag; 0 = unbounded):
``store()`` publishes its entry first, then evicts coldest entries —
``.bin`` and manifest together, ordered by mtime, which ``load()``
touches on every hit so the ordering is least-recently-USED — until
the dir fits. The just-published entry is never evicted (a cap
smaller than one entry must not make the cache thrash itself empty),
and eviction is store-path-only: a capped dir costs nothing on the
hit path beyond the mtime touch.

Counters (always-on; every event here is a cold-start event, never a
per-step cost): ``paddle_deploy_cache_hits_total`` /
``_misses_total`` / ``_quarantined_total`` / ``_evictions_total``.
"""

import hashlib
import json
import os
import pickle
import threading

import jax
import jaxlib

from ..observability import metrics as _metrics
from ..utils import log as _log

__all__ = ["PersistentCompileCache", "active_cache", "entry_digest",
           "env_fingerprint", "serialize_compiled",
           "deserialize_compiled", "enable_jax_cache"]

CACHE_HITS = _metrics.REGISTRY.counter(
    "paddle_deploy_cache_hits_total",
    "Persistent compile-cache entries deserialized instead of compiled")
CACHE_MISSES = _metrics.REGISTRY.counter(
    "paddle_deploy_cache_misses_total",
    "Persistent compile-cache lookups that fell through to an XLA "
    "compile (absent, env-skewed, or quarantined entry)")
CACHE_QUARANTINED = _metrics.REGISTRY.counter(
    "paddle_deploy_cache_quarantined_total",
    "Persistent compile-cache entries moved to corrupt_* after failing "
    "digest verification or deserialization")
CACHE_EVICTIONS = _metrics.REGISTRY.counter(
    "paddle_deploy_cache_evictions_total",
    "Persistent compile-cache entries evicted (mtime-LRU) to keep the "
    "dir under compile_cache_max_bytes")


def enable_jax_cache(default_dir):
    """Turn on JAX's OWN persistent compilation cache for an entry
    point (chip_smoke.py, benchmarks/run.py) — a mechanism apart from the
    executable cache this module implements, and the only one that
    covers every jit in the process. It lives at
    ``$JAX_COMPILATION_CACHE_DIR`` when the environment names a place —
    and then nowhere else — otherwise at ``default_dir``, which must be
    a fixed path: a directory that moves from run to run never hits.
    Every program is cached, however quick its compile. Returns the
    directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", default_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


class _CorruptEntry(Exception):
    """Internal: entry present but failed verification/deserialization."""


def env_fingerprint():
    """Everything that silently changes what an XLA executable means:
    a serialized binary deserialized into a different environment is a
    MISS, not a candidate."""
    devs = jax.devices()
    platform, kind, n = devs[0].platform, devs[0].device_kind, len(devs)
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": platform,
        "device_kind": kind,
        "n_devices": n,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def entry_digest(program, skey_parts):
    """Stable cross-process digest for one executor cache entry.

    ``skey_parts`` is the in-memory cache key minus its process-local
    head (program uid/version), recorded on the entry by
    ``Executor._prepare``; the program itself contributes through its
    serialized content, so a program rebuilt by the same user code — or
    re-read from an exported ``__model__`` — lands on the same digest.
    """
    from .serialization import program_to_dict
    doc = {
        "program": program_to_dict(program),
        "sig": repr(skey_parts),
        "env": env_fingerprint(),
    }
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()).hexdigest()


def serialize_compiled(compiled):
    """One self-contained blob for a ``jax.stages.Compiled``: the PJRT
    executable payload, the arg/out pytree defs (which jax's
    ``serialize`` hands back separately because pytrees aren't part of
    its payload) and the ids of the devices it was compiled for.
    Raises ValueError when the backend's compilation doesn't support
    serialization."""
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree = _se.serialize(compiled)
    device_ids = [d.id for d in
                  compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload, in_tree, out_tree, device_ids))


def deserialize_compiled(blob):
    """Load a :func:`serialize_compiled` blob onto the devices it was
    compiled for. Without ``execution_devices`` jax binds the executable
    to EVERY local device, and a one-device step on a multi-device host
    then refuses its arguments ("expected 8 shards, got 1")."""
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def sha256_bytes(data):
    return hashlib.sha256(data).hexdigest()


def _write_atomic(path, data, mode="wb"):
    # pid + thread id: two threads storing the same digest must not
    # interleave into one temp file (the loser's os.replace publishes
    # a whole file either way)
    tmp = "%s.tmp.%d.%d" % (path, os.getpid(), threading.get_ident())
    with open(tmp, mode) as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class PersistentCompileCache:
    """Directory of serialized executables, one ``entry_<digest>.bin``
    + ``entry_<digest>.json`` manifest per compile-cache entry."""

    def __init__(self, dirname, max_bytes=0):
        self.dirname = str(dirname)
        # 0 = unbounded; refreshed from the compile_cache_max_bytes
        # flag by active_cache() so a flag change applies to the
        # already-constructed instance
        self.max_bytes = int(max_bytes or 0)
        self._serialize_unsupported = False  # log the first failure only

    def _bin(self, digest):
        return os.path.join(self.dirname, "entry_%s.bin" % digest)

    def _meta(self, digest):
        return os.path.join(self.dirname, "entry_%s.json" % digest)

    def load(self, digest):
        """The deserialized ``Compiled`` for ``digest``, or None.

        Never raises: absent/env-skewed entries are plain misses;
        corrupt entries (torn manifest, digest mismatch, unpicklable
        blob, injected ``cache_corrupt`` fault) are quarantined and
        reported as misses — the caller recompiles."""
        bin_path, meta_path = self._bin(digest), self._meta(digest)
        if not (os.path.exists(bin_path) and os.path.exists(meta_path)):
            CACHE_MISSES.inc()
            return None
        try:
            from ..resilience import faults as _faults
            _faults.fire_point("cache_corrupt")
            try:
                with open(meta_path) as f:
                    meta = json.load(f)
            except (OSError, ValueError) as e:
                raise _CorruptEntry("unreadable manifest: %r" % (e,))
            if meta.get("env") != env_fingerprint():
                # a different jax/backend/topology is SKEW, not damage:
                # leave the entry for the environment that wrote it
                _log.structured("compile_cache_env_skew", digest=digest,
                                entry_env=meta.get("env"))
                CACHE_MISSES.inc()
                return None
            with open(bin_path, "rb") as f:
                blob = f.read()
            if sha256_bytes(blob) != meta.get("sha256"):
                raise _CorruptEntry("blob digest mismatch")
            compiled = deserialize_compiled(blob)
        except Exception as e:
            self._quarantine(digest, repr(e))
            CACHE_MISSES.inc()
            return None
        CACHE_HITS.inc()
        try:
            # LRU touch: a hit entry must outrank write-once-read-
            # never entries when the size cap evicts by mtime
            os.utime(bin_path)
            os.utime(meta_path)
        except OSError:
            pass
        return compiled

    def store(self, digest, compiled):
        """Serialize + publish one entry (atomic per file; the manifest
        lands last, so a crashed writer leaves an entry without a
        manifest — a plain miss). Best-effort: serialization
        unsupported on this backend, or a read-only dir, just means no
        persistent cache."""
        try:
            blob = serialize_compiled(compiled)
        except Exception as e:
            if not self._serialize_unsupported:
                self._serialize_unsupported = True
                _log.structured("compile_cache_serialize_unsupported",
                                error=repr(e))
            return False
        try:
            os.makedirs(self.dirname, exist_ok=True)
            _write_atomic(self._bin(digest), blob)
            _write_atomic(
                self._meta(digest),
                json.dumps({"sha256": sha256_bytes(blob),
                            "bytes": len(blob),
                            "env": env_fingerprint()}).encode())
        except OSError as e:
            _log.structured("compile_cache_store_failed", digest=digest,
                            error=repr(e))
            return False
        self._evict_lru(keep_digest=digest)
        return True

    def _evict_lru(self, keep_digest):
        """Bound the dir to ``max_bytes``: drop whole entries (bin +
        manifest together — a half-evicted entry is just a future
        manifestless miss) coldest-mtime first until the cap fits.
        The entry just published is exempt: a cap smaller than one
        executable must degrade to "cache of one", not evict the
        thing it was asked to keep. Best-effort like store() itself —
        a concurrent writer/evictor losing a race is no error."""
        if not self.max_bytes:
            return
        try:
            entries = {}  # digest -> [mtime, bytes, paths]
            for fname in os.listdir(self.dirname):
                # skip quarantine evidence (bounded separately) and a
                # concurrent writer's in-flight temp files
                if not fname.startswith("entry_") or ".tmp." in fname:
                    continue
                digest = fname[len("entry_"):].rsplit(".", 1)[0]
                path = os.path.join(self.dirname, fname)
                try:
                    st = os.stat(path)
                except OSError:
                    continue
                ent = entries.setdefault(digest, [0.0, 0, []])
                ent[0] = max(ent[0], st.st_mtime)
                ent[1] += st.st_size
                ent[2].append(path)
            total = sum(e[1] for e in entries.values())
            for digest in sorted(entries, key=lambda d: entries[d][0]):
                if total <= self.max_bytes:
                    break
                if digest == keep_digest:
                    continue
                for path in entries[digest][2]:
                    try:
                        os.remove(path)
                    except OSError:
                        pass
                total -= entries[digest][1]
                CACHE_EVICTIONS.inc()
                _log.structured("compile_cache_evicted", digest=digest,
                                freed_bytes=entries[digest][1])
        except OSError:
            pass

    def _quarantine(self, digest, reason):
        """Move a corrupt entry aside (evidence, like checkpoint
        quarantine) and bound the evidence to the newest few."""
        moved = False
        for path in (self._bin(digest), self._meta(digest)):
            if not os.path.exists(path):
                continue
            dst = os.path.join(self.dirname,
                               "corrupt_" + os.path.basename(path))
            n = 0
            while os.path.exists(dst):
                n += 1
                dst = os.path.join(self.dirname, "corrupt_%d_%s"
                                   % (n, os.path.basename(path)))
            try:
                os.rename(path, dst)
                moved = True
            except OSError:
                pass
        if moved:
            CACHE_QUARANTINED.inc()
            _log.structured("compile_cache_quarantined", digest=digest,
                            reason=reason)
            try:
                # bound the evidence to the newest 8 ENTRIES, pruning
                # an entry's .bin and .json together (a stem-split
                # prune would orphan a digestless blob or a blobless
                # manifest — useless as evidence either way)
                groups = {}
                for fname in os.listdir(self.dirname):
                    if not fname.startswith("corrupt_"):
                        continue
                    path = os.path.join(self.dirname, fname)
                    stem = os.path.splitext(fname)[0]
                    mtime, paths = groups.setdefault(stem, (0.0, []))
                    groups[stem] = (max(mtime, os.path.getmtime(path)),
                                    paths)
                    paths.append(path)
                for stem in sorted(groups,
                                   key=lambda s: groups[s][0])[:-8]:
                    for path in groups[stem][1]:
                        os.remove(path)
            except OSError:
                pass


_ACTIVE = {}
_ACTIVE_LOCK = threading.Lock()


def active_cache():
    """The PersistentCompileCache for the ``compile_cache_dir`` flag,
    or None when the flag is unset (zero filesystem access)."""
    from .. import config as _config
    dirname = _config.get_flag("compile_cache_dir")
    if not dirname:
        return None
    dirname = os.path.abspath(str(dirname))
    with _ACTIVE_LOCK:
        cache = _ACTIVE.get(dirname)
        if cache is None:
            cache = PersistentCompileCache(dirname)
            _ACTIVE[dirname] = cache
        # store-path-only flag (load never consults it): refresh here
        # so a flag change reaches the cached instance
        cache.max_bytes = int(
            _config.get_flag("compile_cache_max_bytes") or 0)
        return cache
