"""Autoregressive generation serving: on-device KV-cache sessions and
a continuous-batching scheduler.

The PR-2/5/7 serving stack is stateless — every request is one padded
batch through one compiled bucket. An LLM request is a *session*: a
prompt is prefilled once, then the model is stepped token by token
against per-sequence state (the KV cache) that must live on device
between steps. This module adds that stateful tier on top of the same
machinery:

* :class:`GenerationSession` — owns one decode batch: ``slots``
  sequences over per-layer [num_blocks, block_size, d_model] K/V block
  pools resident in a Scope as persistable variables, each sequence
  with a host-side block table (serving/paged_cache.py). ``admit()``
  runs a prompt-bucket prefill program that writes ONE sequence's rows
  through its table and
  returns the first greedy token; ``step()`` runs the single decode
  program — one token per slot, per-slot positions — so sequences at
  different depths decode together. Both programs are compiled exactly
  once per shape (the executor's compile cache sees a closed set:
  one decode entry per (slot-bucket, cache-bucket), one prefill entry
  per prompt bucket — asserted via ``Executor.compile_stats()``), and
  the caches ride the executor's donated state update: every step is
  an in-place scatter in HBM, never a cache copy.

* :class:`GenerationScheduler` — continuous batching:
  ``submit(prompt) -> Future`` with the MicroBatcher's admission
  discipline (bounded-queue backpressure -> ServingOverloadError,
  queue-wait EWMA shedding of hopeless deadlines, per-request
  deadlines -> ServingDeadlineError), a dispatcher thread that admits
  new sequences into free cache slots and retires finished ones
  mid-flight — slot-level, never a whole-batch flush: other sequences
  keep decoding through every admit/retire — plus the engine tier's
  recovery vocabulary: a :class:`ReplicaBreaker` per session
  quarantines a failing session out of admission (trial re-admission
  after cooldown), ``drain()`` serves everything accepted before
  stopping (the redeploy story), and ``swap_weights()`` installs new
  parameter values between decode steps (the deploy-tier hot swap,
  composed with stateful sessions: the flip lands on a step boundary,
  so no single forward pass ever mixes weight versions).

Stateful failure recovery (the zero-client-error contract the
stateless tier has had since PR 5): a session's KV cache is *derived*
state — each request's host-side ``prompt`` + ``tokens`` list is a
complete, deterministic replay journal — so a session fault does not
have to surface to clients:

* **token-replay failover** (``replay_attempts`` /
  ``generation_replay_attempts`` flag): when a session's ``step()``
  or ``admit()`` fails, its in-flight requests are re-queued
  head-of-line carrying their journal; re-admission prefills
  ``prompt ⊕ tokens-so-far`` into a healthy session (promoting to a
  larger prompt bucket when the history outgrew the original one) and
  decoding continues. Greedy decode is deterministic, so the final
  output is token-for-token identical to a fault-free run. Replays
  are bounded per request, the absolute deadline is unchanged across
  them (recovery spends the caller's budget), and a poison prompt
  charges at most one breaker across all its replays — it cannot
  black out every session (the PR-5/7 lesson).
* **session rebuild** (``rebuild_limit`` /
  ``generation_rebuild_limit`` flag): a quarantined session whose
  trial re-admissions keep failing — or that wedged past the step
  timeout — is torn down and reconstructed on a background thread:
  fresh cache variables under a fresh namespace (``spec.rebuild()``;
  a leaked wedged step finishing late scribbles only on orphaned
  names), params re-read from the scope, warmup prefill + decode, and
  an atomic swap into placement on the dispatcher thread. Bounded per
  session: quarantine becomes repair, not amputation.
* **hang-free dispatch** (``step_timeout_ms`` /
  ``generation_step_timeout_ms`` flag): each session's step is
  bounded by the serving tier's worker-thread-timeout pattern
  (``resilience.run_bounded``), so one wedged ``step()`` no longer
  freezes every session and every deadline sweep — a hang is a
  failure (requests replay elsewhere, the breaker opens instantly)
  and the wedged session sits out of placement with its stuck thread
  leaked-and-capped at one.

Nothing here is constructed by default flags: with no session built,
the serving fast path, the batcher, and the executor step are
untouched (the generation_* flags are read only inside constructors),
and with the replay/rebuild/timeout flags at their defaults the
dispatcher loop is the pre-recovery hot path — no flag reads, no
worker threads, failures resolve exceptionally as before.

Metrics (always-on, like the serving front door):
``paddle_generation_requests_total``, ``_tokens_total``,
``_prefills_total``, ``_decode_steps_total``,
``_decode_steps_ahead_total`` (of them, the steps launched while the
one before was uncollected), ``_first_tokens_owed_total`` (the prefills
whose first token was fetched with a decode step queued behind them),
``_retired_total{reason}``, ``_slot_occupancy``,
``_ttft_seconds`` (time to first token), ``_inter_token_seconds``;
the dispatcher's clock by phase: ``_host_ms_total{phase}``,
``_device_wait_ms_total``, and what a step and a prefill worked on:
``_context_tokens_total``, ``_prompt_tokens_total``,
``_prefill_padded_tokens_total`` (see ``GenerationScheduler``, "The
dispatcher's clock"; what a step reads of each kind of layer cache the
kind counts, serving/paged_cache.py); recovery: ``_failover_total``,
``_replayed_tokens_total``, ``_session_rebuilds_total``,
``_step_timeouts_total``, ``_failover_recovery_seconds``.
Shed/deadline events share the serving counters
(``paddle_serving_shed_total`` / ``_deadline_exceeded_total``).
Fault sites: ``generation_step_fail`` (persistent with
``times=None``), ``generation_admit_fail``,
``generation_session_wedge`` — all indexed by session — plus the
decode-policy sites ``decode_draft_mismatch`` (force a full-reject
speculative round) and ``decode_constraint_dead_end`` (force the
typed dead-end client error), both indexed by slot.

Decode policies (PR 17, ``serving/decoding``): a session whose spec
carries a :class:`~paddle_tpu.serving.decoding.DecodePolicy` samples
on device under counter-based keys (``decoding_key(seed, position)``
— the seed is minted per request at the front door, carried in the
replay journal, and re-fed on every replay, so SAMPLED output is as
bit-replayable as greedy), optionally speculates with a draft
session (k drafts verified in ONE suffix-window forward,
rejected rows rolled back via the COW block machinery), and
optionally constrains output with host-compiled additive logit
masks. All of it is construction-gated: no policy, no new feeds, no
new programs — the default dispatcher path is byte-identical.
"""

import collections
import contextlib
import itertools
import queue
import threading
import time
import weakref
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError

import numpy as np

from .. import config as _config
from ..core.executor import Executor
from ..core.scope import global_scope
from ..observability import flight as _flight
from ..observability import metrics as _metrics
from ..observability import request_trace as _rtrace
from ..observability import tracing as _tracing
from ..resilience import faults as _faults
from ..utils import log as _log
from . import resilience as _sres
from .batcher import ServingOverloadError, _resolve, _WAIT_ALPHA
from .decoding.policy import GREEDY_FINGERPRINT, mint_seed
from .paged_cache import (BLOCK_COWS, SPEC_ROLLBACKS, WINDOW_BLOCKS_FREED,
                          LayerCache, PoolExhausted, PrefixIndex,
                          refuse_sharing)
from .resilience import (ReplicaBreaker, ServingDeadlineError,
                         ServingUnavailableError)

__all__ = ["GenerationSpec", "GenerationSession", "GenerationScheduler"]

_REQUESTS = _metrics.REGISTRY.counter(
    "paddle_generation_requests_total",
    "Generation requests admitted into a cache slot")
_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_tokens_total",
    "Tokens decoded across all sequences (prefill's first token "
    "included)")
_PREFILLS = _metrics.REGISTRY.counter(
    "paddle_generation_prefills_total",
    "Prompt prefills executed, per prompt bucket",
    labelnames=("bucket",))
_STEPS = _metrics.REGISTRY.counter(
    "paddle_generation_decode_steps_total",
    "Decode steps executed (one per session step, all slots at once)")
_RETIRED = _metrics.REGISTRY.counter(
    "paddle_generation_retired_total",
    "Sequences retired from their slot", labelnames=("reason",))
_OCCUPANCY = _metrics.REGISTRY.gauge(
    "paddle_generation_slot_occupancy",
    "Active sequences / total cache slots across one scheduler's "
    "sessions (labelled per scheduler — two engines side by side "
    "must not overwrite each other)", labelnames=("scheduler",))
_TTFT_SECONDS = _metrics.REGISTRY.histogram(
    "paddle_generation_ttft_seconds",
    "Submit -> first token latency (queue wait + prefill)")
_INTER_TOKEN_SECONDS = _metrics.REGISTRY.histogram(
    "paddle_generation_inter_token_seconds",
    "Per-sequence latency between consecutive tokens")
_STEPS_AHEAD = _metrics.REGISTRY.counter(
    "paddle_generation_decode_steps_ahead_total",
    "Decode steps put on the device's queue while the session's "
    "previous step was still uncollected (over _decode_steps_total: "
    "the share of steps launched one step ahead)")
_FIRST_TOKENS_OWED = _metrics.REGISTRY.counter(
    "paddle_generation_first_tokens_owed_total",
    "Admissions whose first token was fetched with a decode step already "
    "on the device's queue behind the prefill (over _prefills_total: the "
    "share of prefills whose host turn ran beside the device)")
_HOST_MS = _metrics.REGISTRY.counter(
    "paddle_generation_host_ms_total",
    "Dispatcher milliseconds in host turns (a step's tokens on the "
    "host to the next decode call on the device's queue), by phase: "
    "deliver, admit (holds the prefill's device call), prepare, "
    "dispatch, other",
    labelnames=("phase",))
_DEVICE_WAIT_MS = _metrics.REGISTRY.counter(
    "paddle_generation_device_wait_ms_total",
    "Dispatcher milliseconds blocked on a decode step's result")
_CONTEXT_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_context_tokens_total",
    "Cached tokens attended by decode steps: per step, the sum over "
    "the slots that advanced of their context length, the new token "
    "included")
_ROUTED_PAIRS = _metrics.REGISTRY.counter(
    "paddle_generation_routed_pairs_total",
    "Token-expert pairs routed by the expert layers of decode steps "
    "(rows x top-k), whether the expert is held here or not: over it, "
    "_expert_assignments_total is the share that fell on held experts")
_ZERO_EXPERT_PAIRS = _metrics.REGISTRY.counter(
    "paddle_generation_zero_expert_pairs_total",
    "Token-expert pairs of decode steps that fell on identity experts "
    "(router outputs past the real experts: the pair adds w x and costs "
    "no matmul), every slot's row counted; over _routed_pairs_total the "
    "share of the routed pairs that do no work anywhere")
_MOE_LAYER_STEPS = _metrics.REGISTRY.counter(
    "paddle_generation_moe_layer_steps_total",
    "Expert layers run by decode steps (steps x expert layers)")
_EXPERTS_TOUCHED = _metrics.REGISTRY.counter(
    "paddle_generation_experts_touched_total",
    "Per decode step and expert layer, the experts that took at least "
    "one token of the step's batch, every slot's row counted")
_EXPERT_ASSIGNMENTS = _metrics.REGISTRY.counter(
    "paddle_generation_expert_assignments_total",
    "Token-expert pairs computed by the expert layers of decode steps")
_EXPERT_MAX_LOAD = _metrics.REGISTRY.counter(
    "paddle_generation_expert_max_load_total",
    "Per decode step and expert layer, the pairs of its busiest expert")
_PROMPT_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_prompt_tokens_total",
    "Prompt tokens really prefilled (the prompt less its prefix-cache "
    "hit)")
_PREFILL_PADDED_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_prefill_padded_tokens_total",
    "Tokens the prefill programs ran: the bucket width of each prefill")
_FAILOVERS = _metrics.REGISTRY.counter(
    "paddle_generation_failover_total",
    "Requests re-queued for token-replay after their session failed "
    "(each re-admits into a healthy session, output unchanged)")
_REPLAYED_TOKENS = _metrics.REGISTRY.counter(
    "paddle_generation_replayed_tokens_total",
    "Already-generated tokens re-prefilled by replay re-admissions")
_REBUILDS = _metrics.REGISTRY.counter(
    "paddle_generation_session_rebuilds_total",
    "Quarantined sessions torn down and reconstructed (fresh cache "
    "namespace, warmed) back into placement")
_STEP_TIMEOUTS = _metrics.REGISTRY.counter(
    "paddle_generation_step_timeouts_total",
    "Decode steps that exceeded generation_step_timeout_ms (session "
    "quarantined with its worker thread leaked-and-capped)")
_RECOVERY_SECONDS = _metrics.REGISTRY.histogram(
    "paddle_generation_failover_recovery_seconds",
    "Session failure -> the replayed request decoding again on a "
    "healthy session (re-queue wait + replay prefill)")
_SPEC_DRAFTED = _metrics.REGISTRY.counter(
    "paddle_generation_speculative_drafted_total",
    "Draft tokens proposed by speculative-decoding rounds")
_SPEC_ACCEPTED = _metrics.REGISTRY.counter(
    "paddle_generation_speculative_accepted_total",
    "Draft tokens accepted by the target's verify pass (the ratio to "
    "_drafted_total is the speculative accept rate)")

_STOP = object()

# trial re-admission failures after quarantine before a session is
# torn down and rebuilt (when rebuild is armed): the first failed
# trial may be the tail of a transient; the second says the session
# itself is broken
_REBUILD_AFTER_TRIALS = 2

# distinguishes per-session breaker gauge labels across schedulers
_SCHED_SEQ = itertools.count()

def _scheduler_health(ref):
    """The /healthz component callable for one scheduler: healthy
    while any session can take traffic (closed/half-open breaker) or
    a rebuild is on its way back; None once the scheduler is
    garbage-collected."""
    def snapshot():
        sched = ref()
        if sched is None:
            return None
        states = sched.session_health()
        # _rebuilding belongs to the dispatcher thread and has no
        # lock; this runs on the HTTP request thread, so a concurrent
        # mutation can kill the iteration — retry rather than letting
        # health_snapshot's catch report a healthy scheduler as
        # degraded during exactly the rebuild windows /healthz exists
        # to observe
        for _ in range(4):
            try:
                rebuilding = sorted(sched._rebuilding)
                break
            except RuntimeError:
                continue
        else:
            rebuilding = []
        return {"healthy": not sched._closed and
                (any(s != "open" for s in states)
                 or bool(rebuilding)),
                "closed": sched._closed,
                "sessions": states,
                "rebuilding": rebuilding,
                "active": len(sched._active)}
    return snapshot


# scope -> set of cache-variable names already driven by a live
# session. Two sessions sharing cache names on one scope would
# silently corrupt each other's KV state (slot s of one overwrites
# rows the other's slot s attends), so construction refuses the
# collision — transformer_lm_session generates a fresh cache_ns per
# call, making a second spec the correct way to add a replica.
_CACHE_CLAIMS = weakref.WeakKeyDictionary()


class GenerationSpec:
    """The contract between a model's session builder (e.g.
    ``models.transformer.transformer_lm_session``) and the generic
    session/scheduler: programs plus the feed/fetch naming.

    * ``prefill_programs``: {prompt_bucket P: Program} — a window of
      tokens [1, P] behind ``hist`` cached rows -> first greedy token
      [1], writing the window's rows through the block table.
      ``prefill_feeds`` names (tokens, len, last_pos, hist, pos_idx,
      table).
    * ``decode_program``: one step for ALL slots — tokens [slots, 1] +
      positions [slots] + tables [slots, max_blocks] -> next token per
      slot. ``decode_feeds`` names (tokens, positions, tables).
    * ``cache_vars``: ((name, shape, dtype), ...) persistable
      [num_blocks, block_size, d_model] block POOLS a session
      materializes as device zeros in its scope.
    * ``copy_program``/``copy_feeds``: the copy-on-write block-copy
      program; ``max_blocks`` is the per-sequence table width
      (ceil(cache_len / block_size)), and ``prefix_cache`` arms the
      content-hashed prompt-block index (serving/paged_cache.py).
    * ``rebuild`` (optional): zero-arg factory returning an equivalent
      fresh spec under a NEW cache namespace — what session rebuild
      constructs the replacement from. A fresh namespace is
      load-bearing, not cosmetic: a wedged step leaked on its worker
      thread may finish long after the rebuild and republish the OLD
      cache names into the scope; under a new namespace those writes
      land on orphaned variables, never on the replacement's state.

    ``cache_kinds``: the kinds of layer cache the model has, a tuple of
    ``paged_cache.CacheKind``, one at least (the GPT-2 block's: one
    ``full`` kind). Layers of one kind keep the same rows and share a
    block table: a kind without a ``window`` keeps every block, a window
    kind frees the blocks that fall wholly behind its window. Each kind
    has its own pool of ``num_blocks`` and names its own table feeds; the
    first kind's are ``num_blocks`` and the table feeds of
    ``prefill_feeds`` / ``decode_feeds``. A latent kind has one pool an
    attention site (a layer, or each half of a layer that has two), whose
    row is key and value at once (``cache_vars`` names one variable a
    site, not a K and a V); a state kind three variables a layer, one row
    a slot. ``stats_fetch`` (optional) names a small int array
    ``[expert layers, held experts]`` of the decode program, the pairs
    each held expert took in the step, fetched with the step's tokens;
    ``routed_pairs`` is then how many pairs a step routes, held here or
    not (absent: every expert is held and the counts add up to it);
    with ``zero_experts`` (a router that much wider than its experts)
    the array has one column more, the layer's identity pairs.

    ``kind_block_bytes`` says kind by kind what one block (of a state
    kind: one row) holds over the kind's layers.
    """

    __slots__ = ("slots", "cache_len", "max_len", "prompt_buckets",
                 "bos_id", "eos_id", "cache_vars", "prefill_programs",
                 "prefill_feeds", "prefill_fetch", "decode_program",
                 "decode_feeds", "decode_fetch", "rebuild",
                 "block_size", "num_blocks", "max_blocks",
                 "prefix_cache", "copy_program", "copy_feeds",
                 "vocab_size", "policy", "verify_program",
                 "verify_feeds", "verify_fetch", "draft_spec",
                 "cache_kinds", "stats_fetch", "routed_pairs", "zero_experts",
                 "kind_block_bytes")

    # a constant, kept because benchmarks/harness/serve.py:71 checks it
    paged = True

    def __init__(self, **kwargs):
        kwargs.setdefault("rebuild", None)
        kwargs.setdefault("prefix_cache", False)
        # decode-policy surface (serving/decoding): all None/0 when
        # the decode_* flags sit at their defaults, so every PR-8..16
        # spec construction and pickle stays valid unchanged
        kwargs.setdefault("vocab_size", 0)
        kwargs.setdefault("policy", None)
        kwargs.setdefault("verify_program", None)
        kwargs.setdefault("verify_feeds", None)
        kwargs.setdefault("verify_fetch", None)
        kwargs.setdefault("draft_spec", None)
        kwargs.setdefault("stats_fetch", None)
        kwargs.setdefault("routed_pairs", None)
        kwargs.setdefault("zero_experts", 0)
        for name in self.__slots__:
            setattr(self, name, kwargs.pop(name))
        if kwargs:
            raise TypeError("unknown GenerationSpec fields: %s"
                            % sorted(kwargs))
        # a draft's programs go by roles of their own (Program.name):
        # draft_prefill_<bucket>, draft_decode, draft_copy
        draft = self.draft_spec
        if draft is not None:
            for prog in (*draft.prefill_programs.values(),
                         draft.decode_program, draft.copy_program):
                if prog is not None and prog.name and \
                        not prog.name.startswith("draft_"):
                    prog.name = "draft_" + prog.name


class _Admission:
    """A prefill on the device's queue, between ``admit_launch`` and
    ``admit_collect``: the sequence, the slot and blocks taken for it, and
    ``outs``, the device arrays it will fetch (its first token). Once
    ``admit_enter`` has put the slot in the books with that token owed
    (``entered``), ``published`` holds what the prefix index took of it.
    The handle is also the *tenancy*: a slot's next admission, of the
    same request or another, is another handle. ``admit_s`` is its
    caller's to keep (the scheduler's seconds of ``scheduler:admit``)."""

    __slots__ = ("prompt", "slot", "tables", "bucket", "matched", "outs",
                 "seed", "cstate", "entered", "published", "admit_s")

    def __init__(self, prompt, slot, tables, bucket, matched, outs, seed,
                 cstate):
        self.prompt, self.slot, self.tables = prompt, slot, tables
        self.bucket, self.matched, self.outs = bucket, matched, outs
        self.seed, self.cstate = seed, cstate
        self.entered, self.published, self.admit_s = False, (), 0.0


class _Flight:
    """A decode step on the device's queue, between ``step_launch`` and
    ``step_collect``: the slots it advances with their retirement counts
    at the launch, the device arrays it will fetch, and the cached tokens
    it attends. A speculative round, which is over when it is launched,
    carries what it emitted instead."""

    __slots__ = ("advanced", "retires", "outs", "context", "emitted")

    def __init__(self, advanced=None, retires=None, outs=None, context=0,
                 emitted=None):
        self.advanced, self.retires, self.outs = advanced, retires, outs
        self.context, self.emitted = context, emitted


class GenerationSession:
    """One decode batch: ``spec.slots`` cache slots over one scope.

    Parameters are read from ``scope`` by name (run/load them first —
    a scope trained by the standard program, or a checkpoint/artifact
    restore); cache variables are created here as device zeros. All
    methods are single-threaded by contract: the scheduler's
    dispatcher thread is the only caller in the serving deployment.

    The executor compile cache stays CLOSED over a session's lifetime:
    every ``step()`` has the same (program, feed-signature) key, every
    ``admit()`` one key per prompt bucket — ``compile_stats()`` is the
    proof, asserted in tests and printed by tools/generate_probe.py.
    """

    def __init__(self, spec, scope=None, place=None, draft_scope=None,
                 arm_quant=None):
        import jax.numpy as jnp
        self.spec = spec
        self.scope = scope if scope is not None else global_scope()
        self.place = place  # kept so a rebuild lands on the same device
        self.exe = Executor(place=place)
        # -- int8 quantized compute (serving/quant.py) -----------------
        # construction-time flag read; arming quantizes the scope's
        # weights in place and tags the programs — idempotent across
        # _rebuild (weights already int8 + scale sidecars present).
        # A shared-scope draft's programs MUST join the same arm call
        # (one scope, one selection), so the nested draft constructor
        # is told not to re-arm; a separate-scope draft arms itself.
        if arm_quant is None:
            arm_quant = bool(_config.get_flag("serving_quant_compute"))
        self._quant_armed = []
        if arm_quant:
            from . import quant as _quant
            progs = list(spec.prefill_programs.values())
            progs.append(spec.decode_program)
            if spec.verify_program is not None:
                progs.append(spec.verify_program)
            dspec = spec.draft_spec
            shared_draft = dspec is not None and draft_scope is None
            if shared_draft:
                progs += list(dspec.prefill_programs.values())
                progs.append(dspec.decode_program)
            self._quant_armed = _quant.arm_quant_compute(
                progs, self.scope)
        names = {name for name, _, _ in spec.cache_vars}
        claimed = _CACHE_CLAIMS.setdefault(self.scope, set())
        overlap = sorted(claimed & names)
        if overlap:
            raise ValueError(
                "cache variables %s on this scope are already driven "
                "by another GenerationSession — build a fresh spec "
                "(transformer_lm_session generates a unique cache_ns "
                "per call), or close() the old session" % overlap)
        claimed |= names
        self._claimed = names
        for name, shape, dtype in spec.cache_vars:
            if not self.scope.has_var(name):
                self.scope.set_var(name, jnp.zeros(shape, dtype))
        n = spec.slots
        # the scheduler's round, stamped on this session's spans (0 when
        # driven directly): set by the one thread that drives the session
        self.round = 0
        self.lengths = np.zeros(n, np.int64)     # cached rows per slot
        self.last_token = np.zeros(n, np.int64)  # next token to decode
        self.active = np.zeros(n, bool)
        # the deepest position any sequence may WRITE: bounded by the
        # cache bucket and by the learned position table
        self.max_pos = min(spec.cache_len, spec.max_len)
        # -- block-pool state (serving/paged_cache) ----------------------
        # one entry per kind of layer cache (serving/paged_cache.py
        # LayerCache), walked in the spec's order by everything that takes,
        # feeds, counts or returns blocks
        policy = spec.policy
        if spec.prefix_cache or (policy is not None
                                 and policy.speculate_k > 0):
            refuse_sharing(spec.cache_kinds)
        self.kinds = LayerCache.of_kinds(spec.cache_kinds, spec.block_size,
                                         n, spec.max_blocks,
                                         spec.kind_block_bytes)
        self._window_kinds = tuple(k for k in self.kinds if k.window)
        # the first kind's pool and its host-side block table per slot
        # (physical block ids backing logical rows [0, lengths[slot])), by
        # the names tests and probes read. What a single sharing kind alone
        # has (``refuse_sharing``) is said of them: a shared prefix with its
        # copy-on-write and its evicting allocator, and a speculative
        # round's growth and rollback
        self.pool = self.kinds[0].pool
        self.tables = self.kinds[0].tables
        self.prefix = None
        if spec.prefix_cache:
            self.prefix = PrefixIndex(self.pool)
            self.kinds[0].alloc = self._alloc_block
        # slots whose next write found no allocatable block this
        # step — excluded from step() results; the scheduler (or
        # generate()) finishes them at their current length
        self._starved = set()
        # (bucket, hist, window_len) per prefill — the probe/test
        # surface proving a shared prefix was NOT re-prefilled;
        # bounded (see admit) so a long-lived session
        # doesn't accumulate host memory per admission
        self.prefill_log = []
        # -- decode-policy state (spec.policy; serving/decoding) -------
        self.policy = policy
        self.sampled = policy is not None and policy.sampled
        self.constrained = policy is not None and \
            policy.constraint is not None
        self.speculative = policy is not None and policy.speculate_k > 0
        # per-slot request seed / constraint-automaton state, set at
        # admission, journal-recomputable (the replay contract)
        self.seeds = np.zeros(n, np.int64)
        self.cstate = [None] * n
        self._mask_table = None
        if self.constrained:
            self._mask_table = policy.constraint.mask_table(
                spec.vocab_size)
        # the step's fetches: its tokens, and where the spec names them the
        # expert layers' pair counts beside them
        self._decode_fetches = [spec.decode_fetch]
        if spec.stats_fetch is not None:
            self._decode_fetches.append(spec.stats_fetch)
        # -- steps launched and not yet collected (step_launch) ----------
        # oldest first. A step is prepared with at most one of them
        # uncollected: its tokens are then the next feed on the device
        # (``_merge_tokens``), and only a slot that did not advance in it
        # is fed from the host's ``last_token``. ``_retires`` counts a
        # slot's retirements, so a result that arrives for a sequence
        # retired since its launch is told from its successor's
        self._flights = collections.deque()
        self._retires = np.zeros(n, np.int64)
        # slot -> the admission whose first token is owed (admit_enter):
        # in the books, its prefill launched, the token still on the
        # device, where the next step's feed takes it (``_place_token``)
        self._owed = {}
        # the next feed needs no token on the host: what a scheduler
        # reads to work one step ahead (GenerationScheduler, "One step
        # ahead"). A constraint's mask comes from the token itself and a
        # speculative round interleaves host and device
        self.lookahead = not (self.constrained or self.speculative)
        self._merge_tokens = self._place_token = self._token_dtype = None
        if self.lookahead:
            self._compile_token_merge()
        self.draft = None
        if self.speculative:
            # the draft mirrors the target slot-for-slot: admitted,
            # advanced, and retired in lockstep. Default drafts share
            # the target's scope (parameter-name truncation = free
            # self-draft); dim-changed drafts need their own scope.
            self.draft = GenerationSession(
                spec.draft_spec,
                scope=self.scope if draft_scope is None else draft_scope,
                place=place,
                arm_quant=False if draft_scope is None else None)

    # -- slot bookkeeping ------------------------------------------------
    def free_slots(self):
        return [int(i) for i in np.flatnonzero(~self.active)]

    def active_slots(self):
        return [int(i) for i in np.flatnonzero(self.active)]

    def occupancy(self):
        return float(self.active.sum()) / self.spec.slots

    def capacity_left(self, slot):
        """Decode steps slot can still take before its cache bucket or
        position table runs out."""
        return int(self.max_pos - self.lengths[slot])

    def prompt_bucket(self, n):
        for p in self.spec.prompt_buckets:
            if n <= p:
                return p
        return None

    def compile_stats(self):
        return self.exe.compile_stats()

    # -- the block pools' surface ----------------------------------------
    def admit_ok(self, n_tokens):
        """Can an ``n_tokens``-history admission get storage RIGHT NOW?
        Enough free-or-evictable blocks to cover the
        whole history PLUS one copy-on-write block when the prefix
        cache is armed. The accounting is sharing-independent: if the
        admission matches m cached blocks it needs m fewer fresh ones
        but also pins those m previously-evictable entries, so the two
        cancel and ``free + evictable >= ceil(n/bs) + cow_margin`` is
        the right test without knowing the tokens. The scheduler
        consults this during placement so pool pressure parks a
        request instead of turning into an admit exception that would
        charge a healthy session's breaker."""
        n_tokens = min(int(n_tokens), self.max_pos)
        # a kind holds the whole history until the prefill has run, then
        # what its window keeps
        if self.prefix is None:
            return all(k.pool.free_count() >= k.blocks_for(n_tokens)
                       for k in self.kinds)
        # a matched prefix ending mid-block copies-on-write one
        # extra block during the admission itself — but never
        # demand more than the pool HAS: a history that needs
        # exactly the whole pool can only need the COW block when
        # something matched, in which case the match freed that
        # many fresh allocations; capping keeps such a request
        # admittable instead of parked forever
        need = min(self.kinds[0].blocks_for(n_tokens) + 1,
                   self.pool.num_blocks)
        avail = self.pool.free_count()
        return avail >= need or \
            avail + self.prefix.evictable_count() >= need

    def storable(self, n_tokens):
        """Static bound: could this session's storage EVER hold an
        ``n_tokens`` history? A pool must have enough blocks
        IN TOTAL — placement must not park a request forever on a
        pool that can never satisfy it, however much retires free."""
        return all(self._most_blocks(k, int(n_tokens)) <= k.pool.num_blocks
                   for k in self.kinds)

    def _most_blocks(self, kind, n_tokens):
        """The most blocks a sequence of ``n_tokens`` holds in a kind at
        one time: all its rows' (a chunk kind has a row a chunk), a state
        kind's one row, or with a window those of the longest prompt (a
        prefill writes all its rows before the window is trimmed) or of
        the window with the block being written; an aligned window is
        never written behind its edge and holds its own rows at most."""
        bs = self.spec.block_size
        blocks = kind.blocks_for(n_tokens)
        if kind.state or not kind.window:
            return blocks
        if kind.kind.aligned:
            return min(-(-n_tokens // bs), kind.window // bs)
        return min(blocks, max(-(-self.spec.prompt_buckets[-1] // bs),
                               kind.window // bs + 2))

    def window_fits(self, history):
        """Placement probe for a history whose FULL length fits no
        prompt bucket: with the prefix cache armed, the cached prefix
        shrinks the window that actually needs one — a PR-9 replay
        journal that outgrew every bucket is still admissible here
        when its prompt prefix is cached, so failover composes with
        prefix reuse instead of dying on bucket promotion. Entirely
        side-effect-free (``PrefixIndex.peek``); a session without a
        prefix index returns False."""
        if self.prefix is None:
            return False
        history = np.asarray(history, np.int64).reshape(-1)
        n = history.size
        if n < 1 or n > self.max_pos:
            return False
        matched = self.prefix.peek(history[:n - 1])
        return self.prompt_bucket(n - matched) is not None

    def pool_stats(self):
        """{blocks_in_use, num_blocks, block_size, bytes_per_block} of the
        first kind — probe/bench surface; with more kinds than one,
        ``kinds`` has the same of each by name (a state kind's block is a
        slot's row over its layers)."""
        def stats(kind, size):
            return {"blocks_in_use": kind.pool.used_count(),
                    "num_blocks": kind.pool.num_blocks,
                    "block_size": kind.pool.block_size,
                    "bytes_per_block": size}
        sizes = self.spec.kind_block_bytes
        out = stats(self.kinds[0], sizes[0])
        if len(self.kinds) > 1:
            out["kinds"] = {k.kind.name: stats(k, size)
                            for k, size in zip(self.kinds, sizes)}
        return out

    def prefix_stats(self):
        """Prefix-cache hit counters (zeros when not armed)."""
        if self.prefix is None:
            return {"hits": 0, "misses": 0, "shared_tokens": 0,
                    "entries": 0}
        return self.prefix.stats()

    def check_pool_invariant(self):
        """Assert the block-pool books balance against every live
        table and index pin (serving/paged_cache.py) — the
        pool-accounting invariant tests assert after retire / close /
        failover so a leaked block fails loudly."""
        for kind in self.kinds:
            kind.check_invariant(
                self.prefix if kind.pool is self.pool else None)

    def _alloc_block(self):
        """One fresh block, reclaiming cold prefix-cache entries under
        pressure (LRU, pin-only) before giving up."""
        while True:
            try:
                return self.pool.alloc()
            except PoolExhausted:
                if self.prefix is None or not self.prefix.evict_one():
                    raise

    def _state_bind(self, kind, slot, bound):
        """The span around a state kind's row being bound to ``slot`` or
        returned by it; nothing around a paged kind's blocks."""
        if not kind.state or not (bound or kind.tables[slot]):
            return contextlib.nullcontext()
        return _tracing.span("session:state_bind", round=self.round,
                             slot=slot, bound=bound)

    def _release_table(self, slot):
        for kind in self.kinds:
            with self._state_bind(kind, slot, False):
                kind.release(slot)

    def _copy_block(self, src, dst):
        """Run the block-copy program: block ``src`` -> ``dst`` in
        every layer's K and V pool (device-side, in place under
        donation — COW never round-trips the cache through the
        host)."""
        f_src, f_dst = self.spec.copy_feeds
        self.exe.run(self.spec.copy_program,
                     feed={f_src: np.asarray([src], np.int32),
                           f_dst: np.asarray([dst], np.int32)},
                     fetch_list=[], scope=self.scope)

    def _ensure_writable(self, table, idx):
        """Copy-on-write: if ``table[idx]`` is shared (another
        sequence's table or a prefix-index pin also holds it), copy it
        into a fresh block and swap that into the table — the writer
        diverges onto its own copy, sharers keep the original
        untouched. Raises PoolExhausted when no block is allocatable."""
        old = table[idx]
        if self.pool.refcount(old) <= 1:
            return
        new = self._alloc_block()
        try:
            self._copy_block(old, new)
        except BaseException:
            self.pool.decref(new)
            raise
        self.pool.decref(old)
        table[idx] = new
        BLOCK_COWS.inc()
        # lands on the admitting request's trace (admit-path COW runs
        # under its activated context); step_prepare COWs have no
        # single owner and reach only the flight ring
        _rtrace.global_event("blockCOW", src=int(old), dst=int(new))

    def close(self):
        """Release this session's cache-variable claim (and drop the
        cache arrays from the scope), so a later session may reuse the
        names. Every block reference — slot tables AND prefix
        pins — is returned to the pool first, and the accounting
        invariant is re-checked so a teardown (including the PR-9
        rebuild path, which closes the old session on hand-over) can
        never leak a block. Idempotent; the session must not be
        stepped after."""
        if self.draft is not None:
            self.draft.close()
            self.draft = None
        if self.pool is not None:
            for slot in range(self.spec.slots):
                self._release_table(slot)
            if self.prefix is not None:
                self.prefix.clear()
            self.check_pool_invariant()
            for kind in self.kinds:
                assert kind.pool.used_count() == 0, \
                    "closed session leaked %d blocks of kind %s" % (
                        kind.pool.used_count(), kind.kind.name)
                kind.pool.close()
            self.kinds, self._window_kinds = [], ()
            self.pool = None
            self.prefix = None
        claimed = _CACHE_CLAIMS.get(self.scope)
        if claimed is not None:
            claimed -= self._claimed
        for name in self._claimed:
            self.scope.erase(name)
        self._claimed = set()
        self._flights.clear()
        self._owed.clear()
        self.active[:] = False

    # -- decode-policy plumbing ------------------------------------------
    def _policy_prefill_feed(self, feed, n, seed, cstate):
        """Append the decode-policy feeds to a prefill feed dict.
        ``n`` is the TOTAL history length — the sequence index of the
        token this prefill emits, i.e. the counter in decoding_key —
        so a replay prefilling prompt+journal lands on the exact key
        the original decode used at that position."""
        if self.sampled:
            feed["gen.pseed"] = np.asarray([seed], np.int64)
            feed["gen.pstep"] = np.asarray([n], np.int32)
        if self.constrained:
            c = self.policy.constraint
            state = c.start if cstate is None else cstate
            feed["gen.pmask"] = self._mask_table[
                c.state_index(state)].reshape(1, -1)

    def _policy_admitted(self, slot, first, cstate):
        """Record the constraint's per-slot state once an admission
        emitted its first token."""
        if self.constrained:
            c = self.policy.constraint
            state = c.start if cstate is None else cstate
            self.cstate[slot] = c.advance(state, int(first))

    def _draft_admit(self, prompt, slot, first):
        """Mirror an admission into the draft session (same slot by
        lockstep construction), then pin its pending token to the
        TARGET's emission — the draft guesses continuations of the
        target's trajectory, never its own."""
        if self.draft is None:
            return
        try:
            dslot, _ = self.draft.admit(prompt)
        except BaseException:
            self.retire(slot)
            raise
        if dslot != slot:
            self.retire(slot)
            raise RuntimeError(
                "draft session desynchronized: target slot %d, draft "
                "slot %d" % (slot, dslot))
        self.draft.last_token[slot] = int(first)

    # -- execution -------------------------------------------------------
    def admit(self, prompt, seed=0, cstate=None):
        """Prefill ``prompt`` (1-D int ids) into a free slot: the
        prompt's K/V rows land in the cache, the slot becomes active,
        and the first greedy token is returned as ``(slot, token)``.
        Raises RuntimeError when no slot is free and ValueError when
        the prompt fits no bucket.

        Storage comes from the block pool through a
        fresh block table; with the prefix cache armed, the longest
        content-hash-matched prefix is SHARED (its blocks referenced,
        not recomputed) and only the unshared suffix is prefilled —
        capped at len-1, because logits need the last prompt token's
        hidden state, which only a forward pass produces. The prompt's
        blocks are then registered in the prefix index. All block
        references taken here are rolled back if anything below fails —
        the pool can't leak on an admission error.

        Two phases, like a decode step: :meth:`admit_launch` ends with
        the prefill on the device's queue, :meth:`admit_collect` is the
        wait for its first token and the slot's books. A scheduler that
        works a step ahead calls :meth:`admit_enter` between the two: the
        slot is in the books at once with its first token owed, the next
        decode step takes that token on the device, and the wait comes
        with that step queued behind the prefill."""
        return self.admit_collect(self.admit_launch(prompt, seed, cstate))

    def admit_launch(self, prompt, seed=0, cstate=None):
        """Phase 1 of an admission: the slot, its blocks and the prefill
        call, not waited for. Returns the handle for
        :meth:`admit_collect`; the slot is not active until then (or
        until :meth:`admit_enter`)."""
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        n = prompt.size
        bs = self.spec.block_size
        if n > self.max_pos:
            raise ValueError(
                "prompt length %d exceeds the cache capacity %d"
                % (n, self.max_pos))
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free cache slot (%d active)"
                               % self.spec.slots)
        slot = free[0]
        matched, shared = 0, []
        if self.prefix is not None:
            # cap at n-1: the final prompt token is always re-run —
            # its logits come from hidden states, which are not cached
            matched, shared = self.prefix.match(prompt[:n - 1])
        suffix = prompt[matched:]
        bucket = self.prompt_bucket(suffix.size)
        if bucket is None:
            raise ValueError(
                "prompt length %d (unshared suffix %d) exceeds the "
                "largest prompt bucket %d"
                % (n, suffix.size, self.spec.prompt_buckets[-1]))
        # one table a kind; the first starts with what the prefix shares
        tables = [[] for _ in self.kinds]
        tables[0].extend(shared)
        for block in shared:
            self.pool.incref(block)
        try:
            if matched % bs:
                # the matched prefix ends MID-block: the suffix writes
                # into that shared block, so diverge onto a copy first
                self._ensure_writable(tables[0], len(shared) - 1)
            w = suffix.size
            padded = np.full((1, bucket), self.spec.eos_id, np.int64)
            padded[0, :w] = suffix
            pix = np.clip(matched + np.arange(bucket), 0,
                          self.spec.max_len - 1).astype(np.int32)
            f_tok, f_len, f_pos, f_hist, f_pix = self.spec.prefill_feeds[:5]
            feed = {f_tok: padded,
                    f_len: np.asarray([w], np.int32),
                    f_pos: np.asarray([w - 1], np.int32),
                    f_hist: np.asarray([matched], np.int32),
                    f_pix: pix}
            for kind, table in zip(self.kinds, tables):
                with self._state_bind(kind, slot, True):
                    kind.extend(table, n, slot)
                feed[kind.kind.prefill_table] = kind.table_row(table)
            # the emitted token's index is the TOTAL length n
            # (= matched + w), prefix sharing included
            self._policy_prefill_feed(feed, n, seed, cstate)
            with _tracing.span("session:prefill_call", round=self.round,
                               bucket=bucket, slot=slot, hist=matched):
                outs = self.exe.run(
                    self.spec.prefill_programs[bucket], feed=feed,
                    fetch_list=[self.spec.prefill_fetch],
                    scope=self.scope, return_numpy=False)
        except BaseException:
            self._admit_rollback(tables)
            raise
        return _Admission(prompt, slot, tables, bucket, matched, outs, seed,
                          cstate)

    def _admit_rollback(self, tables):
        for kind, table in zip(self.kinds, tables):
            kind.drop(table)

    def owed(self):
        """The admissions whose first token is owed, oldest first."""
        return list(self._owed.values())

    def owes(self, slot):
        """``slot`` is in the books with its first token owed."""
        return slot in self._owed

    def admit_enter(self, launched):
        """Enter a launched admission in the slot's books with its first
        token *owed*: everything that follows from the prompt alone (the
        tables, the length, the window's trim, the seed, the prefix
        index's entries, the counts), so that the slot is stepped, and the
        next admission sees it taken, before the prefill has run. The
        token stays on the device: ``_token_feed`` hands it to the slot's
        first decode step there, and :meth:`admit_collect` fetches it for
        the host, a launch later. Everything this touches (freed window
        blocks, published prefix blocks) is used by later calls on the
        device's queue than the prefill. Returns the slot."""
        slot, prompt, n = launched.slot, launched.prompt, launched.prompt.size
        if self.prefix is not None:
            # publish the prompt's blocks (full chunks + partial
            # tail) — the next admission sharing this prefix, or a
            # PR-9 token replay of it, prefills only its suffix
            launched.published = self.prefix.register(prompt,
                                                      launched.tables[0])
        for kind, table in zip(self.kinds, launched.tables):
            kind.tables[slot] = table
        self.lengths[slot] = n
        self._trim_windows((slot,))
        self.active[slot] = True
        self.seeds[slot] = int(launched.seed)
        self._starved.discard(slot)
        launched.entered = True
        self._owed[slot] = launched
        self.prefill_log.append((launched.bucket, launched.matched,
                                 n - launched.matched))
        if len(self.prefill_log) > 4096:     # keep a list (tests
            del self.prefill_log[:2048]      # slice it), bounded
        _PREFILLS.labels(bucket=launched.bucket).inc()
        _PROMPT_TOKENS.inc(n - launched.matched)
        _PREFILL_PADDED_TOKENS.inc(launched.bucket)
        for kind in self.kinds:
            kind.count_prefill(n)
        return slot

    def admit_collect(self, launched):
        """Phase 2 of an admission: wait for the prefill's first token
        (``session:prefill_wait``), enter the sequence in the slot's books
        unless :meth:`admit_enter` has, and give the host the token.
        Returns ``(slot, token)``. A prefill that fails here gives back
        all it took: its blocks, and where the slot was entered the slot
        and what the prefix index had published of it (a decode step
        already launched for the slot is told from its next tenant's by
        the retirement count, like any step's for a slot retired since).
        An entered admission whose slot was retired meanwhile has nothing
        left to collect, and says so."""
        slot, entered = launched.slot, launched.entered
        if entered and self._owed.get(slot) is not launched:
            raise RuntimeError("slot %d was retired with this admission's "
                               "first token owed" % slot)
        try:
            with _tracing.span("session:prefill_wait", round=self.round,
                               slot=slot):
                first = int(np.asarray(launched.outs[0]).reshape(-1)[0])
        except BaseException:
            if entered:
                self.retire(slot)
            else:
                self._admit_rollback(launched.tables)
            raise
        if not entered:
            self.admit_enter(launched)
        del self._owed[slot]
        self.last_token[slot] = first
        self._policy_admitted(slot, first, launched.cstate)
        self._draft_admit(launched.prompt, slot, first)
        return slot, first

    def step(self):
        """One decode step for EVERY active slot: each slot's pending
        token is embedded at its own position, its K/V row appended in
        place, and its single query attended against the live cache
        prefix. Returns {slot: next_token} for active slots (free
        slots compute masked garbage that the next prefill
        overwrites). Raises RuntimeError when an active slot is out of
        cache capacity — retire it first.

        A slot whose next write needs a block the pool
        cannot supply (even after evicting cold prefix entries) is
        EXCLUDED from the result — it neither advances nor writes
        (its table feed row is dead, so the device write drops) and
        the caller finishes it at its current length.

        Internally two phases — :meth:`step_prepare` (ALL host-side
        pool/table mutation) then :meth:`step_run` (the device call) —
        so the scheduler's bounded-step path can keep allocator books
        off the worker thread (see step_prepare)."""
        prepared = self.step_prepare()
        if prepared is None:
            return {}
        return self.step_run(prepared)

    def step_prepare(self, hold=()):
        """Phase 1 of a decode step: the active-slot snapshot, the
        capacity check, and EVERY host-side
        pool mutation (block growth, copy-on-write, the table feed)
        plus snapshotted feeds. Returns an opaque handle for
        :meth:`step_run`, or None with nothing to step.

        ``hold`` names active slots that sit this step out: they
        neither write nor advance, like a free slot. A scheduler working
        one step ahead holds the slots whose sequence ends with the step
        still uncollected, or with its first token still owed. With such
        a step uncollected (:meth:`step_launch`) or such a token owed
        (:meth:`admit_enter`) the token feed is built on the device from
        them; any other slot is fed the host's ``last_token``
        (:meth:`_token_feed`).

        The split is a thread-safety contract, not a convenience: the
        scheduler's step-timeout path runs the device call on a
        worker thread it may LEAK past the timeout. A leaked step
        touches only device state and per-slot numpy scalars, which
        tolerate that; allocator refcounts would not —
        so they are only ever touched here, on the caller/dispatcher
        thread, and a wedged worker can never race retire()/close()
        on the pool books.

        One caveat: a copy-on-write divergence runs the (rare,
        per-divergence) block-copy program here too — the table swap
        is only valid once the copy succeeded, so the two cannot be
        split across threads. That device call therefore shares
        ``admit()``'s exposure, not ``step()``'s: like every prefill,
        it runs unbounded on the dispatcher (the step timeout has
        always bounded only the per-token decode call)."""
        active = self.active
        if len(hold):
            active = active.copy()
            active[list(hold)] = False
        act = np.flatnonzero(active)
        if act.size == 0:
            return None
        if len(self._flights) > 1:
            raise RuntimeError(
                "%d decode steps are uncollected — a step is prepared at "
                "most one step ahead" % len(self._flights))
        with _tracing.span("session:step_prepare", round=self.round,
                           active=int(act.size)):
            if (self.lengths[act] >= self.max_pos).any():
                over = [int(s) for s in act
                        if self.lengths[s] >= self.max_pos]
                raise RuntimeError(
                    "slots %s are at cache capacity %d — retire before "
                    "stepping" % (over, self.max_pos))
            if self.speculative:
                W = self.policy.speculate_k + 1
                if all(self.capacity_left(int(s)) >= W for s in act):
                    return self._prepare_spec(act)
                # near capacity: a window write would overrun the cache —
                # fall back to plain single-token rounds, which finish
                # these slots (speculation resumes once they retire)
            # a plain round: grow/copy-on-write each active slot's write
            # block and build the table feed. Inactive and pool-starved
            # slots get all-dead table rows, so their device writes DROP —
            # a slot can never scribble on blocks it does not own
            bs = self.spec.block_size
            self._starved.clear()   # a retire may have freed blocks since
            if self._window_kinds:
                with _tracing.span("session:window_trim", round=self.round):
                    self._trim_windows(act)
            live = []
            for s in act:
                s = int(s)
                pos = int(self.lengths[s])
                try:
                    if self.prefix is not None and \
                            pos // bs < len(self.tables[s]):
                        # writing into a block a sharer or the prefix
                        # index also holds: diverge onto a private copy
                        self._ensure_writable(self.tables[s], pos // bs)
                    for kind in self.kinds:
                        kind.extend(kind.tables[s], pos + 1, s)
                    live.append(s)
                except PoolExhausted:
                    self._starved.add(s)
            f_tok, f_pos = self.spec.decode_feeds[:2]
            feed = {f_tok: self._token_feed(live),
                    f_pos: self.lengths.astype(np.int32)}
            for kind in self.kinds:
                tab = np.full((self.spec.slots, kind.width),
                              kind.pool.num_blocks, np.int32)
                for s in live:
                    kind.feed_row(tab[s], s)
                feed[kind.kind.decode_table] = tab
            self._policy_decode_feed(feed)
            return (act, frozenset(self._starved), feed)

    def _compile_token_merge(self):
        """The device computations a step ahead adds, in the token feed's
        shape and dtype: ``_merge_tokens``, the uncollected step's tokens
        with the host's token where ``from_host`` says so, and
        ``_place_token``, a feed with one slot's token replaced by a
        prefill's first token (applied once an owed slot, so that its
        shape does not depend on how many a step has). Compiled here, with
        the session, so that no step compiles them."""
        import jax
        import jax.numpy as jnp
        from ..core.framework import convert_dtype
        block = self.spec.decode_program.global_block()
        feed = block.var(self.spec.decode_feeds[0])
        out = block.var(self.spec.decode_fetch)
        first = next(iter(self.spec.prefill_programs.values())) \
            .global_block().var(self.spec.prefill_fetch)
        shape, dtype = tuple(feed.shape), convert_dtype(feed.dtype)
        n = self.spec.slots

        def merge(tokens, host, from_host):
            return jnp.where(from_host, host,
                             tokens.reshape(n).astype(dtype)).reshape(shape)

        def place(feed, first, slot):
            return feed.reshape(n).at[slot].set(
                first.reshape(-1)[0].astype(dtype)).reshape(shape)
        self._token_dtype = dtype
        self._merge_tokens = jax.jit(merge).lower(
            jax.ShapeDtypeStruct(tuple(out.shape), convert_dtype(out.dtype)),
            jax.ShapeDtypeStruct((n,), dtype),
            jax.ShapeDtypeStruct((n,), np.bool_)).compile()
        self._place_token = jax.jit(place).lower(
            jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct(tuple(first.shape),
                                 convert_dtype(first.dtype)),
            jax.ShapeDtypeStruct((), np.int32)).compile()

    def _token_feed(self, act):
        """The token feed of a step for the slots ``act``, from three
        sources. The host's ``last_token``; with a step uncollected, that
        step's tokens for the slots that advanced in it, merged on the
        device with the host's for those that did not (starved or held in
        it, or retired since: admitted anew or free); and for a slot whose
        first token is owed (:meth:`admit_enter`) the prefill's own device
        array. No token crosses to the host for a feed. An owed token
        feeds the slot's first step alone: it is fetched
        (:meth:`admit_collect`) before the slot's second is prepared."""
        owed = [s for s in act if s in self._owed] if self._owed else ()
        if not self._flights and not owed:
            return self.last_token.reshape(-1, 1).copy()
        feed = self.last_token.astype(self._token_dtype)
        if self._flights:
            ahead = self._flights[-1]
            from_host = np.ones(self.spec.slots, bool)
            from_host[ahead.advanced[
                ahead.retires == self._retires[ahead.advanced]]] = False
            feed = self._merge_tokens(ahead.outs[0], feed, from_host)
        else:
            feed = feed.reshape(-1, 1)
        for s in owed:
            if self.lengths[s] != self._owed[s].prompt.size:
                raise RuntimeError(
                    "slot %d was stepped with its first token owed: "
                    "admit_collect comes before its second step" % s)
            feed = self._place_token(feed, self._owed[s].outs[0],
                                     np.int32(s))
        return feed

    def _policy_decode_feed(self, feed):
        """Append the decode-policy feeds to a decode-step feed dict.
        Step = lengths + 1: a slot at length L emits the token at
        sequence index L+1 — its decoding_key counter."""
        if self.sampled:
            feed["gen.dseed"] = self.seeds.copy()
            feed["gen.dstep"] = (self.lengths + 1).astype(np.int32)
        if self.constrained:
            c = self.policy.constraint
            mask = np.zeros((self.spec.slots, self.spec.vocab_size),
                            np.float32)
            for s in np.flatnonzero(self.active):
                state = self.cstate[int(s)]
                if state is not None:
                    mask[int(s)] = self._mask_table[c.state_index(state)]
            feed["gen.dmask"] = mask

    def _trim_windows(self, slots):
        """Return to their pools the blocks of ``slots`` that the next
        row's query cannot see (``LayerCache.first_seen``)."""
        freed = 0
        slots = np.asarray(slots, np.int64)
        for kind in self._window_kinds:
            first = kind.first_seen(self.lengths[slots])
            moved = first > kind.first[slots]
            for s, f in zip(slots[moved], first[moved]):
                freed += kind.trim(int(s), f)
        if freed:
            WINDOW_BLOCKS_FREED.inc(freed)

    def _prepare_spec(self, act):
        """Speculative phase 1: extend each active slot's block table
        to cover the verify-window rows [L, L+W) — block growth and
        copy-on-write only, on the dispatcher thread (step_prepare's
        allocator contract). A slot the pool cannot cover is starved
        out of the round exactly like plain starvation, its
        this-round growth returned."""
        bs = self.spec.block_size
        W = self.policy.speculate_k + 1
        self._starved.clear()
        info = {}
        for s in act:
            s = int(s)
            L = int(self.lengths[s])
            tbl = self.tables[s]
            held = len(tbl)
            need = (L + W - 1) // bs + 1
            try:
                for bi in range(L // bs, min(held, need)):
                    self._ensure_writable(tbl, bi)
                while len(tbl) < need:
                    tbl.append(self._alloc_block())
            except PoolExhausted:
                self.pool.truncate_table(tbl, held)
                self._starved.add(s)
                continue
            info[s] = (L, self.kinds[0].table_row(tbl))
        return {"slots": info, "starved": frozenset(self._starved)}

    def step_run(self, prepared, enqueued=None):
        """Phase 2 of a decode step: the device call plus result
        application, :meth:`step_launch` then :meth:`step_collect`.
        Touches no allocator state — safe to execute on
        the scheduler's bounded (leakable) worker thread; the feeds
        and starved-set were snapshotted at prepare time. (The
        speculative round is the one exception: it runs drafting,
        verification AND pool rollback here, which is why the
        scheduler refuses step_timeout_ms on speculative sessions —
        that round only ever executes inline on the dispatcher.)

        ``enqueued``, when given, is called between the two, where the
        host stops working and starts waiting (the scheduler ends its
        host turn there). A speculative round interleaves several device
        calls with host work and is not cut: it is over when
        ``enqueued`` is called."""
        flight = self.step_launch(prepared)
        if enqueued is not None:
            enqueued()
        return self.step_collect(flight)

    def step_launch(self, prepared):
        """Put a prepared step on the device's queue
        (``session:step_dispatch``) and advance the books by what does not
        depend on its tokens: the lengths of the slots it advances. The
        tokens stay on the device until :meth:`step_collect`, in launch
        order; the next step can be prepared and launched before that
        (one step ahead, where ``lookahead`` is true), fed by them there.

        A speculative round is not one asynchronous call: it runs whole
        here, and its collect hands over what it emitted."""
        if isinstance(prepared, dict):
            emitted = self._step_run_spec(prepared)
            flight = _Flight(emitted=emitted, context=int(sum(
                self.lengths[s] for s in emitted)))
            self._flights.append(flight)
            return flight
        act, starved, feed = prepared
        with _tracing.span("session:step_dispatch", round=self.round,
                           active=int(act.size)):
            outs = self.exe.run(
                self.spec.decode_program, feed=feed,
                fetch_list=self._decode_fetches, scope=self.scope,
                return_numpy=False)
        advanced = act if not starved else np.asarray(
            [s for s in act if int(s) not in starved], np.int64)
        self.lengths[advanced] += 1
        lens = self.lengths[advanced]
        if advanced.size:
            for kind in self.kinds:
                kind.count_step(lens)
        flight = _Flight(advanced, self._retires[advanced].copy(), outs,
                         int(lens.sum()))
        self._flights.append(flight)
        return flight

    def step_collect(self, flight):
        """Wait for a launched step's tokens (``session:step_wait``) and
        apply them: ``{slot: token}`` for the slots it advanced. A slot
        retired since the launch is left out: its sequence ended (or its
        session failed) while the step was queued, and the row it wrote
        lies in blocks the slot owned then; whatever reuses them is a
        later call on the device's queue."""
        if self._flights and self._flights[0] is flight:
            self._flights.popleft()
        elif any(f is flight for f in self._flights):
            raise RuntimeError("decode steps are collected in the order "
                               "they were launched")
        # else dropped (drop_flights) while a bounded worker was still in
        # this call: every slot it advanced was retired with it
        if flight.emitted is not None:
            return flight.emitted
        advanced, retires, outs = flight.advanced, flight.retires, \
            flight.outs
        with _tracing.span("session:step_wait", round=self.round):
            for extra in outs[1:]:
                extra.copy_to_host_async()  # beside the tokens, not after
            nxt = np.asarray(outs[0]).reshape(-1)
            if len(outs) > 1:
                self._count_experts(np.asarray(outs[1]))
        result = {}
        for s in advanced[retires == self._retires[advanced]]:
            s = int(s)
            self.last_token[s] = int(nxt[s])
            result[s] = int(nxt[s])
            if self.constrained:
                self.cstate[s] = self.policy.constraint.advance(
                    self.cstate[s], int(nxt[s]))
        if self.draft is not None and result:
            self._draft_mirror_plain(result)
        return result

    def drop_flights(self):
        """Forget the steps launched and not collected: their session
        failed, and what they hold is re-made from the journals."""
        self._flights.clear()

    def _count_experts(self, counts):
        """The routing counters from a step's ``[expert layers, held
        experts]`` pair counts (behind them, where the router has identity
        experts, the layer's identity pairs)."""
        if self.spec.zero_experts:
            _ZERO_EXPERT_PAIRS.inc(int(counts[:, -1].sum()))
            counts = counts[:, :-1]
        held = int(counts.sum())
        _MOE_LAYER_STEPS.inc(counts.shape[0])
        _EXPERTS_TOUCHED.inc(int((counts > 0).sum()))
        _EXPERT_ASSIGNMENTS.inc(held)
        _ROUTED_PAIRS.inc(self.spec.routed_pairs or held)
        _EXPERT_MAX_LOAD.inc(int(counts.max(axis=1).sum()))

    def _draft_mirror_plain(self, result):
        """A plain single-token round under a speculative session (the
        near-capacity fallback): the draft must still append the
        pending token's K/V row to stay coherent, so step it once —
        its own emission is discarded — and pin its pending token to
        the target's."""
        self.draft.step()
        for s in self.draft.active_slots():
            if s in result:
                self.draft.last_token[s] = result[s]
            else:
                # target starved this slot while the draft advanced:
                # mirror the target's (unchanged) state back
                self.draft.lengths[s] = int(self.lengths[s])
                self.draft.last_token[s] = int(self.last_token[s])

    def _step_run_spec(self, prepared):
        """Speculative phase 2: k+1 batched greedy draft steps, then
        per-slot one-pass verification against the TARGET's policy,
        multi-token application, and block rollback. Returns
        {slot: [token, ...]} — each list is the accepted draft prefix
        plus the target's correction/bonus token, so it is exactly
        the tokens plain rounds would have emitted one at a time."""
        info = prepared["slots"]
        starved = prepared["starved"]
        k = self.policy.speculate_k
        W = k + 1
        bs = self.spec.block_size
        # snapshot draft pendings: starved slots sit the round out on
        # the target but the batched draft advances them anyway
        restore = {s: (int(self.draft.lengths[s]),
                       int(self.draft.last_token[s]))
                   for s in starved}
        # phase A: k proposals per slot, plus one extra step so the
        # draft's cache holds a K/V row for EVERY window position a
        # full acceptance confirms (the bonus-token row)
        drafts = {s: [] for s in info}
        for i in range(W):
            out = self.draft.step()
            if i < k:
                for s in drafts:
                    drafts[s].append(out[s])
        # phase B: one suffix-window forward per speculating slot
        vtok, vlen, vhist, vpix, vtab, vseed = self.spec.verify_feeds
        result = {}
        for s, (L, tab) in sorted(info.items()):
            window = np.empty((1, W), np.int64)
            window[0, 0] = self.last_token[s]
            window[0, 1:] = drafts[s]
            pix = np.clip(L + np.arange(W), 0,
                          self.spec.max_len - 1).astype(np.int32)
            with _tracing.span("session:verify_call", round=self.round,
                               slot=s, window=W):
                outs = self.exe.run(
                    self.spec.verify_program,
                    feed={vtok: window,
                          vlen: np.asarray([W], np.int32),
                          vhist: np.asarray([L], np.int32),
                          vpix: pix,
                          vtab: tab,
                          vseed: np.asarray([self.seeds[s]],
                                            np.int64)},
                    fetch_list=list(self.spec.verify_fetch),
                    scope=self.scope)
            toks = np.asarray(outs[0]).reshape(-1)
            accept = int(np.asarray(outs[1]).reshape(-1)[0])
            if _faults.should_fire("decode_draft_mismatch",
                                   index=s) is not None:
                accept = 0   # chaos hook: force a full-reject round
            _SPEC_DRAFTED.inc(k)
            _SPEC_ACCEPTED.inc(accept)
            emitted = [int(t) for t in toks[:accept + 1]]
            new_len = L + accept + 1
            self.lengths[s] = new_len
            self.last_token[s] = emitted[-1]
            # roll back window rows past the confirmed prefix: blocks
            # beyond the new length decref (prepare's COW already
            # diverged every shared write block, so sharers are safe);
            # surviving garbage rows sit beyond the length mask and
            # are overwritten in place by later writes
            freed = self.pool.truncate_table(
                self.tables[s], (new_len - 1) // bs + 1)
            if freed:
                SPEC_ROLLBACKS.inc(freed)
            # draft rollback is a length truncation: rejected rows are
            # overwritten in place, inside blocks its table keeps (the
            # draft's pool has a whole table for every slot)
            self.draft.lengths[s] = new_len
            self.draft.last_token[s] = emitted[-1]
            result[s] = emitted
        for s, (dl, dt) in restore.items():
            self.draft.lengths[s] = dl
            self.draft.last_token[s] = dt
        return result

    def retire(self, slot):
        """Free a slot mid-flight. The cache rows are left as-is — the
        next prefill into this slot overwrites them, and the per-slot
        length mask keeps them unattendable meanwhile. Every
        block reference the slot's table held is returned to the pool
        (a block shared with the prefix index survives as cached
        prompt state; exclusive blocks free immediately). A slot retired
        with its first token owed takes back what the prefix index
        published of its prompt."""
        self.active[slot] = False
        self._retires[slot] += 1
        launched = self._owed.pop(slot, None)
        if launched is not None and self.prefix is not None:
            # its prefill is not known to have run: nobody shares it
            self.prefix.withdraw(launched.published)
        self.lengths[slot] = 0
        self.last_token[slot] = 0
        self.seeds[slot] = 0
        self.cstate[slot] = None
        if self.draft is not None:
            self.draft.retire(slot)
        self._release_table(slot)
        self._starved.discard(slot)

    def generate(self, prompt, max_new_tokens=None, eos_id=None,
                 seed=0):
        """Synchronous single-sequence convenience (tests/probes): the
        policy continuation of ``prompt`` (greedy by default),
        stopping at ``eos_id`` or ``max_new_tokens``, as a list of ids
        (EOS excluded). ``seed`` keys sampled policies."""
        eos = self.spec.eos_id if eos_id is None else eos_id
        slot, first = self.admit(prompt, seed=seed)
        # prefill already produced one token; each further step can
        # write one more K/V row, so cap+1 tokens total fit the slot
        cap = self.capacity_left(slot)
        limit = cap + 1 if max_new_tokens is None \
            else min(int(max_new_tokens), cap + 1)
        tokens = [first]
        try:
            while tokens[-1] != eos and len(tokens) < limit:
                nxt = self.step()
                if slot not in nxt:
                    break  # pool exhausted: finish at length
                got = nxt[slot]
                # speculative rounds emit a LIST per slot; tokens past
                # EOS or the budget are discarded (the round could not
                # know the sequence would end mid-window)
                for t in (got if isinstance(got, list) else [got]):
                    tokens.append(t)
                    if t == eos or len(tokens) >= limit:
                        break
        finally:
            self.retire(slot)
        if tokens and tokens[-1] == eos:
            tokens = tokens[:-1]
        return tokens


class _GenRequest:
    __slots__ = ("prompt", "max_new", "explicit_budget", "eos_id",
                 "future", "deadline", "t_submit", "tokens", "slot",
                 "session_index", "t_last", "t_queued", "replays",
                 "charged", "failed_on", "last_exc", "ctx",
                 "on_token", "seed", "tenant", "ahead", "admission")

    def __init__(self, prompt, max_new, explicit_budget, eos_id,
                 deadline, on_token=None, seed=0, tenant=None):
        self.prompt = prompt
        # the request's decode-RNG seed: minted ONCE at the front
        # door, re-fed on every replay admission — together with the
        # prompt+tokens journal it makes SAMPLED decode exactly as
        # replayable as greedy (serving/decoding)
        self.seed = seed
        # tenant id forwarded over the fleet envelope (None when the
        # caller is single-tenant): shed/trace attribution only — the
        # scheduler's admission math is tenant-blind, quotas live at
        # the router
        self.tenant = tenant
        self.max_new = max_new
        # True when the CALLER asked for max_new tokens (placement
        # must find a session able to serve them all); False when the
        # budget is the implicit "as much as fits" cap, which any
        # fitting session satisfies by definition
        self.explicit_budget = explicit_budget
        self.eos_id = eos_id  # None until placement picks a session
        self.future = Future()
        self.deadline = deadline  # absolute time.monotonic() or None
        self.t_submit = time.perf_counter()
        # last enqueue time: t_submit at first, reset on a replay
        # re-queue so the admission-wait EWMA keeps measuring QUEUE
        # wait, not time-since-original-submit (a replay would
        # otherwise latch the shed estimate high); the deadline keeps
        # using t_submit — replay spends the caller's budget
        self.t_queued = self.t_submit
        self.tokens = []
        # decode steps launched for this request whose tokens are not
        # delivered yet (the dispatcher works one step ahead)
        self.ahead = 0
        # the session's handle of the admission that put it in its slot:
        # a launched step delivers to that tenancy and to no later one
        self.admission = None
        self.slot = None
        self.session_index = None
        self.t_last = None
        self.replays = 0      # replay re-admissions consumed
        # True once this request's own failure charged a breaker: a
        # poison prompt failing over across sessions charges at most
        # ONE — it cannot quarantine the whole fleet
        self.charged = False
        # sessions this request has already failed on: replay
        # re-placement prefers anything else first. Without this, a
        # sub-threshold breaker (still closed after the charge) keeps
        # winning lowest-index placement and the request burns its
        # whole replay budget on the one broken session while a
        # healthy one sits idle.
        self.failed_on = set()
        # the failure that parked this request for replay: if the
        # replay turns out to be impossible (journal outgrew every
        # prompt bucket, no session ever heals), THIS surfaces — not
        # a generic unavailable error that masks what happened
        self.last_exc = None
        # request-scoped TraceContext (None = tracing off/unsampled).
        # It lives on the SAME object as the replay journal, so a
        # failover hop keeps its trace id across sessions for free —
        # the one-trace-per-request contract.
        self.ctx = None
        # optional per-token observer (the fleet tier streams tokens
        # over the wire as they decode, so a killed process's journal
        # survives on the router). Called on the dispatcher thread
        # with each NEWLY generated token — including an EOS the
        # resolution then strips (the Future's result stays
        # authoritative) and the token a replay re-admission owed;
        # never re-called for journal tokens a replay re-prefills.
        # Must not block; an observer exception is the caller's bug
        # but must not kill the dispatcher.
        self.on_token = on_token

    def notify_token(self, token):
        if self.on_token is not None:
            try:
                self.on_token(token)
            except Exception:  # noqa: BLE001 — dispatcher must live
                _log.logger().warning(
                    "generation on_token observer failed",
                    exc_info=True)

    def history(self):
        """The replay journal: prompt plus every token generated so
        far — prefilling it reconstructs the exact decode state (and
        the next prefill token IS the token the failed step owed)."""
        if not self.tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int64)])


class _Launched:
    """A session's decode step between the dispatcher's launch and its
    collect: the requests it steps, by slot, with the admission each was
    in its slot by at the launch (a slot may have been retired since, and
    taken again, by the same request too), the slots the pool starved
    out of it, ``wait()`` that blocks for ``{slot: token}``, and the
    cached tokens it attends where the launch knows them."""

    __slots__ = ("mine", "admissions", "starved", "wait", "context")

    def __init__(self, mine, starved):
        self.mine, self.starved = mine, starved
        self.admissions = [it.admission for _, it in mine]
        self.wait = self.context = None


class GenerationScheduler:
    """Continuous-batching front door over one or more
    :class:`GenerationSession` replicas.

    ``submit(prompt) -> Future`` resolves to the generated ids as an
    int64 array (greedy continuation, EOS excluded). The dispatcher
    thread interleaves two moves forever: admit queued requests into
    free cache slots (prefill), and run one decode step for every
    session with active slots. Sequences finish (EOS / token budget /
    deadline) and retire slot-by-slot — co-resident sequences never
    stall or flush for an admit or retire.

    Admission reuses the MicroBatcher discipline: bounded queue
    (``submit`` blocks, or raises :class:`ServingOverloadError` with a
    ``timeout``), queue-wait EWMA shedding when a deadline budget is
    already hopeless, expired deadlines resolved with
    :class:`ServingDeadlineError` before touching a device; a deadline
    that expires MID-generation retires the slot and resolves the
    Future with ServingDeadlineError (stateful requests hold a slot —
    letting them linger past their budget starves admission).

    With ``breaker_failures`` (default: the
    ``serving_breaker_failures`` flag; 0 = off) each session gets a
    :class:`ReplicaBreaker`: a failing session is quarantined out of
    admission and a cooldown-gated trial re-admits it. Its active
    requests' device-side cache died with it, but their host-side
    journals didn't: with ``replay_attempts`` > 0 (default: the
    ``generation_replay_attempts`` flag) they re-queue head-of-line
    and re-prefill ``prompt ⊕ tokens`` into a healthy session —
    token-for-token identical output, zero client-visible errors;
    with replay off they resolve exceptionally. ``step_timeout_ms``
    bounds each session's step so one wedged device call can't freeze
    the dispatcher, and ``rebuild_limit`` lets a broken session be
    reconstructed in the background (see the module docstring).

    ``drain()`` stops admission and serves everything accepted;
    ``close()`` is the bounded fast exit. ``swap_weights(params)``
    installs new values between decode steps (see method docs).

    **One step ahead.** Nothing a decode step needs from the host
    depends on the tokens of the step before it: lengths advance by
    one, block tables, window trims, copy-on-write, sampling counters
    and token budgets follow from counts, and the tokens themselves are
    fed to the next step on the device
    (``GenerationSession.step_launch``). So an iteration of the
    dispatcher on a session is: prepare and launch step n+1, *then*
    collect step n's tokens, deliver them, retire, admit. The device
    has step n+1 queued while the host does its turn. A prefill's first
    token is such a token too: an admission ends with the prefill on the
    queue and the slot in the books with its first token *owed*
    (``GenerationSession.admit_enter``), step n+1 is launched behind
    the prefill and takes the token from the prefill's own device array,
    and only then, after step n's collect, is the token fetched and the
    request booked (``scheduler:first_token``): the turn after a prefill
    runs beside the device like any other. How far ahead is
    read off the session, set by nobody (``_depth``): one step where
    ``session.lookahead`` is true (greedy and sampled policies), none,
    which is launch then collect of the same step, where the next feed
    needs the token on the host or the step is not one asynchronous
    call: constrained decoding, speculative rounds, and
    ``step_timeout_ms`` (the bounded worker).
    ``paddle_generation_decode_steps_ahead_total`` counts the steps
    launched with their predecessor uncollected (the step behind a
    prefill is one), ``paddle_generation_first_tokens_owed_total`` the
    admissions whose first token was fetched with a decode step queued
    behind the prefill. At depth 0 an admission is launch, wait, book,
    with nothing between. What follows from it:

    * A request that what is launched and undelivered ends *by count*
      (token budget, cache capacity; an owed first token counts as a
      token launched) sits the next step out (``hold``); its Future
      resolves when the tokens arrive. What ends a request *by value*
      (EOS, a constraint, a deadline read at delivery) is seen one step
      late, at its first token too: the slot's one extra step wrote
      into blocks it owned, and ``_deliver`` discards its result.
    * Whatever acted "between two steps" first collects and delivers
      what is launched (``_settle``: the decode step, then the first
      tokens owed): ``swap_weights``, the end of
      ``drain``/``close``/serving out; a rebuild's hand-over (which
      waits until nothing is active, so no token is owed) and a session
      failure drop it instead (``drop_flights``): a failed step n
      surfaces at its collect, the step launched behind it and the
      admissions whose token is owed go with it (their slots retired,
      their requests to replay as they came), and the journals, which
      hold delivered tokens only, replay bit-identically. A prefill
      that fails at the fetch of its first token with a decode step
      launched behind it is such a failure (that step took the token on
      the device): the session takes the slot and what the prefix index
      had published back (``admit_collect``), the step goes
      (``drop_flights``), and every request of the session replays.
      With nothing launched behind it (depth 0; a settle) it is that
      admission's failure alone, as it was.
    * A slot's next tenant enters ``_active`` while a step launched for
      the tenant before may still be uncollected. That step never
      delivers into the new tenant, be it the same request admitted
      again (preempted and replayed into the slot it left): ``_collect``
      hands a result only to the request the step was launched for
      *by the admission it was launched for* (``_Launched.admissions``:
      the session's handle is the tenancy), ``step_collect`` and the
      token feed leave out a slot whose retirement count moved since the
      launch, and an owed first token is booked before any step
      launched after its prefill is collected. Which first tokens are
      owed is the session's book alone (``GenerationSession.owed``).
    * Blocks freed by a retire or a window trim may be reused at once:
      every reuse is a later call on the device's queue than every
      read or write of them (a prefill's window is trimmed, and its
      prefix published, with the prefill still queued).

    **The dispatcher's clock.** The dispatcher's time is cut, by spans
    (``observability/tracing.py``: in any ``jax.profiler`` trace, no
    flag) and by always-on counters read from the same clock readings,
    into three kinds of stretch. A *host turn*
    (``scheduler:host_turn``, numbered ``round=``) runs from the moment
    a decode step's tokens are on the host to the moment the next
    decode call is on the device's queue. Working a step ahead, that is
    no longer time the device has nothing of the session queued: the
    step launched in the turn before runs beside it, and the turn
    shows in a token gap only by what it exceeds that step. At depth 0
    the device still idles through it. Its named
    children are ``scheduler:deliver`` (tokens to requests, finish,
    retire), ``scheduler:admit`` (one per admitted request; ends with
    the prefill's device call ``session:prefill_call`` on the queue)
    and ``scheduler:first_token`` (the wait for it,
    ``session:prefill_wait``, and the request's entry into the counts;
    both count as ``phase="admit"``, and ``paddle_request_prefill_ms``
    is the two of them, not what lies between), ``session:step_prepare``
    and ``session:step_dispatch``; each adds its milliseconds to
    ``paddle_generation_host_ms_total{phase}``, and ``phase="other"``
    takes the turn less its named children (swap, expiry and queue
    bookkeeping), so the five phases sum to the host turns. A *device
    wait* (``session:step_wait``,
    ``paddle_generation_device_wait_ms_total``) is the dispatcher
    blocked on the step it collects, booked to that step: working a
    step ahead, the next step's launch and the collect of the step
    queued ahead of a prefill lie between ``scheduler:admit`` and
    ``scheduler:first_token``, the wait outside the turn, so it is not
    read as prefill time. ``paddle_request_decode_step_ms`` observes,
    at each collect, that wait plus the prepare and dispatch seconds
    spent since the last observation (one step ahead: the *next* step's
    prepare and dispatch), so the observations do not overlap and sum
    to prepare + dispatch + wait. An *idle wait*
    (``scheduler:idle_wait``) is the dispatcher blocked on its queue
    with nothing active. With ``step_timeout_ms`` the call runs on a
    worker thread, which records the session's spans; the dispatcher's
    own dispatch phase is then the hand-over and its wait covers the
    worker's dispatch. A speculative round is not cut: it counts as
    dispatch, with the device calls as spans inside.
    """

    def __init__(self, sessions, max_queue=256, deadline_ms=None,
                 breaker_failures=None, breaker_cooldown_ms=None,
                 replay_attempts=None, rebuild_limit=None,
                 step_timeout_ms=None, autostart=True):
        if isinstance(sessions, GenerationSession):
            sessions = [sessions]
        if not sessions:
            raise ValueError("need at least one GenerationSession")
        self.sessions = list(sessions)
        # every session must make the SAME next-token decisions: a
        # replay journal only resumes bit-identically where the
        # decode policy is identical (the weights-version rule of the
        # fleet tier, applied inside one scheduler)
        fps = {(s.policy.fingerprint() if s.policy is not None
                else GREEDY_FINGERPRINT) for s in self.sessions}
        if len(fps) > 1:
            raise ValueError(
                "sessions disagree on decode policy (%s) — a replay "
                "journal is only re-drivable across sessions that "
                "make identical next-token decisions" % sorted(fps))
        self._policy_fp = fps.pop()
        self._sampled = any(s.sampled for s in self.sessions)
        self._q = queue.Queue(maxsize=max_queue)
        # dispatcher-local order-preserving buffer: items parked when
        # no slot is free right now, and re-queue overflow from the
        # deadline sweep (consumed before the queue)
        self._pending = collections.deque()
        # True while some waiting item MAY carry a deadline — gates
        # the per-tick expiry sweep, which would otherwise rotate the
        # whole bounded queue on every decode step for nothing
        self._has_deadlines = False
        self._closed = False
        self._thread = None
        self._wait_ewma = 0.0
        self._active = {}   # (session_index, slot) -> _GenRequest
        self._sched_id = next(_SCHED_SEQ)
        # the dispatcher's clock (class docstring): the open host turn's
        # span, start and named-children seconds; the step in flight's
        # dispatch start and enqueue time. Owned by whichever thread
        # drives the loop (the dispatcher, or a dispatcherless drain())
        self._round = 0
        self._turn = None
        self._turn_t0 = self._turn_named = 0.0
        self._t_dispatch = self._t_enqueued = None
        # prepare + dispatch seconds no decode-step observation holds yet
        self._unobserved = 0.0
        # per session, the step launched and not collected (_Launched):
        # only a session that works a step ahead leaves one here between
        # two dispatcher iterations ("One step ahead", class docstring)
        self._inflight = [None] * len(self.sessions)
        if deadline_ms is None:
            deadline_ms = _config.get_flag("serving_deadline_ms")
        self.default_deadline_ms = deadline_ms
        if breaker_failures is None:
            breaker_failures = _config.get_flag(
                "serving_breaker_failures")
        if breaker_cooldown_ms is None:
            breaker_cooldown_ms = _config.get_flag(
                "serving_breaker_cooldown_ms")
        if breaker_failures:
            # namespaced like the engine tier's "e<N>:<replica>" (PR
            # 7): a process running serving engines AND generation
            # schedulers publishes both families of per-replica health
            # gauges on the one registry — "g<N>:<session>" keeps them
            # from overwriting each other
            self._breakers = [
                ReplicaBreaker(i, breaker_failures,
                               float(breaker_cooldown_ms) / 1e3,
                               label="g%d:%d" % (self._sched_id, i))
                for i in range(len(self.sessions))]
        else:
            self._breakers = None
        # -- stateful-failure recovery (flags read HERE only: the
        # dispatcher loop never consults config, and at the defaults
        # none of the machinery below is exercised) ---------------------
        if replay_attempts is None:
            replay_attempts = _config.get_flag(
                "generation_replay_attempts")
        self.replay_attempts = int(replay_attempts or 0)
        if rebuild_limit is None:
            rebuild_limit = _config.get_flag("generation_rebuild_limit")
        self.rebuild_limit = int(rebuild_limit or 0)
        if step_timeout_ms is None:
            step_timeout_ms = _config.get_flag(
                "generation_step_timeout_ms")
        self.step_timeout = (float(step_timeout_ms) / 1e3
                             if step_timeout_ms else None)
        if self.step_timeout is not None and \
                any(s.speculative for s in self.sessions):
            raise ValueError(
                "step_timeout_ms does not compose with speculative "
                "decoding: the speculative round mutates the block "
                "pool inside step_run, which must stay on the "
                "dispatcher thread — a leaked bounded worker could "
                "race retire()/close() on the allocator books")
        self._wedged = {}        # si -> done-Event of the leaked step
        self._rebuilding = set()  # session indices down for rebuild
        # True only once NOTHING will absorb rebuilds anymore (the
        # dispatcher exited, or a dispatcherless close()/drain()
        # finished serving) — _closed alone is not it: a draining
        # scheduler is closed to admission but still absorbing
        self._terminal = False
        self._rebuilt = queue.Queue()  # (si, session|None, err, secs)
        self._rebuilds = [0] * len(self.sessions)
        self._trial_failures = [0] * len(self.sessions)
        self._swap_lock = threading.Lock()
        self._pending_swap = None  # (params, Future)
        self._weights_version = 0
        # live introspection: /healthz aggregates every live
        # scheduler's session view (weakref — GC drops it lazily,
        # the dispatcher-exit epilogue unregisters eagerly)
        from ..observability import health as _health
        self._health_name = "generation%d" % self._sched_id
        _health.register_health(self._health_name,
                              _scheduler_health(weakref.ref(self)))
        if autostart:
            self.start()

    # -- lifecycle -------------------------------------------------------
    def start(self):
        if self._closed:
            raise RuntimeError("scheduler is closed")
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            name="generation-scheduler",
                                            daemon=True)
            self._thread.start()
        return self

    @property
    def weights_version(self):
        return self._weights_version

    def session_health(self):
        if self._breakers is None:
            return ["closed"] * len(self.sessions)
        return [b.state for b in self._breakers]

    def policy_fingerprint(self):
        """The decode-policy fingerprint every session here shares
        (``"greedy"`` with no policy) — what the fleet worker acks so
        the router can gate journal reuse (serving/fleet.py)."""
        return self._policy_fp

    # -- admission -------------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, eos_id=None,
               deadline_ms=None, timeout=None, on_token=None,
               seed=None, tenant=None):
        """Enqueue one prompt; returns a Future of its generated ids.

        ``max_new_tokens`` is capped by the slot capacity left after
        the prompt (cache bucket / position table). ``deadline_ms``
        (default: the scheduler's ``deadline_ms``, itself defaulting
        to the ``serving_deadline_ms`` flag; 0/None = none) bounds the
        WHOLE generation. ``timeout``: seconds to wait on a full
        queue before :class:`ServingOverloadError`. ``on_token``:
        optional observer called with each newly generated token on
        the dispatcher thread (the fleet tier's streaming hook —
        default None costs one attribute check per token). ``seed``:
        the request's decode-RNG seed under a sampled policy — minted
        fresh when None, pass one explicitly to reproduce a sampled
        generation exactly (the fleet router does, so every failover
        hop resumes the same trajectory). ``tenant``: the submitting
        tenant's id (the fleet worker forwards the envelope's) —
        worker-side sheds of tenant-tagged requests charge
        ``paddle_serving_tenant_shed_total{tenant=...}`` beside the
        global counter, and the trace carries the id; admission math
        itself is tenant-blind (quotas are the router's job)."""
        if self._closed:
            raise RuntimeError("scheduler is closed")
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        # the prompt must fit SOME session's buckets (placement later
        # routes it only to sessions that can take it); the decode
        # budget cap comes from the most permissive fitting session
        fitting = [s for s in self.sessions
                   if s.prompt_bucket(prompt.size) is not None]
        if not fitting:
            raise ValueError(
                "prompt length %d exceeds every session's largest "
                "prompt bucket (max %d)"
                % (prompt.size,
                   max(s.spec.prompt_buckets[-1]
                       for s in self.sessions)))
        cap = max(s.max_pos for s in fitting) - prompt.size + 1
        if cap < 1:
            raise ValueError(
                "prompt length %d leaves no decode capacity in any "
                "session's cache bucket" % prompt.size)
        explicit = max_new_tokens is not None
        max_new = cap if not explicit else min(int(max_new_tokens), cap)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        deadline = None
        if deadline_ms:  # 0/None = no deadline, the PR-5 contract
            budget = float(deadline_ms) / 1e3
            if budget < 0:
                _sres.DEADLINE_EXCEEDED.inc()
                raise ServingDeadlineError(
                    "deadline budget %.1f ms already spent"
                    % float(deadline_ms))
            projected = self._wait_ewma * (1.0 + self._q.qsize())
            if projected > budget:
                # same geometric decay as the batcher: sheds must not
                # latch the estimate high on an idle queue
                self._wait_ewma *= (1.0 - _WAIT_ALPHA)
                _sres.SHED.inc()
                if tenant is not None:
                    _sres.TENANT_SHED.labels(
                        tenant=str(tenant)).inc()
                raise ServingOverloadError(
                    "shed: projected admission wait %.1f ms exceeds "
                    "the %.1f ms deadline budget"
                    % (projected * 1e3, budget * 1e3))
            deadline = time.monotonic() + budget
        if seed is None:
            seed = mint_seed() if self._sampled else 0
        item = _GenRequest(prompt, max_new, explicit, eos_id, deadline,
                           on_token=on_token, seed=int(seed),
                           tenant=None if tenant is None
                           else str(tenant))
        # minted at the front door (one attribute read when off),
        # carried on the item/journal through every queue, session,
        # and replay hop
        mint_kw = {}
        if item.tenant is not None:
            mint_kw["tenant"] = item.tenant
        item.ctx = _rtrace.mint("generation.submit",
                                prompt_len=int(prompt.size),
                                max_new=int(max_new), **mint_kw)
        try:
            self._q.put(item, block=True, timeout=timeout)
        except queue.Full:
            _sres.SHED.inc()
            # never entered the system: a rejection storm must not
            # churn real in-flight traces out of the bounded store
            _rtrace.discard(item.ctx)
            if item.tenant is not None:
                _sres.TENANT_SHED.labels(tenant=item.tenant).inc()
            raise ServingOverloadError(
                "generation queue full (%d pending)"
                % self._q.qsize()) from None
        if deadline is not None:
            # AFTER the put: the sweep recomputes the flag from queue
            # content, so this order can never strand a deadline item
            # behind a cleared flag
            self._has_deadlines = True
        if self._closed and self._thread is None:
            # raced a close()/drain() past its leftover sweep (the
            # batcher's shutdown race, same resolution: fail OUR
            # future idempotently and refuse the submit)
            _rtrace.discard(item.ctx)
            _resolve(item.future,
                     exception=RuntimeError("scheduler closed"))
            raise RuntimeError("scheduler is closed")
        return item.future

    # -- dispatcher ------------------------------------------------------
    def _next_item(self, block):
        """Next request to place: the parked buffer first (preserves
        order), then the queue. None when nothing is waiting."""
        if self._pending:
            return self._pending.popleft()
        try:
            if block:
                return self._q.get(timeout=0.05)
            return self._q.get_nowait()
        except queue.Empty:
            return None

    def _fits(self, sess, item):
        """Can ``sess`` serve this request IN FULL — prompt bucket and
        enough cache capacity for the promised token budget? Placement
        on a smaller-cache session would silently retire the sequence
        early with reason 'capacity', under-delivering the budget
        submit() accepted. An implicit ("as much as fits") budget is
        satisfied by ANY fitting session — requiring the largest
        session's cap would strand idle smaller replicas.

        A replay re-admission prefills the whole journal (prompt plus
        tokens already generated), so its length — and therefore its
        prompt bucket, possibly a larger one than the original
        admission used — and its REMAINING budget are what must fit.
        For a fresh item both reduce to the original check.

        Sessions with the prefix cache armed get one more
        chance: when the FULL journal outgrew every bucket, a cached
        prefix may shrink the actual prefill window back under one
        (``window_fits``, side-effect-free)."""
        n = item.prompt.size + len(item.tokens)
        need = max(1, item.max_new - len(item.tokens)) \
            if item.explicit_budget else 1
        if sess.max_pos - n + 1 < need or \
                not sess.storable(n + need - 1):
            return False
        return sess.prompt_bucket(n) is not None or \
            sess.window_fits(item.history())

    def _is_wedged(self, si):
        """True while session ``si``'s timed-out step worker is still
        stuck — it must not be stepped or admitted into (its executor
        and cache state are mid-flight). Once the leaked worker
        finishes, the marker clears; the breaker (opened by the hang)
        still gates re-admission through a cooldown trial."""
        ev = self._wedged.get(si)
        if ev is None:
            return False
        if ev.is_set():
            self._wedged.pop(si, None)
            return False
        return True

    def _eligible_session(self, item, claim=False):
        """Index of a session that can take this request NOW
        (free slot + fitting bucket/capacity + breaker closed, or a
        cooldown-elapsed trial when nothing fitting is closed), or
        None. Wedged and mid-rebuild sessions are never eligible. The
        half_open transition — a trial admission is the probe — fires
        only with ``claim=True``, i.e. when an actual request is about
        to be admitted; a capacity poll must not burn a breaker's
        cooldown with no trial to run."""
        candidates = [i for i, s in enumerate(self.sessions)
                      if i not in self._rebuilding
                      and not self._is_wedged(i)
                      and s.free_slots() and self._fits(s, item)
                      and s.admit_ok(item.prompt.size
                                     + len(item.tokens))]
        if item.failed_on:
            # a session this request already failed on is the LAST
            # resort, breaker state notwithstanding: its breaker may
            # still be closed (sub-threshold after the at-most-once
            # charge), and replaying straight back would burn the
            # whole budget on the one broken session
            candidates.sort(key=lambda i: i in item.failed_on)
        if not candidates:
            return None
        if self._breakers is None:
            return candidates[0]
        closed = [i for i in candidates
                  if self._breakers[i].state == "closed"]
        if closed:
            return closed[0]
        now = time.monotonic()
        for i in candidates:
            breaker = self._breakers[i]
            if breaker.state == "half_open" or \
                    breaker.ready_to_probe(now):
                if claim:
                    breaker.to_half_open()
                return i
        return None

    def _recovery_pending(self, item):
        """True while a FINITE recovery will make a fitting session
        placeable for ``item``: a rebuild hand-over is on its way, or
        replay is armed and a fitting session's breaker is riding a
        cooldown toward a trial. Shutdown serving (serve-out / drain)
        waits these out instead of failing the request — the wait is
        bounded by the cooldown/rebuild plus the item's replay
        budget. All-closed breakers with no free slots (external slot
        holders) are NOT recovery: nothing here ever frees them."""
        for i, s in enumerate(self.sessions):
            if not self._fits(s, item):
                continue
            if i in self._rebuilding:
                return True
            if self.replay_attempts and self._breakers is not None \
                    and not self._is_wedged(i) \
                    and self._breakers[i].state != "closed":
                return True
        return False

    def _dispatchable_later(self, item):
        """True when some session fitting this request is healthy
        (or trial-ready) but merely out of free slots — a retiring
        sequence will make room — or is being rebuilt and will rejoin.
        A still-wedged session is NOT a reason to wait: nothing drains
        it unless a rebuild is in flight.

        With replay armed, an open breaker whose cooldown is still
        running also counts: the cooldown is finite, the trial
        admission is how the session re-enters, and the wait is
        bounded — by the request's deadline (the expiry sweep keeps
        covering parked items) and by its replay budget (each failed
        trial it is admitted into burns one). Fast-failing here
        instead would break the zero-client-error contract for the
        exact window recovery needs. Replay off keeps the PR-8
        honesty: quarantine-with-cooldown-pending fails fast."""
        for i, s in enumerate(self.sessions):
            if not self._fits(s, item):
                continue
            if i in self._rebuilding:
                return True
            if self._is_wedged(i):
                continue
            breaker = self._breakers[i] if self._breakers else None
            if breaker is None or \
                    breaker.state in ("closed", "half_open") or \
                    breaker.ready_to_probe():
                return True
            if self.replay_attempts and breaker.state == "open":
                return True
        return False

    def _resolve_err(self, item, exc):
        """Exceptional resolution WITH its trace ending: every failed
        request's span tree ends in a ``resolveError`` edge (deadline
        endings have their own ``deadlineExpired``), so the trace an
        operator pulls for a failure never just stops mid-life."""
        if item.ctx is not None:
            _rtrace.event(item.ctx, "resolveError",
                          error=repr(exc)[:200],
                          error_type=type(exc).__name__)
        _resolve(item.future, exception=exc)

    def _expire(self, item, where):
        _sres.DEADLINE_EXCEEDED.inc()
        if item.ctx is not None:
            _rtrace.event(item.ctx, "deadlineExpired", where=where,
                          replays=item.replays)
        _resolve(item.future, exception=ServingDeadlineError(
            "deadline expired after %.1f ms %s"
            % ((time.perf_counter() - item.t_submit) * 1e3, where)))

    def _expire_queued(self):
        """Resolve expired deadlines for requests still waiting — even
        while every slot is busy. The batcher drops expired items at
        every dispatch tick; a slot-starved stretch must not suspend
        that contract and leave a doomed caller blocked until some
        unrelated sequence retires. Gated by ``_has_deadlines`` so a
        deadline-free workload never pays the queue rotation."""
        if not self._has_deadlines:
            return
        now = time.monotonic()
        remaining = False
        keep = collections.deque()
        while self._pending:
            item = self._pending.popleft()
            if item is not _STOP and item.deadline is not None \
                    and now >= item.deadline:
                self._expire(item, "in queue")
            else:
                if item is not _STOP and item.deadline is not None:
                    remaining = True
                keep.append(item)
        self._pending = keep
        for _ in range(self._q.qsize()):
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not _STOP and item.deadline is not None \
                    and now >= item.deadline:
                self._expire(item, "in queue")
            else:
                if item is not _STOP and item.deadline is not None:
                    remaining = True
                try:
                    self._q.put_nowait(item)
                except queue.Full:
                    # a racing submit took the freed capacity: the
                    # parked buffer keeps the item dispatchable
                    self._pending.append(item)
        # recomputed from content — a submit landing mid-sweep re-arms
        # the flag itself after its put
        self._has_deadlines = remaining

    def _place(self, item):
        """Admit ``item`` somewhere, park it for later, or resolve it.
        Returns False when the item was parked (no capacity right now
        — the caller should stop pulling from the queue)."""
        if item.deadline is not None and \
                time.monotonic() >= item.deadline:
            self._expire(item, "in queue")
            return True
        si = self._eligible_session(item, claim=True)
        if si is None:
            if self._dispatchable_later(item):
                self._pending.appendleft(item)
                return False
            # nothing can ever take this request: fail explicitly
            # rather than wedging it in a queue nothing drains. For a
            # replay, surface the SESSION failure that parked it (a
            # generic unavailable error would mask it — e.g. when the
            # journal outgrew every prompt bucket, the caller should
            # see why the generation actually died).
            self._resolve_err(item, item.last_exc
                              if item.last_exc is not None
                              else ServingUnavailableError(
                                  "no healthy generation session for "
                                  "this prompt"))
            return True
        self._admit_item(item, si)
        return True

    # -- the dispatcher's clock (class docstring) -------------------------
    def _host_ms(self, phase, seconds):
        self._turn_named += seconds
        _HOST_MS.labels(phase=phase).inc(seconds * 1e3)

    @contextlib.contextmanager
    def _host_phase(self, name, phase, **args):
        """A named child of the open host turn: its span, and its
        milliseconds on the phase's counter."""
        t0 = time.perf_counter()
        try:
            with _tracing.span(name, round=self._round, **args):
                yield
        finally:
            self._host_ms(phase, time.perf_counter() - t0)

    def _turn_open(self, now):
        self._round += 1
        self._turn_t0, self._turn_named = now, 0.0
        self._turn = _tracing.span("scheduler:host_turn",
                                   round=self._round)
        self._turn.__enter__()

    def _turn_close(self, now):
        if self._turn is None:
            return
        self._turn.__exit__(None, None, None)
        self._turn = None
        # float rounding can leave the remainder a hair under zero
        self._host_ms("other", max(
            0.0, now - self._turn_t0 - self._turn_named))

    def _enqueued(self, now=None):
        """The decode call is on the device's queue (``step_run`` calls
        this between dispatch and wait): the host turn ends here."""
        if now is None:
            now = time.perf_counter()
        self._host_ms("dispatch", now - self._t_dispatch)
        self._unobserved += now - self._t_dispatch
        self._turn_close(now)
        self._t_enqueued = now

    def _idle_wait(self, wait, *args):
        """Block in ``wait(*args)`` (the queue read, a back-off sleep)
        as ``scheduler:idle_wait``, outside any host turn; what it
        returns, if anything, opens the next one."""
        self._turn_close(time.perf_counter())
        with _tracing.span("scheduler:idle_wait", round=self._round):
            got = wait(*args)
        if got is not None:
            self._turn_open(time.perf_counter())
        return got

    def _admit_item(self, item, si):
        """Admit ``item`` into session ``si``, in the two phases of
        ``GenerationSession.admit``. ``scheduler:admit`` ends with the
        prefill on the device's queue and the request in ``_active``;
        ``scheduler:first_token`` (``_first_token``) holds the wait for
        its first token and the request's entry into the counts. Where
        the session works a step ahead the slot enters the session's
        books at once with the token *owed* (``admit_enter``), and the
        wait comes in ``_step_all``, behind the launch of the decode step
        that takes the token on the device; at depth 0 the next feed
        needs the token on the host, and the wait follows here."""
        sess = self.sessions[si]
        t_admit0 = time.perf_counter()
        with self._host_phase("scheduler:admit", "admit", session=si):
            wait = t_admit0 - item.t_queued
            self._wait_ewma += _WAIT_ALPHA * (wait - self._wait_ewma)
            _rtrace.QUEUE_WAIT_MS.observe(wait * 1e3)
            if item.ctx is not None:
                _rtrace.event(item.ctx, "queueWait", dur_ms=wait * 1e3,
                              replay=bool(item.tokens))
            launched = self._admit_guarded(
                item, si, lambda: self._prefill_launch(item, si, sess))
            if launched is None:
                return
            if item.eos_id is None:
                item.eos_id = sess.spec.eos_id
            item.slot, item.ahead = launched.slot, 0
            item.session_index, item.admission = si, launched
            self._active[(si, item.slot)] = item
            self._update_occupancy()
        launched.admit_s = time.perf_counter() - t_admit0
        if not self._depth(sess):
            self._first_token(si, sess, item)

    def _first_tokens(self, si, sess):
        """Fetch and book the first tokens session ``si`` owes (the
        session's book: ``GenerationSession.owed``), oldest first: after
        the launch of the step that takes them on the device, and after
        the collect of the step that was in flight, whose tokens are
        older."""
        for launched in sess.owed():
            item = self._active.get((si, launched.slot))
            if item is not None and item.admission is launched:
                self._first_token(si, sess, item)   # else failed since

    def _first_token(self, si, sess, item):
        """``scheduler:first_token``: wait for the first token of
        ``item``'s prefill and enter the request into the counts. A
        fetch that fails with a decode step launched behind the prefill
        is the session's failure, like a step's with another behind it:
        that step took the token on the device."""
        launched = item.admission
        replay = bool(item.tokens)
        behind = self._inflight[si] is not None
        if behind:
            # the step uncollected now was launched behind the prefill:
            # an older one is collected before this fetch
            _FIRST_TOKENS_OWED.inc()
        sess.round = self._round
        t_resume = time.perf_counter()
        with self._host_phase("scheduler:first_token", "admit",
                              session=si):
            got = self._admit_guarded(
                item, si, lambda: sess.admit_collect(launched),
                session_wide=behind)
            if got is None:
                return
            slot, first = got
            # breaker success is recorded by a surviving STEP, not here:
            # a persistently step-broken session would otherwise launder
            # itself closed through every trial admission it then fails
            now_pc = time.perf_counter()
            prefill_ms = (launched.admit_s + now_pc - t_resume) * 1e3
            _rtrace.PREFILL_MS.observe(prefill_ms)
            if item.ctx is not None:
                # hist = prefix-cache hit length: tokens served from
                # shared blocks instead of re-prefilled (0 on a prefix
                # miss)
                _rtrace.event(item.ctx,
                              "replayAdmit" if replay else "prefill",
                              dur_ms=prefill_ms,
                              session=si, slot=slot,
                              journal_len=int(item.prompt.size)
                              + len(item.tokens),
                              hist=int(launched.matched))
            if replay:
                # the same logical request, resumed — requests_total
                # must not double-count it; the re-prefilled history is
                # what the failover actually cost
                _REPLAYED_TOKENS.inc(len(item.tokens))
                _RECOVERY_SECONDS.observe(now_pc - item.t_queued)
            else:
                _REQUESTS.inc()
                _TTFT_SECONDS.observe(now_pc - item.t_submit)
            _TOKENS.inc()  # the prefill produced one NEW token either way
            item.t_last = now_pc
            item.tokens.append(first)
            item.notify_token(first)
            # EOS/budget can end it at token 1; a surviving constrained
            # request may already be in a dead automaton state
            if not self._finish_if_done(item):
                self._check_dead_end(sess, item)

    def _prefill_launch(self, item, si, sess):
        _faults.fire_point("generation_admit_fail", index=si)
        cstate = None
        if sess.constrained:
            # replay state folds the journal through the
            # automaton — the host state is journal-derived,
            # exactly like the KV cache
            c = sess.policy.constraint
            cstate = c.advance_many(c.start, item.tokens)
        sess.round = self._round
        launched = sess.admit_launch(item.history(), seed=item.seed,
                                     cstate=cstate)
        if self._depth(sess):
            sess.admit_enter(launched)
        return launched

    def _admit_guarded(self, item, si, call, session_wide=False):
        """``call()`` under the request's activated context (it follows
        the admission into the fault hook and the prefill's
        executor.run: deviceCall spans land on this request's trace),
        or None with the failure handled: the admission's own, or with
        ``session_wide`` the session's."""
        try:
            with _rtrace.activate(item.ctx):
                return call()
        except Exception as exc:
            if session_wide:
                self._on_session_failure(si, self.sessions[si], exc)
                return None
            if self._active.get((si, item.slot)) is item:
                # the first token's fetch failed: the session gave the
                # slot back
                del self._active[(si, item.slot)]
                self._update_occupancy()
            if isinstance(exc, ValueError):
                # a client-shaped prompt (bucket/length) is the request's
                # fault, not the session's — it must not charge the
                # breaker and quarantine a healthy session
                self._resolve_err(item, exc)
            else:
                self._on_admit_failure(item, si, exc)
        return None

    def _on_admit_failure(self, item, si, exc):
        """A session failed this request's (re-)admission: charge its
        breaker (at most once per request across all its replays —
        the poison-prompt discipline; a half-open trial failure always
        records, the PR-5 rule), then replay the request elsewhere or
        surface the failure when the budget is spent."""
        breaker = self._breakers[si] if self._breakers else None
        if breaker is not None:
            was_trial = breaker.state == "half_open"
            if was_trial or not item.charged:
                breaker.record_failure()
                item.charged = True
            if was_trial:
                self._trial_failures[si] += 1
        item.failed_on.add(si)
        if item.ctx is not None:
            _rtrace.event(item.ctx, "admitFailure", session=si,
                          trial=was_trial if breaker is not None
                          else False, error=repr(exc)[:200])
        _log.structured("generation_admit_failed", session=si,
                        error=repr(exc), replay=bool(item.tokens))
        self._maybe_rebuild(si)
        # no slot was held here, so no retirement to count either way
        self._requeue_for_replay([item], exc)

    def _requeue_for_replay(self, items, exc):
        """Park failed requests head-of-line for replay re-admission;
        items whose replay budget is spent resolve with ``exc``
        instead. Returns the list actually re-queued (slot/retirement
        accounting stays with the caller, which knows whether the
        items were holding slots)."""
        requeued, spent = [], []
        for item in items:
            if self.replay_attempts and \
                    item.replays < self.replay_attempts:
                requeued.append(item)
            else:
                spent.append(item)
        # appendleft in reverse keeps the failed batch's own order at
        # the head of the parked buffer (consumed before the queue)
        for item in reversed(requeued):
            item.replays += 1
            item.t_queued = time.perf_counter()
            item.last_exc = exc
            _FAILOVERS.inc()
            if item.ctx is not None:
                # the failover hop, from the journal's side: the next
                # replayAdmit event names the NEW session — together
                # they are the old-session -> new-session edge
                _rtrace.event(item.ctx, "failoverRequeue",
                              from_session=item.session_index,
                              replays=item.replays,
                              journal_len=int(item.prompt.size)
                              + len(item.tokens),
                              error=repr(exc)[:200])
            self._pending.appendleft(item)
        if any(item.deadline is not None for item in requeued):
            # the expiry sweep must keep covering parked replays: a
            # deadline that runs out while parked resolves WITHOUT
            # ever re-prefilling
            self._has_deadlines = True
        for item in spent:
            self._resolve_err(item, exc)
        return requeued

    def _finish_if_done(self, item):
        """Retire/resolve when EOS, budget, capacity, or deadline ends
        the sequence. Returns True when the request left its slot."""
        sess = self.sessions[item.session_index]
        reason = None
        if item.tokens and item.tokens[-1] == item.eos_id:
            item.tokens.pop()
            reason = "eos"
        elif len(item.tokens) >= item.max_new:
            reason = "max_tokens"
        elif sess.capacity_left(item.slot) + item.ahead <= 0:
            # ``ahead``: the lengths already hold the step launched and
            # not delivered
            reason = "capacity"
        elif item.deadline is not None and \
                time.monotonic() >= item.deadline:
            reason = "deadline"
        if reason is None:
            return False
        sess.retire(item.slot)
        del self._active[(item.session_index, item.slot)]
        _RETIRED.labels(reason=reason).inc()
        if reason == "deadline":
            _sres.DEADLINE_EXCEEDED.inc()
            if item.ctx is not None:
                _rtrace.event(item.ctx, "deadlineExpired",
                              where="mid-generation",
                              tokens=len(item.tokens))
            _resolve(item.future, exception=ServingDeadlineError(
                "deadline expired mid-generation after %d tokens"
                % len(item.tokens)))
        else:
            e2e = time.perf_counter() - item.t_submit
            _rtrace.E2E_MS.observe(e2e * 1e3)
            if item.ctx is not None:
                _rtrace.event(item.ctx, "resolve", reason=reason,
                              tokens=len(item.tokens),
                              dur_ms=e2e * 1e3)
            _resolve(item.future,
                     result=np.asarray(item.tokens, np.int64))
        self._update_occupancy()
        return True

    def _check_dead_end(self, sess, item):
        """Constraint dead end: the automaton state a just-landed
        token advanced into bans EVERY next token. Resolved as a
        typed CLIENT error — no breaker charge, no replay, and above
        all no hang (an all--inf mask row would otherwise argmax
        garbage forever). The ``decode_constraint_dead_end`` fault
        site forces this path for chaos tests. Returns True when the
        request left its slot."""
        if not sess.constrained:
            return False
        key = (item.session_index, item.slot)
        if key not in self._active:
            return False
        state = sess.cstate[item.slot]
        fired = _faults.should_fire("decode_constraint_dead_end",
                                    index=item.slot)
        if fired is None and not sess.policy.constraint.dead(state):
            return False
        sess.retire(item.slot)
        del self._active[key]
        _RETIRED.labels(reason="dead_end").inc()
        from .decoding import ConstraintDeadEnd
        self._resolve_err(
            item, ConstraintDeadEnd(state, len(item.tokens)))
        self._update_occupancy()
        return True

    def _step_session(self, si, sess, prepared):
        """One session's decode step plus its fault hooks, on the
        bounded worker — so injected faults (including a wedge callback)
        land inside whatever bounds the step. ``prepared`` is the
        step_prepare() handle ``_launch`` made on the dispatcher thread,
        which keeps pool mutation there and outside any request's
        activated trace context."""
        self._step_faults(si)
        return sess.step_run(prepared)

    @staticmethod
    def _step_faults(si):
        _faults.fire_point("generation_session_wedge", index=si)
        _faults.fire_point("generation_step_fail", index=si)

    def _step_timed(self, si, sess, prepared):
        """Step bounded by ``self.step_timeout`` on a worker thread
        (resilience.run_bounded). A hang raises ServingTimeoutError
        and marks the session wedged — its stuck worker is leaked and
        CAPPED at one: the wedge marker keeps the session out of
        placement and stepping until the thread finishes, so retries
        can't stack blocked threads behind a dead device call.

        ``prepared`` is the session's step_prepare() handle, produced
        by _launch on the dispatcher thread — which
        is where ALL block-pool mutation happens: a worker
        leaked past its timeout only ever executes the device call
        plus per-slot scalar advances, never allocator mutation, so
        it cannot race the dispatcher's retire()/close() on the pool
        accounting."""
        try:
            return _sres.run_bounded(
                lambda: self._step_session(si, sess, prepared),
                self.step_timeout,
                name="generation-step-%d" % si)
        except _sres.ServingTimeoutError as err:
            pending = getattr(err, "pending", None)
            if pending is not None:
                self._wedged[si] = pending
            _STEP_TIMEOUTS.inc()
            raise

    def _on_session_failure(self, si, sess, exc):
        """A session's step failed (or hung): free its slots, charge
        its breaker once for the event, and replay the affected
        requests into healthy sessions (default-off: they resolve
        exceptionally, the pre-replay contract). The cache state died
        with the session, but each request's prompt+tokens journal is
        a complete deterministic transcript — re-prefilling it
        elsewhere resumes the generation with identical output.

        A step launched behind the failed one is dropped with it,
        uncollected: the journals hold delivered tokens only, so the
        replay re-makes what both would have given."""
        hang = isinstance(exc, _sres.ServingTimeoutError)
        self._inflight[si] = None
        sess.drop_flights()
        self._unobserved = 0.0
        mine = self._on_session(si)
        breaker = self._breakers[si] if self._breakers else None
        if breaker is not None:
            # one breaker charge per failure EVENT (the step is the
            # unit of failure, not the co-batched requests on it) —
            # and at most one per REQUEST across its replays: when
            # every affected request has already charged a breaker
            # elsewhere, this event is those suspects re-failing (the
            # poison shape), and charging again would let one bad
            # request quarantine session after session. Hangs are
            # always the session's fault, and a half-open trial
            # failure must always record (the PR-5 rules).
            was_trial = breaker.state == "half_open"
            uncharged = [it for _, it in mine if not it.charged]
            if hang or was_trial or uncharged:
                breaker.record_failure(hang=hang)
                for it in uncharged:
                    it.charged = True
            if was_trial:
                self._trial_failures[si] += 1
        _log.structured("generation_step_failed", session=si,
                        error=repr(exc), hang=hang, requests=len(mine))
        for slot, it in mine:
            sess.retire(slot)
            self._active.pop((si, slot), None)
            it.failed_on.add(si)
            if it.ctx is not None:
                _rtrace.event(it.ctx, "sessionFailure", session=si,
                              slot=slot, hang=hang,
                              error=repr(exc)[:200])
        items = [it for _, it in mine]
        requeued = set()
        if self.replay_attempts:
            requeued = set(map(id, self._requeue_for_replay(items, exc)))
        else:
            for it in items:
                self._resolve_err(it, exc)
        for it in items:
            _RETIRED.labels(
                reason="failover" if id(it) in requeued
                else "error").inc()
        self._update_occupancy()
        # a wedged session can't run cooldown trials at all — when
        # rebuild is armed it goes straight to reconstruction
        self._maybe_rebuild(si, force=hang)

    def _on_session(self, si):
        """The requests active on session ``si``, as (slot, request)."""
        return [(slot, it) for (s_i, slot), it
                in list(self._active.items()) if s_i == si]

    def _busy(self):
        """Something is active or a launched step is uncollected."""
        return bool(self._active) or any(
            rec is not None for rec in self._inflight)

    def _depth(self, sess):
        """How many decode steps of ``sess`` the dispatcher keeps on the
        device's queue beyond the one it waits for: read off the
        session ("One step ahead", class docstring)."""
        return 1 if sess.lookahead and self.step_timeout is None else 0

    def _ends_in_flight(self, sess, it):
        """The tokens launched for ``it`` and not delivered (decode
        steps, and a prefill's first token while it is owed) end it by
        count (token budget, cache capacity): it needs no further step."""
        ahead = it.ahead + sess.owes(it.slot)
        return ahead and (len(it.tokens) + ahead >= it.max_new or
                          sess.capacity_left(it.slot) <= 0)

    def _step_all(self):
        """One dispatcher iteration on every session: launch its next
        decode step, then collect and deliver the one launched an
        iteration earlier — or, at depth 0, the one just launched —
        then fetch the first tokens owed by the prefills that the
        iteration's admissions launched ahead of that next step."""
        for si, sess in enumerate(self.sessions):
            if si in self._rebuilding:
                continue  # down for reconstruction; nothing is active
            ahead_of = self._inflight[si]
            on_session = self._on_session(si)
            hold = [slot for slot, it in on_session
                    if self._ends_in_flight(sess, it)]
            mine = [(slot, it) for slot, it in on_session
                    if slot not in hold]
            self._t_dispatch = self._t_enqueued = None
            new = None
            if mine:
                try:
                    new = self._launch(si, sess, mine, hold)
                except Exception as exc:
                    now = time.perf_counter()
                    if self._t_dispatch is not None and \
                            self._t_enqueued is None:
                        self._enqueued(now)  # a failed dispatch is all
                    if self._turn is None:   # dispatch
                        self._turn_open(now)
                    self._on_session_failure(si, sess, exc)
                    continue
            if new is not None and ahead_of is None and self._depth(sess):
                # nothing older to collect: this step is collected an
                # iteration later, behind the next one's launch
                self._inflight[si] = new
                self._turn_open(self._t_enqueued)
                self._t_enqueued = None
            else:
                if ahead_of is None:
                    ahead_of, new = new, None
                elif new is not None:
                    _STEPS_AHEAD.inc()
                self._inflight[si] = new
                if ahead_of is not None:
                    self._collect(si, sess, ahead_of)
            self._first_tokens(si, sess)

    def _launch(self, si, sess, mine, hold):
        """Prepare a decode step of ``sess`` for the requests ``mine``
        and put it on the device's queue: the host turn ends there.
        Returns the ``_Launched``, or None with nothing to step."""
        # one decode program serves every co-resident request:
        # the step's deviceCall span is carried by the FIRST
        # sampled request's context (the inline path; a
        # worker-bounded step loses it by design), each sampled
        # request then gets its own slot-annotated decodeStep
        # event at delivery
        step_ctx = next((it.ctx for _, it in mine
                         if it.ctx is not None), None)
        sess.round = self._round
        t_step0 = time.perf_counter()
        # step_prepare runs OUTSIDE the activated context on
        # both paths: its pool mutations (grow, COW,
        # eviction pressure) are batch-level — slot B's COW
        # must not land in request A's span tree, so those
        # global events reach only the flight ring
        prepared = sess.step_prepare(hold)
        if prepared is None:
            return None
        self._t_dispatch = time.perf_counter()
        self._host_ms("prepare", self._t_dispatch - t_step0)
        self._unobserved += self._t_dispatch - t_step0
        rec = _Launched(mine, prepared["starved"] if isinstance(
            prepared, dict) else prepared[1])
        if self.step_timeout is not None:
            # handed to a worker: from here the dispatcher only waits
            self._enqueued()
            rec.wait = lambda: self._step_timed(si, sess, prepared)
        else:
            with _rtrace.activate(step_ctx):
                self._step_faults(si)
                flight = sess.step_launch(prepared)
            self._enqueued()
            rec.context = flight.context
            rec.wait = lambda: sess.step_collect(flight)
        for slot, it in mine:
            if slot not in rec.starved:
                it.ahead += 1
        return rec

    def _collect(self, si, sess, rec):
        """Block on a launched step's tokens (the device wait), then
        deliver them. One clock reading per boundary: the prepare and
        dispatch seconds no observation holds yet plus this wait are the
        step's time in ``paddle_request_decode_step_ms``, so the
        observations neither overlap nor leave any of the three out."""
        if self._t_enqueued is None:
            # nothing was launched in this iteration: the host turn ends
            # where the wait starts
            self._t_enqueued = time.perf_counter()
            self._turn_close(self._t_enqueued)
        failure = None
        try:
            toks = rec.wait()
        except Exception as exc:
            failure = exc
        now_pc = time.perf_counter()
        waited, self._t_enqueued = now_pc - self._t_enqueued, None
        _DEVICE_WAIT_MS.inc(waited * 1e3)
        self._turn_open(now_pc)
        if failure is not None:
            self._on_session_failure(si, sess, failure)
            return
        # whoever left the slot since the launch gets nothing of the step:
        # ended by what the tokens of the step before said (EOS, a
        # deadline), preempted, failed at its first token. The one step
        # it ran beyond that is discarded, as a speculative round's
        # tokens past the end are, also where the same request holds the
        # slot again by a later admission
        mine = [(slot, it)
                for (slot, it), adm in zip(rec.mine, rec.admissions)
                if it.admission is adm and
                self._active.get((si, slot)) is it]
        for slot, it in mine:
            if slot not in rec.starved:
                it.ahead -= 1
        breaker = self._breakers[si] if self._breakers else None
        if breaker is not None:
            breaker.record_success()
            self._trial_failures[si] = 0
        _STEPS.inc()
        step_ms = (self._unobserved + waited) * 1e3
        self._unobserved = 0.0
        _rtrace.DECODE_STEP_MS.observe(step_ms)
        # a bounded worker's step knows its lengths only now, and before
        # delivery retires slots and zeroes them
        _CONTEXT_TOKENS.inc(
            int(sum(sess.lengths[s] for s in toks))
            if rec.context is None else rec.context)
        _TOKENS.inc(self._deliver(si, sess, mine, toks, now_pc, step_ms))

    def _settle(self, si):
        """Collect and deliver what session ``si`` has launched and not
        collected, the decode step and then the first tokens owed by
        prefills (launched after it): what everything that acts between
        two steps does first (a weight swap, the end of serving)."""
        rec, self._inflight[si] = self._inflight[si], None
        sess = self.sessions[si]
        if rec is not None:
            self._collect(si, sess, rec)
        self._first_tokens(si, sess)

    def _deliver(self, si, sess, mine, toks, now_pc, step_ms):
        """Hand a step's tokens to ``mine``, its requests that are still
        in their slots by the admission it was launched for; finish and
        retire what ended. Returns the number of tokens delivered."""
        advanced = 0
        with self._host_phase("scheduler:deliver", "deliver",
                              active=len(mine)):
            for slot, it in mine:
                if slot not in toks:
                    # pool exhausted for this sequence (no
                    # allocatable block even after eviction): it
                    # cannot grow HERE.
                    sess.retire(slot)
                    del self._active[(si, slot)]
                    self._update_occupancy()
                    if self.replay_attempts and it.explicit_budget \
                            and len(it.tokens) < it.max_new and \
                            it.replays < self.replay_attempts:
                        # preemption, not truncation: the journal
                        # re-queues and resumes BIT-identically once
                        # blocks free (admit_ok parks it meanwhile) —
                        # possibly on a less contended session, which
                        # placement prefers via failed_on. Only an
                        # exhausted replay budget falls through to
                        # the capacity finish below.
                        it.failed_on.add(si)
                        _RETIRED.labels(reason="preempted").inc()
                        if it.ctx is not None:
                            _rtrace.event(it.ctx, "preempted",
                                          session=si, slot=slot,
                                          tokens=len(it.tokens))
                        self._requeue_for_replay(
                            [it], PoolExhausted(
                                "session %d pool exhausted after %d "
                                "tokens" % (si, len(it.tokens))))
                        continue
                    # implicit budgets asked for "as much as fits":
                    # finishing at the current length IS the
                    # contract — the 'capacity' retirement, reached
                    # through pool bytes instead of the position
                    # table
                    _RETIRED.labels(reason="capacity").inc()
                    _rtrace.E2E_MS.observe((now_pc - it.t_submit) * 1e3)
                    if it.ctx is not None:
                        _rtrace.event(it.ctx, "resolve",
                                      reason="capacity",
                                      tokens=len(it.tokens))
                    _resolve(it.future,
                             result=np.asarray(it.tokens, np.int64))
                    continue
                got = toks[slot]
                # a speculative round emits a LIST per slot — the
                # accepted draft prefix plus the correction/bonus
                # token; plain rounds stay a bare int
                for tok in (got if isinstance(got, list) else [got]):
                    advanced += 1
                    it.tokens.append(tok)
                    it.notify_token(tok)
                    _INTER_TOKEN_SECONDS.observe(now_pc - it.t_last)
                    it.t_last = now_pc
                    if it.ctx is not None:
                        _rtrace.event(it.ctx, "decodeStep",
                                      dur_ms=step_ms, session=si,
                                      slot=slot, active=len(mine),
                                      token_index=len(it.tokens))
                    if self._finish_if_done(it) or \
                            self._check_dead_end(sess, it):
                        # EOS/budget/dead-end mid-window: the round
                        # could not know — the rest of the list is
                        # discarded with the slot already retired
                        break
        return advanced

    # -- session rebuild -------------------------------------------------
    def _maybe_rebuild(self, si, force=False):
        """Kick off a background teardown/reconstruct of session
        ``si`` when it has proven broken: its post-quarantine trial
        re-admissions keep failing (>= _REBUILD_AFTER_TRIALS), or
        ``force`` (a wedge — trials are impossible). Bounded by
        ``rebuild_limit`` per session; needs ``spec.rebuild``."""
        if not self.rebuild_limit or si in self._rebuilding:
            return
        if self._rebuilds[si] >= self.rebuild_limit:
            return
        if not force and self._trial_failures[si] < _REBUILD_AFTER_TRIALS:
            return
        sess = self.sessions[si]
        if sess.spec.rebuild is None:
            return
        if any(s_i == si for (s_i, _) in self._active):
            return  # live requests still decoding there; next event
        # a step still uncollected there stepped requests that have all
        # ended since: nobody waits for it
        self._inflight[si] = None
        sess.drop_flights()
        self._rebuilding.add(si)
        self._rebuilds[si] += 1
        # a rebuild is incident-grade (quarantine became repair):
        # annotate the active request's trace and snapshot the flight
        # ring while the lead-up events are still in it
        _rtrace.global_event("sessionRebuildStart", session=si,
                            forced=bool(force),
                            rebuilds=self._rebuilds[si])
        _flight.RECORDER.trigger_async("session_rebuild", session=si,
                                       forced=bool(force))
        threading.Thread(
            target=self._rebuild_worker, args=(si, sess),
            name="generation-rebuild-%d" % si, daemon=True).start()

    # Bound on one rebuild's construct + warmup (covers fresh XLA
    # compiles, which reach tens of seconds on a real chip): a rebuild
    # was triggered because the session was broken — possibly a DEAD
    # device — and an unbounded warmup against it would pin
    # _rebuilding forever, parking every request that fits only this
    # session and spinning shutdown serving for good.
    REBUILD_TIMEOUT = 120.0

    def _rebuild_worker(self, si, old_sess):
        """Background thread: construct the replacement session —
        fresh spec (new cache namespace), params re-read from the same
        scope, cache zeros re-materialized — and warm every prompt
        bucket's prefill plus the decode program so the executor
        compiles land before it takes traffic. The whole build is
        bounded by REBUILD_TIMEOUT (a dead device must fail the
        rebuild, not hang it). Hand-over happens on the dispatcher
        thread (_absorb_rebuilds); only the build runs here."""
        t0 = time.perf_counter()
        # abandon handshake: the builder COMMITS its session and the
        # timed-out waiter ABANDONS under one lock, and whichever
        # loses the race releases the session — a build finishing in
        # the instant the bounded wait gives up must not leak its
        # cache claims/arrays into nowhere
        state = {"abandoned": False, "new": None}
        state_lock = threading.Lock()

        def build():
            new = None
            try:
                spec = old_sess.spec.rebuild()
                new = GenerationSession(spec, scope=old_sess.scope,
                                        place=old_sess.place)
                # warm EVERY prompt bucket plus the decode program:
                # the hand-over must not leave a bucket whose first
                # live (or replay-promoted) request pays an XLA
                # compile stall on the dispatcher thread. The prefix
                # index is detached for the warmups: otherwise a
                # later bucket's warm prompt matches an earlier one's
                # cached prefix, the SUFFIX picks a smaller program,
                # and the large bucket never actually compiles (and
                # warm-junk tokens would stay pinned in the index).
                prefix, new.prefix = new.prefix, None
                try:
                    for bucket in spec.prompt_buckets:
                        n = max(1, min(int(bucket), new.max_pos))
                        slot, _ = new.admit([spec.bos_id] * n)
                        new.retire(slot)
                    slot, _ = new.admit([spec.bos_id])
                    new.step()
                    new.retire(slot)
                    # the COW program too (block 0 onto itself is
                    # a harmless identity copy)
                    new._copy_block(0, 0)
                finally:
                    new.prefix = prefix
            except BaseException:
                if new is not None:
                    try:
                        new.close()
                    except Exception:
                        pass
                raise
            with state_lock:
                if not state["abandoned"]:
                    state["new"] = new  # committed
                    return new
            # the bounded wait gave up on us: release rather than
            # hand a session to nobody
            try:
                new.close()
            except Exception:
                pass
            return None

        try:
            new = _sres.run_bounded(
                build, self.REBUILD_TIMEOUT,
                name="generation-rebuild-build-%d" % si)
        except Exception as exc:
            with state_lock:
                state["abandoned"] = True
                committed = state["new"]
                state["new"] = None
            if committed is not None:
                # the build committed in the instant we gave up
                try:
                    committed.close()
                except Exception:
                    pass
            self._rebuilt.put((si, None, exc,
                               time.perf_counter() - t0))
            return
        if new is None:  # abandoned race: already released
            self._rebuilt.put((si, None,
                               RuntimeError("rebuild abandoned"),
                               time.perf_counter() - t0))
            return
        if self._terminal:
            # the scheduler is fully shut down mid-build (a merely
            # DRAINING scheduler still absorbs — parked requests may
            # be waiting on exactly this hand-over): nobody will
            # absorb the replacement — release its cache
            # claims/arrays instead of leaking them
            try:
                new.close()
            except Exception:
                pass
            self._rebuilding.discard(si)
            return
        self._rebuilt.put((si, new, None, time.perf_counter() - t0))
        if self._terminal:
            # shutdown raced the put past its final sweep: drain our
            # own hand-over (idempotent with that sweep)
            self._drain_rebuilt()

    def _absorb_rebuilds(self):
        """Dispatcher-thread hand-over: swap finished rebuilds into
        the session list (the dispatcher is the only session caller,
        so the swap is race-free) and re-admit them."""
        if not self._rebuilding:
            # nothing can be in the queue (entries join _rebuilding
            # before their worker starts): the default-off dispatcher
            # tick pays one truthiness check, not a queue lock +
            # caught queue.Empty
            return
        while True:
            try:
                si, new, err, secs = self._rebuilt.get_nowait()
            except queue.Empty:
                return
            self._rebuilding.discard(si)
            if new is None:
                _log.structured("generation_rebuild_failed",
                                session=si, error=repr(err),
                                rebuilds=self._rebuilds[si])
                continue  # budget permitting, a later event retries
            old = self.sessions[si]
            try:
                # release the old claim and drop the old cache arrays;
                # a still-wedged step finishing later republishes only
                # the ORPHANED old names (the new namespace is why)
                old.close()
            except Exception:
                pass
            self.sessions[si] = new
            self._wedged.pop(si, None)
            self._trial_failures[si] = 0
            if self._breakers is not None:
                # fresh warmed session: straight back into rotation
                self._breakers[si].record_success()
            _REBUILDS.inc()
            _rtrace.global_event("sessionRebuilt", session=si,
                                 seconds=round(secs, 3))
            _log.structured("generation_session_rebuilt", session=si,
                            seconds=round(secs, 3),
                            rebuilds=self._rebuilds[si])

    def _update_occupancy(self):
        total = sum(s.spec.slots for s in self.sessions)
        _OCCUPANCY.labels(scheduler="gen%d" % self._sched_id).set(
            len(self._active) / float(total))

    def _apply_pending_swap(self):
        with self._swap_lock:
            pending, self._pending_swap = self._pending_swap, None
        if pending is None:
            return
        params, future = pending
        # what is launched was launched on the old weights: its tokens
        # are delivered before the flip, so every token handed over after
        # swap_weights() returns was computed on the new ones
        for si in range(len(self.sessions)):
            self._settle(si)
        try:
            scopes = []
            for sess in self.sessions:
                if sess.scope not in scopes:
                    scopes.append(sess.scope)
            cache_names = {name for s in self.sessions
                           for name, _, _ in s.spec.cache_vars}
            # phase 1: validate EVERY scope before mutating ANY — a
            # rejection on the second scope must not leave the first
            # already serving the rejected weights (torn swap)
            for scope in scopes:
                for name, val in params.items():
                    if name in cache_names:
                        raise ValueError(
                            "refusing to overwrite cache variable %r"
                            % name)
                    cur = scope.find_var(name)
                    if cur is None:
                        raise ValueError(
                            "swap names unknown variable %r" % name)
                    val = np.asarray(val)
                    # metadata-only checks: materializing live device
                    # params on host here would stall the decode loop
                    # for a full model D2H copy per swap
                    cur_shape = tuple(np.shape(cur))
                    cur_dtype = np.dtype(cur.dtype) \
                        if hasattr(cur, "dtype") \
                        else np.asarray(cur).dtype
                    if tuple(val.shape) != cur_shape or \
                            val.dtype != cur_dtype:
                        raise ValueError(
                            "signature mismatch on %r: push %s/%s vs "
                            "live %s/%s"
                            % (name, val.shape, val.dtype,
                               cur_shape, cur_dtype))
            # phase 2: install everywhere (pure pointer installs —
            # nothing here can raise and tear the fleet)
            for scope in scopes:
                for name, val in params.items():
                    scope.set_var(name, np.asarray(val))
            self._weights_version += 1
            _log.structured("generation_weights_swapped",
                            version=self._weights_version,
                            params=len(params))
            _resolve(future, result=self._weights_version)
        except Exception as exc:
            _resolve(future, exception=exc)

    def swap_weights(self, params, timeout=30.0):
        """Install new parameter values (``{name: array}``) on every
        session's scope BETWEEN decode steps — the hot-swap story for
        stateful serving. The flip lands on a step boundary (the
        dispatcher applies it before its next admit/step, after it has
        collected and delivered the step it had launched ahead), so no
        forward pass mixes versions and no token delivered after this
        returns comes from the old ones; sequences already mid-generation
        continue on the new weights, which is the documented semantic
        for session state (their KV cache keeps the old weights'
        values — retire-and-retry callers who need strict isolation).
        Cache variables are refused; name/shape/dtype mismatches
        reject the push. Returns the new weights version."""
        if self._closed:
            raise RuntimeError("scheduler is closed")
        future = Future()
        with self._swap_lock:
            if self._pending_swap is not None:
                raise RuntimeError("a weight swap is already pending")
            self._pending_swap = (dict(params), future)
        if self._thread is None:
            self._apply_pending_swap()
        try:
            return future.result(timeout=timeout)
        except FutureTimeoutError:
            with self._swap_lock:
                if self._pending_swap is not None and \
                        self._pending_swap[1] is future:
                    # still queued: cancel it so the "failed" push can
                    # never land silently later, and a retry isn't
                    # blocked by a phantom pending swap
                    self._pending_swap = None
                    raise RuntimeError(
                        "weight swap not applied within %.0fs — "
                        "cancelled" % timeout) from None
            # the dispatcher picked it up mid-wait: the install is a
            # bounded pointer flip, give it a moment to land
            return future.result(timeout=5.0)

    def _serve_out(self):
        """Post-stop epilogue, on the dispatcher thread: finish every
        active slot AND place-and-serve everything still waiting —
        including submits that raced the stop marker into the queue
        (a timed-out close()/drain() join leaves this thread sole
        owner of the queues, so an unserved straggler here would be a
        Future nothing ever resolves). Waiting items co-batch into
        free slots like live traffic."""
        while True:
            self._apply_pending_swap()
            self._absorb_rebuilds()
            if self._busy():
                self._step_all()
                continue
            item = self._next_item(block=False)
            if item is None:
                return
            if item is _STOP:
                continue
            if not self._place(item) and not self._active:
                if self._recovery_pending(item):
                    # a rebuild hand-over or a breaker cooldown trial
                    # will make room in finite time: the parked
                    # request is served then, not failed now
                    self._idle_wait(time.sleep, 0.02)
                    continue
                # unplaceable with nothing in flight (external slot
                # holders): resolve rather than spinning forever
                parked = self._pending.popleft()
                self._resolve_err(parked, parked.last_exc
                                  if parked.last_exc is not None
                                  else ServingUnavailableError(
                                      "scheduler stopped before the "
                                      "request could be placed"))

    def _dispatcher_exit(self):
        """Dispatcher epilogue: nothing absorbs rebuilds past this
        point, so mark terminal and release any stragglers (the
        rebuild worker double-checks the flag around its put, closing
        the hand-over race from its side). Health-gauge children
        retire here too: this epilogue is the one point EVERY
        shutdown shape reaches — including a drain() whose bounded
        join expired and whose caller never calls close()."""
        for si in range(len(self.sessions)):
            self._settle(si)    # a dispatcherless close(): see drain()
        self._turn_close(time.perf_counter())
        self._terminal = True
        self._drain_rebuilt()
        self._retire_breaker_gauges()
        from ..observability import health as _health
        _health.unregister_health(getattr(self, "_health_name", ""))

    def _loop(self):
        try:
            self._loop_inner()
        finally:
            self._dispatcher_exit()

    def _loop_inner(self):
        while True:
            self._apply_pending_swap()
            self._absorb_rebuilds()
            if self._busy():
                self._expire_queued()
                got_stop = self._fill_slots()
                self._step_all()
                if got_stop:
                    self._serve_out()
                    return
            else:
                # parked replay items may be waiting out a rebuild
                # with nothing active — their deadlines must keep
                # firing meanwhile (gated by _has_deadlines, so a
                # deadline-free workload pays an attribute check)
                self._expire_queued()
                item = self._idle_wait(self._next_item, True)
                if item is None:
                    if self._closed:
                        return
                    continue
                if item is _STOP:
                    self._serve_out()  # stragglers behind the marker
                    return
                if not self._place(item):
                    # parked with nothing active: only possible while
                    # every fitting session's slots are held outside
                    # this scheduler or a rebuild is in flight — back
                    # off instead of spinning
                    self._idle_wait(time.sleep, 0.02)

    def _fill_slots(self):
        """Admit waiting requests into free slots without blocking.
        Returns True when the stop marker was consumed."""
        while True:
            item = self._next_item(block=False)
            if item is None:
                return False
            if item is _STOP:
                return True
            if not self._place(item):
                return False  # head parked: no capacity this tick

    # -- shutdown --------------------------------------------------------
    def _stop_dispatcher(self, timeout):
        self._closed = True
        if self._thread is not None:
            try:
                self._q.put_nowait(_STOP)
            except queue.Full:
                pass
            self._thread.join(timeout)
            if self._thread.is_alive():
                # the dispatcher is still finishing in-flight
                # generations past the bounded wait: it OWNS the
                # queues (sweeping them from under a live thread
                # races its every tick) and will serve what it holds
                # and exit on closed. Leave everything to it.
                return []
            self._thread = None
        leftovers = [item for item in self._pending if item is not _STOP]
        self._pending.clear()
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not _STOP:
                leftovers.append(item)
        return leftovers

    def drain(self, timeout=None):
        """Graceful drain: stop admission, generate every accepted
        request to completion — in-flight slots AND waiting submits
        (parked or racing the stop marker; served synchronously here,
        slot by slot) — then stop. Every accepted Future resolves."""
        leftovers = self._stop_dispatcher(timeout)
        if self._thread is not None:
            # bounded join expired with the dispatcher still serving:
            # it finishes and resolves everything it holds on its own
            # thread (_serve_out) — two threads must not step the
            # same sessions
            return
        # dispatcher never started or was wedged: serve the remainder
        # here, co-batching waiting requests into free slots (placing
        # one-at-a-time would run each generation solo and forfeit
        # the batching this layer exists for)
        self._pending.extend(leftovers)
        while self._pending or self._busy():
            self._absorb_rebuilds()
            progressed = False
            while self._pending:
                if not self._place(self._pending.popleft()):
                    break  # head parked again: a step must free slots
                progressed = True
            if self._busy():
                self._step_all()
            elif not progressed and self._pending:
                if self._recovery_pending(self._pending[0]):
                    # a rebuild hand-over or cooldown trial serves
                    # the parked items in finite time
                    self._idle_wait(time.sleep, 0.02)
                    continue
                # unplaceable with nothing in flight (external slot
                # holders): resolve rather than spinning forever
                parked = self._pending.popleft()
                self._resolve_err(parked, parked.last_exc
                                  if parked.last_exc is not None
                                  else ServingUnavailableError(
                                      "drain: no session could take "
                                      "the request"))
        self._dispatcher_exit()  # retires the health gauges too

    def _drain_rebuilt(self):
        """Terminal sweep (close()/drain(), or the rebuild worker
        itself when it races a close): completed rebuilds that no
        dispatcher will ever absorb are released — their cache
        claims and device arrays must not outlive the scheduler."""
        while True:
            try:
                si, new, _err, _secs = self._rebuilt.get_nowait()
            except queue.Empty:
                return
            self._rebuilding.discard(si)
            if new is not None:
                try:
                    new.close()
                except Exception:
                    pass

    def _retire_breaker_gauges(self):
        """Drop this scheduler's per-session health-gauge children so
        redeploy cycles don't accumulate stale labels on the shared
        registry (the engine tier's close() discipline); ``retired``
        keeps a straggling transition from resurrecting a child."""
        if self._breakers is None:
            return
        for breaker in self._breakers:
            breaker.retired = True
        # the registry-level sweep retires every family labelled on
        # this scheduler's "g<N>:*" namespace in one pass (the PR-9
        # per-child removal, generalized)
        _metrics.REGISTRY.remove_labeled(
            "replica", prefix="g%d:" % self._sched_id)

    def close(self, timeout=5.0):
        """Fast exit: a live dispatcher serves out everything it owns
        (active slots AND accepted submits) before exiting — past the
        bounded join it keeps doing so on its own thread — so no
        accepted Future is ever left hanging; with no dispatcher
        running, queued requests are failed instead."""
        for item in self._stop_dispatcher(timeout):
            self._resolve_err(item, RuntimeError("scheduler closed"))
        if self._thread is None:
            # dispatcher gone (or never started): nothing absorbs
            # rebuilds anymore; a live dispatcher past the bounded
            # join runs the same epilogue itself when it exits
            self._dispatcher_exit()
        self._retire_breaker_gauges()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()
