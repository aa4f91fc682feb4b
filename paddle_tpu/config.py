"""Framework-level config flags.

The analog of the reference's gflags registry (``paddle/utils/Flags.cpp``,
``FLAGS_check_nan_inf`` in ``framework/executor.cc:26``), reduced to what
matters on TPU.

matmul_precision: precision for dot/conv inside executor traces.
  None (default) resolves per platform: on TPU, 'BF16_BF16_F32' — bf16
  multiplies with f32 accumulation on the MXU (f32 inputs/outputs; the
  standard TPU training recipe; f32-precise to ~3 decimal digits). On
  CPU, leave jax's global setting alone (tests pin 'highest').
  Set explicitly (e.g. 'highest') to force full f32 everywhere.

check_nan_inf: if True, the executor asserts every fetched value is finite
  (reference FLAGS_check_nan_inf per-op scan done once per step here —
  per-op would break XLA fusion).

amp: None or 'bfloat16'. Mixed-precision policy applied by the executor at
  trace time (white/black-listed op boundaries, executor.py): params stay
  f32 master copies in the scope; inputs of matmul/conv ops are cast to
  bf16 (the cast sits inside the op's vjp, so param gradients come back
  f32 — the standard master-weight recipe); loss ops force f32.
  Motivation (measured, see PROFILE.md): the f32 ResNet-50 train step
  moves ~140 GB HBM/step at batch 256 and is bandwidth-bound on a TPU
  v5e (~819 GB/s); bf16 activations halve that.

serving_buckets: default batch buckets for serving.ServingEngine —
  incoming request batches are zero-padded up to the nearest bucket so
  the executor's compile cache sees a closed set of shapes (engines
  constructed with explicit ``buckets=`` ignore this).

serving_breaker_failures: default per-replica circuit-breaker
  threshold for ServingEngine — N CONSECUTIVE execution failures (or a
  single hang past the execution timeout) open the replica's breaker,
  quarantining it out of round-robin until the half-open probe
  re-admits it. 0 (default) = breakers off: no breaker objects are
  constructed and run() keeps the PR-2 fast path (a few None checks
  per request — the serving analog of the ``telemetry`` off-hot-path
  guarantee). Engines constructed with explicit ``breaker_failures=``
  ignore this.

serving_breaker_cooldown_ms: how long an open replica breaker waits
  before the background probe re-runs a warmed bucket there
  (half-open); success re-admits the replica, failure re-opens with a
  fresh cooldown.

serving_deadline_ms: default per-request deadline budget for
  MicroBatcher.submit (and BUCKETED capi_bridge forwards; the raw
  non-bucketed C path has no deadline machinery). 0 (default) = no
  deadline: submit() costs one flag check. When set (or passed
  per-call as ``deadline_ms=``), already-hopeless submits are shed at
  the door (ServingOverloadError, queue-wait EWMA projection) and
  items that expire while queued resolve with ServingDeadlineError
  BEFORE dispatch, so doomed work never occupies a device.

packed_feeds: if True, reader/staging.py packs every batch's feed
  arrays into ONE contiguous 64B-aligned arena block and issues ONE
  ``jax.device_put`` per batch (one per mesh shard under data
  parallelism — jax.make_array_from_single_device_arrays, never a
  replicated full-batch transfer). The executor unpacks inside the
  compiled step (static slices + bitcasts, core/ingest.py) and donates
  the consumed buffer. Off (default): the legacy one-device_put-per-
  array staging path, byte-identical behavior. Independent of
  wire_dtype declarations (layers.data), which are opt-in per feed.

telemetry: if True, arm the observability layer (observability/):
  executor compile-cache + cost-analysis metrics, trainer step-latency/
  throughput metrics, staging queue/arena gauges, and host trace spans
  into the Chrome-trace ring buffer. Off (default), the per-step cost of
  the instrumentation is a flag check — no spans, no metric updates.

nonfinite_guard: if True, the executor wraps the donated state update in
  a finite-check select: when any inexact fetched value (loss/metrics)
  is NaN/Inf, the step becomes an identity update — params and optimizer
  state keep their pre-step values ON DEVICE (RNG still advances so a
  retried batch sees fresh randomness). This is what makes the
  resilience skip/rollback policies safe under donation: by the time the
  host sees the NaN, the update would otherwise already be applied.
  Keyed into the executor compile cache like every trace-time flag.

nonfinite_policy / nonfinite_budget: defaults for
  resilience.RecoveryPolicy — what a ResilientTrainer does on a
  non-finite step ('raise' | 'skip' | 'rollback') and how many
  CONSECUTIVE non-finite steps it tolerates before giving up and
  raising (a finite step resets the count: the budget distinguishes
  divergence from isolated glitches).

reader_retries: default retry budget for the resilient reader wrapper
  (transient OSError-family reader failures are retried with exponential
  backoff; the pass resumes at the first unconsumed sample).

step_deadline_sec: default hung-step watchdog deadline for
  ResilientTrainer (0 = watchdog off).

fault_injection: master switch for resilience.faults — with it False
  (default) every armed fault is inert and each hook site costs one
  flag check. Chaos tests/probes arm it explicitly.

elastic_heartbeat_interval_sec: default cadence of the membership
  heartbeat thread (distributed/elastic.py MembershipHeartbeat). Pair
  with the master's ``heartbeat_timeout_ms`` (MasterServer): the
  deadline should cover several beats so one delayed beat isn't a
  declared death.

elastic_max_restarts: how many teardown/rebuild cycles an
  ElasticTrainerLoop tolerates before raising ElasticRestartLimit —
  bounds a flapping cluster, like nonfinite_budget bounds divergence.

compile_cache_dir: None (default) or a directory path. When set, every
  single-host executor compile (train step or serving bucket) is also
  serialized to disk (core/compile_cache.py), keyed by a stable digest
  of the program content + feed/fetch signature + trace-time flags +
  the jax/backend fingerprint, and a process restart deserializes the
  XLA executable instead of re-tracing and re-compiling it — the
  cold-start story for autoscaling replicas and restarting trainers.
  Entries are sha256-manifested; a corrupt/truncated entry is
  quarantined to ``corrupt_*`` and silently recompiled (a poisoned
  cache dir can slow a start, never crash or mis-execute one). None:
  no filesystem access at all — byte-identical legacy behavior.
  Trust boundary: entries deserialize via jax's pickling executable
  format, so point this only at directories you write.

generation_slots / generation_cache_buckets /
generation_prompt_buckets: defaults for the autoregressive generation
  session (models.transformer.transformer_lm_session +
  serving/generation.py). ``generation_slots`` is the decode
  batch-bucket — how many sequences decode together, each owning one
  KV-cache slot; ``generation_cache_buckets`` are the cache-length
  buckets a session pre-allocates (the smallest covering max_len is
  chosen); ``generation_prompt_buckets`` are the prompt paddings a
  prefill program is compiled for. Together they close the decode
  shape set: exactly one compile per (slot-bucket, cache-bucket) plus
  one per prompt bucket, however many requests flow. Read only at
  session construction — generation unused costs zero flag checks
  anywhere.

generation_replay_attempts: default token-replay failover budget for
  GenerationScheduler. 0 (default) = off: a session failure resolves
  its in-flight requests exceptionally (the pre-replay behavior).
  N > 0: a request whose session fails mid-generation is re-queued
  head-of-line carrying its replay journal (prompt + every token
  generated so far) and re-admitted into a healthy session — the
  prefill of ``prompt ⊕ tokens`` recomputes the exact decode state, so
  greedy output stays token-for-token identical to a fault-free run —
  up to N times before the original failure surfaces. The deadline is
  unchanged across replays (recovery spends the same budget). Read
  only at scheduler construction.

generation_rebuild_limit: how many background teardown/reconstruct
  cycles a quarantined GenerationSession gets (0 = default = off:
  quarantine is permanent until a cooldown trial succeeds). A session
  whose trial re-admissions keep failing — or that wedged past the
  step timeout — is rebuilt in the background: fresh cache variables
  in a fresh namespace (a leaked wedged step can never scribble on the
  new session's state), params re-read from the scope, warmup
  prefill + decode before it re-enters placement. Requires the spec to
  carry a ``rebuild`` factory (transformer_lm_session provides one).
  Read only at scheduler construction.

generation_step_timeout_ms: per-session decode-step timeout for the
  GenerationScheduler dispatcher (0 = default = off: step() runs
  inline, the pre-timeout hot path). When set, each session's step is
  bounded by a worker thread (serving/resilience.py run_bounded): a
  hang past the timeout is treated as a failure — the session's
  requests replay elsewhere, its breaker opens (hang = instant open,
  the PR-5 rule), and the wedged session is excluded from placement
  with its stuck thread leaked-and-capped at one — so one wedged
  step() can no longer freeze every other session and the deadline
  sweeps. Read only at scheduler construction.

generation_block_size / generation_pool_blocks /
generation_prefix_cache: the K/V cache's defaults for
  ``transformer_lm_session`` (models/transformer.py +
  serving/paged_cache.py). A session keeps each layer's K/V as ONE
  [num_blocks, block_size, d_model] block pool: each sequence owns a
  host-side block table, cache writes are block-granular in-place
  updates through the table (under the executor's donation), and HBM
  pinned per sequence is proportional to its LIVE length — concurrency
  is "pool bytes / live tokens", not "slots x worst-case bucket".
  ``generation_block_size`` is the rows-per-block
  granularity (small = less fragmentation waste per sequence, large =
  fewer gather indices and better prefix-sharing amortization);
  ``generation_pool_blocks`` sizes the pool (0 = auto: a whole table
  for every slot, slots x ceil(cache_len/block_size) blocks);
  ``generation_prefix_cache`` additionally content-hashes prefill
  blocks at block granularity and shares full blocks read-only across
  sequences via refcounts (copy-on-write when a sequence writes into
  a shared block), so a shared system prompt prefills ONCE and a
  PR-9 token replay re-prefills only its unshared suffix. All read
  only at session construction — generation unused costs zero flag
  checks anywhere.

generation_paged_kv: the constant True. The dense per-slot layout it
  once switched off went in PR 29; the name stays because
  benchmarks/harness/lm.py::flags reads, sets and restores it, and
  setting it to anything false raises.

decode_policy / decode_temperature / decode_top_k / decode_top_p /
decode_speculate_k / decode_draft_model / decode_constraint: the
  decode-policy tier (serving/decoding/, ops/decoding_ops.py).
  ``decode_policy`` is "greedy" (default) or "sample";
  temperature/top-k/top-p parameterize sampling (RNG is counter-keyed
  per request seed + token position, so sampled streams replay
  bit-identically through PR-9 session failover and PR-13 fleet
  hops). ``decode_speculate_k`` > 0 turns on speculative decoding
  (requires the paged KV layout): a draft model proposes k tokens per
  round and ONE suffix-window forward pass verifies them;
  ``decode_draft_model`` is a dict of transformer_lm_session
  overrides for the draft (None = 1-layer truncated self-draft
  sharing the target's weights). ``decode_constraint`` is a
  TokenConstraint (serving/decoding/constrain.py) whose per-state
  [vocab] -inf mask rows are added to the logits on device. ALL read
  exactly once, at session construction, inside
  ``DecodePolicy.from_flags`` — and the all-defaults combination
  constructs nothing: spec.policy is None, the epilogue is the same
  arg_max, and the dispatcher hot path reads no decode_* flag
  (counting-asserted in tests/test_generation_failover.py).

compile_cache_max_bytes: 0 (default) = the persistent compile cache
  dir grows without bound (the pre-cap behavior). When set, store()
  evicts coldest-mtime entries (bin+manifest together; load() hits
  touch mtime, so this is LRU, not FIFO) until the dir fits, never
  evicting the entry it just published. Evictions are counted in
  ``paddle_deploy_cache_evictions_total``. Only consulted on the
  store path — cache-off means zero flag reads.

request_tracing: if True, arm request-scoped tracing
  (observability/request_trace.py) and the flight recorder
  (observability/flight.py): each sampled serving/generation request
  is minted a TraceContext at submit and typed span events record its
  whole life — queue wait, prefill (prefix-cache hit length), decode
  steps, COW copies, failover hops, rebuilds, breaker transitions,
  deadline expiry, device calls, resolution — retrievable as a span
  tree via /debug/trace. Off (default): mint() is one attribute read
  returning None, every event site is a None check, and the serving
  hot paths keep their flag-check counts and byte-identical behavior.
  The per-stage latency histograms (paddle_request_*_ms) are
  always-on regardless, like every serving front-door metric. Synced
  into module state by the observability config hook — nothing reads
  this flag per request.

trace_sample_rate: fraction of requests minted a TraceContext while
  ``request_tracing`` is armed (1.0 = every request). Sampling
  happens at mint — an unsampled request records no events anywhere
  (including the flight ring) but keeps its always-on histograms.

telemetry_port: 0 (default) = no introspection server. N = serve
  live introspection on 127.0.0.1:N (observability/http.py, stdlib
  http.server on a daemon thread): /metrics (Prometheus text),
  /healthz (engine/scheduler component health, 200/503),
  /debug/trace?id= (one request's span tree), /debug/flight (latest
  flight-recorder bundle). Started/stopped by the config hook when
  the flag changes; a bind failure logs and never breaks set_flags.

flight_dir: where flight-recorder bundles are dumped (None = default
  <tempdir>/paddle_tpu_flight). Bundles are bounded to the newest
  FlightRecorder.max_dumps files; read only at dump time.

fleet_heartbeat_ms: cadence of an EngineWorker's membership beats to
  its FleetRouter (serving/fleet.py); the router's default member
  deadline is 3x this, so one delayed beat is never a declared death
  (the PR-6 rule at the serving tier). Read only inside the fleet
  constructors — the default flags construct no router, no worker, no
  sockets, and no threads, and nothing on the single-process serving
  path reads any fleet_* flag.

fleet_members_min: how many live members a router considers a healthy
  fleet: the /healthz threshold and the ``wait_members`` rendezvous
  default. Routing itself degrades gracefully below it (whoever is
  alive serves). Read only at router construction.

fleet_canary_fraction: the share of live traffic a freshly-swapped
  member receives during a rolling deploy's canary watch (the rest of
  the fleet keeps serving the stable version). Read only at router
  construction.

fleet_metrics_interval_ms: cadence at which an EngineWorker
  piggybacks a mergeable registry snapshot (observability/
  aggregate.py) on its membership heartbeat, for the router-side
  FleetAggregator to fold in with per-(member, incarnation) delta
  accounting. 0 (default): no snapshots ship and the heartbeat frames
  stay byte-identical. Read only inside the fleet constructors.

slo_target_p99_ms: the latency objective an SLOTracker
  (observability/slo.py) judges requests against — observations above
  it (plus shed/deadline events) burn the error budget. 0 (default):
  the fleet router constructs no tracker. Read only at construction.

slo_windows: the SLO burn-rate window widths in seconds, shortest
  first (the multi-window SRE convention: the fast window trips the
  alert, the slow window confirms it is sustained). Read only at
  tracker construction.

fleet_members_max: the autoscaler's upper capacity bound — live
  members plus pending spawns never exceed it, no matter how hard the
  SLO burns (a runaway burn cannot fork-bomb the host). Read only at
  FleetAutoscaler construction; without an autoscaler attached nothing
  reads it.

fleet_tenants: the multi-tenant admission table, or None (default) —
  a dict of ``tenant id -> {"quota": N, "priority": P}``. quota is the
  max in-flight requests that tenant may hold at the router (0 =
  unlimited); priority orders placement under contention (lower number
  wins). A ``"*"`` entry sets the policy for tenants not named.
  None: the router builds no tenant table, ``submit(tenant=...)`` is
  carried for tracing only, and no per-tenant child metrics exist.
  Read only at router construction.

autoscale_burn_threshold: fast-window SLO burn rate above which the
  autoscaler calls the fleet under-provisioned and spawns a member
  (1.0 = burning budget exactly as fast as the objective allows).
  Read only at FleetAutoscaler construction.

autoscale_cooldown_ms: minimum spacing between ANY two capacity
  actions (spawn or retire) — the hysteresis that keeps a flapping
  breaker or a noisy burn signal from oscillating capacity. Read only
  at FleetAutoscaler construction.

autoscale_idle_ms: how long a member must hold zero in-flight
  requests before the autoscaler will drain and retire it (never below
  ``fleet_members_min``). Read only at FleetAutoscaler construction.

autoscale_spawn_timeout_ms: the bound on spawn-to-REG — a spawned
  process that has not joined the membership within it is killed and
  charged to the spawn-failure budget (the monitor tick is never
  blocked; the sweep just checks deadlines). Read only at
  FleetAutoscaler construction.

autoscale_spawn_failures: the spawn-failure budget — after this many
  failed or wedged spawns the autoscaler stops spawning (scale-downs
  still run) until ``reset_spawn_budget()``; a persistently broken
  launch path degrades to a fixed-size fleet instead of a crash loop.
  Read only at FleetAutoscaler construction.

fleet_models: the multi-model catalog, or None (default) — a dict of
  ``model id -> {"params_path"/"model_dir": ..., "tag": ...,
  "bytes": N, "tenants": (...)}`` naming every model the fleet may
  page (serving/model_paging.py). With a catalog armed the router
  routes each tenant to its model's resident members
  (residency-affinity placement), demand-pages non-resident models in
  through the PR-7 swap gates, and applies LRU eviction pressure
  against ``member_resident_bytes``. None: no catalog, no residency
  state, no paging verbs on any frame — routing and envelopes stay
  byte-identical. Read only at router construction.

member_resident_bytes: per-member resident-set byte budget for the
  multi-model fleet — when the catalog-accounted bytes of a member's
  resident models exceed it after a page-in, the router evicts LRU
  resident models from that member (never a model with in-flight
  requests — the BlockPool refcount discipline applied to whole
  weight sets). 0 (default): no eviction pressure. Read only at
  router construction, and only when ``fleet_models`` armed a
  catalog.

model_page_timeout_ms: the bound on one demand page-in (staged load
  -> canary -> flip on the target member) — a page-in that has not
  completed within it is treated as wedged and charged to the
  autoscaler's spawn-failure budget, exactly like a wedged spawn.
  Read only at router construction, and only when ``fleet_models``
  armed a catalog.

embedding_shard_rows: if True, DistEmbedding tables created by
  ``layers.embedding(..., is_distributed=True)`` are row-sharded over
  the mesh data axis by ``row_id % num_shards`` (mod-interleaved
  storage layout, embeddings/sharded.py) — with their optimizer slots
  sharded alongside — so no device ever holds a full table. False
  (default): distributed tables stay replicated and the lookup is a
  plain dense gather; programs without a DistEmbedding never read this
  flag (the executor gates on the program's table registry, one
  getattr). Trace-time: keyed into the executor compile cache for
  DistEmbedding programs.

embedding_a2a: if True (and ``embedding_shard_rows`` is sharding), the
  lookup and its gradient exchange run as an explicit two-hop
  ``all_to_all`` inside the jitted step — id buckets to owning shards,
  rows back; gradients reverse the route and are merged per shard —
  the pserver request/response cycle as ICI collectives. False
  (default): the gather goes through the mod layout as a global-view
  take and GSPMD chooses the collectives. Same numerics either way;
  same read discipline as embedding_shard_rows.

embedding_wire_dtype: payload dtype of the a2a ROW hop (the return
  leg of the two-hop lookup). "int8": rows are quantized shard-side
  (symmetric per-row amax/127 scale), the int8 rows plus one f32
  scale per row cross the wire, and the receiver dequantizes after
  the return hop — ~3.9x fewer row-payload bytes per step (the
  gradient hop stays f32: training cotangents are not forward
  activations). None (default): f32 rows, byte-identical route.
  Trace-time for DistEmbedding programs only (read inside the a2a
  lookup's _trace_mode and keyed into the executor compile cache);
  plain programs never read it.

serving_quant_compute: if True, serving consumers run int8-exported
  weights AS int8 on device — ``ServingEngine`` asks
  ``load_inference_model`` to skip the f32 dequantize copy, and
  ``GenerationSession`` quantizes its programs' eligible weights in
  place at construction (serving/quant.py arm/install); matmul/conv
  ops on those weights then take the int8 x int8 -> int32 MXU path
  with the per-output-channel scale fused into the f32 epilogue
  (ops/quant_ops.py). False (default): int8 artifacts dequantize at
  load exactly as before. Read only at engine/session construction;
  the executor gates per program on one getattr, zero flag reads.

quant_pallas: route the quantized DECODE matmul through the fused
  Pallas dequant-matmul kernel (ops/quant_ops.py) instead of the
  dense XLA int8 path. Same numerics bit-for-bit (the int8 dot is
  exact in int32 and the f32 epilogue expression is shared); the
  kernel fuses activation-quantize + int8 dot + scale epilogue into
  one VMEM pass. Read only where serving_quant_compute arms a
  program (construction); stored on the program tag, so the trace
  itself reads no flags.

generation_kv_dtype: dtype of the generation K/V block pools.
  "bfloat16": cache writes
  round to bf16 and attention reads upcast to f32 (halves
  kv_cache_bytes_per_token, doubling fixed-budget
  concurrency). None (default): caches stay f32, byte-identical.
  Read only inside ``transformer_lm_session`` at spec construction
  (and only when the caller left ``dtype`` at its default);
  rebuilds inherit the resolved dtype without re-reading.

fused_conv_bn: if True, ``models.resnet.conv_bn_layer`` emits the
  fused ``conv2d_bn`` op (ops/pallas_conv_bn.py) — conv and the BN
  batch moments in ONE kernel pass (Pallas epilogue accumulates
  per-channel sum/sumsq as the conv output is produced), so the
  bandwidth-bound ResNet step writes activations once instead of
  re-reading the conv output for the moments. False (default): the
  separate conv2d + batch_norm ops, byte-identical. Read only at
  model construction.
"""

import jax

_flags = {
    "matmul_precision": None,
    "check_nan_inf": False,
    "amp": None,
    # Pallas fused attention kernel for multihead_attention (see
    # ops/pallas_attention.py); interpret-mode off-TPU
    "flash_attention": False,
    "packed_feeds": False,
    "telemetry": False,
    "serving_buckets": (1, 8, 32),
    # serving resilience (serving/resilience.py; see docstring)
    "serving_breaker_failures": 0,
    "serving_breaker_cooldown_ms": 1000.0,
    "serving_deadline_ms": 0,
    # resilience (resilience/supervisor.py defaults; see docstring)
    "nonfinite_guard": False,
    "nonfinite_policy": "raise",
    "nonfinite_budget": 8,
    "reader_retries": 3,
    "step_deadline_sec": 0,
    "fault_injection": False,
    # elastic multi-host (distributed/elastic.py; only read by the
    # elastic runtime — with no ElasticTrainerLoop constructed, nothing
    # on the single-process train path looks at these)
    "elastic_heartbeat_interval_sec": 2.0,
    "elastic_max_restarts": 3,
    # deploy resilience (core/compile_cache.py; None = no disk access)
    "compile_cache_dir": None,
    # autoregressive generation serving (serving/generation.py +
    # models.transformer.transformer_lm_session). Read ONLY when a
    # session/scheduler is constructed — with generation unused,
    # nothing on the serving fast path or the executor step looks at
    # these (the off-hot-path guarantee extends to them).
    "generation_slots": 4,
    "generation_cache_buckets": (128,),
    "generation_prompt_buckets": (16,),
    # stateful-generation resilience (serving/generation.py; read only
    # at scheduler construction — defaults keep the PR-8 dispatcher
    # hot path and failure behavior byte-identical)
    "generation_replay_attempts": 0,
    "generation_rebuild_limit": 0,
    "generation_step_timeout_ms": 0,
    # the KV cache's block pool + prefix reuse (serving/paged_cache.py;
    # read only at session construction). generation_paged_kv is a
    # constant: benchmarks/harness/lm.py::flags still sets it
    "generation_paged_kv": True,
    "generation_block_size": 16,
    "generation_pool_blocks": 0,
    "generation_prefix_cache": False,
    # decode policy (serving/decoding/; read only at session
    # construction via DecodePolicy.from_flags — the all-defaults
    # combination resolves to NO policy object at all, so the greedy
    # argmax epilogue, programs, and dispatcher hot path stay
    # byte-identical and flag-check-count-identical to PR-8..16)
    "decode_policy": "greedy",
    "decode_temperature": 1.0,
    "decode_top_k": 0,
    "decode_top_p": 1.0,
    "decode_speculate_k": 0,
    "decode_draft_model": None,
    "decode_constraint": None,
    # persistent compile cache size cap (core/compile_cache.py)
    "compile_cache_max_bytes": 0,
    # request-scoped tracing + flight recorder + live introspection
    # (observability/request_trace.py, flight.py, http.py; synced into
    # module state by the observability config hook — no serving hot
    # path reads these per request)
    "request_tracing": False,
    "trace_sample_rate": 1.0,
    "telemetry_port": 0,
    "flight_dir": None,
    # serving fleet (serving/fleet.py; read only inside FleetRouter /
    # EngineWorker constructors — defaults construct no router, no
    # sockets, no threads anywhere)
    "fleet_heartbeat_ms": 1000.0,
    "fleet_members_min": 1,
    "fleet_canary_fraction": 0.25,
    # fleet telemetry plane (observability/aggregate.py + slo.py,
    # wired in serving/fleet.py; read only inside the fleet
    # constructors — 0 disables snapshot shipping / SLO tracking and
    # keeps the defaults byte-identical)
    "fleet_metrics_interval_ms": 0.0,
    "slo_target_p99_ms": 0.0,
    "slo_windows": (5.0, 60.0),
    # autoscaling + multi-tenancy (serving/autoscale.py + fleet.py;
    # read only inside FleetAutoscaler construction / FleetRouter
    # construction — defaults construct no autoscaler, no tenant
    # table, no extra threads or sockets, and the monitor tick gates
    # on one attribute-is-None check)
    "fleet_members_max": 8,
    "fleet_tenants": None,
    "autoscale_burn_threshold": 1.0,
    "autoscale_cooldown_ms": 5000.0,
    "autoscale_idle_ms": 10000.0,
    "autoscale_spawn_timeout_ms": 30000.0,
    "autoscale_spawn_failures": 3,
    # multi-model fleet paging (serving/model_paging.py + fleet.py;
    # read only at router construction — and the byte budget / page
    # timeout only when a catalog is actually armed. None/0 defaults
    # build no catalog, no residency state, and keep every envelope
    # and heartbeat frame byte-identical)
    "fleet_models": None,
    "member_resident_bytes": 0,
    "model_page_timeout_ms": 30000.0,
    # sharded embedding tables (embeddings/sharded.py; read only when a
    # program registered a DistEmbedding — defaults construct none of
    # the subsystem and plain programs never read these)
    "embedding_shard_rows": False,
    "embedding_a2a": False,
    # quantized COMPUTE (ops/quant_ops.py, serving/quant.py arm/install;
    # read only at engine/session/model construction — defaults keep
    # every artifact load, decode program, and a2a route byte-identical)
    "embedding_wire_dtype": None,
    "serving_quant_compute": False,
    "quant_pallas": False,
    "generation_kv_dtype": None,
    "fused_conv_bn": False,
}

# Observers called with the flag dict after every set_flags (the
# observability package arms/disarms its tracer through this).
_on_change = []


_DENSE_KV_REMOVED = (
    "the dense per-slot KV layout went in PR 29: the paged block pool is "
    "the only cache a generation session has (generation_paged_kv is the "
    "constant True, and paged= takes None or True)")


def set_flags(**kwargs):
    for k, v in kwargs.items():
        if k not in _flags:
            raise KeyError("unknown flag %r (have %s)" % (k, sorted(_flags)))
        if k == "generation_paged_kv" and not v:
            raise ValueError(_DENSE_KV_REMOVED)
        _flags[k] = v
    for cb in list(_on_change):
        cb(_flags)


def get_flag(name):
    return _flags[name]


def resolve_matmul_precision():
    """The precision context to trace executor blocks under, or None."""
    p = _flags["matmul_precision"]
    if p is not None:
        return p
    if jax.devices()[0].platform == "tpu":
        return "BF16_BF16_F32"
    return None
