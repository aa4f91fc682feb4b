"""Inference serving subsystem (the reference's ``paddle/capi``
examples tier, rebuilt TPU-native — see ROADMAP north star).

Four cooperating pieces:

* :mod:`engine`     — :class:`ServingEngine`: loads an exported/merged
  model once, pads requests to fixed batch buckets (the Executor's
  compile cache then sees a closed shape set), AOT-warms every bucket,
  and dispatches round-robin across device replicas — skipping
  replicas whose circuit breaker is open, failing requests over to the
  next healthy replica.
* :mod:`batcher`    — :class:`MicroBatcher`: thread-safe
  ``submit(feed) -> Future`` micro-batching with a max-latency
  deadline, bounded-queue backpressure, per-request serve-by
  deadlines, EWMA-based adaptive load shedding, and a graceful
  ``drain()``.
* :mod:`resilience` — the failure model: :class:`ReplicaBreaker`
  (closed/open/half-open with background probe re-admission),
  :class:`ServingDeadlineError` / :class:`ServingTimeoutError` /
  :class:`ServingUnavailableError`, and the always-on recovery
  counters (``paddle_serving_failover_total``,
  ``paddle_serving_breaker_transitions_total``, ...).
* :mod:`quant`      — post-training int8 weight quantization
  (per-output-channel symmetric scales) wired into
  ``io.save_inference_model(..., quantize="int8")`` and transparently
  dequantized at load.
* :mod:`generation` — the stateful (LLM) tier:
  :class:`GenerationSession` (on-device KV-cache decode batch,
  prefill/step/retire over cache slots) and
  :class:`GenerationScheduler` (continuous batching:
  ``submit(prompt) -> Future`` with deadlines/backpressure/shedding,
  mid-flight slot-level admit/retire, per-session breakers, drain,
  and between-step weight swap).
* :mod:`paged_cache` — the memory tier of a generation session's
  K/V cache: :class:`BlockPool` (fixed-size block
  allocator with refcounts over the per-layer K/V pools) and
  :class:`PrefixIndex` (content-hashed prompt caching: shared prefix
  blocks, copy-on-write divergence, LRU eviction under pressure).
* :mod:`fleet` — the multi-process tier: :class:`FleetRouter`
  (line-protocol membership with heartbeats and generation fencing,
  least-loaded routing over per-member breakers, cross-process
  token-replay failover, rolling deploys with canary watch and
  fleet-wide rollback) and :class:`EngineWorker` (the process wrapper
  a member runs, streaming tokens over ``wire.py``'s JSON-line
  transport).

Everything is instrumented through :mod:`paddle_tpu.observability`;
``tools/serving_probe.py`` exercises the stack headless and
``tools/serving_chaos_probe.py`` drives it through injected replica
failures and overload (fault sites ``serving_replica_fail`` /
``serving_replica_slow`` / ``serving_overload``).
"""

from . import deploy  # noqa: F401
from . import quant  # noqa: F401
from . import resilience  # noqa: F401
from .resilience import (ServingDeadlineError,  # noqa: F401
                         ServingTimeoutError, ServingUnavailableError,
                         ReplicaBreaker)
from .deploy import SwapRejectedError  # noqa: F401
from .engine import ServingEngine  # noqa: F401
from .batcher import MicroBatcher, ServingOverloadError  # noqa: F401
from .generation import (GenerationScheduler,  # noqa: F401
                         GenerationSession, GenerationSpec)
from .paged_cache import (BlockPool, PoolExhausted,  # noqa: F401
                          PrefixIndex)
from .fleet import EngineWorker, FleetRouter  # noqa: F401
from .wire import WireError  # noqa: F401

__all__ = ["ServingEngine", "MicroBatcher", "ServingOverloadError",
           "ServingDeadlineError", "ServingTimeoutError",
           "ServingUnavailableError", "SwapRejectedError",
           "ReplicaBreaker", "GenerationSession", "GenerationScheduler",
           "GenerationSpec", "BlockPool", "PrefixIndex",
           "PoolExhausted", "FleetRouter", "EngineWorker", "WireError",
           "deploy", "fleet", "generation", "paged_cache",
           "quant", "resilience", "wire"]
