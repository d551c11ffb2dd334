"""paddle_tpu.serving: multi-tenant inference with continuous batching.

The "millions of users" layer over the PR 1–5 stack: exported models
(``jit.load`` / ``inference.load_inference_model``) are kept warm in the
compiled-program caches and served under load with

- **fixed bucket shapes** (``bucketing``) — a closed compiled-shape set,
  so steady-state traffic never traces or compiles (``jax.compiles`` flat
  after ``warmup()``; graftlint GL013 lints for violations statically);
- **iteration-level continuous batching** (``runners``) — one-shot models
  re-pack the queue every batch; generative models join/leave the
  **paged** KV cache per decode step (``paged_kv`` / ``paged_runner``:
  block tables over a refcounted page pool, prefix sharing of identical
  prompt prefixes, chunked prefill for long prompts, speculative
  decoding via a draft spec; ``kv_cache`` holds the contract a model
  implements);
- **production edges** (``scheduler``) — bounded admission queues with
  429-style shedding, per-request deadlines (expired work is dropped, not
  run), watchdog-bounded client waits;
- **tenancy + elasticity** (``admission`` / ``autoscaler``) — per-tenant
  weighted-fair (deficit-round-robin) admission with token-bucket quotas
  (over-quota submits shed with reason ``quota``), per-tenant SLO burn
  isolation, and an autoscaler that grows the router fleet on sustained
  SLO burn (warm, zero-compile via the artifact tier) and shrinks it
  through ``drain()`` with zero aborted in-flight work;
- **telemetry** on the PR 3 spine — ``serving.*`` counters, latency /
  queue-wait / batch-occupancy histograms, per-request events
  (``tools/telemetry_dump.py --serving`` summarizes them).

Quick start (docs/SERVING.md has the full guide)::

    engine = serving.ServingEngine(queue_capacity=64)
    ep = engine.register('clf', layer=model,
                         example={'x': np.zeros((16,), np.float32)})
    engine.warmup()          # compile every bucket now
    engine.start()           # background worker thread
    resp = ep.predict({'x': features}, deadline_ms=50)
"""
from .admission import (DEFAULT_TENANT, QuotaExceededError, TenantArbiter,
                        TenantPolicy, WeightedFairQueue, tenant_stats)
from .autoscaler import FleetAutoscaler
from .bucketing import (DEFAULT_BATCH_BUCKETS, BucketSpec, pad_to_bucket,
                        select_bucket, stack_examples)
from .engine import Endpoint, EngineDeadError, ServingEngine
from .fleet_supervisor import FleetSupervisor
from .kv_cache import GenerativeSpec, TinyCausalLM
from .paged_kv import (PageAllocator, PagesExhaustedError, PrefixCache,
                       chain_hashes)
from .paged_runner import PagedGenerativeRunner
from .router import (CircuitBreaker, FleetOverloadError, FleetPending,
                     FleetRouter, NoHealthyReplicaError, ReplicaError,
                     ReplicaHandle, RouterPolicy)
from .runners import BatchRunner
from .scheduler import (AdmissionQueue, PendingRequest, QueueFullError,
                        Request, Response, STATUS_CANCELLED,
                        STATUS_DEADLINE, STATUS_ERROR, STATUS_OK)
from . import (admission, autoscaler, bucketing, engine,  # noqa: F401
               fleet_supervisor, kv_cache, paged_kv, paged_runner, router,
               runners, scheduler)

__all__ = [
    'ServingEngine', 'Endpoint', 'EngineDeadError',
    'FleetRouter', 'RouterPolicy', 'ReplicaHandle', 'CircuitBreaker',
    'FleetPending', 'ReplicaError', 'NoHealthyReplicaError',
    'FleetOverloadError', 'FleetSupervisor', 'FleetAutoscaler',
    'TenantPolicy', 'TenantArbiter', 'WeightedFairQueue',
    'QuotaExceededError', 'DEFAULT_TENANT', 'tenant_stats',
    'BucketSpec', 'DEFAULT_BATCH_BUCKETS', 'select_bucket', 'pad_to_bucket',
    'stack_examples',
    'GenerativeSpec', 'TinyCausalLM',
    'BatchRunner', 'PagedGenerativeRunner',
    'PageAllocator', 'PagesExhaustedError', 'PrefixCache', 'chain_hashes',
    'AdmissionQueue', 'PendingRequest', 'QueueFullError', 'Request',
    'Response', 'STATUS_OK', 'STATUS_DEADLINE', 'STATUS_ERROR',
    'STATUS_CANCELLED',
]
