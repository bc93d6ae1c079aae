"""Dynamic-batching TPU inference serving (SERVING.md is the guide).

The synchronous `inference.Predictor` is a library; this package is the
deployment surface in front of it:

- bucketing.py — shape-bucket policy (powers-of-two batch buckets with
                 pad/slice helpers) shared with the Predictor, so any
                 batch size maps onto a small AOT-warmable signature
                 set.
- batcher.py   — bounded request queue + coalescing thread: largest
                 fitting bucket under a max_wait_ms deadline,
                 per-request timeouts, reject-not-block admission
                 control, graceful drain.
- engine.py    — Predictor wrapped with bucket-aware dispatch, AOT
                 warmup of every bucket at startup, per-bucket
                 latency/count accounting.
- kv_cache.py  — paged/blocked KV cache: preallocated device block
                 pool + host-side allocator + per-sequence block
                 tables, so decode memory scales with live tokens.
- kv_reuse.py  — block-level KV reuse: ref-counted allocator with a
                 content-hash prefix index (LRU retention, COW) and
                 the speculative-decoding accept rule (SERVING.md
                 §KV reuse).
- decode.py    — continuous-batching autoregressive decode engine:
                 prefill/decode phase split, in-flight batching,
                 streaming token handles, warmstart phase-grid bake,
                 chunked prefill + prefix caching + speculative
                 decoding (SERVING.md §Continuous batching, §KV
                 reuse).
- httpd.py     — JSON-over-HTTP frontend (POST /v1/predict, chunked
                 POST /v1/generate token streaming, GET /v1/status
                 /v1/models, the /v1/load probe + stateful
                 /v1/healthz) on the shared observability HTTP base;
                 multi-model Server (one engine+batcher slot per model
                 id) with zero-downtime hot-swap.
- qos.py       — per-tenant QoS (SERVING.md §Multi-tenancy): tier/
                 weight/quota policy, start-time-fair weighted token
                 scheduling, shed-lowest-tier-first admission and the
                 typed ShedError behind the Retry-After 503.
- registry.py  — content-addressed model registry: publish warmstart
                 artifacts under digest, replicas watch and hot-swap
                 new versions with zero failed requests.
- router.py    — fleet front tier (SERVING.md §Fleet): power-of-two-
                 choices load balancing over N replicas, health
                 ejection, per-endpoint circuit breakers, idempotent
                 retry failover, rendezvous-backed elastic membership.
- replica.py   — one fleet replica process (warmstart boot, rendezvous
                 heartbeat, SIGTERM → leave/drain/stop).
- autoscale.py — queue-depth/p99 control loop moving the replica count
                 within min/max bounds with hysteresis.

Telemetry flows through the PR 1/2 observability stack: queue depth,
batch-size/queue-wait/end-to-end histograms, reject/timeout counters,
per-bucket compile events — all visible on the /metrics endpoint and
the JSONL event log.
"""

from .bucketing import BucketPolicy, common_batch  # noqa: F401
from .batcher import (  # noqa: F401
    Batcher, EngineError, QueueFullError, RequestTimeout, ServerClosed,
)
from .engine import Engine, ServingConfig  # noqa: F401
from .kv_cache import BlockAllocator, KVCacheConfig, NoBlocksError  # noqa: F401
from .kv_reuse import ReuseBlockAllocator, accept_length, hash_blocks  # noqa: F401
from .decode import DecodeConfig, DecodeEngine, DecodeHandle  # noqa: F401
from .httpd import Server  # noqa: F401
from .qos import (  # noqa: F401
    QoSPolicy, ShedError, TenantSpec, WeightedFairScheduler,
)
from .registry import ModelRegistry, RegistryError  # noqa: F401
from .router import (  # noqa: F401
    FleetError, FleetTimeout, NoReplicasError, ReplicaRejected, Router,
    RouterServer, StreamBrokenError, TierShed,
)
from .autoscale import Autoscaler  # noqa: F401

__all__ = [
    "BucketPolicy", "common_batch",
    "Batcher", "EngineError", "QueueFullError", "RequestTimeout",
    "ServerClosed",
    "Engine", "ServingConfig", "Server",
    "BlockAllocator", "KVCacheConfig", "NoBlocksError",
    "ReuseBlockAllocator", "accept_length", "hash_blocks",
    "DecodeConfig", "DecodeEngine", "DecodeHandle",
    "QoSPolicy", "ShedError", "TenantSpec", "WeightedFairScheduler",
    "ModelRegistry", "RegistryError",
    "Router", "RouterServer", "Autoscaler",
    "FleetError", "NoReplicasError", "ReplicaRejected", "FleetTimeout",
    "StreamBrokenError", "TierShed",
]
