"""Overload protection for the serving plane (docs/SERVING.md).

Overload is a fault class, not a steady state to be endured: without
admission control a surge degrades latency for *every* request instead
of shedding the excess (ROADMAP item 3's "per-class SLOs with admission
control and 503/Retry-After backpressure"). This package gives
tools/serve.py the three mechanisms that bound the damage:

- `admission`: per-class token-bucket rate limits, a bounded
  earliest-deadline-first admission queue, load shedding with a
  Retry-After computed from the observed service rate, deadline
  bookkeeping, and — with a paged KV plane (pipeedge_tpu/kv) — a KV
  TOKEN budget: each grant charges the request's prompt+max-new-tokens
  page reservation, so concurrency is bounded by cache tokens instead
  of `max_active` slots (`AdmissionController`).
- `brownout`: a watermark-driven degradation ladder that steps through
  disable-speculative -> clamp new_tokens -> evict cold KV pages ->
  shed best-effort -> shed batch, and steps back down with hysteresis
  (`BrownoutLadder`).
- deadline propagation itself lives in the executor
  (`parallel/batcher.py`): each request's absolute deadline rides into
  the decode loop, and expiry fires the existing `cancel` flag at the
  next decode-step boundary so dead work stops consuming TPU time.
- `router`: the routed decode fleet's front end (`--role router`) — a
  health-checked replica registry with EWMA-scored hysteresis
  (healthy→suspect→drained→dead), prefix-affinity routing, bounded
  retry/failover, tail hedging, graceful drain with KV page migration
  over the ship codec (`DecodeRouter`, `ReplicaRegistry`,
  `RouterPolicy` — docs/SERVING.md router topology).
- `autoscale`: the closed capacity loop over that membership plane
  (`--autoscale {off,advise,auto}`) — a governor-ticked
  `CapacityController` with confirm/dwell hysteresis, a flap damper,
  scale-down ordered behind brownout, and dry-run `held` transitions
  (docs/FAULT_TOLERANCE.md autoscale lifecycle).
"""
from .admission import (AdmissionController, AdmissionShed, ClassPolicy,
                        DeadlineExceeded, EDFQueue, REQUEST_CLASSES,
                        ServiceRateEstimator, TokenBucket, default_policies,
                        parse_class_map)
from .autoscale import (AutoscaleRunner, CapacityController,  # noqa: F401
                        CapacityPolicy)
from .brownout import BrownoutLadder, LEVEL_NAMES, Watermarks
from .router import (DecodeRouter, NoReplicaAvailable,  # noqa: F401
                     REPLICA_DEAD, REPLICA_DRAINED, REPLICA_HEALTHY,
                     REPLICA_SUSPECT, ReplicaRegistry, RouterPolicy)

__all__ = [
    "AdmissionController", "AdmissionShed", "AutoscaleRunner",
    "BrownoutLadder", "CapacityController", "CapacityPolicy",
    "ClassPolicy", "DeadlineExceeded", "DecodeRouter", "EDFQueue",
    "LEVEL_NAMES", "NoReplicaAvailable", "REPLICA_DEAD",
    "REPLICA_DRAINED", "REPLICA_HEALTHY", "REPLICA_SUSPECT",
    "REQUEST_CLASSES", "ReplicaRegistry", "RouterPolicy",
    "ServiceRateEstimator", "TokenBucket", "Watermarks",
    "default_policies", "parse_class_map",
]
