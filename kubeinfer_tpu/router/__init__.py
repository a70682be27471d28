"""Prefix-cache-aware fleet router: the request-path tier.

The reference operator stops at placement — its controller creates pods
and copies ready counts (llmservice_controller.go:66-174) but never
touches a request; clients are assumed to sit behind a dumb Service VIP.
At fleet scale that throws away what the radix prefix cache buys: a
hit skips the prefill of every shared block, and which replica a
request lands on decides whether that hit exists. Routing IS the cache policy — the
same insight behind SGLang's cache-aware router and Mooncake's
KVCache-centric scheduling.

This package is an HTTP front door over N inference servers:

- Each replica advertises a capped, versioned set of rolling-hash path
  fingerprints (``RadixCache.summary()``) plus its queue signal, via
  ``GET /cache/summary`` directly or via the node-agent heartbeat's
  ``servingStats`` in the control-plane store.
- ``FleetRouter.route`` scores each live replica as
  ``prefix_match_blocks - alpha * queue_pressure`` (scoring.py), with a
  stale-heartbeat penalty; no positive match degrades to least-loaded.
- ``RouterServer`` proxies ``POST /v1/completions`` to the winner under
  a per-replica RetryPolicy + CircuitBreaker, re-scoring onto the next
  replica when a transport fails — a dead replica degrades routing,
  never correctness (completions are a deterministic function of
  (prompt, seed, sampling), so any replica serves the same tokens).

The same (prefix-affinity, queue-pressure) pair feeds the reconciler's
placement cost (controller/reconciler.py), so the control plane and
the data plane optimize one objective.
"""

from kubeinfer_tpu.router.core import (
    FleetRouter,
    NoReplicaError,
    ReplicaView,
    RouteDecision,
)
from kubeinfer_tpu.router.server import RouterServer

__all__ = [
    "FleetRouter",
    "NoReplicaError",
    "ReplicaView",
    "RouteDecision",
    "RouterServer",
]
