"""cxxnet_tpu.serve — dynamic-batching inference serving over exported
artifacts (or a live trainer), single-engine or multi-replica.

The deployment story past ``task=export_model``: ``serving.py`` turns a
trained net into a self-contained AOT artifact, and this package turns
that artifact into a trafficable service —

* :mod:`.engine` — :class:`ServingEngine`: bounded admission queue +
  one dispatch thread coalescing arbitrary per-request batch sizes
  into shape-bucket batches (max_wait_ms / max_batch / queue_limit /
  timeout_ms knobs), slot-granular continuous admission for exported
  decoders, per-request deadlines, expired-request sweeping, and a
  formal ``drain(timeout)`` (:class:`DrainError`);
* :mod:`.server` — stdlib ThreadingHTTPServer exposing /predict,
  /generate, /healthz, /metrics (+ /swap under a router) with JSON
  bodies, per-request timeouts, computed Retry-After backpressure;
* :mod:`.stats` — streaming latency/occupancy telemetry
  (p50/p90/p99, throughput, queue depth, batch occupancy) built on
  ``metrics.StreamingQuantile``;
* :mod:`.replica` — :class:`ReplicaSet`: N supervised engine replicas
  (warming/healthy/degraded/draining/dead, heartbeat probes,
  exponential-backoff re-admission);
* :mod:`.router` — :class:`Router`: least-outstanding load balancing,
  bounded deadline-respecting failover, priority + deadline shedding
  with computed Retry-After, graceful drain, zero-downtime hot swap;
* :mod:`.faults` — :class:`FaultInjector`: the deterministic fault
  seam every robustness claim above is tested against;
* :mod:`.loadgen` — open-loop trace replay: the scenario catalog
  (bursty / mixed-priority / mixed predict+generate / slow-client /
  mixed-prompt-length), a replayable JSONL trace format the access log
  can produce, and the scoring of a replay (docs/scenarios.md);
* :mod:`.continuous` — :class:`ContinuousDecodeEngine`: iteration-
  level continuous batching over a split-phase ``export_decode_step``
  artifact — paged KV pool (:mod:`.kvpool`), prefill/decode phase
  split, per-token streaming (:class:`StreamRequest`);
* :mod:`.kvpool` — :class:`BlockPool`: the host-side page allocator
  behind the paged KV pool (block tables, trash page, leak checks).

CLI: ``task = serve`` (+ ``serve_replicas = N`` for the router
topology) — docs/serving.md, docs/tasks.md.
"""

from .engine import (DrainError, QueueFullError, Request,
                     RequestExpired, ServingEngine)
from .stats import ServeStats

__all__ = ["QueueFullError", "Request", "RequestExpired", "DrainError",
           "ServingEngine", "ServeStats",
           "ContinuousDecodeEngine", "StreamRequest",
           "BlockPool", "PoolExhausted",
           "ServeHTTPServer", "build_server",
           "Router", "RouterRequest", "ShedError", "NoReplicaError",
           "FailoverExhausted",
           "ReplicaSet", "Replica",
           "FaultInjector", "FaultError", "ReplicaDead",
           "LoadGen", "EngineTarget", "HTTPTarget", "make_scenario"]

# lazily-resolved names -> defining submodule: server.py pulls in
# http.server, router/replica/faults are only needed by multi-replica
# deployments — engine-only users (and the package import) stay light
_LAZY = {
    "ContinuousDecodeEngine": "continuous",
    "StreamRequest": "continuous",
    "BlockPool": "kvpool", "PoolExhausted": "kvpool",
    "ServeHTTPServer": "server", "build_server": "server",
    "LoadGen": "loadgen", "EngineTarget": "loadgen",
    "HTTPTarget": "loadgen", "make_scenario": "loadgen",
    "Router": "router", "RouterRequest": "router",
    "ShedError": "router", "NoReplicaError": "router",
    "FailoverExhausted": "router",
    "ReplicaSet": "replica", "Replica": "replica",
    "FaultInjector": "faults", "FaultError": "faults",
    "ReplicaDead": "faults",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is not None:
        import importlib
        return getattr(importlib.import_module("." + mod, __name__),
                       name)
    raise AttributeError(name)
