"""Online policy serving: bucketed padding + deadline microbatching +
one fixed-shape jitted forward per bucket + heuristic degraded mode,
scaled out as a multi-replica fleet with routing, quotas, trace-driven
load, and telemetry-driven autoscaling.

See docs/serving.md for the design and its invariants; the entry points:

* :class:`PolicyServer` — in-process request/response server;
* :class:`Router` / :class:`ReplicaSet` / :func:`build_fleet` —
  multi-replica fleet: admission control, least-loaded + consistent-hash
  tenant routing, token-bucket quotas, shed-before-degrade, checkpoint
  hot-swap and bucket-ladder re-fit (serve/fleet.py);
* :class:`Autoscaler` / :class:`AutoscaleController` — replica-count
  control loop over the per-replica telemetry registries
  (serve/autoscale.py);
* ``ddls_tpu.serve.loadgen`` — seeded, fingerprinted open-loop traces
  (diurnal + bursts + heavy-tailed sizes);
* :class:`ObsBucketer` / :func:`default_buckets` / :func:`fit_buckets`
  — (max_nodes, max_edges) bucket ladders;
* :class:`MicrobatchEngine` — flush-on-fill-or-deadline queueing;
* :func:`load_checkpoint_params` — checkpoint -> policy variables without
  a training loop;
* ``scripts/serve_policy.py`` — stdin/JSON front end (``--replicas N``
  routes through the fleet Router).

Serving speed is measured by ``benchmarks/run.py`` (``benchmarks/paths/
serve.py``; PERF.md says which serve cells are listed).
"""
from ddls_tpu.serve.autoscale import (AutoscaleConfig, AutoscaleController,
                                      AutoscaleDecision, Autoscaler)
from ddls_tpu.serve.bucketing import (BucketOverflowError, BucketSpec,
                                      ObsBucketer, default_buckets)
from ddls_tpu.serve.fleet import (FleetResponse, ReplicaSet, Router,
                                  TokenBucket, build_fleet, fit_buckets)
from ddls_tpu.serve.microbatch import MicrobatchEngine, PendingRequest
from ddls_tpu.serve.server import (DEFAULT_FALLBACK_DEGREE, BucketForward,
                                   PolicyServer, ServeResponse, ServeStats,
                                   build_model_from_config,
                                   checkpoint_graph_feature_dim,
                                   load_checkpoint_params)

__all__ = [
    "AutoscaleConfig",
    "AutoscaleController",
    "AutoscaleDecision",
    "Autoscaler",
    "BucketForward",
    "BucketOverflowError",
    "BucketSpec",
    "DEFAULT_FALLBACK_DEGREE",
    "FleetResponse",
    "MicrobatchEngine",
    "ObsBucketer",
    "PendingRequest",
    "PolicyServer",
    "ReplicaSet",
    "Router",
    "ServeResponse",
    "ServeStats",
    "TokenBucket",
    "build_fleet",
    "build_model_from_config",
    "checkpoint_graph_feature_dim",
    "default_buckets",
    "fit_buckets",
    "load_checkpoint_params",
]
