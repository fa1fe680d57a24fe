"""The online inference plane (design.md §15) — the ROADMAP
``[serving]`` lane: training has been industrial for ten PRs; this
package is the runtime that lets the resulting models face traffic.

The reference project stops at batch prediction (``ParallelPostFit``-
style shard-wise apply, SURVEY §3.5); an online plane is a new
subsystem, built entirely on substrate earlier PRs shipped:

* **micro-batching** (:mod:`.batcher`): queued single-row / small-batch
  requests coalesce into the shared bucket ladder
  (``DASK_ML_TPU_BUCKET``), so every dispatch hits a warm cached
  program (:mod:`dask_ml_tpu.programs`) — zero steady-state compiles,
  sanitizer-verified;
* **model residency** (:mod:`.residency`): many fitted models stay
  device-resident at once under an HBM budget with LRU parking, and
  homogeneous models lane-pack into one vmapped program per window;
* **admission control**: a bounded request queue sheds load with an
  explicit ``queue_full`` rejection, per-request deadlines drop stale
  work before dispatch — backpressure is a fast error, never silent
  latency;
* **ops for free** (:mod:`.runtime`): the serve loop is a supervised
  unit (``/healthz`` flips when it dies, restarts ride the fault
  budget with the in-flight batch replayed), and per-model p50/p99
  request latency, batch occupancy, and rejection counters export
  through the live ``/metrics`` endpoint.

Quick start::

    from dask_ml_tpu.serve import ModelServer

    server = ModelServer()
    server.load("churn", fitted_sgd_classifier)
    label = server.predict("churn", one_row)        # sync, micro-batched
    fut = server.submit("churn", rows, deadline_s=0.05)
    labels = fut.result()
    server.close()

Fleet quick start (replication + routing, design.md §22)::

    from dask_ml_tpu.serve import ServeFleet

    fleet = ServeFleet(replicas=4)        # DASK_ML_TPU_FLEET_REPLICAS
    fleet.load("churn", fitted_sgd_classifier, hot=True, slo_ms=20)
    labels = fleet.predict("churn", rows, priority="high")
    fleet.rolling_refresh("churn", retrained_model)  # drain barrier
    fleet.close()
"""

from .batcher import RequestRejected, ServeFuture  # noqa: F401
from .config import (  # noqa: F401
    DEADLINE_ENV,
    FLEET_DRAIN_ENV,
    FLEET_HEDGE_ENV,
    FLEET_INJECT_ENV,
    FLEET_PRIORITIES_ENV,
    FLEET_REPLICAS_ENV,
    FLEET_RETRIES_ENV,
    HBM_ENV,
    MAX_BATCH_ENV,
    QUEUE_ENV,
    WINDOW_ENV,
)
from .fleet import FleetFuture, Replica, ServeFleet  # noqa: F401
from .residency import ModelRegistry, serve_pack_key  # noqa: F401
from .router import (  # noqa: F401
    REPLICA_STATES,
    Router,
    full_jitter_backoff,
    rendezvous,
)
from .runtime import (  # noqa: F401
    SERVE_THREAD_NAME,
    ModelServer,
    report,
)

__all__ = [
    "DEADLINE_ENV",
    "FLEET_DRAIN_ENV",
    "FLEET_HEDGE_ENV",
    "FLEET_INJECT_ENV",
    "FLEET_PRIORITIES_ENV",
    "FLEET_REPLICAS_ENV",
    "FLEET_RETRIES_ENV",
    "HBM_ENV",
    "MAX_BATCH_ENV",
    "QUEUE_ENV",
    "WINDOW_ENV",
    "REPLICA_STATES",
    "SERVE_THREAD_NAME",
    "FleetFuture",
    "ModelRegistry",
    "ModelServer",
    "Replica",
    "RequestRejected",
    "Router",
    "ServeFleet",
    "ServeFuture",
    "full_jitter_backoff",
    "rendezvous",
    "report",
    "serve_pack_key",
]
