"""The serving tier: snapshot-subscribing predict replicas with
freshness-lag SLOs.

Training is half of "serve millions of users"; this package is the other
half -- the first READ path in the codebase.  :class:`ModelReplica`
subscribes to the ParameterServer's versioned snapshots over the existing
``net/`` plane (delta-mode ``have=`` pulls on a background refresh loop,
CRC-gated, full-pull fallback), holds the current model behind an atomic
swap, and answers PREDICT RPCs while training continues;
:class:`ServingFrontend` registers replicas (HELLO, the PR 2 membership
machinery in ``adopt=False`` mode) and round-robins client requests with
retry/circuit-breaker failover, so a SIGKILLed replica mid-load degrades
to a failover, never an outage.  Every reply carries its freshness lag
(PS clock minus served version, in versions and ms); replicas past the
``async.serve.max.staleness.ms`` SLO answer UNHEALTHY and the frontend
routes around them.

Knobs: ``async.serve.*`` (conf.py).  Entry point: ``bin/async-serve``
(``python -m asyncframework_tpu.serving.cli``).  No benchmark cell
reaches this tier (PERF.md section 7).
"""

from asyncframework_tpu.serving.frontend import PredictError, ServingFrontend
from asyncframework_tpu.serving.metrics import (
    reset_serving_totals,
    serving_snapshot,
    serving_totals,
)
from asyncframework_tpu.serving.replica import ModelReplica

__all__ = [
    "ModelReplica",
    "ServingFrontend",
    "PredictError",
    "serving_totals",
    "serving_snapshot",
    "reset_serving_totals",
]
