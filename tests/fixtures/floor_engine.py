"""Service-floor recommendation engine for the ``fleet`` drill (drills.py).

Real fleet replicas are service-time-bound — each query pays an
accelerator dispatch and storage hops — so adding replicas adds capacity.
On the 2-core CI box three CPU-bound replica subprocesses merely contend
with each other, the router, and the load client, and fleet goodput
*shrinks* as replicas are added: a property of the box, not the router.

This engine pins per-query service cost to a configured floor
(``PIO_BENCH_SERVICE_FLOOR_MS`` per query, charged per dispatch as
``floor x batch_size`` inside the executor thread, on top of the real ALS
compute), so each replica's capacity is a known constant and the fleet
scenario's goodput scaling measures what it claims to: the router's
spreading, health-aware balancing, and retry behaviour.  Model-math
throughput is the benchmark's (``BENCHMARK.json``), not a drill's.
"""

from __future__ import annotations

import os
import time

from incubator_predictionio_tpu.core import (
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
)
from incubator_predictionio_tpu.templates.recommendation import (
    ALSAlgorithm,
    DataSource,
)


def _floor_s() -> float:
    return float(os.environ.get("PIO_BENCH_SERVICE_FLOOR_MS", "8")) / 1000.0


class FloorALSAlgorithm(ALSAlgorithm):
    """ALS whose serving cost is floored per query (training untouched)."""

    def predict(self, model, query):
        time.sleep(_floor_s())
        return super().predict(model, query)

    def batch_predict(self, model, queries):
        time.sleep(_floor_s() * max(len(queries), 1))
        return super().batch_predict(model, queries)


class FloorRecommendationEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            DataSource,
            IdentityPreparator,
            {"als": FloorALSAlgorithm, "": FloorALSAlgorithm},
            FirstServing,
        )
