"""Benchmark-owned DASE pieces plugged into the program's ordinary engine.

- ``SeededDataSource`` yields a ``TrainingData`` the runner made from the seed
  and parked in ``DATA`` (no run imports events: the event store takes 31k
  events/s, PERF.md section 5).
- ``SeededTowersAlgorithm`` is ``ALSAlgorithm`` whose ``train`` builds
  structured seeded towers on the device instead of calling ``fit`` (no serve
  run trains). BiMaps, the IVF build, persist, restore, quantize, warm-up and
  ``batch_predict`` are the program's, inherited.
- ``BenchEngine`` is the factory the engine variant names.
"""

from __future__ import annotations

import dataclasses
import time

from incubator_predictionio_tpu.core import (
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
    Params,
    PDataSource,
)
from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.models.two_tower import (
    TwoTowerConfig,
    TwoTowerModel,
)
from incubator_predictionio_tpu.templates.recommendation import (
    ALSAlgorithm,
    RecModel,
    TrainingData,
)

from benchmarks import harness, seeded_data

#: in-process hand-over of the seeded TrainingData, by the params' ``key``
DATA: dict[str, TrainingData] = {}


@dataclasses.dataclass(frozen=True)
class SeededDataSourceParams(Params):
    key: str = "default"


class SeededDataSource(PDataSource):
    params_class = SeededDataSourceParams

    def read_training(self, ctx) -> TrainingData:
        return DATA[self.params.key]


@dataclasses.dataclass(frozen=True)
class SeededTowersParams(Params):
    rank: int = 128
    tower_seed: int = 0
    groups: int = 64
    noise: float = 0.35
    user_bias_sd: float = 0.1
    item_bias_sd: float = 0.3
    mean: float = 3.5


class SeededTowersAlgorithm(ALSAlgorithm):
    params_class = SeededTowersParams

    def train(self, ctx, pd: TrainingData) -> RecModel:
        p = self.params
        user_map = BiMap({u: i for i, u in enumerate(pd.user_vocab)})
        item_map = BiMap({t: i for i, t in enumerate(pd.item_vocab)})
        mf = TwoTowerModel(
            mean=p.mean, config=TwoTowerConfig(rank=p.rank, gather="device"))
        mf._tables = seeded_data.towers(
            p.tower_seed, len(user_map), len(item_map), dataclasses.asdict(p))
        mf._n_users = len(user_map)
        mf._n_items = len(item_map)
        mf._prepare_index()  # two-stage IVF when the catalog qualifies
        return RecModel(mf, user_map, item_map)

    def batch_predict(self, model, queries):
        with harness.span("bench.serve.batch_predict"):
            return super().batch_predict(model, queries)


#: what each ``RecordingALSAlgorithm.train`` saw, oldest first: the fit's own
#: phase timings and final loss, and the wall time of the whole algorithm call
FITS: list[dict] = []


class RecordingALSAlgorithm(ALSAlgorithm):
    """The program's ``ALSAlgorithm`` unchanged; the benchmark's span around
    its ``train`` and a note of what the fit reported about itself."""

    def train(self, ctx, pd: TrainingData) -> RecModel:
        t0 = time.perf_counter()
        with harness.span("bench.verb.algorithm_train"):
            model = super().train(ctx, pd)
        FITS.append({
            "algorithm_train_s": time.perf_counter() - t0,
            "timings": dict(model.mf.timings),
            "final_loss": float(model.mf.final_loss),
        })
        return model


class BenchEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SeededDataSource,
            IdentityPreparator,
            {"als": RecordingALSAlgorithm, "seeded": SeededTowersAlgorithm},
            FirstServing,
        )
