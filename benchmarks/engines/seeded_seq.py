"""Benchmark-owned DASE pieces around the program's sequence template.

- ``SeededSessions`` yields the item vocabulary (``i1`` .. ``i<V-1>``; token
  0 is padding) and one placeholder row: no serve run trains.
- ``SeededBlockAlgorithm`` is the template's ``TransformerAlgorithm`` whose
  ``train`` fills the program's parameter tree on the device from the seed
  (``benchmarks/seeded_seq.py``) instead of calling ``fit``. The model
  class, persist (PersistentModel SPI, orbax), restore, the latent cache,
  warm-up and ``batch_predict`` are the program's, inherited.
- ``SeqBenchEngine`` is the factory the engine variant names.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from incubator_predictionio_tpu.core import (
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
    Params,
    PDataSource,
)
from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.models.transformer import TransformerModel
from incubator_predictionio_tpu.templates.sequential import (
    TrainingData,
    TransformerAlgorithm,
    TransformerAlgorithmParams,
)

from benchmarks import harness, seeded_seq

FACTORY = "benchmarks.engines.seeded_seq.SeqBenchEngine"

#: in-process hand-over of the cell's configuration dict, by the params' ``key``
CONFIGS: dict[str, dict] = {}


def algorithm_params(cfg: dict, seed: int, lower: bool = False) -> dict:
    """The engine variant's algorithm params from a configuration file: the
    published keys onto the template's names."""
    return {
        "key": "bench", "blockSeed": seed, "lower": lower,
        "maxLen": cfg["serve"]["max_len"],
        "cachePage": cfg["serve"]["cache_page"],
        "cacheTokens": cfg["serve"]["cache_tokens"],
        "dModel": cfg["hidden_size"], "nHeads": cfg["num_attention_heads"],
        "nLayers": cfg["num_hidden_layers"], "attentionKind": "mla",
        "qLoraRank": cfg["q_lora_rank"], "kvLoraRank": cfg["kv_lora_rank"],
        "qkNopeHeadDim": cfg["qk_nope_head_dim"],
        "qkRopeHeadDim": cfg["qk_rope_head_dim"],
        "vHeadDim": cfg["v_head_dim"], "rmsNormEps": cfg["rms_norm_eps"],
        "ropeParameters": cfg["rope_parameters"],
        "nRoutedExperts": cfg["n_routed_experts"],
        "numExpertsPerTok": cfg["num_experts_per_tok"],
        "moeIntermediateSize": cfg["moe_intermediate_size"],
        "nSharedExperts": cfg["n_shared_experts"],
        "routedScalingFactor": cfg["routed_scaling_factor"],
        "expertsHeld": cfg["experts_held"],
        "expertOffset": cfg["expert_offset"],
        "tieHead": cfg["tie_word_embeddings"],
        "weightDtype": cfg["serve"].get("weight_dtype", "bfloat16"),
    }


@dataclasses.dataclass(frozen=True)
class SeededSessionsParams(Params):
    key: str = "bench"


class SeededSessions(PDataSource):
    params_class = SeededSessionsParams

    def read_training(self, ctx) -> TrainingData:
        cfg = CONFIGS[self.params.key]
        item_map = BiMap({f"i{t}": t for t in range(1, cfg["vocab_size"])})
        row = np.zeros((1, cfg["serve"]["max_len"] + 1), np.int32)
        row[0, -2:] = (1, 2)
        return TrainingData(sequences=row, item_map=item_map)


@dataclasses.dataclass(frozen=True)
class SeededBlockParams(TransformerAlgorithmParams):
    key: str = "bench"
    block_seed: int = 0
    lower: bool = False     # the control: weights one precision step down


class SeededBlockAlgorithm(TransformerAlgorithm):
    params_class = SeededBlockParams

    def train(self, ctx, pd: TrainingData) -> TransformerModel:
        p, cfg = self.params, CONFIGS[self.params.key]
        params = seeded_seq.top_weights(p.block_seed, cfg, p.lower)
        params["layers"] = [
            seeded_seq.layer_weights(p.block_seed, layer, cfg, p.lower)
            for layer in range(cfg["num_hidden_layers"])]
        if p.weight_dtype != "bfloat16":
            # (the CPU backend of the harness tests multiplies no bfloat16:
            # the same values, held wider)
            params = jax.tree.map(
                lambda a: a.astype(p.weight_dtype)
                if a.dtype == jnp.bfloat16 else a, params)
        return TransformerModel(
            params, pd.item_map, self.model_config(len(pd.item_map) + 1))

    def batch_predict(self, model, queries):
        with harness.span("bench.serve.batch_predict"):
            return super().batch_predict(model, queries)


class SeqBenchEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SeededSessions,
            IdentityPreparator,
            {"seeded_block": SeededBlockAlgorithm},
            FirstServing,
        )
