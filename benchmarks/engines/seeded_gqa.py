"""Benchmark-owned DASE pieces around the program's sequence template for
the sparse-index block: ``benchmarks/engines/seeded_seq.py``'s data source
(the item vocabulary, no training) and an algorithm whose ``train`` fills the
program's parameter tree on the device from the seed
(``benchmarks/seeded_gqa.py``). The model class, persist, restore, the paged
cache, warm-up and ``batch_predict`` are the program's, inherited.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.core import (
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
)
from incubator_predictionio_tpu.models.transformer import TransformerModel
from incubator_predictionio_tpu.templates.sequential import (
    TrainingData,
    TransformerAlgorithm,
    TransformerAlgorithmParams,
)

from benchmarks import harness, seeded_gqa
from benchmarks.engines.seeded_seq import CONFIGS, SeededSessions

FACTORY = "benchmarks.engines.seeded_gqa.SparseBenchEngine"

#: the program with the mathematics changed, for the runner's controls:
#: algorithm params laid over the configuration's own. ``dense`` (no
#: selection: every query attends to all it sees): a turn then gathers its
#: whole context for each of its 16 query slots (1 GB a session at 32,768
#: rows; the block serves turns one session a dispatch)
CONTROLS = {
    "dense": {"params": lambda cfg: {"indexTopk": cfg["serve"]["max_len"]}},
    "topk_half": {"params": lambda cfg: {
        "indexTopk": cfg["sa_config"]["topk"] // 2}},
}


def algorithm_params(cfg: dict, seed: int, control=False) -> dict:
    """The engine variant's algorithm params from a configuration file: the
    published keys onto the template's names. ``control``: False, True /
    "float8" (weights one precision step down) or a name of ``CONTROLS``."""
    sa, serve = cfg["sa_config"], cfg["serve"]
    out = {
        "key": "bench", "blockSeed": seed,
        "lower": control in (True, "float8"),
        "maxLen": serve["max_len"], "cachePage": serve["cache_page"],
        "cacheTokens": serve["cache_tokens"],
        "dModel": cfg["hidden_size"], "nHeads": cfg["num_attention_heads"],
        "nLayers": cfg["num_hidden_layers"], "attentionKind": "gqa_sparse",
        "numKeyValueHeads": cfg["num_key_value_heads"],
        "headDim": cfg["head_dim"], "ropeTheta": cfg["rope_theta"],
        "indexerNumHeads": sa["indexer_num_heads"],
        "indexerHeadDim": sa["indexer_head_dim"], "indexTopk": sa["topk"],
        "indexKvTile": sa["kv_chunk_size"],
        "rmsNormEps": cfg["rms_norm_eps"], "routerScoring": "softmax",
        "nRoutedExperts": cfg["num_experts"],
        "numExpertsPerTok": cfg["num_experts_per_tok"],
        "moeIntermediateSize": cfg["moe_intermediate_size"],
        "expertsHeld": cfg["experts_held"],
        "expertOffset": cfg["expert_offset"],
        "tieHead": cfg["tie_word_embeddings"],
        "weightDtype": serve.get("weight_dtype", "bfloat16"),
    }
    if control in CONTROLS:
        out.update(CONTROLS[control]["params"](cfg))
    return out


@dataclasses.dataclass(frozen=True)
class SeededSparseParams(TransformerAlgorithmParams):
    key: str = "bench"
    block_seed: int = 0
    lower: bool = False     # the control: weights one precision step down


class SeededSparseAlgorithm(TransformerAlgorithm):
    params_class = SeededSparseParams

    def train(self, ctx, pd: TrainingData) -> TransformerModel:
        p, cfg = self.params, CONFIGS[self.params.key]
        params = seeded_gqa.top_weights(p.block_seed, cfg, p.lower)
        params["layers"] = [
            seeded_gqa.layer_weights(p.block_seed, layer, cfg, p.lower)
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


class SparseBenchEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SeededSessions,
            IdentityPreparator,
            {"seeded_block": SeededSparseAlgorithm},
            FirstServing,
        )
