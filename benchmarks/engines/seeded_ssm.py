"""Benchmark-owned DASE pieces around the program's sequence template for
the state-space / grouped-query / routed-expert pattern:
``benchmarks/engines/seeded_seq.py``'s data source (the item vocabulary, no
training) and an algorithm whose ``train`` fills the program's parameter tree
on the device from the seed (``benchmarks/seeded_ssm.py``). The model class,
persist, restore, the session cache, warm-up and ``batch_predict`` are the
program's, inherited.
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
from incubator_predictionio_tpu.models import latent_moe
from incubator_predictionio_tpu.models.transformer import TransformerModel
from incubator_predictionio_tpu.templates.sequential import (
    TrainingData,
    TransformerAlgorithm,
    TransformerAlgorithmParams,
)

from benchmarks import harness, seeded_ssm
from benchmarks.engines.seeded_seq import CONFIGS, SeededSessions

FACTORY = "benchmarks.engines.seeded_ssm.StateSpaceBenchEngine"

#: the program with one thing changed, for the runner's controls: algorithm
#: params laid over the configuration's own (``float8`` and ``no_skip``
#: change the seeded weights instead: ``seeded_ssm.layer_weights``)
CONTROLS = {"state_bf16": {"stateDtype": "bfloat16"}}
#: the published pattern's letters under the program's names for the kinds
KINDS = str.maketrans("M*", "SA")


def algorithm_params(cfg: dict, seed: int, control=False) -> dict:
    """The engine variant's algorithm params from a configuration file: the
    published keys onto the template's names. ``control``: False, True /
    "float8", "no_skip" (both change the weights) or a name of
    ``CONTROLS``."""
    serve = cfg["serve"]
    out = {
        "key": "bench", "blockSeed": seed,
        "control": {True: "float8", False: ""}.get(control, control),
        "maxLen": serve["max_len"], "cachePage": serve["cache_page"],
        "cacheTokens": serve["cache_tokens"],
        "stateSlots": serve["state_slots"],
        "dModel": cfg["hidden_size"], "nHeads": cfg["num_attention_heads"],
        "nLayers": cfg["num_hidden_layers"], "attentionKind": "gqa",
        "layerPattern": cfg["hybrid_override_pattern"].translate(KINDS),
        "numKeyValueHeads": cfg["num_key_value_heads"],
        "headDim": cfg["head_dim"],
        "ssmNumHeads": cfg["mamba_num_heads"],
        "ssmHeadDim": cfg["mamba_head_dim"],
        "ssmStateSize": cfg["ssm_state_size"], "ssmGroups": cfg["n_groups"],
        "convKernel": cfg["conv_kernel"], "ssmChunkSize": cfg["chunk_size"],
        "rmsNormEps": cfg["layer_norm_epsilon"], "routerScoring": "sigmoid",
        "nRoutedExperts": cfg["n_routed_experts"],
        "numExpertsPerTok": cfg["num_experts_per_tok"],
        "moeIntermediateSize": cfg["moe_intermediate_size"],
        "nSharedExperts": cfg["n_shared_experts"],
        "sharedIntermediateSize": cfg["moe_shared_expert_intermediate_size"],
        "expertActivation": cfg["mlp_hidden_act"],
        "routedScalingFactor": cfg["routed_scaling_factor"],
        "expertsHeld": cfg["experts_held"],
        "expertOffset": cfg["expert_offset"],
        "tieHead": cfg["tie_word_embeddings"],
        "weightDtype": serve.get("weight_dtype", "bfloat16"),
    }
    out.update(CONTROLS.get(control, {}))
    return out


@dataclasses.dataclass(frozen=True)
class SeededStateSpaceParams(TransformerAlgorithmParams):
    key: str = "bench"
    block_seed: int = 0
    control: str = ""       # "float8" | "no_skip": the seeded weights changed


class SeededStateSpaceAlgorithm(TransformerAlgorithm):
    params_class = SeededStateSpaceParams

    def train(self, ctx, pd: TrainingData) -> TransformerModel:
        p, cfg = self.params, CONFIGS[self.params.key]
        config = self.model_config(len(pd.item_map) + 1)
        params = seeded_ssm.top_weights(p.block_seed, cfg,
                                        p.control == "float8")
        params["layers"] = []
        for layer, kind in enumerate(latent_moe.layer_kinds(config)):
            made = seeded_ssm.layer_weights(
                p.block_seed, layer, cfg, p.control)
            # (where the program stores an array wider than it is published)
            params["layers"].append({
                name: latent_moe.pad_stored(made[name], shape)
                for name, (shape, _) in latent_moe.layer_shapes(
                    config, kind).items()})
        if p.weight_dtype != "bfloat16":
            # (the CPU backend of the harness tests multiplies no bfloat16:
            # the same values, held wider)
            params = jax.tree.map(
                lambda a: a.astype(p.weight_dtype)
                if a.dtype == jnp.bfloat16 else a, params)
        return TransformerModel(params, pd.item_map, config)

    def batch_predict(self, model, queries):
        with harness.span("bench.serve.batch_predict"):
            return super().batch_predict(model, queries)


class StateSpaceBenchEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SeededSessions,
            IdentityPreparator,
            {"seeded_block": SeededStateSpaceAlgorithm},
            FirstServing,
        )
