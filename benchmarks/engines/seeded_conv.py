"""Benchmark-owned DASE pieces around the program's sequence template for
the gated-short-convolution / rotary grouped-query / routed-expert pattern:
``benchmarks/engines/seeded_seq.py``'s data source (the item vocabulary, no
training) and an algorithm whose ``train`` fills the program's parameter tree
on the device from the seed (``benchmarks/seeded_conv.py``). The model class,
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

from benchmarks import harness, seeded_conv
from benchmarks.engines.seeded_seq import CONFIGS, SeededSessions

FACTORY = "benchmarks.engines.seeded_conv.ShortConvBenchEngine"

#: the published parts under the program's letters for the kinds
LETTER = {"conv": "C", "full_attention": "A", "dense": "D", "experts": "E"}


def algorithm_params(cfg: dict, seed: int, control=False) -> dict:
    """The engine variant's algorithm params from a configuration file: the
    published keys onto the template's names. ``control``: False, True /
    "float8" (the seeded weights change) or "zero_carry" (every turn starts
    from a zero carry: ``SeededShortConvAlgorithm``)."""
    serve = cfg["serve"]
    pattern = "".join(LETTER[p] for p in seeded_conv.parts(cfg))
    return {
        "key": "bench", "blockSeed": seed,
        "control": {True: "float8", False: ""}.get(control, control),
        "maxLen": serve["max_len"], "cachePage": serve["cache_page"],
        "cacheTokens": serve["cache_tokens"],
        "stateSlots": serve["state_slots"],
        "dModel": cfg["hidden_size"], "nHeads": cfg["num_attention_heads"],
        "nLayers": len(pattern), "attentionKind": "gqa",
        "layerPattern": pattern,
        "numKeyValueHeads": cfg["num_key_value_heads"],
        "headDim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "qkNorm": True, "attentionRope": True, "ropeTheta": cfg["rope_theta"],
        "convKernel": cfg["conv_L_cache"],
        "intermediateSize": cfg["intermediate_size"],
        "rmsNormEps": cfg["norm_eps"], "routerScoring": "sigmoid",
        "nRoutedExperts": cfg["num_experts"],
        "numExpertsPerTok": cfg["num_experts_per_tok"],
        "moeIntermediateSize": cfg["moe_intermediate_size"],
        "nSharedExperts": 0, "expertActivation": "gated_silu",
        "routedScalingFactor": cfg["routed_scaling_factor"],
        "expertsHeld": cfg["experts_held"],
        "expertOffset": cfg["expert_offset"], "tieHead": True,
        "weightDtype": serve.get("weight_dtype", "bfloat16"),
    }


@dataclasses.dataclass(frozen=True)
class SeededShortConvParams(TransformerAlgorithmParams):
    key: str = "bench"
    block_seed: int = 0
    control: str = ""       # "float8" | "zero_carry"


class SeededShortConvAlgorithm(TransformerAlgorithm):
    params_class = SeededShortConvParams

    def __init__(self, params):
        super().__init__(params)
        if params.control == "zero_carry":
            # the control: a turn reads zeros where its session's carry is
            # (the program has no such option: its slot read is replaced
            # before any serving program is traced)
            from incubator_predictionio_tpu.models import short_conv

            short_conv.slot_rows = lambda kept, slots: jnp.zeros(
                (slots.shape[0], kept.shape[1]), kept.dtype)

    def train(self, ctx, pd: TrainingData) -> TransformerModel:
        p, cfg = self.params, CONFIGS[self.params.key]
        config = self.model_config(len(pd.item_map) + 1)
        params = seeded_conv.top_weights(p.block_seed, cfg, p.control)
        params["layers"] = []
        for index, kind in enumerate(latent_moe.layer_kinds(config)):
            made = seeded_conv.layer_weights(p.block_seed, index, cfg,
                                             p.control)
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


class ShortConvBenchEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SeededSessions,
            IdentityPreparator,
            {"seeded_block": SeededShortConvAlgorithm},
            FirstServing,
        )
