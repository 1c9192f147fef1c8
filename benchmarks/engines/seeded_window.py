"""Benchmark-owned DASE pieces around the program's sequence template for
the window / full grouped-query attention pattern with softmax-routed
experts: ``benchmarks/engines/seeded_seq.py``'s data source (the item
vocabulary, no training) and an algorithm whose ``train`` fills the
program's parameter tree on the device from the seed
(``benchmarks/seeded_window.py``). The model class, persist, restore, the
session cache, warm-up and ``batch_predict`` are the program's, inherited.
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

from benchmarks import harness, seeded_window
from benchmarks.engines.seeded_seq import CONFIGS, SeededSessions

FACTORY = "benchmarks.engines.seeded_window.WindowBenchEngine"

#: the published parts under the program's letters for the kinds
LETTER = {"sliding_attention": "W", "full_attention": "A", "experts": "E"}
#: sessions the control that leaves the window out keeps at a time (a ring
#: as long as ``max_len`` is 16 times a window's: it asks one session at a
#: time, and holds three)
NO_WINDOW_SLOTS = 3


def algorithm_params(cfg: dict, seed: int, control=False) -> dict:
    """The engine variant's algorithm params from a configuration file: the
    published keys onto the template's names. ``control``: False, True /
    "float8" (the seeded weights change), "no_window" (the ``W`` layers see
    the whole session: ``SeededWindowAlgorithm.model_config``) or "no_yarn"
    (plain angles on the ``A`` layers)."""
    serve = cfg["serve"]
    pattern = "".join(LETTER[p] for p in seeded_window.parts(cfg))
    rope = cfg["rope_parameters"]
    params = {
        "key": "bench", "blockSeed": seed,
        "control": {True: "float8", False: ""}.get(control, control),
        "maxLen": serve["max_len"], "cachePage": serve["cache_page"],
        "cacheTokens": serve["cache_tokens"],
        "stateSlots": serve["state_slots"],
        "dModel": cfg["hidden_size"], "nHeads": cfg["num_attention_heads"],
        "nLayers": len(pattern), "attentionKind": "gqa",
        "layerPattern": pattern,
        "numKeyValueHeads": cfg["num_key_value_heads"],
        "headDim": cfg["head_dim"], "attentionRope": True,
        "ropeTheta": rope["sliding_attention"]["rope_theta"],
        "ropeParameters": rope, "slidingWindow": cfg["sliding_window"],
        # (pieces of 4 x 512 = 2,048 tokens unless a test cuts them)
        "indexKvTile": serve.get("index_kv_tile", 512),
        "rmsNormEps": cfg["rms_norm_eps"], "routerScoring": "softmax",
        "nRoutedExperts": cfg["num_experts"],
        "numExpertsPerTok": cfg["num_experts_per_tok"],
        "moeIntermediateSize": cfg["moe_intermediate_size"],
        "nSharedExperts": 0, "expertActivation": "gated_silu",
        "routedScalingFactor": 1.0, "expertsHeld": cfg["experts_held"],
        "expertOffset": cfg["expert_offset"],
        "tieHead": cfg["tie_word_embeddings"],
        "weightDtype": serve.get("weight_dtype", "bfloat16"),
    }
    if control == "no_yarn":
        params["ropeParameters"] = None
    return params


@dataclasses.dataclass(frozen=True)
class SeededWindowParams(TransformerAlgorithmParams):
    key: str = "bench"
    block_seed: int = 0
    control: str = ""       # "float8" | "no_window" | "no_yarn"


class SeededWindowAlgorithm(TransformerAlgorithm):
    params_class = SeededWindowParams

    def model_config(self, vocab_size: int):
        config = super().model_config(vocab_size)
        if self.params.control == "no_window":
            # the control: every key a query may see (the program has no
            # option that leaves the window out; a window as long as the
            # longest session is the same mathematics on the same path)
            config = dataclasses.replace(
                config, sliding_window=config.max_len,
                state_slots=NO_WINDOW_SLOTS,
                cache_tokens=NO_WINDOW_SLOTS * config.max_len)
        return config

    def train(self, ctx, pd: TrainingData) -> TransformerModel:
        p, cfg = self.params, CONFIGS[self.params.key]
        config = self.model_config(len(pd.item_map) + 1)
        params = seeded_window.top_weights(p.block_seed, cfg, p.control)
        params["layers"] = []
        for index, kind in enumerate(latent_moe.layer_kinds(config)):
            made = seeded_window.layer_weights(p.block_seed, index, cfg,
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


class WindowBenchEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SeededSessions,
            IdentityPreparator,
            {"seeded_block": SeededWindowAlgorithm},
            FirstServing,
        )
