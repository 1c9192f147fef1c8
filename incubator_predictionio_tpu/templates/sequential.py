"""Sequential recommender template — next-item prediction over session events.

New capability relative to the reference (whose only sequence model is
``e2.engine.MarkovChain``): a Transformer4Rec-style causal transformer
(models/transformer.py) trained on per-user item sequences, with optional
ring-attention sequence parallelism on meshes with a ``seq`` axis. The DASE
wiring mirrors the other templates: events in, engine params from variant
JSON, /queries.json out.

Query: ``{"recent_items": [...], "num": N}`` scores the next item after an
explicit session, or ``{"user": U, "num": N}`` reads the user's recent
view/buy events live from the event store (LEventStore, like the ecommerce
template's serving-time reads).

With the latent-attention block (``attentionKind: "mla"``), the
sparse-index block (``"gqa_sparse"``) or a layer pattern (``"gqa"`` with
``layerPattern``: state-space, attention and expert layers in a given order)
the deployed model keeps a device-resident cache per session
(serving/latent_cache.py): per-token rows, for state-space layers a
recurrent state and for window-attention layers a ring of the last
``slidingWindow`` key/value rows, which stand at one position: they are
reused only by a list that CONTINUES the cached one. ``recent_items`` is still the whole session as the
application knows it; ``user``, when given WITH it, is the **cache key**: the
server reuses the longest prefix of the incoming list that equals what it has
cached under that key, token for token, and computes only the rest. The
answer never depends on the cache (a hit, a partial hit, a miss and an
evicted session all give the same scores); a query without ``user``, or with
``user`` alone, is computed whole.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Sequence

import numpy as np

from incubator_predictionio_tpu.core import (
    Engine,
    EngineFactory,
    EngineParamsGenerator,
    Evaluation,
    FirstServing,
    IdentityPreparator,
    MetricEvaluator,
    OptionAverageMetric,
    PAlgorithm,
    Params,
    PDataSource,
    SanityCheck,
)
from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.data.store import LEventStore, PEventStore
from incubator_predictionio_tpu.models.transformer import (
    TransformerConfig,
    TransformerModel,
    TransformerRecommender,
)
from incubator_predictionio_tpu.obs.trace import span
from incubator_predictionio_tpu.parallel.mesh import MeshContext

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class Query:
    user: Optional[str] = None
    recent_items: Optional[tuple[str, ...]] = None
    num: int = 10


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...] = ()


@dataclasses.dataclass(frozen=True)
class ActualResult:
    """Held-out next item of one session (eval ground truth)."""

    next_item: str


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "sequential"
    max_len: int = 32
    events: tuple[str, ...] = ("view", "buy")
    eval_k: Optional[int] = None  # k-fold next-item eval when set
    eval_num: int = 10            # top-N asked per eval query


@dataclasses.dataclass
class TrainingData(SanityCheck):
    sequences: np.ndarray  # [n, max_len+1] int32 tokens, 0-padded left
    item_map: BiMap        # item id → token (1-based; 0 = padding)
    # multi-process sharded read: sequences are THIS process's user shard
    # only (sessions never cross shards; item_map/tokens are global)
    rows_are_local: bool = False
    n_rows_global: Optional[int] = None

    def sanity_check(self) -> None:
        total = (self.n_rows_global if self.n_rows_global is not None
                 else len(self.sequences))
        if total == 0:
            raise ValueError("no sessions found")


def encode_session(items: Sequence[str], item_map: BiMap, width: int) -> np.ndarray:
    """Left-pad a session's tokens to ``width`` (newest item last)."""
    tokens = [item_map[i] for i in items if i in item_map][-width:]
    out = np.zeros(width, np.int32)
    if tokens:
        out[-len(tokens):] = tokens
    return out


class DataSource(PDataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        super().__init__(params)
        self._store = PEventStore()

    def _collect_sessions(self, ctx: MeshContext) -> tuple[dict[str, list[str]], bool]:
        """user → ordered item list, for this process's user shard
        (sessions are per-user; users are entity-sharded, so a session
        never splits across processes)."""
        p = self.params
        procs, pid = ctx.process_count, ctx.process_index
        sharded = procs > 1
        sessions: dict[str, list[str]] = {}
        if sharded:
            events = self._store.find_sharded(
                p.app_name, procs, entity_type="user",
                event_names=tuple(p.events))[pid]
        else:
            events = self._store.find(
                p.app_name, entity_type="user", event_names=tuple(p.events),
                target_entity_type="item",
            )
        for e in events:  # find() is event-time ordered
            if e.target_entity_type != "item":
                continue
            sessions.setdefault(e.entity_id, []).append(e.target_entity_id)
        return sessions, sharded

    def _build_fold(self, ctx: MeshContext, sessions_list: list[list[str]],
                    sharded: bool) -> TrainingData:
        """Token space + encoded rows from the given sessions (global vocab
        union when sharded; token 0 reserved for padding)."""
        base = BiMap.string_int(
            [i for items in sessions_list for i in items])
        n_rows_global = None
        if sharded:
            from incubator_predictionio_tpu.data.sharded import union_vocab

            # global token space: first-seen union over shards in process
            # order (one vocab-sized allgather)
            vocab, _ = union_vocab(ctx, list(base))
            base = BiMap({v: i for i, v in enumerate(vocab.tolist())})
        item_map = BiMap({k: v + 1 for k, v in base.items()})
        width = self.params.max_len + 1
        rows = [
            encode_session(items, item_map, width)
            for items in sessions_list
            if len(items) >= 2
        ]
        if sharded:
            from incubator_predictionio_tpu.data.sharded import global_row_count

            n_rows_global = global_row_count(ctx, len(rows))
            logger.info("sharded read: %d of %d rows (shard %d/%d)",
                        len(rows), n_rows_global, ctx.process_index,
                        ctx.process_count)
        return TrainingData(
            sequences=np.stack(rows) if rows else np.zeros((0, width), np.int32),
            item_map=item_map,
            rows_are_local=sharded,
            n_rows_global=n_rows_global,
        )

    def read_training(self, ctx: MeshContext) -> TrainingData:
        sessions, sharded = self._collect_sessions(ctx)
        return self._build_fold(ctx, list(sessions.values()), sharded)

    def read_eval(self, ctx: MeshContext):
        """k-fold next-item evaluation: sessions split by a stable user
        hash; a held-out session becomes (Query(recentItems=prefix),
        ActualResult(last item)). Fold vocabularies come from the fold's
        TRAIN sessions only, so unseen items stay genuinely unknown (the
        recommendation template's per-fold BiMap discipline)."""
        import zlib

        k = self.params.eval_k
        if not k:
            return []
        p = self.params
        sessions, sharded = self._collect_sessions(ctx)
        # fold assignment computed ONCE per user (recommendation.py's
        # fold_of discipline), not re-hashed per fold
        fold_of = {
            user: zlib.crc32(f"{p.app_name}|{user}".encode()) % k
            for user in sessions
        }
        folds = []
        for fold in range(k):
            train_sessions, held = [], []
            for user, items in sessions.items():
                if fold_of[user] == fold:
                    held.append(items)
                else:
                    train_sessions.append(items)
            td = self._build_fold(ctx, train_sessions, sharded)
            local_qa = [
                (Query(recent_items=tuple(items[:-1]), num=p.eval_num),
                 ActualResult(items[-1]))
                for items in held if len(items) >= 3
            ]
            if sharded:
                # every process evaluates the same (small) global query set
                parts = ctx.allgather_obj([
                    (list(q.recent_items), q.num, a.next_item)
                    for q, a in local_qa
                ])
                qa = [
                    (Query(recent_items=tuple(r), num=num), ActualResult(nx))
                    for part in parts for r, num, nx in part
                ]
            else:
                qa = local_qa
            folds.append((td, {"fold": fold}, qa))
        return folds


@dataclasses.dataclass(frozen=True)
class TransformerAlgorithmParams(Params):
    app_name: str = "sequential"
    max_len: int = 32
    d_model: int = 64
    n_heads: int = 2
    n_layers: int = 2
    learning_rate: float = 1e-3
    batch_size: int = 256
    epochs: int = 10
    seed: int = 0
    attention: str = "auto"  # "auto" | "local" | "ring"
    # mixture-of-experts FFN: 0 = dense; >0 switches to top-1 routed experts
    # sharded over the mesh's "expert" axis when present
    num_experts: int = 0
    # pipeline parallelism: stage count over the mesh's "pipe" axis (0 = off)
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0
    # recompute activations in backward (jax.checkpoint): fits longer
    # sequences in HBM for ~1 extra forward of FLOPs
    remat: bool = False
    # Megatron-style tensor parallelism over the mesh's "model" axis
    tensor_parallel: bool = False
    recent_events: tuple[str, ...] = ("view", "buy")
    checkpoint_dir: Optional[str] = None   # mid-training resume (utils/checkpoint.py)
    checkpoint_every: int = 0
    # the block (models/transformer.py TransformerConfig): "mha", "mla" (the
    # latent-attention / routed-expert block) or "gqa_sparse" (grouped-query
    # heads behind a learned sparse index, the same routed experts), whose
    # sizes follow under the published configs' names (d_model / n_heads /
    # n_layers above); "gqa" with a layer_pattern: one letter a layer, "S" a
    # state-space mixer (ssm_*, conv_kernel), "A" dense grouped-query
    # attention (num_key_value_heads, head_dim; qk_norm: per-head RMSNorm of
    # q and k; attention_rope: rotary pairs at rope_theta), "E" the routed
    # experts, "C" a gated short convolution (conv_kernel taps), "D" a dense
    # gated feed-forward part (intermediate_size), "W" the "A" letter's
    # attention over the last sliding_window keys (plain rotary angles; the
    # "A" layers take rope_parameters, the published "yarn" dict or the dict
    # that holds it under "full_attention", when given)
    attention_kind: str = "mha"
    layer_pattern: str = ""
    qk_norm: bool = False
    attention_rope: bool = False
    sliding_window: int = 0
    intermediate_size: int = 0
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk_size: int = 128
    state_dtype: str = "float32"  # what a session's state is kept in
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rms_norm_eps: float = 1e-6
    rope_parameters: Optional[dict] = None
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    shared_intermediate_size: int = 0   # 0: moe_intermediate_size each
    expert_activation: str = "gated_silu"   # or "relu2" (two matrices)
    routed_scaling_factor: float = 1.0
    router_scoring: str = "sigmoid"   # or "softmax" (no selection bias)
    num_key_value_heads: int = 0      # "gqa_sparse": grouped-query heads ...
    head_dim: int = 0
    rope_theta: float = 10000.0
    indexer_num_heads: int = 0        # ... and its indexer (sa_config)
    indexer_head_dim: int = 0
    index_topk: int = 0
    # rows a key tile of a long block: it is cut into pieces of 4 tiles, by
    # the sparse-index block (which also reads keys a tile at a time) and by
    # a pattern with "W" layers, which has no index and only the pieces
    index_kv_tile: int = 512
    experts_held: int = 0         # this chip's share (0 = all), from expert_offset
    expert_offset: int = 0
    tie_head: bool = True
    weight_dtype: str = "float32"
    cache_page: int = 128         # latent cache: tokens a page, and its size
    cache_tokens: int = 0         # in tokens (0 = 16 sessions of max_len)
    state_slots: int = 0          # sessions whose state is kept (0 = what
    #                               cache_tokens / max_len sessions need)


class TransformerAlgorithm(PAlgorithm):
    params_class = TransformerAlgorithmParams
    serving_thread_safe = True  # jit dispatch + read-only served arrays
    query_cls = Query

    def __init__(self, params: TransformerAlgorithmParams):
        super().__init__(params)
        self._levents = LEventStore()

    def model_config(self, vocab_size: int) -> TransformerConfig:
        p = self.params
        latent = {}
        if p.attention_kind == "mla":
            latent = dict(
                q_lora_rank=p.q_lora_rank, kv_lora_rank=p.kv_lora_rank,
                qk_nope_head_dim=p.qk_nope_head_dim,
                qk_rope_head_dim=p.qk_rope_head_dim, v_head_dim=p.v_head_dim,
                rope_parameters=tuple(sorted(
                    (p.rope_parameters or {}).items())))
        elif p.attention_kind == "gqa_sparse":
            latent = dict(
                n_kv_heads=p.num_key_value_heads, head_dim=p.head_dim,
                rope_theta=p.rope_theta, index_n_heads=p.indexer_num_heads,
                index_head_dim=p.indexer_head_dim, index_topk=p.index_topk,
                index_kv_tile=p.index_kv_tile)
        elif p.attention_kind == "gqa":
            scaled = p.rope_parameters or {}
            latent = dict(
                sliding_window=p.sliding_window,
                index_kv_tile=p.index_kv_tile,
                rope_parameters=tuple(sorted(
                    scaled.get("full_attention", scaled).items())),
                n_kv_heads=p.num_key_value_heads, head_dim=p.head_dim,
                layer_pattern=p.layer_pattern, ssm_heads=p.ssm_num_heads,
                ssm_head_dim=p.ssm_head_dim, ssm_state=p.ssm_state_size,
                ssm_groups=p.ssm_groups, conv_kernel=p.conv_kernel,
                ssm_chunk=p.ssm_chunk_size, state_dtype=p.state_dtype,
                state_slots=p.state_slots, qk_norm=p.qk_norm,
                attention_rope=p.attention_rope, rope_theta=p.rope_theta,
                intermediate_size=p.intermediate_size)
        if p.attention_kind != "mha":
            latent.update(
                rms_norm_eps=p.rms_norm_eps,
                shared_intermediate_size=p.shared_intermediate_size,
                expert_activation=p.expert_activation,
                router_scoring=p.router_scoring,
                n_routed_experts=p.n_routed_experts,
                experts_per_token=p.num_experts_per_tok,
                moe_intermediate_size=p.moe_intermediate_size,
                n_shared_experts=p.n_shared_experts,
                routed_scaling_factor=p.routed_scaling_factor,
                experts_held=p.experts_held, expert_offset=p.expert_offset,
                tie_head=p.tie_head, weight_dtype=p.weight_dtype,
                cache_page=p.cache_page, cache_tokens=p.cache_tokens)
        return TransformerConfig(
            vocab_size=vocab_size,
            max_len=p.max_len,
            d_model=p.d_model,
            n_heads=p.n_heads,
            n_layers=p.n_layers,
            learning_rate=p.learning_rate,
            batch_size=p.batch_size,
            epochs=p.epochs,
            seed=p.seed,
            attention=p.attention,
            n_experts=p.num_experts,
            pipeline_stages=p.pipeline_stages,
            pipeline_microbatches=p.pipeline_microbatches,
            remat=p.remat,
            tensor_parallel=p.tensor_parallel,
            checkpoint_dir=p.checkpoint_dir,
            checkpoint_every=p.checkpoint_every,
            attention_kind=p.attention_kind, **latent,
        )

    def train(self, ctx: MeshContext, pd: TrainingData) -> TransformerModel:
        cfg = self.model_config(len(pd.item_map) + 1)
        return TransformerRecommender(cfg).fit(
            ctx, pd.sequences, pd.item_map,
            rows_are_local=pd.rows_are_local)

    def _history(self, query: Query, model: TransformerModel) -> list[str]:
        if query.recent_items is not None:
            return list(query.recent_items)
        if query.user is None:
            return []
        try:
            events = list(self._levents.find_by_entity(
                self.params.app_name, "user", query.user,
                event_names=tuple(self.params.recent_events),
                target_entity_type="item",
                limit=model.config.max_len, latest=True,
            ))
        except ValueError:
            return []
        return [e.target_entity_id for e in reversed(events) if e.target_entity_id]

    def predict(self, model: TransformerModel, query: Query) -> PredictedResult:
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(
        self, model: TransformerModel, queries: Sequence[tuple[int, Query]]
    ) -> list[tuple[int, PredictedResult]]:
        if not queries:
            return []
        if model.config.latent:
            return self._batch_predict_latent(model, queries)
        histories = [self._history(q, model) for _, q in queries]
        rows = np.stack([
            encode_session(h, model.item_map, model.config.max_len)
            for h in histories
        ])
        scores = TransformerRecommender.next_item_scores(model, rows)
        inv = model.item_map.inverse()
        out = []
        for (qi, q), h, row_scores in zip(queries, histories, scores):
            if not any(i in model.item_map for i in h):
                out.append((qi, PredictedResult()))  # cold session
                continue
            s = row_scores.copy()
            s[0] = -np.inf  # padding token
            for i in h:     # exclude history items
                tok = model.item_map.get(i)
                if tok is not None:
                    s[tok] = -np.inf
            num = min(q.num, len(s) - 1)
            top = np.argpartition(-s, num - 1)[:num]
            top = top[np.argsort(-s[top])]
            out.append((qi, PredictedResult(tuple(
                ItemScore(inv[int(t)], float(s[t]))
                for t in top if np.isfinite(s[t])
            ))))
        return out


    def _batch_predict_latent(self, model, queries):
        """The latent block's path: sessions are matched against the device
        cache, extended by what is new and ranked on the device; only the
        top rows come back."""
        if model.serving is None:   # eval / batchpredict outside a deploy
            model.prepare_for_serving().warmup()
        item_map, width = model.item_map, model.config.max_len

        def encode(items):
            tokens = [item_map[i] for i in items if i in item_map]
            return np.asarray(tokens[-width:], np.int32)

        requests = [
            # ``user`` keys the cache only beside the session it names
            (q.user if q.recent_items is not None else None,
             self._history(q, model)) for _, q in queries]
        scores, tokens = model.serving.extend(
            requests, encode, max(q.num for _, q in queries))
        with span("seq.batch.rows", rows=len(queries)):
            inv = item_map.inverse()
            out = []
            for (qi, q), s, t in zip(queries, scores, tokens):
                keep = np.isfinite(s[:max(q.num, 0)])
                out.append((qi, PredictedResult(tuple(
                    ItemScore(inv[int(tok)], float(sc))
                    for tok, sc in zip(t[:len(keep)][keep],
                                       s[:len(keep)][keep])))))
        return out


class SequentialEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            DataSource,
            IdentityPreparator,
            {"transformer": TransformerAlgorithm, "": TransformerAlgorithm},
            FirstServing,
        )


# -- evaluation -------------------------------------------------------------

class HitRateAtK(OptionAverageMetric):
    """Fraction of held-out sessions whose true next item appears in the
    top-k (the standard next-item metric; the serving path's unseen-only
    policy applies, so repeat-item sessions count as misses)."""

    def __init__(self, k: int = 10):
        self.k = k

    @property
    def header(self) -> str:
        return f"HitRate@K (k={self.k})"

    def calculate_qpa(self, q: Query, p: PredictedResult, a: ActualResult):
        if not p.item_scores:
            return 0.0  # cold/unknown-vocab session: a miss, not a skip
        return 1.0 if a.next_item in {
            s.item for s in p.item_scores[: self.k]} else 0.0


class SequentialEvaluation(Evaluation, EngineParamsGenerator):
    """HitRate@10 over a small schedule grid — makes ``pio-tpu eval`` work
    on the long-context flagship like it does on the recommendation
    template."""

    def __init__(self, app_name: str = "sequential", eval_k: int = 3):
        from incubator_predictionio_tpu.core import EngineParams

        self.engine = SequentialEngine().apply()
        self.evaluator = MetricEvaluator(metric=HitRateAtK(k=10))
        self.engine_params_list = [
            EngineParams.create(
                data_source=DataSourceParams(app_name=app_name, eval_k=eval_k),
                algorithms=[("transformer", TransformerAlgorithmParams(
                    app_name=app_name, d_model=32, n_layers=1,
                    epochs=epochs, learning_rate=lr, batch_size=64))],
            )
            for epochs in (10, 30)
            for lr in (1e-3, 5e-3)
        ]
