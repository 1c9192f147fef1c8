"""Core workflow — drives one train or evaluation run.

Parity targets: workflow/CoreWorkflow.scala:45-167 (runTrain/runEvaluation:
create context, run, persist models into MODELDATA, flip instance status),
workflow/CleanupFunctions.scala:42-65, workflow/WorkflowContext.scala:29-47.

The "Spark driver JVM" disappears: the workflow runs in-process, building a
:class:`MeshContext` where the reference builds a SparkContext. Deviation from
the reference, deliberately: failed runs are marked FAILED (the reference
leaves them INIT forever — operability wins here).
"""

from __future__ import annotations

import datetime as _dt
import logging
import traceback
from dataclasses import replace
from typing import Callable, Optional, Sequence

from incubator_predictionio_tpu.core.controller import Engine, EngineParams, WorkflowParams
from incubator_predictionio_tpu.core.evaluator import Evaluation
from incubator_predictionio_tpu.data.storage.base import (
    EngineInstance,
    EvaluationInstance,
    Model,
)
from incubator_predictionio_tpu.data.storage.registry import Storage, get_storage
from incubator_predictionio_tpu.obs.profile import device_memory_report
from incubator_predictionio_tpu.obs.trace import span
from incubator_predictionio_tpu.parallel.mesh import MeshContext
from incubator_predictionio_tpu.utils.serialization import serialize_model

logger = logging.getLogger(__name__)


class CleanupFunctions:
    """Global finally-block hooks (CleanupFunctions.scala:42-65)."""

    _fns: list[Callable[[], None]] = []

    @classmethod
    def add(cls, fn: Callable[[], None]) -> None:
        cls._fns.append(fn)

    @classmethod
    def run(cls) -> None:
        for fn in cls._fns:
            try:
                fn()
            except Exception:  # noqa: BLE001 - cleanup must not mask the run error
                logger.exception("cleanup function failed")

    @classmethod
    def clear(cls) -> None:
        cls._fns.clear()


def _now() -> _dt.datetime:
    return _dt.datetime.now(_dt.timezone.utc)


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    engine_instance: EngineInstance,
    params: WorkflowParams = WorkflowParams(),
    storage: Optional[Storage] = None,
    ctx: Optional[MeshContext] = None,
) -> str:
    """Train, persist models, mark the instance COMPLETED
    (CoreWorkflow.runTrain, CoreWorkflow.scala:45-102). Returns instance id.

    In a multi-process job every process trains (SPMD collectives need all of
    them), but only process 0 touches storage — the single-Spark-driver role
    (``MeshContext.is_primary``); secondaries return a placeholder id."""
    storage = storage or get_storage()
    # one trace per verb: train.verb is the root of everything the verb does
    # (train.verb.read|bimaps|index|persist|commit, train.fit.*,
    # train.persist.orbax — docs/observability.md "Profiling")
    with span("train.verb") as root:
        return _run_train(engine, engine_params, engine_instance, params,
                          storage, ctx, root)


def _run_train(engine, engine_params, engine_instance, params, storage, ctx,
               root) -> str:
    instances = storage.get_meta_data_engine_instances()
    ctx = ctx or MeshContext.from_conf(engine_instance.mesh_conf or None)
    primary = ctx.is_primary
    if primary:
        instance_id = engine_instance.id or instances.insert(engine_instance)
        if engine_instance.id:
            instances.update(engine_instance)
    else:
        instance_id = engine_instance.id or "<secondary>"
    root.set_attr("instance", instance_id)
    try:
        with ctx.activate():
            models = engine.train(ctx, engine_params, params)
            # training ends with a collective host gather (all processes),
            # but persistence — and its save side effects, e.g.
            # PersistentModel files keyed by instance id — is primary-only
            if primary:
                with span("train.verb.persist"):
                    persisted = engine.models_for_persistence(
                        ctx, models, instance_id, engine_params
                    )
        if primary:
            with span("train.verb.commit"):
                blob = serialize_model(persisted)
                storage.get_model_data_models().insert(
                    Model(instance_id, blob))
                inst = instances.get(instance_id)
                instances.update(
                    replace(inst, status="COMPLETED", end_time=_now()))
            logger.info("training finished: instance %s (%d bytes of models)",
                        instance_id, len(blob))
        # placement evidence from the process that owns the devices: the
        # trained tables are still resident here (device-gather models)
        for row in device_memory_report():
            if row["bytes_in_use"] is not None:  # CPU has no allocator stats
                logger.info(
                    "device memory: %s bytes_in_use=%s peak=%s limit=%s",
                    row["device"], row["bytes_in_use"],
                    row["peak_bytes_in_use"], row["bytes_limit"])
        return instance_id
    except Exception:
        if primary:
            inst = instances.get(instance_id)
            if inst is not None:
                instances.update(replace(inst, status="FAILED", end_time=_now()))
        logger.error("training failed:\n%s", traceback.format_exc())
        raise
    finally:
        CleanupFunctions.run()
        ctx.stop()


def run_evaluation(
    evaluation: Evaluation,
    engine_params_list: Sequence[EngineParams],
    evaluation_instance: EvaluationInstance,
    params: WorkflowParams = WorkflowParams(),
    storage: Optional[Storage] = None,
    ctx: Optional[MeshContext] = None,
):
    """Evaluate all variants, store results on the instance
    (CoreWorkflow.runEvaluation :104-165 + EvaluationWorkflow.scala:34).
    Returns (instance_id, evaluator result)."""
    if evaluation.engine is None or evaluation.evaluator is None:
        raise ValueError("Evaluation must define engine and evaluator (engine_metric=…)")
    storage = storage or get_storage()
    ctx = ctx or MeshContext.create()
    # multi-process eval: every process computes (identical QA set, replicated
    # model → identical metrics); only the primary writes metadata rows
    primary = ctx.is_primary
    instances = storage.get_meta_data_evaluation_instances()
    if primary:
        instance_id = evaluation_instance.id or instances.insert(evaluation_instance)
        if evaluation_instance.id:
            instances.update(evaluation_instance)
    else:
        instance_id = "<secondary>"
    try:
        with ctx.activate():
            eval_data_set = evaluation.engine.batch_eval(ctx, list(engine_params_list), params)
            result = evaluation.evaluator.evaluate(ctx, evaluation, eval_data_set, params)
        if primary:
            inst = instances.get(instance_id)
            if not result.no_save:
                instances.update(
                    replace(
                        inst,
                        status="EVALCOMPLETED",
                        end_time=_now(),
                        evaluator_results=result.to_one_liner(),
                        evaluator_results_html=result.to_html(),
                        evaluator_results_json=result.to_json(),
                    )
                )
        logger.info("evaluation finished: %s", result.to_one_liner())
        return instance_id, result
    except Exception:
        if primary:
            inst = instances.get(instance_id)
            if inst is not None:
                instances.update(replace(inst, status="EVALFAILED", end_time=_now()))
        raise
    finally:
        CleanupFunctions.run()
        ctx.stop()
