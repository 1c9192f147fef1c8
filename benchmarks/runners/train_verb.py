"""Traffic kind ``train_verb``: whole ``run_train`` calls, back to back.

Set-up: the configuration's rating triples are made from the seed and parked
for the benchmark's DataSource (no run imports events); one warm-up verb
compiles every program. The window then runs verbs on the same triples, a
fresh model seed each, until ``--seconds`` have elapsed; the verb in flight
finishes and counts. ``train_events_per_s`` is events x epochs x verbs over the
window's wall time: DataSource -> Preparator -> ALSAlgorithm.train (BiMaps,
fit's stage / init / train / finalize, the IVF build) -> orbax persist ->
instance COMPLETED.

After the window the last verb's persisted model is loaded (the program's
own ``RecModel.load``) and compared with the plain reference, which trains
from the same seed on the same triples with float32 dense adam.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import time

import numpy as np

from benchmarks import harness, seeded_data, trace_reduce
from benchmarks.runners import common

TRAFFIC_KEYS = {"kind", "why", "per_cell", "trace_verbs", "limits"}
CONFIG_KEYS = {
    "name", "source", "deployment", "n_users", "n_items", "events_per_user",
    "rank", "train", "towers", "env", "precision", "assumed", "reduced",
}


class Bench:
    """One cell's engine, storage and data, and the call that runs a verb."""

    def __init__(self, cell, seed: int, work: str, devices):
        from incubator_predictionio_tpu.core.controller import (
            resolve_engine_factory,
        )
        from incubator_predictionio_tpu.data.storage import Storage
        from incubator_predictionio_tpu.parallel.mesh import MeshContext
        from incubator_predictionio_tpu.templates.recommendation import (
            TrainingData,
        )

        from benchmarks.engines import seeded

        self.cell, self.seed, self.work = cell, seed, work
        cfg = cell.config
        self.storage = Storage(common.clean_env(work, cfg.get("env", {})))
        self.ctx = MeshContext.create(devices=devices)
        self.users, self.items, self.ratings = seeded_data.rating_triples(
            seed, cfg["n_users"], cfg["n_items"], cfg["events_per_user"],
            cfg["towers"])
        seeded.DATA["bench"] = TrainingData(
            self.users, self.items, self.ratings,
            seeded_data.vocab("u", cfg["n_users"]),
            seeded_data.vocab("i", cfg["n_items"]))
        self.engine = resolve_engine_factory(common.FACTORY)()
        self.fits = seeded.FITS
        self.fits.clear()
        self.verbs: list[dict] = []

    def model_seed(self, i: int) -> int:
        return seeded_data.fold_seed(self.seed + i, 11)

    def verb(self, i: int) -> dict:
        """One whole run_train; the previous verb's checkpoint is dropped
        first so that the work directory holds one model."""
        params = {**self.cell.config["train"], "seed": self.model_seed(i)}
        path, variant = common.write_variant(self.work, "als", params)
        t0 = time.perf_counter()
        with harness.span("bench.verb"):
            instance_id = common.train_once(
                self.engine, variant, path, self.storage, self.ctx)
        rec = {"i": i, "wall_s": time.perf_counter() - t0,
               "instance_id": instance_id, "params": params,
               "variant": variant, **self.fits[-1]}
        inst = self.storage.get_meta_data_engine_instances().get(instance_id)
        rec["status"] = inst.status
        if self.verbs:
            shutil.rmtree(self.model_dir(self.verbs[-1]["instance_id"]),
                          ignore_errors=True)
        self.verbs.append(rec)
        return rec

    def model_dir(self, instance_id: str) -> str:
        return os.path.join(self.work, "home", "device_models",
                            f"{instance_id}_0")

    def load_tables(self, rec: dict) -> dict:
        """The persisted model of one verb, through the program's own deploy
        loader (blob -> ``prepare_deploy``), as host float32 tables
        ``[rows, rank+1]``; the device copy is dropped."""
        import jax

        from incubator_predictionio_tpu.utils.serialization import (
            deserialize_model,
        )

        blob = self.storage.get_model_data_models().get(rec["instance_id"])
        models = self.engine.prepare_deploy(
            self.ctx, self.engine.engine_params_from_variant(rec["variant"]),
            deserialize_model(blob.models), rec["instance_id"])
        mf = models[0].mf
        if mf.device_resident:
            tables = {k: np.asarray(jax.device_get(v))
                      for k, v in mf._tables.items()}
        else:
            tables = {
                "ue": np.concatenate(
                    [mf.user_emb, mf.user_bias[:, None]], axis=1),
                "ie": np.concatenate(
                    [mf.item_emb, mf.item_bias[:, None]], axis=1)}
        del models, mf
        gc.collect()
        return tables


def check_model(bench: Bench, rec: dict, prog_tables: dict) -> dict:
    """The numbers compared for one verb's model: the plain reference trains
    from the same model seed on the same triples."""
    from benchmarks.reference import two_tower_ref as ref

    cfg, tr = bench.cell.config, bench.cell.config["train"]
    reference = ref.train(
        bench.users, bench.items, bench.ratings, cfg["n_users"],
        cfg["n_items"], tr["rank"], tr["batchSize"], tr["numIterations"],
        tr.get("learningRate", 0.03), tr["lambda_"], rec["params"]["seed"])
    return ref.training_numbers(rec["final_loss"], prog_tables, reference)


def judge(cell, numbers: dict, verbs: list, compiles: int) -> bool:
    limits = cell.traffic["limits"]
    ok = True
    for name in ("loss_gap", "dnorm_gap", "row_rms_gap", "untouched_max"):
        ok &= common.print_check(name, numbers[name], "<=", limits[name])
    ok &= common.print_check(
        "verbs_not_completed",
        float(sum(v["status"] != "COMPLETED" for v in verbs)), "==", 0.0)
    ok &= common.print_check(
        "verbs_loss_not_finite",
        float(sum(not math.isfinite(v["final_loss"]) for v in verbs)),
        "==", 0.0)
    ok &= common.print_check("compiles_in_window", float(compiles), "==", 0.0)
    return ok


def run(cell, seed: int, seconds: float, trace: bool, devices,
        process_start: float) -> str:
    harness.check_keys(f"traffic {cell.traffic_name}", cell.traffic,
                       TRAFFIC_KEYS)
    harness.check_keys(f"config {cell.config_name}", cell.config, CONFIG_KEYS)
    work = harness.work_dir(cell)
    counter = harness.CompileCounter()
    memory = harness.MemoryWatch(devices)
    bench = Bench(cell, seed, work, devices)
    bench.verb(0)  # warm-up: every compile is paid here
    bench.verbs.clear()

    setup_s = time.time() - process_start
    compiles_before = counter.count
    profiler = harness.ProfilerWindow(os.path.join(work, "trace"))
    trace_path = None
    if trace:
        profiler.start()
    memory.window(True)
    t_start = time.perf_counter()
    i = 1
    while True:
        bench.verb(i)
        if trace and trace_path is None and i >= int(cell.traffic["trace_verbs"]):
            trace_path = profiler.stop()
        if time.perf_counter() - t_start >= seconds:
            break
        i += 1
    window_s = time.perf_counter() - t_start
    memory.window(False)
    compiles = counter.count - compiles_before
    verbs = list(bench.verbs)
    cfg = cell.config
    events = cfg["n_users"] * cfg["events_per_user"]
    epochs = cfg["train"]["numIterations"]
    rate = len(verbs) * events * epochs / window_s
    print(f"window: {len(verbs)} verbs in {window_s:.2f} s; setup_s "
          f"{setup_s:.1f}", flush=True)
    for v in verbs:
        print(f"verb {v['i']}: wall {v['wall_s']:.2f} s, algorithm.train "
              f"{v['algorithm_train_s']:.2f} s, fit {v['timings']}, loss "
              f"{v['final_loss']:.6f}", flush=True)

    prog_tables = bench.load_tables(verbs[-1])  # "model loadable"
    gc.collect()
    memory.stop()
    device = harness.device_report(devices, memory)
    print(f"device: {device}; whole run {memory.run}; window {memory.win}",
          flush=True)
    t_check = time.perf_counter()
    numbers = check_model(bench, verbs[-1], prog_tables)
    print(f"reference: {time.perf_counter() - t_check:.1f} s", flush=True)
    correct = judge(cell, numbers, verbs, compiles)
    failed = sum(v["status"] != "COMPLETED"
                 or not math.isfinite(v["final_loss"]) for v in verbs)

    e2e = {"train_events_per_s": rate, "setup_s": setup_s}
    layer, breakdown = {}, None
    if trace:
        reduced = trace_reduce.reduce_file(trace_path)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = profiler.window_s
        breakdown = trace_reduce.breakdown(reduced)
        n_batches = -(-events // cfg["train"]["batchSize"])
        layer = harness.read_layer_metrics(cell, {
            "kind": "train", "verbs": verbs, "trace": reduced,
            "trace_window_s": profiler.window_s,
            "peaks": harness.load_peaks(device["kind"], cell.root),
            "shape": {"n_users": cfg["n_users"], "n_items": cfg["n_items"],
                      "rank": cfg["rank"], "batch": cfg["train"]["batchSize"],
                      "steps_per_verb": n_batches * epochs},
        })
    shutil.rmtree(os.path.join(work, "home"), ignore_errors=True)
    return harness.result_line(cell, trace, correct, len(verbs), failed, e2e,
                               layer, device, breakdown)
