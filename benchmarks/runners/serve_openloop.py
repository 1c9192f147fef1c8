"""Traffic kind ``serve_openloop``: a deployed engine under open-loop load.

Set-up (all of it counts as ``setup_s``): the configuration's towers are made
on the device from the seed by the benchmark's algorithm inside one ordinary
``run_train`` (BiMaps, IVF build, orbax persist are the program's); a
``QueryServer`` in this process restores, quantizes, warms every batch bucket
and listens on loopback; the load generator, a child process that never
imports jax, sends a short unmeasured warm-up at the cell's rate. The window
is then ``--seconds`` of open-loop traffic. After it: status and counters are
read, the server is shut down, the chip's memory peak is taken, and a seeded
sample of the window's answers is compared with the plain reference.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np

from benchmarks import harness, loadgen, seeded_data, trace_reduce
from benchmarks.runners import common

TRAFFIC_KEYS = {
    "kind", "why", "per_cell", "rate_qps", "knee_qps", "limit_ms", "zipf_s",
    "num", "connections", "warmup_seconds", "timeout_s", "schedule_seed",
    "check_sample", "trace_seconds", "max_batch", "limits",
}
CONFIG_KEYS = {
    "name", "source", "deployment", "n_users", "n_items", "n_ratings", "rank",
    "towers", "env", "precision", "expect", "assumed", "reduced",
}


async def _get(port: int, path: str):
    import aiohttp

    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{port}{path}") as r:
            if path == "/metrics":
                return await r.text()
            return await r.json()


async def _drive(cell, port, spec_path, trace, work, counter, memory=None):
    """Runs the generator child; returns what was seen at the window's edges.
    The child's stdout is pumped by a thread into the loop's queue (a plain
    Popen: its pipes are closed when it ends, with no transport left to the
    garbage collector)."""
    seen = {}
    loop = asyncio.get_running_loop()
    lines: asyncio.Queue = asyncio.Queue()
    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH_DIR, "loadgen.py"),
         spec_path], stdout=subprocess.PIPE, text=True)

    def pump():
        for raw in child.stdout:
            loop.call_soon_threadsafe(lines.put_nowait, raw)
        loop.call_soon_threadsafe(lines.put_nowait, None)

    threading.Thread(target=pump, daemon=True).start()
    profiler = harness.ProfilerWindow(os.path.join(work, "trace"))
    stop_task = None
    try:
        while (raw := await lines.get()) is not None:
            msg = json.loads(raw)
            if msg["event"] == "window_start":
                seen["start_wall"] = msg["wall"]
                if memory is not None:
                    memory.window(True)
                seen["compiles_before"] = counter.count
                seen["metrics_before"] = common.parse_metrics(
                    await _get(port, "/metrics"))
                if trace:
                    profiler.start()

                    async def stop_later():
                        await asyncio.sleep(float(cell.traffic["trace_seconds"]))
                        seen["trace_path"] = await loop.run_in_executor(
                            None, profiler.stop)

                    stop_task = asyncio.ensure_future(stop_later())
            elif msg["event"] == "window_end":
                seen["end_wall"] = msg["wall"]
                if memory is not None:
                    memory.window(False)
                seen["compiles_after"] = counter.count
                seen["metrics_after"] = common.parse_metrics(
                    await _get(port, "/metrics"))
            elif msg["event"] == "done":
                seen["done"] = msg
        if stop_task is not None:
            await stop_task
            seen["trace_window_s"] = profiler.window_s
    finally:
        if child.poll() is None and "done" not in seen:
            child.kill()
        child.wait()
        child.stdout.close()
    if child.returncode != 0 or "done" not in seen:
        raise harness.HarnessError(
            f"load generator exited {child.returncode} without a result")
    seen["status"] = await _get(port, "/")
    seen["health"] = await _get(port, "/health")
    return seen


def build_and_deploy(cell, seed: int, work: str, devices):
    """run_train with seeded towers; returns the call that deploys the
    instance in a QueryServer (made inside the event loop) and its port."""
    from incubator_predictionio_tpu.core.controller import (
        resolve_engine_factory,
    )
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.parallel.launcher import free_port
    from incubator_predictionio_tpu.parallel.mesh import MeshContext
    from incubator_predictionio_tpu.server.query_server import (
        QueryServer,
        ServerConfig,
    )
    from incubator_predictionio_tpu.templates.recommendation import (
        TrainingData,
    )

    from benchmarks.engines import seeded

    cfg = cell.config
    env = common.clean_env(work, cfg.get("env", {}))
    storage = Storage(env)
    ctx = MeshContext.create(devices=devices)
    seeded.DATA["bench"] = TrainingData(
        user_idx=np.zeros(1, np.int32), item_idx=np.zeros(1, np.int32),
        ratings=np.full(1, 3.5, np.float32),
        user_vocab=seeded_data.vocab("u", cfg["n_users"]),
        item_vocab=seeded_data.vocab("i", cfg["n_items"]))
    params = {"rank": cfg["rank"], "towerSeed": seeded_data.fold_seed(seed),
              **{k: cfg["towers"][k] for k in (
                  "groups", "noise", "user_bias_sd", "item_bias_sd", "mean")}}
    variant_path, variant = common.write_variant(work, "seeded", params)
    engine = resolve_engine_factory(common.FACTORY)()
    with harness.span("bench.setup.run_train"):
        common.train_once(engine, variant, variant_path, storage, ctx)
    del seeded.DATA["bench"]
    gc.collect()
    port = free_port()

    def deploy():
        with harness.span("bench.setup.deploy"):
            return QueryServer(
                ServerConfig(engine_variant=variant_path, ip="127.0.0.1",
                             port=port,
                             max_batch=int(cell.traffic["max_batch"])),
                storage=storage, ctx=ctx)

    return deploy, port


def write_spec(cell, port: int, seed: int, seconds: float, rate: float,
               out: str) -> str:
    """The load generator's whole input, as a file beside its output."""
    t = cell.traffic
    spec = {
        "host": "127.0.0.1", "port": port, "seed": seed, "seconds": seconds,
        "rate_qps": rate, "out": out, "n_users": cell.config["n_users"],
        "zipf_s": t["zipf_s"], "num": t["num"],
        "connections": t["connections"],
        "warmup_seconds": t["warmup_seconds"], "timeout_s": t["timeout_s"],
        "schedule_seed": t["schedule_seed"],
    }
    path = os.path.splitext(out)[0] + ".json"
    with open(path, "w") as f:
        json.dump(spec, f)
    return path


def check_answers(cell, seed: int, result) -> dict:
    """The numbers compared: a seeded sample of the window's answers against
    the plain reference over the whole catalog."""
    from benchmarks.reference import two_tower_ref as ref

    cfg, shape = cell.config, {**cell.config["towers"],
                               "rank": cell.config["rank"]}
    ok = np.flatnonzero(result["ok"])
    n = min(int(cell.traffic["check_sample"]), len(ok))
    if n == 0:
        return {}
    pick = np.random.default_rng(seeded_data.fold_seed(seed, 3)).choice(
        ok, size=n, replace=False)
    scores_ref = ref.full_scores(
        seed, result["users"][pick], cfg["n_items"], shape, shape["mean"])
    return ref.serving_numbers(
        scores_ref, result["items"][pick], result["scores"][pick])


def judge(cell, numbers: dict, seen: dict, summary: dict) -> bool:
    limits, expect = cell.traffic["limits"], cell.config["expect"]
    ok = bool(numbers)
    if numbers:
        for name in ("score_gap_max", "regret_max"):
            if name in limits:
                ok &= common.print_check(name, numbers[name], "<=",
                                         limits[name])
            else:  # read in every run, compared where the cell sets a limit
                print(f"check {name} = {numbers[name]!r}  not compared in "
                      "this cell", flush=True)
        ok &= common.print_check("recall_at_k", numbers["recall_at_k"],
                                 ">=", limits["recall_at_k_min"])
    path = seen["status"]["servingPaths"][0]
    print(f"check serving path = {path['path']!r} / {path['retrieval_mode']!r}"
          f"  want {expect['serve_path_prefix']!r}* / "
          f"{expect['retrieval_mode']!r}", flush=True)
    ok &= path["path"].startswith(expect["serve_path_prefix"])
    ok &= path["retrieval_mode"] == expect["retrieval_mode"]
    health = seen["health"]
    breakers = [health["servingBreaker"], *health["algorithmBreakers"].values(),
                *health["backendBreakers"].values()]
    ok &= common.print_check(
        "degraded_responses", float(health["degradedResponses"]), "==", 0.0)
    ok &= common.print_check(
        "open_breakers",
        float(sum(b["state"] != "closed" for b in breakers)), "==", 0.0)
    ok &= common.print_check(
        "compiles_in_window",
        float(seen["compiles_after"] - seen["compiles_before"]), "==", 0.0)
    ok &= common.print_check(
        "failed_share", summary["failed"] / max(summary["attempted"], 1),
        "<=", limits["failed_share_max"])
    return ok


def run(cell, seed: int, seconds: float, trace: bool, devices,
        process_start: float) -> str:
    harness.check_keys(f"traffic {cell.traffic_name}", cell.traffic,
                       TRAFFIC_KEYS)
    harness.check_keys(f"config {cell.config_name}", cell.config, CONFIG_KEYS)
    work = harness.work_dir(cell)
    counter = harness.CompileCounter()
    memory = harness.MemoryWatch(devices)
    deploy, port = build_and_deploy(cell, seed, work, devices)
    out = os.path.join(work, "loadgen.npz")
    spec_path = write_spec(cell, port, seeded_data.fold_seed(seed), seconds,
                           cell.traffic["rate_qps"], out)

    async def session():
        server = deploy()
        await server.start()
        try:
            return await _drive(cell, port, spec_path, trace, work, counter,
                                memory)
        finally:
            await server.shutdown()

    seen = asyncio.run(session())
    setup_s = seen["start_wall"] - process_start
    result = dict(np.load(out))
    summary = loadgen.summarize(
        result["due"], result["sent"], result["done"], result["ok"], seconds,
        float(cell.traffic["limit_ms"]))
    print(f"window: {summary}; setup_s {setup_s:.1f}; status maxBatchSeen "
          f"{seen['status'].get('maxBatchSeen')}", flush=True)
    slow = loadgen.stalls(result["due"], result["done"], result["ok"],
                          4 * summary["p50_ms"])
    print(f"window stalls over 4 x p50 [due s, requests, slowest ms]: {slow}",
          flush=True)

    # the program's state goes before the reference's comes
    del deploy
    gc.collect()
    memory.stop()
    device = harness.device_report(devices, memory)
    print(f"device: {device}; whole run {memory.run}; window {memory.win}",
          flush=True)
    t_check = time.perf_counter()
    numbers = check_answers(cell, seed, result)
    print(f"reference: {time.perf_counter() - t_check:.1f} s", flush=True)
    correct = judge(cell, numbers, seen, summary)

    e2e = {"serve_p50_ms": summary["p50_ms"],
           "serve_within_limit_pct": summary["within_limit_pct"],
           "serve_qps": summary["qps"], "setup_s": setup_s}
    layer, breakdown = {}, None
    if trace:
        reduced = trace_reduce.reduce_file(seen["trace_path"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = seen["trace_window_s"]
        breakdown = trace_reduce.breakdown(reduced)
        status = seen["status"]
        index = status["servingPaths"][0].get("index") or {}
        layer = harness.read_layer_metrics(cell, {
            "status": status, "metrics_before": seen["metrics_before"],
            "metrics_after": seen["metrics_after"], "loadgen": summary,
            "trace": reduced, "trace_window_s": seen["trace_window_s"],
            "peaks": harness.load_peaks(device["kind"], cell.root),
            "shape": {"n_items": cell.config["n_items"],
                      "rank": cell.config["rank"],
                      "n_partitions": index.get("n_partitions")},
        })
    shutil.rmtree(os.path.join(work, "home"), ignore_errors=True)
    return harness.result_line(
        cell, trace, correct, summary["attempted"], summary["failed"], e2e,
        layer, device, breakdown)
