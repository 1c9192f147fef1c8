"""Traffic kind ``serve_sessions``: the sequence template's latent-attention
block, deployed, under open-loop traffic of growing sessions.

Set-up (all of it counts as ``setup_s``): the configuration's weights are
made on the device from the seed by the benchmark's algorithm inside one
ordinary ``run_train`` (the model class, the orbax persist through the
PersistentModel SPI and the instance row are the program's); a ``QueryServer``
in this process restores them, sizes and allocates the latent cache, compiles
every bucket of the ladder and listens on loopback; the generator, a child
process that never imports jax (``benchmarks/loadgen_sessions.py``), asks
every session of its pool once and sends a short unmeasured warm-up at the
cell's rate. The window is then ``--seconds`` of open-loop traffic. After it:
status, counters and the executables' scope map are read, the server is shut
down and its state freed, the chip's memory peak is taken, and a seeded
sample of the window's answers is compared with the plain reference's full
forward over each whole session at the published widths.
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

from benchmarks import (
    harness,
    loadgen,
    seeded_data,
    seeded_seq,
    seq_trace,
    trace_reduce,
)
from benchmarks.runners import common
from benchmarks.runners.serve_openloop import _get

GENERATOR = "loadgen_sessions.py"
TRAFFIC_KEYS = {
    "kind", "why", "per_cell", "rate_qps", "knee_qps", "limit_ms", "pool",
    "length_median", "length_sigma", "length_min", "length_max", "retire_at",
    "miss_share", "growth_mean", "growth_max", "session_zipf_s",
    "item_zipf_s", "num", "connections", "prefill_connections", "max_batch",
    "warmup_seconds", "timeout_s", "schedule_seed", "check_sample",
    "check_min_turns", "check_min_extended", "check_min_misses",
    "reuse_tolerance", "trace_seconds", "limits",
}
CONFIG_KEYS = {
    "name", "source", "deployment", "reduced", "reduced_why", "bytes",
    "precision", "assumed", "seeded", "serve", "expect", "experts_held",
    "expert_offset",
    # the published config.json, key for key
    "attention_bias", "first_k_dense_replace", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "kv_lora_rank",
    "max_position_embeddings", "mlp_bias", "model_type",
    "moe_intermediate_size", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "q_lora_rank", "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
    "rms_norm_eps", "rope_interleave", "rope_parameters",
    "routed_scaling_factor", "sliding_window", "tie_word_embeddings",
    "topk_group", "v_head_dim", "vocab_size",
}
PAD_TO = 1024  # the reference runs sessions padded to whole multiples


async def drive(cell, port, spec_path, trace, work, counter, memory=None):
    """Runs the generator child; returns what was seen at the window's
    edges (``serve_openloop._drive`` for this kind's generator)."""
    seen = {}
    loop = asyncio.get_running_loop()
    lines: asyncio.Queue = asyncio.Queue()
    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH_DIR, GENERATOR),
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
                seen["setup_failed"] = msg["setup_failed"]
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
            f"session generator exited {child.returncode} without a result")
    seen["status"] = await _get(port, "/")
    seen["health"] = await _get(port, "/health")
    return seen


def dispatches_by_bucket(seen: dict) -> dict:
    """The window's extend dispatches by ``<batch>x<block>@<context>``."""
    return {labels[0][1]: int(n) for labels, n in sorted(seq_trace.delta(
        seen, "pio_seq_dispatches_total").items()) if n}


def build_and_deploy(cell, seed: int, work: str, devices, lower: bool = False):
    """run_train with seeded weights; returns the call that deploys the
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

    from benchmarks.engines import seeded_seq as engine_mod

    env = common.clean_env(work, {})
    storage = Storage(env)
    ctx = MeshContext.create(devices=devices)
    engine_mod.CONFIGS["bench"] = cell.config
    variant = {
        "id": "bench", "version": "1", "engineFactory": engine_mod.FACTORY,
        "datasource": {"params": {"key": "bench"}},
        "algorithms": [{
            "name": "seeded_block",
            "params": engine_mod.algorithm_params(cell.config, seed, lower)}],
    }
    variant_path = os.path.join(work, "engine.json")
    with open(variant_path, "w") as f:
        json.dump(variant, f)
    engine = resolve_engine_factory(engine_mod.FACTORY)()
    try:
        with harness.span("bench.setup.run_train"):
            common.train_once(engine, variant, variant_path, storage, ctx)
    except TypeError as e:
        # a program from before this configuration's block: its algorithm
        # params do not bind (unknown keys), before anything is built
        raise harness.HarnessError(
            f"the program cannot run configuration {cell.config_name!r}: "
            f"{e}") from e
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
    t = cell.traffic
    spec = {k: t[k] for k in (
        "pool", "length_median", "length_sigma", "length_min", "length_max",
        "retire_at", "miss_share", "growth_mean", "growth_max",
        "session_zipf_s", "item_zipf_s", "num", "connections",
        "prefill_connections", "warmup_seconds", "timeout_s",
        "schedule_seed")}
    spec.update(host="127.0.0.1", port=port, seed=seed, seconds=seconds,
                rate_qps=rate, out=out,
                vocab_size=cell.config["vocab_size"])
    path = os.path.splitext(out)[0] + ".json"
    with open(path, "w") as f:
        json.dump(spec, f)
    return path


# -- the comparison that decides ``correct`` -----------------------------------------

def pick_sample(cell, seed: int, result) -> np.ndarray:
    """Seeded sample of answered requests: turns whose session had been
    extended ``check_min_extended`` times or more first, misses, then any."""
    t = cell.traffic
    rng = np.random.default_rng(seeded_data.fold_seed(seed, 3))
    ok, kind = result["ok"], result["kind"]
    turns = np.flatnonzero(ok & (kind == 0)
                           & (result["extended"] >= t["check_min_extended"]))
    misses = np.flatnonzero(ok & (kind == 1))
    n = int(t["check_sample"])
    take_m = min(len(misses), max(int(t["check_min_misses"]), n // 3))
    take_t = min(len(turns), n - take_m)
    return np.concatenate([
        rng.choice(turns, take_t, replace=False),
        rng.choice(misses, take_m, replace=False)]).astype(np.int64)


def reference_logits(cfg: dict, seed: int, sessions: list,
                     lower: bool = False) -> np.ndarray:
    """``[S, V]`` float32: the plain reference's logits after the last item of
    each session, a full forward over the whole session at the configuration's
    widths. Weights are made again from the seed a layer at a time (the
    reference up-casts them as it multiplies); sessions are padded to whole
    multiples of ``PAD_TO`` so that a handful of shapes compile (the block is
    causal: what follows a position cannot reach it)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import mla_moe_ref as ref

    shape = seeded_seq.shape_config(cfg)
    top = seeded_seq.top_weights(seed, cfg, lower)
    layer = jax.jit(lambda h, lw, pos: ref.layer(h, lw, shape, pos))
    hidden = []
    for tokens in sessions:
        n = -(-len(tokens) // PAD_TO) * PAD_TO
        padded = np.ones(n, np.int32)
        padded[:len(tokens)] = tokens
        hidden.append(ref.embed(top, padded))
    for i in range(cfg["num_hidden_layers"]):
        lw = seeded_seq.layer_weights(seed, i, cfg, lower)
        hidden = [layer(h, lw, jnp.arange(h.shape[0])) for h in hidden]
        del lw
    last = jnp.stack([h[len(t) - 1] for h, t in zip(hidden, sessions)])
    return np.asarray(ref.logits(top, last, shape))


def compare(logits: np.ndarray, sessions: list, items, scores) -> dict:
    """The served top-``num`` against the reference's logits with padding and
    each session's own items masked, under the serve cells' names."""
    from benchmarks.reference import two_tower_ref

    masked = np.array(logits)
    masked[:, 0] = -np.inf
    for row, tokens in zip(masked, sessions):
        row[tokens] = -np.inf
    out = two_tower_ref.serving_numbers(masked, items, scores)
    # the middle request's widest gap: a router flip upstream moves one
    # session in six a long way (the two widest-gap numbers carry that
    # tail), a precision step moves every session a little
    ref_of_served = np.take_along_axis(masked, np.asarray(items), axis=1)
    gap = np.abs(np.asarray(scores, np.float32) - ref_of_served).max(axis=1)
    out["score_gap_p50"] = float(np.median(gap))
    return out


def sample_sessions(result, pick) -> list:
    return [result["sess_flat"][result["sess_start"][s]:
                                result["sess_start"][s] + n].astype(np.int32)
            for s, n in zip(result["sid"][pick], result["length"][pick])]


def check_answers(cell, seed: int, result) -> tuple:
    pick = pick_sample(cell, seed, result)
    if not len(pick):
        return {}, pick
    sessions = sample_sessions(result, pick)
    logits = reference_logits(cell.config, seed, sessions)
    return compare(logits, sessions, result["items"][pick],
                   result["scores"][pick]), pick


def judge(cell, numbers: dict, pick, result, seen: dict,
          summary: dict) -> bool:
    t, limits = cell.traffic, cell.traffic["limits"]
    ok = bool(numbers)
    if numbers:
        for name in ("score_gap_max", "score_gap_p50", "regret_max"):
            ok &= common.print_check(name, numbers[name], "<=", limits[name])
        ok &= common.print_check("recall_at_k", numbers["recall_at_k"], ">=",
                                 limits["recall_at_k_min"])
    kind, ext = result["kind"][pick], result["extended"][pick]
    ok &= common.print_check(
        "sampled_turns_extended",
        float(((kind == 0) & (ext >= t["check_min_extended"])).sum()), ">=",
        float(t["check_min_turns"]))
    ok &= common.print_check("sampled_misses", float((kind == 1).sum()), ">=",
                             float(t["check_min_misses"]))
    path = seen["status"]["servingPaths"][0]
    want = cell.config["expect"]["serve_path"]
    print(f"check serving path = {path['path']!r}  want {want!r}; cache "
          f"{path.get('cache_capacity_tokens')} tokens, buckets "
          f"{path.get('buckets')}", flush=True)
    ok &= path["path"] == want
    print(f"window dispatches by bucket: {dispatches_by_bucket(seen)}",
          flush=True)
    # the cache did what the schedule implies: a server that quietly
    # recomputes, or one that evicts live sessions, is not the cell
    a, b = seen["metrics_after"], seen["metrics_before"]
    reused = a.get("pio_seq_tokens_reused_total", 0.0) \
        - b.get("pio_seq_tokens_reused_total", 0.0)
    computed = a.get("pio_seq_tokens_computed_total", 0.0) \
        - b.get("pio_seq_tokens_computed_total", 0.0)
    implied = result["reused"].sum() / max(
        result["reused"].sum() + result["computed"].sum(), 1)
    share = reused / max(reused + computed, 1.0)
    print(f"check reuse share = {share!r}  schedule implies {implied!r}",
          flush=True)
    ok &= common.print_check("reuse_share_gap", abs(share - implied), "<=",
                             float(t["reuse_tolerance"]))
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
    ok &= common.print_check("setup_failed", float(seen["setup_failed"]),
                             "==", 0.0)
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
    fold = seeded_data.fold_seed(seed)
    deploy, port = build_and_deploy(cell, fold, work, devices)
    out = os.path.join(work, "loadgen.npz")
    spec_path = write_spec(cell, port, fold, seconds,
                           cell.traffic["rate_qps"], out)

    async def session():
        server = deploy()
        await server.start()
        try:
            seen = await drive(cell, port, spec_path, trace, work, counter,
                               memory)
            seen["device_scopes"] = \
                server.deployed.models[0].serving.device_scopes()
            return seen
        finally:
            await server.shutdown()
            # a stopped server object stays reachable (aiohttp caches its
            # application): the model is told to give the device back
            server.deployed.models[0].release()

    seen = asyncio.run(session())
    setup_s = seen["start_wall"] - process_start
    result = dict(np.load(out))
    summary = loadgen.summarize(
        result["due"], result["sent"], result["done"], result["ok"], seconds,
        float(cell.traffic["limit_ms"]))
    print(f"window: {summary}; setup_s {setup_s:.1f}; turns "
          f"{int((result['kind'] == 0).sum())} misses "
          f"{int((result['kind'] == 1).sum())}; tokens reused "
          f"{int(result['reused'].sum())} computed "
          f"{int(result['computed'].sum())} by the schedule", flush=True)
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
    numbers, pick = check_answers(cell, fold, result)
    print(f"reference: {time.perf_counter() - t_check:.1f} s", flush=True)
    correct = judge(cell, numbers, pick, result, seen, summary)

    e2e = {"serve_p50_ms": summary["p50_ms"],
           "serve_within_limit_pct": summary["within_limit_pct"],
           "serve_qps": summary["qps"], "setup_s": setup_s}
    layer, breakdown = {}, None
    if trace:
        reduced = trace_reduce.reduce_file(seen["trace_path"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = seen["trace_window_s"]
        breakdown = trace_reduce.breakdown(reduced)
        print("executables in the trace [runs, ms a run]: " + str({
            n: [reduced["module_runs"][n],
                round(1e3 * s / reduced["module_runs"][n], 3)]
            for n, s in sorted(reduced["module_s"].items())}), flush=True)
        info = seen["status"]["servingPaths"][0]
        layer = harness.read_layer_metrics(cell, {
            "status": seen["status"], "metrics_before": seen["metrics_before"],
            "metrics_after": seen["metrics_after"], "loadgen": summary,
            "trace": reduced, "trace_window_s": seen["trace_window_s"],
            "peaks": harness.load_peaks(device["kind"], cell.root),
            "device_scopes": seen["device_scopes"],
            "requests": {k: result[k] for k in (
                "due", "ok", "reused", "computed")},
            "shape": {**seeded_seq.shape_config(cell.config),
                      "num_hidden_layers": cell.config["num_hidden_layers"],
                      "short_block": int(info["short_block"])},
        })
    shutil.rmtree(os.path.join(work, "home"), ignore_errors=True)
    return harness.result_line(
        cell, trace, correct, summary["attempted"], summary["failed"], e2e,
        layer, device, breakdown)
