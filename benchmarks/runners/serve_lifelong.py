"""Traffic kind ``serve_lifelong``: the sequence template's sparse-index
block (grouped-query attention behind a learned top-k index, softmax-routed
experts), deployed, under open-loop traffic of lifelong sessions (4k-24k
items) answered from a key/value cache and an index cache.

The run is ``serve_sessions``' run (its generator child, ``drive``,
``pick_sample``, ``compare``, ``judge`` and result line are imported, not
copied) with what names this block: its own engine and seeded weights
(``benchmarks/engines/seeded_gqa.py``), its own plain reference
(``benchmarks/reference/gqa_sparse_moe_ref.py``) and the shape its readers
and cost functions take.

The same module is this kind's entry to the knee finder and to the controls
(``benchmarks/sweep_sessions.py`` and ``benchmarks/control_sessions.py`` name
the ``serve_sessions`` runner; here they are given this one):

    python3 -m benchmarks.runners.serve_lifelong sweep --workload <cell> \\
        --seed 7 --seconds 51 --repeats 2 --rates 2,6,8,10
    python3 -m benchmarks.runners.serve_lifelong control --workload <cell> \\
        --seeds 1 --controls float8,dense,topk_half

A control is the PROGRAM with one thing changed (weights through
float8_e4m3fn; no selection: every query attends to all it sees; top-1024
in place of top-2048), asked as a window's sample is made up and compared
with the reference of the configuration as it stands: each has to come out
NOT correct by at least one of the cell's limits.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import shutil
import sys
import time

import numpy as np

from benchmarks import (
    control,
    control_sessions,
    harness,
    loadgen,
    seeded_data,
    seeded_gqa,
    sweep_sessions,
    trace_reduce,
)
from benchmarks.runners import common
from benchmarks.runners import serve_sessions as ss
from benchmarks.runners.serve_sessions import (  # noqa: F401  (the sweep's)
    compare,
    dispatches_by_bucket,
    drive,
    write_spec,
)

TRAFFIC_KEYS = ss.TRAFFIC_KEYS
CONFIG_KEYS = {
    "name", "source", "deployment", "reduced", "reduced_why", "bytes",
    "precision", "assumed", "seeded", "serve", "expect", "experts_held",
    "expert_offset",
    # the published config.json, key for key
    "attention_bias", "decoder_sparse_step", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "max_position_embeddings",
    "max_window_layers", "mlp_only_layers", "model_type",
    "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_local_experts", "rms_norm_eps",
    "rope_scaling", "rope_theta", "sa_config", "sliding_window",
    "tie_word_embeddings", "use_sliding_window", "vocab_size",
}
PAD_TO = 4096  # the reference runs sessions padded to whole multiples


def build_and_deploy(cell, seed: int, work: str, devices, lower=False):
    """run_train with seeded weights; returns the call that deploys the
    instance in a QueryServer (made inside the event loop) and its port.
    ``lower``: False, True (float8 weights) or a control's name
    (``engines/seeded_gqa.CONTROLS``)."""
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

    from benchmarks.engines import seeded_gqa as engine_mod

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
    max_batch = int(cell.traffic["max_batch"])

    def deploy():
        with harness.span("bench.setup.deploy"):
            return QueryServer(
                ServerConfig(engine_variant=variant_path, ip="127.0.0.1",
                             port=port, max_batch=max_batch),
                storage=storage, ctx=ctx)

    return deploy, port


def reference_logits(cfg: dict, seed: int, sessions: list,
                     lower: bool = False) -> np.ndarray:
    """``[S, V]`` float32: the plain reference's logits after the last item
    of each session, a full forward over the whole session at the
    configuration's widths. Weights are made again from the seed a layer at
    a time; sessions are padded to whole multiples of ``PAD_TO`` so that a
    handful of shapes compile (the block is causal: what follows a position
    cannot reach it)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gqa_sparse_moe_ref as ref

    shape = seeded_gqa.shape_config(cfg)
    top = seeded_gqa.top_weights(seed, cfg, lower)
    layer = jax.jit(lambda h, lw, pos: ref.layer(h, lw, shape, pos))
    hidden = []
    for tokens in sessions:
        n = -(-len(tokens) // PAD_TO) * PAD_TO
        padded = np.ones(n, np.int32)
        padded[:len(tokens)] = tokens
        hidden.append(ref.embed(top, padded))
    for i in range(cfg["num_hidden_layers"]):
        lw = seeded_gqa.layer_weights(seed, i, cfg, lower)
        hidden = [layer(h, lw, jnp.arange(h.shape[0])) for h in hidden]
        del lw
    last = jnp.stack([h[len(t) - 1] for h, t in zip(hidden, sessions)])
    return np.asarray(ref.logits(top, last, shape))


def check_answers(cell, seed: int, result) -> tuple:
    pick = ss.pick_sample(cell, seed, result)
    if not len(pick):
        return {}, pick
    sessions = ss.sample_sessions(result, pick)
    logits = reference_logits(cell.config, seed, sessions)
    return compare(logits, sessions, result["items"][pick],
                   result["scores"][pick]), pick


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
            server.deployed.models[0].release()

    seen = asyncio.run(session())
    setup_s = seen["start_wall"] - process_start
    result = dict(np.load(out))
    summary = loadgen.summarize(
        result["due"], result["sent"], result["done"], result["ok"], seconds,
        float(cell.traffic["limit_ms"]))
    lat = (result["done"] - result["due"]) * 1e3
    by_kind = {name: [round(float(np.percentile(lat[m], q)), 1)
                      for q in (50, 90, 99)] if m.any() else None
               for name, m in (("turns", result["ok"] & (result["kind"] == 0)),
                               ("misses", result["ok"] & (result["kind"] == 1)))}
    print(f"window: {summary}; setup_s {setup_s:.1f}; turns "
          f"{int((result['kind'] == 0).sum())} misses "
          f"{int((result['kind'] == 1).sum())}; p50/p90/p99 ms {by_kind}; "
          f"tokens reused {int(result['reused'].sum())} computed "
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
    correct = ss.judge(cell, numbers, pick, result, seen, summary)

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
            "shape": {**seeded_gqa.shape_config(cell.config),
                      "num_hidden_layers": cell.config["num_hidden_layers"],
                      "short_block": int(info["short_block"])},
        })
    shutil.rmtree(os.path.join(work, "home"), ignore_errors=True)
    return harness.result_line(
        cell, trace, correct, summary["attempted"], summary["failed"], e2e,
        layer, device, breakdown)


# -- this kind's entry to the knee finder and the controls ----------------------------

def main(argv=None) -> int:
    this = sys.modules[__name__]
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("what", choices=("sweep", "control"))
    args, rest = p.parse_known_args(argv)
    if args.what == "sweep":
        sweep_sessions.ss = this          # the same routine, this runner
        return sweep_sessions.main(rest)
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="float8,dense,topk_half")
    args = p.parse_args(rest)
    control_sessions.ss = this
    cell = harness.resolve_cell(args.workload)
    devices = harness.claim_chip(cell.chips)
    harness.configure_jax_cache()
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in args.controls.split(","):
            got = control_sessions.numbers(
                cell, seed, devices,
                lower={"float8": True, "sound": False}.get(name, name))
            failed = control.fails(cell, got)
            print(f"control {name} {cell.name} seed {seed}: {got} limits "
                  f"{cell.traffic['limits']} fails {failed}", flush=True)
            passed |= (not failed) != (name == "sound")
    return 1 if passed else 0  # a control that passes is the error


if __name__ == "__main__":
    sys.exit(main())
