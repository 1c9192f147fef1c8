"""Traffic kind ``serve_feed``: the sequence template's gated-short-
convolution / rotary grouped-query / routed-expert pattern, deployed, under
open-loop traffic of an app's home feed: a thousand live medium sessions
(8-4096 items), nine requests in ten a turn of a session the server knows,
answered from a per-session convolution carry (147 KB) and paged key/value
rows.

The run is ``serve_sessions``' run (its generator child, ``drive``,
``pick_sample``, ``compare``, ``judge`` and result line are imported, not
copied) with what names this stack: its own engine and seeded weights
(``benchmarks/engines/seeded_conv.py``, ``benchmarks/seeded_conv.py``), its
own plain reference (``benchmarks/reference/conv_gqa_moe_ref.py``) and the
shape its readers and cost functions take.

The same module is this kind's entry to the knee finder and to the controls
(``benchmarks/sweep_sessions.py`` names the ``serve_sessions`` runner and is
given this one):

    python3 -m benchmarks.runners.serve_feed sweep --workload <cell> \\
        --seed 7 --seconds 51 --repeats 2 --rates 20,100,150,200
    python3 -m benchmarks.runners.serve_feed control --workload <cell> \\
        --seeds 1 --controls float8
    python3 -m benchmarks.runners.serve_feed control --workload <cell> \\
        --seeds 1 --controls zero_carry      (a process of its own)

A control is the PROGRAM with one thing changed (weights through
float8_e4m3fn; every turn started from a zero carry), asked as a window's
sample is made up and compared with the reference of the configuration as it
stands: each has to come out NOT correct by at least one of the cell's
limits.

Beside the answers, ``correct`` here holds the served CARRY: after the
window, what the first layer's convolution keeps for sampled sessions (read
from their slots, ``LatentServing.session_state``) against the reference's
last ``L - 1`` inputs over the tokens the carry stands at, rounded to the
dtype the configuration keeps them in (``carry_gap``).
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
    harness,
    loadgen,
    loadgen_sessions,
    seeded_conv,
    seeded_data,
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
from benchmarks.runners.serve_visits import TRAFFIC_KEYS

CONFIG_KEYS = {
    "name", "source", "deployment", "reduced", "reduced_why", "bytes",
    "precision", "assumed", "seeded", "serve", "expect", "experts_held",
    "expert_offset",
    # the published config.json, key for key (the catalog row's ``config``)
    "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size",
    "layer_types", "max_position_embeddings", "model_type",
    "moe_intermediate_size", "norm_eps", "norm_topk_prob",
    "num_attention_heads", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "rope_theta", "routed_scaling_factor", "use_expert_bias", "vocab_size",
}
PAD_TO = 512  # the reference runs sessions padded to whole multiples


def build_and_deploy(cell, seed: int, work: str, devices, lower=False):
    """run_train with seeded weights; returns the call that deploys the
    instance in a QueryServer (made inside the event loop) and its port.
    ``lower``: False, True (float8 weights) or a control's name
    (``engines/seeded_conv.algorithm_params``)."""
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

    from benchmarks.engines import seeded_conv as engine_mod

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
        # a program from before this configuration's letters: its algorithm
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


def _padded(tokens) -> np.ndarray:
    out = np.ones(-(-len(tokens) // PAD_TO) * PAD_TO, np.int32)
    out[:len(tokens)] = tokens
    return out


def reference_logits(cfg: dict, seed: int, sessions: list,
                     lower: bool = False) -> np.ndarray:
    """``[S, V]`` float32: the plain reference's logits after the last item
    of each session, a full forward over the whole session at the
    configuration's widths. Weights are made again from the seed a sub-block
    at a time; sessions are padded to whole multiples of ``PAD_TO`` so that
    a handful of shapes compile (every layer is causal: what follows a
    position cannot reach it)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import conv_gqa_moe_ref as ref

    shape = seeded_conv.shape_config(cfg)
    top = seeded_conv.top_weights(seed, cfg, lower)
    parts = seeded_conv.parts(cfg)
    blocks = {part: jax.jit(lambda h, lw, part=part: ref.sub_block(
        h, lw, shape, part)) for part in set(parts)}
    hidden = [ref.embed(top, _padded(tokens)) for tokens in sessions]
    for i, part in enumerate(parts):
        lw = seeded_conv.layer_weights(seed, i, cfg, lower)
        hidden = [blocks[part](h, lw) for h in hidden]
        del lw
    last = jnp.stack([h[len(t) - 1] for h, t in zip(hidden, sessions)])
    return np.asarray(ref.logits(top, last, shape))


def read_carries(cell, seed: int, serving, result) -> list:
    """``[(tokens, carry)]``: what the first layer (a convolution) keeps,
    now, for up to ``check_states`` of the sessions of the sampled turns
    that the table still holds, each with the tokens its carry stands at,
    which have to be the head of the list the generator grew."""
    pick = ss.pick_sample(cell, seed, result)
    out = []
    for sid in dict.fromkeys(result["sid"][pick][result["kind"][pick] == 0]):
        held = serving.session_state(f"s{sid}", 0)
        if held is None:
            continue
        tokens, start = held[0], result["sess_start"][sid]
        if not np.array_equal(
                tokens, result["sess_flat"][start:start + len(tokens)]):
            raise harness.HarnessError(
                f"session s{sid}: the table's tokens are not the head of "
                "the list it was sent")
        out.append((tokens, held[1]["conv"]))
        if len(out) == int(cell.traffic["check_states"]):
            break
    return out


def carry_gap(cfg: dict, seed: int, carries: list) -> float:
    """The largest ``|U - U_ref| / |U_ref|`` (the ``L - 1`` carried rows,
    Frobenius) over ``carries``: the served carry of the first layer's
    convolution against the reference's inputs over the same tokens, the
    reference rounding where the configuration's ``precision`` holds values
    in the weights' dtype (the projection's input and the carried rows
    themselves). The two differ by the order of the projection's sums, so by
    a step of that dtype in the few values that lie at a rounding edge."""
    import jax

    from benchmarks.reference import conv_gqa_moe_ref as ref

    shape = seeded_conv.shape_config(cfg)
    top = seeded_conv.top_weights(seed, cfg)
    lw = seeded_conv.layer_weights(seed, 0, cfg)
    stored = cfg["serve"].get("weight_dtype", "bfloat16")
    first = jax.jit(lambda tokens, count: ref.first_carry(
        top, lw, tokens, count, shape, stored))
    worst = 0.0
    for tokens, carry in carries:
        want = np.asarray(first(_padded(tokens), len(tokens))).reshape(-1)
        got = np.asarray(carry, np.float32)
        worst = max(worst, float(
            np.linalg.norm(got - want) / np.linalg.norm(want)))
    return worst


def check_answers(cell, seed: int, result, carries: list) -> tuple:
    pick = ss.pick_sample(cell, seed, result)
    if not len(pick):
        return {}, pick
    sessions = ss.sample_sessions(result, pick)
    numbers = {"carry_gap": carry_gap(cell.config, seed, carries)} \
        if carries else {}
    logits = reference_logits(cell.config, seed, sessions)
    numbers.update(compare(logits, sessions, result["items"][pick],
                           result["scores"][pick]))
    return numbers, pick


def judge(cell, numbers: dict, pick, result, seen: dict,
          summary: dict) -> bool:
    """``serve_sessions.judge`` and the served carry."""
    ok = ss.judge(cell, numbers, pick, result, seen, summary)
    ok &= common.print_check(
        "sampled_carries", float(len(seen["carries"])), ">=",
        float(cell.traffic["check_min_states"]))
    if "carry_gap" in numbers:
        ok &= common.print_check("carry_gap", numbers["carry_gap"], "<=",
                                 cell.traffic["limits"]["carry_gap"])
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
            serving = server.deployed.models[0].serving
            seen["device_scopes"] = serving.device_scopes()
            seen["carries"] = read_carries(cell, fold, serving,
                                           dict(np.load(out)))
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
    numbers, pick = check_answers(cell, fold, result, seen["carries"])
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
            "shape": {**seeded_conv.shape_config(cell.config),
                      "num_hidden_layers": cell.config["num_hidden_layers"],
                      "short_block": int(info["short_block"])},
        })
    shutil.rmtree(os.path.join(work, "home"), ignore_errors=True)
    return harness.result_line(
        cell, trace, correct, summary["attempted"], summary["failed"], e2e,
        layer, device, breakdown)


# -- this kind's entry to the knee finder and the controls ----------------------------

def control_numbers(cell, seed: int, devices, lower) -> dict:
    """``control_sessions.numbers`` for this stack: the same sessions asked
    the same way (``check_sample`` of the traffic's own lengths; misses
    whole, turns grown ``check_min_extended`` times through the carry), and,
    before the model goes, the turns' carries read from their slots:
    ``compare``'s numbers and ``carry_gap``."""
    from incubator_predictionio_tpu.templates.sequential import Query

    t = cell.traffic
    fold = seeded_data.fold_seed(seed)
    spec = {**{k: t[k] for k in t if k not in ("limits", "per_cell")},
            "seed": fold, "seconds": 1.0, "rate_qps": 1.0,
            "vocab_size": cell.config["vocab_size"]}
    plan = loadgen_sessions.plan(dict(spec, pool=int(t["check_sample"])))
    pool = np.flatnonzero(plan["phase"] == 0)
    sessions = [plan["sessions"][s][:n].astype(np.int32)
                for s, n in zip(plan["sid"][pool], plan["length"][pool])]
    deploy, _ = build_and_deploy(cell, fold, harness.work_dir(cell), devices,
                                 lower=lower)
    deployed = deploy().deployed
    algo, model = deployed.algorithms[0], deployed.models[0]
    num = int(t["num"])
    items = np.zeros((len(sessions), num), np.int64)
    scores = np.zeros((len(sessions), num), np.float64)
    n_misses = max(int(t["check_min_misses"]), len(sessions) // 3)
    turns = int(t["check_min_extended"])
    for i, tokens in enumerate(sessions):
        grow = min(int(t["growth_mean"]), (len(tokens) - 1) // turns)
        asked = [len(tokens)] if i < n_misses or not grow else [
            len(tokens) - j * grow for j in range(turns, -1, -1)]
        for n in asked:
            q = Query(user=f"c{i}", num=num,
                      recent_items=tuple(f"i{x}" for x in tokens[:n]))
            rows = algo.batch_predict(model, [(0, q)])[0][1].item_scores
        items[i] = [int(r.item[1:]) for r in rows]
        scores[i] = [r.score for r in rows]
    held = [model.serving.session_state(f"c{i}", 0)
            for i in range(n_misses, len(sessions))]
    carries = [(h[0], h[1]["conv"]) for h in held if h is not None][
        -int(t["check_states"]):]          # (an early one may be evicted)
    model.release()
    del deployed, algo, model, deploy
    gc.collect()
    numbers = {"carry_gap": carry_gap(cell.config, fold, carries)}
    logits = reference_logits(cell.config, fold, sessions)
    numbers.update(compare(logits, sessions, items, scores))
    return numbers


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
    p.add_argument("--controls", default="float8")
    args = p.parse_args(rest)
    controls = args.controls.split(",")
    if "zero_carry" in controls and len(controls) > 1:
        # it replaces the process's slot read, and a step once traced is
        # found again by the other controls: a process of its own
        p.error("zero_carry is asked alone")
    cell = harness.resolve_cell(args.workload)
    devices = harness.claim_chip(cell.chips)
    harness.configure_jax_cache()
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in controls:
            got = control_numbers(
                cell, seed, devices,
                lower={"float8": True, "sound": False}.get(name, name))
            failed = control.fails(cell, got)
            print(f"control {name} {cell.name} seed {seed}: {got} limits "
                  f"{cell.traffic['limits']} fails {failed}", flush=True)
            passed |= (not failed) != (name == "sound")
    return 1 if passed else 0  # a control that passes is the error


if __name__ == "__main__":
    sys.exit(main())
