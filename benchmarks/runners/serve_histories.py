"""Traffic kind ``serve_histories``: the sequence template's window / full
grouped-query attention pattern with softmax-routed experts, deployed, under
open-loop traffic of signed-in users' whole histories: two dozen live
sessions from under the 1,024-key window to 14k items, nearly every request
a turn of a session the server knows, answered from a ring of key/value rows
a window layer and paged rows a full layer.

The run is ``serve_sessions``' run (its generator child, ``drive``,
``compare``, ``judge`` and result line are imported, not copied) with what
names this stack: its own engine and seeded weights
(``benchmarks/engines/seeded_window.py``, ``benchmarks/seeded_window.py``),
its own plain reference (``benchmarks/reference/window_gqa_moe_ref.py``) and
the shape its readers and cost functions take; its generator child is
``benchmarks/loadgen_histories.py`` (the same plan and schedule, every
request's bytes made before the first send: a 14k-item list takes 4 ms to
format); the sample it compares holds at least one session past
``check_long_over`` positions (where the yarn rule departs from plain angles),
one whose ring has wrapped, and a turn of a session that has passed the
ring's end since the server cached it wherever a turn of the window is one.

The same module is this kind's entry to the knee finder and to the controls
(``benchmarks/sweep_sessions.py`` and ``benchmarks/control_sessions.py`` name
the ``serve_sessions`` runner; here they are given this one):

    python3 -m benchmarks.runners.serve_histories sweep --workload <cell> \\
        --seed 7 --seconds 51 --repeats 2 --rates 4,30,45,60
    python3 -m benchmarks.runners.serve_histories control --workload <cell> \\
        --seeds 1 --controls float8,no_window,no_yarn

A control is the PROGRAM with one thing changed (weights through
float8_e4m3fn; a window as long as the longest session, so the window layers
see every key; plain angles on the full layers), asked as a window's sample
is made up and compared with the reference of the configuration as it
stands: each has to come out NOT correct by at least one of the cell's
limits.
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
    loadgen_sessions,
    seeded_data,
    seeded_window,
    sweep_sessions,
    trace_reduce,
)
from benchmarks.runners import common
from benchmarks.runners import serve_sessions as ss
from benchmarks.runners.serve_sessions import (  # noqa: F401  (the sweep's)
    compare,
    dispatches_by_bucket,
    write_spec,
)

GENERATOR = "loadgen_histories.py"
TRAFFIC_KEYS = ss.TRAFFIC_KEYS | {"check_long_over", "check_min_long",
                                  "check_min_wrapped"}
CONFIG_KEYS = {
    "name", "source", "deployment", "reduced", "reduced_why", "bytes",
    "precision", "assumed", "seeded", "serve", "expect", "experts_held",
    "expert_offset",
    # the published config.json, key for key (the catalog row's ``config``)
    "attention_bias", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "layer_types", "max_position_embeddings",
    "max_window_layers", "mlp_layer_types", "model_type",
    "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "rms_norm_eps", "rope_parameters",
    "sliding_window", "tie_word_embeddings", "use_sliding_window",
    "vocab_size",
}
PAD_TO = 2048  # the reference runs sessions padded to whole multiples


async def drive(*args, **kwargs):
    """``serve_sessions.drive`` with this kind's generator child (it starts
    the child before it first waits)."""
    theirs, ss.GENERATOR = ss.GENERATOR, GENERATOR
    try:
        return await ss.drive(*args, **kwargs)
    finally:
        ss.GENERATOR = theirs


def build_and_deploy(cell, seed: int, work: str, devices, lower=False):
    """run_train with seeded weights; returns the call that deploys the
    instance in a QueryServer (made inside the event loop) and its port.
    ``lower``: False, True (float8 weights) or a control's name
    (``engines/seeded_window.algorithm_params``)."""
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

    from benchmarks.engines import seeded_window as engine_mod

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
        # a program from before this configuration's letter: its algorithm
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

    from benchmarks.reference import window_gqa_moe_ref as ref

    shape = seeded_window.shape_config(cfg)
    top = seeded_window.top_weights(seed, cfg, lower)
    parts = seeded_window.parts(cfg)
    blocks = {part: jax.jit(lambda h, lw, part=part: ref.sub_block(
        h, lw, shape, part)) for part in set(parts)}
    hidden = [ref.embed(top, _padded(tokens)) for tokens in sessions]
    for i, part in enumerate(parts):
        lw = seeded_window.layer_weights(seed, i, cfg, lower)
        hidden = [blocks[part](h, lw) for h in hidden]
        del lw
    last = jnp.stack([h[len(t) - 1] for h, t in zip(hidden, sessions)])
    return np.asarray(ref.logits(top, last, shape))


def wrapped(cell, result) -> np.ndarray:
    """Per request of the window: a turn on a session longer than the
    window, so its ring has wrapped: every row of it is in use and the
    newest row lies before the oldest in the array."""
    return (result["kind"] == 0) \
        & (result["reused"] >= int(cell.config["sliding_window"]))


def first_cached(spec: dict) -> dict:
    """``{session: its length when the server first cached it}`` over the
    whole run, set-up and warm-up counted: the schedule simulated again
    (``loadgen_sessions.plan``)."""
    plan, out = loadgen_sessions.plan(spec), {}
    for sid, n in zip(plan["sid"], plan["length"]):
        out.setdefault(int(sid), int(n))
    return out


def crossed(cell, result, cached_at: dict) -> np.ndarray:
    """The stricter thing, which a window may not hold (a session gains ~4
    items a turn; the whole pool passes a multiple of the window a few times
    a minute): a turn whose session has passed a multiple of
    ``sliding_window`` positions since the server first cached it
    (``first_cached``), so that turns wrote across the ring's end and later
    ones read it there. ``pick_sample`` takes such a turn wherever an
    answered one exists, and ``judge`` fails a sample without one then."""
    window = int(cell.config["sliding_window"])
    first = np.asarray([cached_at[int(s)] for s in result["sid"]])
    return wrapped(cell, result) & (result["length"] // window
                                    > first // window)


def pick_sample(cell, seed: int, result, cached_at: dict) -> np.ndarray:
    """``serve_sessions.pick_sample``'s seeded sample, with its last turns
    given up, where it has none, for an answered turn past
    ``check_long_over`` items, for one whose ring has wrapped, and for one
    whose session passed the ring's end since it was cached where any did."""
    t = cell.traffic
    pick = list(ss.pick_sample(cell, seed, result))
    turn = result["ok"] & (result["kind"] == 0) \
        & (result["extended"] >= t["check_min_extended"])
    wants = (
        (turn & (result["length"] > t["check_long_over"]),
         int(t["check_min_long"])),
        (turn & wrapped(cell, result), int(t["check_min_wrapped"])),
        (turn & crossed(cell, result, cached_at), 1))
    # the rarest want first; a turn that answers one is not given up for the
    # next
    taken: set = set()
    for rows, least in wants[::-1]:
        have = [i for i, row in enumerate(pick) if rows[row]]
        spare = [i for i, row in enumerate(pick)
                 if result["kind"][row] == 0 and i not in taken
                 and i not in have][::-1]
        for row in np.flatnonzero(rows):
            if len(have) >= least or not spare:
                break
            if row not in pick:
                have.append(spare.pop(0))
                pick[have[-1]] = int(row)
        taken |= set(have)
    return np.asarray(pick, np.int64)


def check_answers(cell, seed: int, result, cached_at: dict) -> tuple:
    pick = pick_sample(cell, seed, result, cached_at)
    if not len(pick):
        return {}, pick
    sessions = ss.sample_sessions(result, pick)
    logits = reference_logits(cell.config, seed, sessions)
    return compare(logits, sessions, result["items"][pick],
                   result["scores"][pick]), pick


def judge(cell, numbers: dict, pick, result, seen: dict,
          summary: dict, cached_at: dict) -> bool:
    """``serve_sessions.judge``, and the sample holds what this stack has to
    be held to: a session past the yarn rule's original context, one whose
    ring has wrapped, and, wherever an answered turn of the window is on a
    session that passed the ring's end since it was cached, such a turn."""
    t = cell.traffic
    ok = ss.judge(cell, numbers, pick, result, seen, summary)
    turns = result["kind"][pick] == 0
    ok &= common.print_check(
        "sampled_past_" + str(t["check_long_over"]),
        float((turns & (result["length"][pick] > t["check_long_over"])).sum()),
        ">=", float(t["check_min_long"]))
    ok &= common.print_check(
        "sampled_ring_wrapped", float(wrapped(cell, result)[pick].sum()),
        ">=", float(t["check_min_wrapped"]))
    past = crossed(cell, result, cached_at) & result["ok"] \
        & (result["extended"] >= t["check_min_extended"])
    ok &= common.print_check(
        "sampled_past_the_rings_end", float(past[pick].sum()), ">=",
        float(min(int(past.sum()), int(t["check_min_wrapped"]))))
    print(f"answered turns of sessions that passed the ring's end since "
          f"they were cached: {int(past.sum())} of the window's", flush=True)
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
    with open(spec_path) as f:
        cached_at = first_cached(json.load(f))
    numbers, pick = check_answers(cell, fold, result, cached_at)
    print(f"reference: {time.perf_counter() - t_check:.1f} s; sampled "
          f"lengths {sorted(int(n) for n in result['length'][pick])}",
          flush=True)
    correct = judge(cell, numbers, pick, result, seen, summary,
                    cached_at)

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
            "shape": {**seeded_window.shape_config(cell.config),
                      "num_hidden_layers": cell.config["num_hidden_layers"],
                      "short_block": int(info["short_block"]),
                      # (a bucket reads "<batch>x<block>@<context>:<form>")
                      "piece": max(int(b.split("x")[1].split("@")[0])
                                   for b in info["buckets"])},
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
    p.add_argument("--controls", default="float8,no_window,no_yarn")
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
