"""The control of the sequence-serving cell's comparison: the PROGRAM with
its weights one precision step down (the bfloat16 matrices rounded through
float8_e4m3fn, ``benchmarks/seeded_seq.py`` ``lower``) answering sessions
drawn as the traffic draws them and asked as a window's sample is made up
(two thirds are turns: the session's start is asked first and extended
``check_min_extended`` times through the latent cache, a few items a turn;
the rest are misses, asked whole), against the plain reference with the
configuration's own weights. It has to come out as NOT correct by at least
one of the cell's limits; the benchmark's own runs never run it.

    python3 -m benchmarks.control_sessions --workload <cell> --seeds 1,2

runs it on the chip at the cell's own size (no HTTP: ``batch_predict`` is
called directly) and prints each number beside the cell's limit.
tests/bench_harness runs the same function at a small size.
"""

from __future__ import annotations

import argparse
import gc
import sys

import numpy as np

from benchmarks import control, harness, loadgen_sessions, seeded_data
from benchmarks.runners import serve_sessions as ss


def numbers(cell, seed: int, devices, lower: bool = True) -> dict:
    """Deploys the (lowered) model, asks ``check_sample`` sessions of the
    traffic's own length distribution (turns grown through the cache, misses
    whole, in the runner's own shares), compares each one's last answer."""
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
    work = harness.work_dir(cell)
    deploy, _ = ss.build_and_deploy(cell, fold, work, devices, lower=lower)
    server = deploy()
    deployed = server.deployed
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
    model.release()
    del server, deployed, algo, model, deploy
    gc.collect()
    logits = ss.reference_logits(cell.config, fold, sessions)
    return ss.compare(logits, sessions, items, scores)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    devices = harness.claim_chip(cell.chips)
    harness.configure_jax_cache()
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        got = numbers(cell, seed, devices)
        failed = control.fails(cell, got)
        print(f"control {cell.name} seed {seed}: {got} limits "
              f"{cell.traffic['limits']} fails {failed}", flush=True)
        passed |= not failed
    return 1 if passed else 0  # a control that passes is the error


if __name__ == "__main__":
    sys.exit(main())
