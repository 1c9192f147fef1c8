"""The control of every comparison that decides ``correct``: the plain
reference put in the program's place, computed one precision step below what
the configuration states (int4 catalog rows for the int8 serving paths,
bfloat16 adam moments for the float32 trainer). It has to come out as NOT
correct; the benchmark's own runs never run it.

    python3 -m benchmarks.control --workload <cell> --seeds 1,2,3

runs it on the chip at the cell's own size and prints each number beside the
cell's limit. tests/bench_harness runs the same functions at a small size.
"""

from __future__ import annotations

import argparse
import sys


from benchmarks import harness, loadgen, seeded_data


def serve_numbers(cell, seed: int) -> dict:
    """Control numbers of a serving cell: as many users as a run compares,
    drawn as the traffic draws them; no server is needed."""
    from benchmarks.reference import two_tower_ref as ref

    cfg = cell.config
    shape = {**cfg["towers"], "rank": cfg["rank"]}
    users = loadgen.zipf_users(
        int(cell.traffic["check_sample"]), cfg["n_users"],
        cell.traffic["zipf_s"], seeded_data.fold_seed(seed))
    full = ref.full_scores(seed, users, cfg["n_items"], shape, shape["mean"])
    low = ref.full_scores(seed, users, cfg["n_items"], shape, shape["mean"],
                          lower=True)
    items, scores = ref.control_answers(low, int(cell.traffic["num"]))
    return ref.serving_numbers(full, items, scores)


def train_numbers(cell, seed: int) -> dict:
    """Control numbers of a training cell: the reference with bfloat16
    moments against the float32 reference, same triples, same model seed."""
    from benchmarks.reference import two_tower_ref as ref

    cfg, tr = cell.config, cell.config["train"]
    users, items, ratings = seeded_data.rating_triples(
        seed, cfg["n_users"], cfg["n_items"], cfg["events_per_user"],
        cfg["towers"])
    args = (users, items, ratings, cfg["n_users"], cfg["n_items"], tr["rank"],
            tr["batchSize"], tr["numIterations"],
            tr.get("learningRate", 0.03), tr["lambda_"],
            seeded_data.fold_seed(seed + 1, 11))
    sound = ref.train(*args)
    low = ref.train(*args, lower=True)
    return ref.training_numbers(low["loss"], low["tables"], sound)


def fails(cell, numbers: dict) -> list:
    """Names of the compared numbers that fall outside the cell's limits."""
    limits = cell.traffic["limits"]
    out = []
    for name, value in numbers.items():
        if name + "_min" in limits:
            if value < limits[name + "_min"]:
                out.append(name)
        elif name in limits and value > limits[name]:
            out.append(name)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    harness.claim_chip(cell.chips)
    harness.configure_jax_cache()
    fn = serve_numbers if cell.kind == "serve_openloop" else train_numbers
    worst = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = fn(cell, seed)
        failed = fails(cell, numbers)
        print(f"control {cell.name} seed {seed}: {numbers} limits "
              f"{cell.traffic['limits']} fails {failed}", flush=True)
        worst |= not failed
    return 1 if worst else 0  # a control that passes is the error


if __name__ == "__main__":
    sys.exit(main())
