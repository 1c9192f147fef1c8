"""Finds the sequence-serving cell's knee, once, when the cell is defined
(``benchmarks/sweep.py``'s method for the ``serve_sessions`` kind):

    python3 -m benchmarks.sweep_sessions --workload <cell> --seed 7 \
        --seconds 60 --repeats 2 --rates 10,40,50,60

One deploy, then the cell's own open-loop traffic at each rate in turn,
``--repeats`` windows a rate, each with its own traffic seed and its own pool
(asked once before its window). For each window: latency percentiles from
the due instant, the share of requests inside each candidate limit, and the
second half of the window against the first. The FIRST rate is the unloaded
one: the limit is 4.8 x the p99 of its windows' requests together (the two
serve cells' ratio), so give it a rate and a length that make >= 1,000
requests (a window of it in which a request failed is left out). The knee
is the highest rate at which >= 99% of the requests finish inside the limit
and the second half is no slower than the first. The share inside the limit
is held in EVERY window of the rate: the cell's run is one window, so a rate
at which one window in two leaves more than 1% outside is not one a run
sustains (the windows pooled hide it: PERF.md section 4). A stationary
queue's halves differ either way by chance, a growing one's do not, so
"slower" is read as PR 23 read its two sweeps: the second half's median
above the first's in every window of the rate. The cell then runs at 0.8 x knee (its ``cells/`` file).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

import numpy as np

from benchmarks import harness, seeded_data
from benchmarks.runners import serve_sessions as ss
from benchmarks.sweep import describe

LIMITS_MS = (300, 500, 750, 1000, 1500)  # a miss alone takes 30-200 ms
LIMIT_OVER_UNLOADED_P99 = 4.8


def window(r: dict, seconds: float) -> dict:
    """One window's latencies (a failure counts as the window's length),
    which requests were misses and which were due in the first half."""
    lat = np.where(r["ok"], (r["done"] - r["due"]) * 1e3, seconds * 1e3)
    return {"lat": lat, "miss": r["kind"] == 1, "first": r["due"] < seconds / 2,
            "failed": int((~r["ok"]).sum())}


def verdict(windows: list, limit_ms: float) -> dict:
    """Whether a rate is sustained, from all its windows (module docstring)."""
    lat = np.concatenate([w["lat"] for w in windows])
    halves = [[float(np.percentile(w["lat"][w["first"]], 50)),
               float(np.percentile(w["lat"][~w["first"]], 50))]
              for w in windows]
    within = min(float((w["lat"] <= limit_ms).mean()) for w in windows)
    slower = all(second > first for first, second in halves)
    return {"n": int(len(lat)), "failed": sum(w["failed"] for w in windows),
            "p50": round(float(np.percentile(lat, 50)), 2),
            "p95": round(float(np.percentile(lat, 95)), 1),
            "p99": round(float(np.percentile(lat, 99)), 1),
            "within_limit_lowest_window": round(within, 4),
            "p50_halves": [[round(a, 2), round(b, 2)] for a, b in halves],
            "second_half_slower_in_every_window": slower,
            "sustained": within >= 0.99 and not slower}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=60.0)
    p.add_argument("--repeats", type=int, default=2)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    cell = harness.resolve_cell(args.workload)
    devices = harness.claim_chip(cell.chips)
    harness.configure_jax_cache()
    work = harness.work_dir(cell)
    counter = harness.CompileCounter()
    memory = harness.MemoryWatch(devices)
    fold = seeded_data.fold_seed(args.seed)
    deploy, port = ss.build_and_deploy(cell, fold, work, devices)
    by_rate: dict = {}

    async def session():
        server = deploy()
        await server.start()
        try:
            for i, rate in enumerate(rates):
                for rep in range(args.repeats):
                    n = i * args.repeats + rep
                    out = os.path.join(work, f"sweep{n}.npz")
                    spec_path = ss.write_spec(
                        cell, port, seeded_data.fold_seed(args.seed, n + 1),
                        args.seconds, rate, out)
                    seen = await ss.drive(cell, port, spec_path, False, work,
                                          counter)
                    r = dict(np.load(out))
                    w = window(r, args.seconds)
                    by_rate.setdefault(rate, []).append(w)
                    lat, miss = w["lat"], w["miss"]
                    within = {lim: round(float((lat <= lim).mean()), 4)
                              for lim in LIMITS_MS}
                    print(f"rate {rate:g} window {rep}: "
                          f"{describe(r, args.seconds)} within {within} "
                          f"turns p50 {np.percentile(lat[~miss], 50):.1f} "
                          f"misses p50 {np.percentile(lat[miss], 50):.1f} "
                          f"p90 {np.percentile(lat[miss], 90):.1f} ms; "
                          f"maxBatchSeen "
                          f"{seen['status'].get('maxBatchSeen')} compiles "
                          f"{seen['compiles_after'] - seen['compiles_before']}"
                          f" dispatches {ss.dispatches_by_bucket(seen)}",
                          flush=True)
        finally:
            await server.shutdown()
            server.deployed.models[0].release()

    asyncio.run(session())
    memory.stop()
    # a window in which a request failed was not unloaded (the machine
    # stops for seconds now and then, PERF.md section 5): left out
    calm = [w for w in by_rate[rates[0]] if not w["failed"]] \
        or by_rate[rates[0]]
    unloaded = np.concatenate([w["lat"] for w in calm])
    p99 = float(np.percentile(unloaded, 99))
    limit_ms = LIMIT_OVER_UNLOADED_P99 * p99
    print(f"unloaded ({rates[0]:g} q/s, {len(unloaded)} requests): p50 "
          f"{np.percentile(unloaded, 50):.2f} p99 {p99:.1f} ms -> limit "
          f"{limit_ms:.0f} ms", flush=True)
    knee = None
    for rate in rates:
        v = verdict(by_rate[rate], limit_ms)
        print(f"rate {rate:g}: {v}", flush=True)
        if v["sustained"]:
            knee = rate
    print(f"knee {knee} q/s -> the cell runs at "
          f"{None if knee is None else 0.8 * knee} q/s", flush=True)
    print(f"device: {harness.device_report(devices, memory)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
