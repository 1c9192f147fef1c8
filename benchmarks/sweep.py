"""Finds a serving cell's knee, once, when the cell is defined:

    python3 -m benchmarks.sweep --workload <cell> --seed 7 --seconds 12 \
        --rates 200,400,600,800

One deploy, then the cell's own open-loop traffic at each rate in turn. For
each rate: latency percentiles from the due instant, the share of requests
inside each candidate limit, and the second half of the window against the
first (a queue that grows shows there). The knee is the highest rate at which
>= 99% of requests finish inside the limit and the second half is no slower
than the first; the cell then runs at 0.8 x knee (its ``cells/`` file).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

import numpy as np

from benchmarks import harness, seeded_data
from benchmarks.runners import serve_openloop as so

LIMITS_MS = (25, 50, 100, 200)


def describe(r: dict, seconds: float) -> dict:
    lat = np.where(r["ok"], (r["done"] - r["due"]) * 1e3, seconds * 1e3)
    half = r["due"] < seconds / 2
    out = {"n": int(len(lat)), "failed": int((~r["ok"]).sum()),
           "p50": float(np.percentile(lat, 50)),
           "p99": float(np.percentile(lat, 99)),
           "p50_halves": [float(np.percentile(lat[half], 50)),
                          float(np.percentile(lat[~half], 50))],
           "p99_halves": [float(np.percentile(lat[half], 99)),
                          float(np.percentile(lat[~half], 99))],
           "lag_p99": float(np.percentile((r["sent"] - r["due"]) * 1e3, 99))}
    for lim in LIMITS_MS:
        out[f"within_{lim}ms"] = float((lat <= lim).mean())
    return {k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in out.items()}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    devices = harness.claim_chip(cell.chips)
    harness.configure_jax_cache()
    work = harness.work_dir(cell)
    counter = harness.CompileCounter()
    memory = harness.MemoryWatch(devices)
    deploy, port = so.build_and_deploy(cell, args.seed, work, devices)

    async def session():
        server = deploy()
        await server.start()
        try:
            for i, rate in enumerate(float(x) for x in args.rates.split(",")):
                out = os.path.join(work, f"sweep{i}.npz")
                spec_path = so.write_spec(
                    cell, port, seeded_data.fold_seed(args.seed, i),
                    args.seconds, rate, out)
                seen = await so._drive(cell, port, spec_path, False, work,
                                       counter)
                r = dict(np.load(out))
                print(f"rate {rate:g}: {describe(r, args.seconds)} "
                      f"maxBatchSeen {seen['status'].get('maxBatchSeen')} "
                      f"compiles {seen['compiles_after'] - seen['compiles_before']}",
                      flush=True)
                numbers = so.check_answers(cell, args.seed, r)
                print(f"rate {rate:g}: numbers {numbers}", flush=True)
        finally:
            await server.shutdown()

    asyncio.run(session())
    memory.stop()
    print(f"device: {harness.device_report(devices, memory)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
