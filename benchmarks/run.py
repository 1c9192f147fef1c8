"""The benchmark's command (see BENCHMARK.json):

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run: claim the chip (no chip is an error, never a CPU run),
resolve the cell from its data files, hand it to the runner its traffic kind
names, print the contract's JSON object as the last line of stdout.
"""

from __future__ import annotations

import time

_PROCESS_START = time.time()  # setup_s counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    from benchmarks import harness

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = harness.resolve_cell(args.workload)
        runner = harness.load_runner(cell.kind)
        # importing the system under test is what fails in a directory that
        # holds only BENCHMARK.json and the benchmark's own paths
        import incubator_predictionio_tpu  # noqa: F401

        devices = harness.claim_chip(cell.chips)
        harness.configure_jax_cache()
        line = runner.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), devices=devices,
                          process_start=_PROCESS_START)
    except harness.HarnessError as e:
        print(f"benchmarks.run: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
