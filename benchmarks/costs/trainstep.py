"""Operations and HBM bytes one dense-adam train step NEEDS, from its shapes.

Copy of bench.py ``_two_tower_flops_bytes`` (one step of it). Assumption,
stated: dense adam touches every row of both tables every step — parameters
float32 read+write, two moments at ``moment_bytes`` read+write — plus the
batch's embedding gathers (forward, backward). A sparse optimizer would need
less; this is what the schedule the program runs today needs.
"""


def cost(n_users: int, n_items: int, rank: int, batch: int,
         moment_bytes: int = 4) -> dict:
    n_params = (n_users + n_items) * (rank + 1)
    return {
        "ops": 12 * rank * batch + 12 * n_params,
        "bytes": n_params * (4 * 2 + moment_bytes * 4) + batch * rank * 4 * 4,
        "ops_peak": "bf16_flops_per_s",
    }
