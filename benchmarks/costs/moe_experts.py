"""The routed experts held on this chip (``moe_experts`` scope): bytes and
operations the equations need for a number of token-picks.

Bytes: the three matrices of every expert that received a pick, once a
dispatch (``experts_touched`` is summed over dispatches and layers); each
pick's input row in bfloat16 and its output row in float32. Operations: three
matmuls, one multiply-add per (pick, d, f) each.
"""


def cost(picks_held: float, experts_touched: float, d: int, f: int) -> dict:
    return {
        "ops": 2 * 3 * picks_held * d * f,
        "bytes": experts_touched * 3 * d * f * 2 + picks_held * d * (2 + 4),
        "ops_peak": "bf16_flops_per_s",
    }
