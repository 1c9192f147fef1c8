"""The routed experts held on this chip where an expert is ``W2 relu(W1
h)^2`` (``moe_experts`` scope): ``benchmarks/costs/moe_experts.py`` with TWO
matrices an expert instead of three.

Bytes: the two matrices of every expert that received a pick, once a
dispatch (``experts_touched`` is summed over dispatches and layers), at the
width of the mathematics (what the program pads its storage with is its
own); each pick's input row in bfloat16 and its output row in float32.
Operations: two matmuls, one multiply-add per (pick, d, f) each.
"""


def cost(picks_held: float, experts_touched: float, d: int, f: int) -> dict:
    return {
        "ops": 2 * 2 * picks_held * d * f,
        "bytes": experts_touched * 2 * d * f * 2 + picks_held * d * (2 + 4),
        "ops_peak": "bf16_flops_per_s",
    }
