"""The gated short convolution (``conv_proj`` + ``conv_mix`` scopes): bytes
and operations the equations need for ONE convolution sub-block to carry
``sessions`` sessions over ``tokens`` new tokens in ``dispatches``
dispatches, whatever implements it (a convolution over a whole block, taps
from a carried window, a fused kernel).

Bytes: ``W_in`` (``d x 3 d``) and ``W_out`` (``d x d``) in bfloat16, the
``taps x d`` float32 taps and the norm's ``d`` float32 gains, read once a
dispatch; for every token its row of the residual stream in and out (``d``
float32 each); for every session its carry read once and written once
(``(taps - 1) x d`` bfloat16 values). Operations: the two projections, one
multiply-add per (token, d, 3 d) and (token, d, d); the taps, one
multiply-add per (token, tap, d); the two gates, one multiply per (token, d)
each.
"""


def cost(dispatches: float, sessions: float, tokens: float, shape: dict) -> dict:
    d, taps = shape["hidden_size"], shape["conv_L_cache"]
    return {
        "ops": tokens * (2 * (3 * d * d + d * d) + 2 * taps * d + 2 * d),
        "bytes": dispatches * (2 * (3 * d * d + d * d) + 4 * (taps * d + d))
        + tokens * 2 * 4 * d + sessions * 2 * 2 * (taps - 1) * d,
        "ops_peak": "bf16_flops_per_s",
    }
