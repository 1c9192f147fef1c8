"""The state-space recurrence (``ssm_scan`` scope): bytes and operations the
equations need for ONE state-space layer to carry ``sessions`` sessions over
``tokens`` new tokens in all, whatever implements the scan (token by token,
in tiles, from a cached state).

Bytes: each session's recurrent state read once and written once (``heads x
head_dim x state`` float32 values); for every token its ``x`` and ``y`` rows
(``heads x head_dim`` float32 each), its ``B`` and ``C`` rows (``groups x
state`` float32 each) and its step (``heads`` float32). Operations: one
multiply-add per (head, head_dim, state) for the update ``S <- a S + dt x (x)
B`` and one for the read-out ``S C``: ``4 x heads x head_dim x state`` a
token. The peak is the chip's bfloat16 one: it has no other for matrix
products, and a float32 product at full precision takes several passes of it
(the share reads lower for that, never higher).
"""


def cost(sessions: float, tokens: float, shape: dict) -> dict:
    heads, p = shape["mamba_num_heads"], shape["mamba_head_dim"]
    n, g = shape["ssm_state_size"], shape["n_groups"]
    state = heads * p * n
    return {
        "ops": 4 * tokens * state,
        "bytes": 4 * (2 * sessions * state
                      + tokens * (2 * heads * p + 2 * g * n + heads)),
        "ops_peak": "bf16_flops_per_s",
    }
