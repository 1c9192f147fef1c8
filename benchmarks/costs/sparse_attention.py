"""Attention over the selected rows (``sparse_attn`` scope): bytes and
operations the equations need to extend ONE session by ``new`` tokens at
offset ``reused``, one layer.

Rows selected: the query at absolute index i attends ``min(i + 1, topk)``
keys (what ``pio_seq_sparse_rows_selected_total`` counts). Operations:
``q . k`` and ``p v`` over ``head_dim`` for every (row selected, query
head). Bytes: each selected key/value row (``2 x num_key_value_heads x
head_dim`` bfloat16 values) once per query that selected it, but never more
than the session's rows once each: a block of many queries can share one
read of the context, and the least the equations need is the smaller.
"""


def rows_selected(reused: float, new: float, topk: int) -> float:
    first, last = reused + 1, reused + new
    below = max(0.0, min(last, topk) - first + 1)    # queries that see <= topk
    return (2 * first + below - 1) * below / 2 + (new - below) * topk


def cost(reused: float, new: float, shape: dict) -> dict:
    h, kv, dh = (shape["num_attention_heads"], shape["num_key_value_heads"],
                 shape["head_dim"])
    rows = rows_selected(reused, new, shape["sa_config"]["topk"])
    return {"ops": 2 * 2 * rows * h * dh,
            "bytes": min(rows, reused + new) * 2 * kv * dh * 2,
            "ops_peak": "bf16_flops_per_s"}
