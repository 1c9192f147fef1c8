"""The sparse index (``idx_score`` + ``idx_select`` scopes): bytes and
operations the equations need to extend ONE session by ``new`` tokens at
offset ``reused``, one layer.

Rows scored: the query at absolute index i scores the ``i + 1`` keys it
sees, ``new * reused + new (new + 1) / 2`` in all (what
``pio_seq_index_rows_scored_total`` counts). Operations: a multiply-add per
(row scored, index head, index dim) for ``q_idx . k_idx``, and one more per
(row scored, index head) for the weighted sum after the relu. Bytes: the
session's index keys read once (``indexer_head_dim`` bfloat16 values a
token, without the padding the cache keeps them in). The selection itself
(top-k or threshold of each query's row) is comparisons, counted as
nothing: whatever it costs is the program's.
"""


def rows_scored(reused: float, new: float) -> float:
    return new * reused + new * (new + 1) / 2


def cost(reused: float, new: float, shape: dict) -> dict:
    sa = shape["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    rows = rows_scored(reused, new)
    return {"ops": 2 * rows * j * di + 2 * rows * j,
            "bytes": (reused + new) * di * 2,
            "ops_peak": "bf16_flops_per_s"}
