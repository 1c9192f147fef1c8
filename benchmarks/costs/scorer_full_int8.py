"""One call of the full-catalog int8 scorer (``two_tower._topk_quantized``:
Pallas score kernel + top-k) at a batch bucket: bytes and operations it needs.

Bytes: the int8 catalog once, its float32 scale / bias / mask rows, the
bucket's bfloat16 query rows, and the float32 score matrix written once and
read once by the top-k. Operations: one multiply-add per (query, item, dim).
"""


def cost(bucket: int, n_items: int, rank: int) -> dict:
    return {
        "ops": 2 * bucket * n_items * rank,
        "bytes": (n_items * rank + 3 * 4 * n_items + 2 * bucket * rank
                  + 2 * 4 * bucket * n_items),
        "ops_peak": "int8_ops_per_s",
    }
