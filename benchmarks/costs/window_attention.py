"""Window attention (``win_attn`` scope): bytes and operations the equations
need for ONE window layer to answer a list of requests, each extending a
session that stands at ``offset`` tokens by ``count`` new ones, whatever
implements it (a ring, released pages, a banded kernel).

Operations: the query at position i sees ``min(i + 1, window)`` rows; for
each of them one multiply-add per (head, head_dim) for ``q . k`` and one for
``p v``. Bytes: the rows any of the request's queries may see, once a
session a layer (those of the ``window - 1`` positions before the block that
exist, and the block's own), a key and a value of ``kv_heads x head_dim``
bfloat16 values each, and the block's own rows written once.
"""


def cost(offsets, counts, shape: dict) -> dict:
    window = shape["sliding_window"]
    heads, dh = shape["num_attention_heads"], shape["head_dim"]
    row_bytes = 2 * shape["num_key_value_heads"] * dh * 2
    seen = read = written = 0
    for offset, count in zip(offsets, counts):
        offset, count = int(offset), int(count)
        # positions offset .. offset + count - 1; the first `ramp` of them
        # still see fewer rows than the window holds
        ramp = min(max(window - 1 - offset, 0), count)
        seen += (2 * offset + ramp + 1) * ramp // 2 + (count - ramp) * window
        read += min(offset, window - 1) + count
        written += count
    return {
        "ops": 2 * 2 * heads * dh * seen,
        "bytes": (read + written) * row_bytes,
        "ops_peak": "bf16_flops_per_s",
    }
