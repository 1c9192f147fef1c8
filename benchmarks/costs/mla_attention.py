"""Latent attention over the session cache (``mla_attn`` scope): bytes and
operations the equations need to extend ONE session by ``new`` tokens at
offset ``reused``, one layer, by form.

Bytes: the session's latent rows read once (``kvr + dr`` bfloat16 values a
token). Causal pairs: ``new * reused + new (new + 1) / 2``.

- ``up`` (blocks above the shortest bucket): keys and values of the whole
  context rebuilt from the latent rows (``kvr x H (dn + dv)`` a token),
  scores over ``dn + dr`` and values over ``dv`` a head and pair;
- ``absorbed``: the query carried into the latent space (``H dn kvr`` a new
  token) and the weighted sum back out (``H kvr dv``), scores over ``kvr +
  dr`` and values over ``kvr`` a head and pair.

The up-projection's own weights (3 MB) are left out: counted a request they
would be counted once a batch-mate.
"""


def cost(reused: float, new: float, form: str, shape: dict) -> dict:
    h, kvr = shape["num_attention_heads"], shape["kv_lora_rank"]
    dn, dr, dv = (shape["qk_nope_head_dim"], shape["qk_rope_head_dim"],
                  shape["v_head_dim"])
    total = reused + new
    pairs = new * reused + new * (new + 1) / 2
    if form == "up":
        ops = 2 * total * kvr * h * (dn + dv) \
            + 2 * h * pairs * (dn + dr + dv)
    else:
        ops = 2 * new * h * kvr * (dn + dv) \
            + 2 * h * pairs * (2 * kvr + dr)
    return {"ops": ops, "bytes": total * (kvr + dr) * 2,
            "ops_peak": "bf16_flops_per_s"}
