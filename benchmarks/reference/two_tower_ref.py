"""The plain reference: two-tower scores, top-k and the dense-adam training
loop in straightforward float32 jax.numpy. No kernels, no batching ladder, no
index, nothing imported from the program and nothing the program made: towers
and triples come from the seed (``benchmarks.seeded_data``), the trainer's
initial tables and batch order are re-derived here from the formulas the
program documents (models/two_tower.py ``fit``; sharding/table.py).

Matrix products run at ``jax.default_matmul_precision("highest")``: on a TPU a
float32 product otherwise runs in bfloat16 passes.

Every function takes ``lower`` — the nearest precision BELOW what the
configuration states — and then serves as the control that the comparison has
to fail: int4 item rows for the int8 serving paths, bfloat16 adam moments for
the float32 trainer.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded_data


# -- serving --------------------------------------------------------------------

def _quantize_rows(x, bits: int):
    """Symmetric per-row quantisation to ``bits`` (the program's int8 scheme
    at 8; the control's at 4), returned de-quantised in float32."""
    import jax.numpy as jnp

    top = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-12) / top
    return jnp.clip(jnp.round(x / scale), -top, top) * scale


def full_scores(seed: int, users, n_items: int, shape: dict, mean: float,
                lower: bool = False):
    """``[len(users), n_items]`` float32 scores of every item for each user:
    u.i + item bias + user bias + mean. ``lower``: item rows in int4."""
    import jax
    import jax.numpy as jnp

    rank = int(shape["rank"])
    u = seeded_data.tower_rows(seed, seeded_data.USER_SIDE, users, shape)
    it = seeded_data.tower_rows(
        seed, seeded_data.ITEM_SIDE, jnp.arange(n_items), shape)
    ie = it[:, :rank]
    if lower:
        ie = _quantize_rows(ie, 4)
    with jax.default_matmul_precision("highest"):
        s = u[:, :rank] @ ie.T
    return s + it[:, rank][None, :] + u[:, rank][:, None] + mean


def serving_numbers(scores_ref, served_items, served_scores) -> dict:
    """What is compared, for answers ``served_items/served_scores [S, k]``
    against the reference's full ``scores_ref [S, n_items]``:

    - ``score_gap_max``: the widest |served score - reference score of that
      item|;
    - ``regret_max``: the widest gap by which a served item's reference score
      lies below the reference's k-th best (0 where every served item is a
      true top-k item);
    - ``recall_at_k``: mean share of the reference's top-k that was served.
    """
    import jax
    import jax.numpy as jnp

    served_items = jnp.asarray(served_items, jnp.int32)
    k = served_items.shape[1]
    top_vals, top_idx = jax.lax.top_k(scores_ref, k)
    ref_of_served = jnp.take_along_axis(scores_ref, served_items, axis=1)
    gap = jnp.abs(jnp.asarray(served_scores, jnp.float32) - ref_of_served)
    regret = jnp.maximum(top_vals[:, -1:] - ref_of_served, 0.0)
    hit = (served_items[:, :, None] == top_idx[:, None, :]).any(axis=2)
    return {
        "score_gap_max": float(gap.max()),
        "regret_max": float(regret.max()),
        "recall_at_k": float(hit.mean()),
    }


def control_answers(scores_lower, k: int):
    """The lower-precision scorer put in the program's place: its top-k."""
    import jax

    vals, idx = jax.lax.top_k(scores_lower, k)
    return np.asarray(idx), np.asarray(vals)


# -- training -------------------------------------------------------------------

def stage_batches(users, items, ratings, batch: int, seed: int):
    """The program's documented staging (two_tower.fit): permute, pad to whole
    batches with zero-weight repeats, sort each batch by user. Returns
    ``(ub, ib, rb, wb)`` ``[n_batches, batch]`` and the rating mean."""
    n = len(users)
    mean = float(ratings.mean())
    n_batches = max(1, -(-n // batch))
    n_pad = n_batches * batch
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    pad_idx = rng.integers(0, max(n, 1), n_pad - n)
    order = np.concatenate([perm, pad_idx]).reshape(n_batches, batch)
    w = np.concatenate([np.ones(n, np.float32),
                        np.zeros(n_pad - n, np.float32)]).reshape(
                            n_batches, batch)
    srt = np.argsort(np.asarray(users, np.int32)[order], axis=1, kind="stable")
    order = np.take_along_axis(order, srt, 1)
    w = np.take_along_axis(w, srt, 1)
    return (np.asarray(users, np.int32)[order],
            np.asarray(items, np.int32)[order],
            (np.asarray(ratings, np.float32) - mean)[order], w, mean)


def init_tables(seed: int, n_users: int, n_items: int, rank: int) -> dict:
    """The trainer's initial tables: N(0, 1/rank) vectors, zero bias column,
    keys split from ``key(seed)`` (sharding/table.py, one shard)."""
    import jax
    import jax.numpy as jnp

    ku, ki = jax.random.split(jax.random.key(seed))
    scale = 1.0 / np.sqrt(rank)

    def table(k, rows):
        t = jnp.zeros((rows, rank + 1), jnp.float32)
        return t.at[:, :rank].set(
            jax.random.normal(k, (rows, rank), jnp.float32) * scale)

    return {"ue": table(ku, n_users), "ie": table(ki, n_items)}


def _step(p, m, v, count, bu, bi, br, bw, lr, reg, moments_dtype):
    import jax
    import jax.numpy as jnp

    def loss_fn(p):
        gu, gi = p["ue"][bu], p["ie"][bi]
        ue, ie = gu[:, :-1], gi[:, :-1]
        pred = jnp.sum(ue * ie, axis=-1) + gu[:, -1] + gi[:, -1]
        denom = jnp.maximum(jnp.sum(bw), 1.0)
        mse = jnp.sum((pred - br) ** 2 * bw) / denom
        return mse + reg * (jnp.sum(ue ** 2) + jnp.sum(ie ** 2)) / denom

    loss, g = jax.value_and_grad(loss_fn)(p)
    count = count + 1
    cf = count.astype(jnp.float32)
    bc1, bc2 = 1.0 - 0.9 ** cf, 1.0 - 0.999 ** cf
    m32 = jax.tree.map(lambda m_, g_: 0.9 * m_.astype(jnp.float32) + 0.1 * g_,
                       m, g)
    v32 = jax.tree.map(
        lambda v_, g_: 0.999 * v_.astype(jnp.float32) + 0.001 * g_ * g_, v, g)
    p = jax.tree.map(
        lambda p_, m_, v_: p_ - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8),
        p, m32, v32)
    m = jax.tree.map(lambda x: x.astype(moments_dtype), m32)
    v = jax.tree.map(lambda x: x.astype(moments_dtype), v32)
    return p, m, v, count, loss


def train(users, items, ratings, n_users: int, n_items: int, rank: int,
          batch: int, epochs: int, lr: float, reg: float, seed: int,
          lower: bool = False) -> dict:
    """Plain dense adam over the staged batches, one jitted step at a time.
    ``lower``: adam moments stored in bfloat16 (the control). Returns the last
    epoch's mean loss, the final tables and the initial ones (device)."""
    import jax
    import jax.numpy as jnp

    ub, ib, rb, wb, mean = stage_batches(users, items, ratings, batch, seed)
    ub, ib, rb, wb = (jnp.asarray(a) for a in (ub, ib, rb, wb))
    p0 = init_tables(seed, n_users, n_items, rank)
    dt = jnp.bfloat16 if lower else jnp.float32
    step = jax.jit(_step, static_argnames=("lr", "reg", "moments_dtype"),
                   donate_argnums=(0, 1, 2))
    p = jax.tree.map(jnp.copy, p0)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, dt), p)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, dt), p)
    count = jnp.zeros((), jnp.int32)
    loss = None
    for _ in range(epochs):
        losses = []
        for b in range(ub.shape[0]):
            p, m, v, count, step_loss = step(
                p, m, v, count, ub[b], ib[b], rb[b], wb[b], lr=lr, reg=reg,
                moments_dtype=dt)
            losses.append(step_loss)
        loss = float(jnp.mean(jnp.stack(losses)))
    return {"loss": loss, "tables": p, "init": p0, "mean": mean,
            "touched": {"ue": np.unique(np.asarray(users)),
                        "ie": np.unique(np.asarray(items))},
            "n_batches": int(ub.shape[0])}


def training_numbers(prog_loss: float, prog_tables: dict, ref: dict) -> dict:
    """What is compared between the program's trained model and the
    reference's, leaf by leaf and worst leaf reported:

    - ``loss_gap``: |program's last-epoch loss - reference's| / reference's;
    - ``dnorm_gap``: | ||dp_program|| - ||dp_reference|| | / ||dp_reference||
      with dp = final - initial tables (the gap between norms, not the norm
      of the difference);
    - ``row_rms_gap``: the same over the RMS of each row's change, mean over
      rows (a step that skips rows moves it; a scale error moves it);
    - ``untouched_max``: the largest |change| of a row no triple names
      (dense adam leaves a zero-gradient row where it was: exactly 0).
    """
    import jax.numpy as jnp

    out = {"loss_gap": abs(prog_loss - ref["loss"]) / abs(ref["loss"])}
    dnorm, rowgap, untouched = [], [], []
    for leaf in ("ue", "ie"):
        rows = ref["init"][leaf].shape[0]
        dp = jnp.asarray(prog_tables[leaf])[:rows] - ref["init"][leaf]
        dr = ref["tables"][leaf] - ref["init"][leaf]
        n_p, n_r = float(jnp.linalg.norm(dp)), float(jnp.linalg.norm(dr))
        dnorm.append(abs(n_p - n_r) / n_r)
        rp = jnp.sqrt(jnp.mean(dp * dp, axis=1))
        rr = jnp.sqrt(jnp.mean(dr * dr, axis=1))
        rowgap.append(float(jnp.mean(jnp.abs(rp - rr)) / jnp.mean(rr)))
        mask = np.ones(rows, bool)
        mask[ref["touched"][leaf]] = False
        if mask.any():
            untouched.append(
                float(jnp.max(jnp.abs(dp[jnp.asarray(np.flatnonzero(mask))]))))
    out["dnorm_gap"] = max(dnorm)
    out["row_rms_gap"] = max(rowgap)
    out["untouched_max"] = max(untouched) if untouched else 0.0
    return out
