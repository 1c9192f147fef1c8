"""Fused adam parity (VERDICT r4 next #5).

Three claims, each load-bearing for the bf16-moments HBM lever of a
dense-adam table trainer:

1. ``adam_apply`` in fp32-moments mode IS optax.adam — same update math,
   elementwise-close over many steps on random trees (the two-tower trainer
   swapped optax for it, so the default path must not drift).
2. bf16-moment storage changes outcomes only within tight bounds: a real
   two-tower fit converges to the same loss (rel. tolerance) and
   substantially the same recommendations as fp32 moments.
3. The state layout is as claimed: bf16 moments really are stored bf16
   (the traffic cut is real, not a cast-through).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from incubator_predictionio_tpu.utils.optim import adam_apply, adam_tree_init


def test_adam_apply_matches_optax_fp32():
    rng = np.random.default_rng(0)
    params = {
        "a": jnp.asarray(rng.normal(size=(17, 5)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(9,)).astype(np.float32)),
    }
    lr = 3e-2
    tx = optax.adam(lr)
    o_ref = tx.init(params)
    p_ref = params
    p_new = params
    o_new = adam_tree_init(params, "float32")
    for step in range(25):
        grads = jax.tree.map(
            lambda x: jnp.asarray(
                rng.normal(size=x.shape).astype(np.float32)), params)
        updates, o_ref = tx.update(grads, o_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, updates)
        p_new, o_new = adam_apply(p_new, grads, o_new, lr)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_new[k]), np.asarray(p_ref[k]),
                rtol=2e-6, atol=2e-7, err_msg=f"step {step} key {k}")


def test_bf16_moment_state_is_actually_bf16():
    params = {"t": jnp.zeros((4, 3), jnp.float32)}
    count, m, v = adam_tree_init(params, "bfloat16")
    assert m["t"].dtype == jnp.bfloat16 and v["t"].dtype == jnp.bfloat16
    grads = {"t": jnp.ones((4, 3), jnp.float32)}
    _, (count, m, v) = adam_apply(params, grads, (count, m, v), 1e-2)
    assert m["t"].dtype == jnp.bfloat16 and v["t"].dtype == jnp.bfloat16
    assert int(count) == 1


def _fit(moments_dtype, seed=0):
    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerMF,
    )
    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    ctx = MeshContext.create()
    rng = np.random.default_rng(11)
    n, n_users, n_items = 6000, 300, 120
    users = rng.integers(0, n_users, n).astype(np.int32)
    items = rng.integers(0, n_items, n).astype(np.int32)
    # planted low-rank structure so convergence is meaningful, not noise
    uf = rng.normal(size=(n_users, 4))
    vf = rng.normal(size=(n_items, 4))
    ratings = (uf[users] * vf[items]).sum(1).astype(np.float32)
    model = TwoTowerMF(TwoTowerConfig(
        rank=8, epochs=30, batch_size=1024, seed=seed, gather="host",
        adam_moments_dtype=moments_dtype,
    )).fit(ctx, users, items, ratings, n_users=n_users, n_items=n_items)
    return model


def test_bf16_moments_converge_like_fp32():
    m32 = _fit("float32")
    m16 = _fit("bfloat16")
    assert np.isfinite(m32.final_loss) and np.isfinite(m16.final_loss)
    # same optimization trajectory within reduced-precision wiggle
    assert m16.final_loss == pytest.approx(m32.final_loss, rel=0.05)
    # and substantially the same top-8 recommendations per user
    s32 = m32.user_emb @ m32.item_emb.T + m32.item_bias[None, :]
    s16 = m16.user_emb @ m16.item_emb.T + m16.item_bias[None, :]
    top32 = np.argsort(-s32, axis=1)[:, :8]
    top16 = np.argsort(-s16, axis=1)[:, :8]
    overlap = np.mean([
        len(set(a) & set(b)) / 8.0 for a, b in zip(top32, top16)])
    assert overlap > 0.8, overlap
