"""What a run makes from ``--seed``: serving towers and training triples.

Imports nothing of the program, so the plain reference can call it too: the
towers the deployed model is built from and the towers the reference scores
are the same function of the same seed, made twice.

Towers are structured, not noise: a row is its taste group's centre plus
noise, an item's last column its quality bias. Noise towers give an IVF index
nothing to prune (PERF.md section 6, PR 21).
"""

from __future__ import annotations

import functools

import numpy as np

JAX_SEED_MOD = 2**31 - 1  # jax.random.key and numpy both take a seed below this

USER_SIDE, ITEM_SIDE, CENTRE_SIDE = 1, 2, 3


def fold_seed(seed: int, salt: int = 0) -> int:
    """Any ``--seed`` (the driver's pass 2**31) to one jax and numpy take."""
    return (int(seed) + 1_000_003 * int(salt)) % JAX_SEED_MOD


def _centres(key, groups: int, rank: int):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(
        jax.random.fold_in(key, CENTRE_SIDE), (groups, rank),
        jnp.float32) / np.sqrt(rank)


def _rows(key, side: int, idx, centres, noise: float, bias_sd: float):
    """Rows ``idx`` of one tower, f32 ``[len(idx), rank+1]``: each row is a
    function of (seed, side, row index) alone, so any subset can be made
    without the rest."""
    import jax
    import jax.numpy as jnp

    groups, rank = centres.shape
    side_key = jax.random.fold_in(key, side)

    def one(i):
        kg, kn, kb = jax.random.split(jax.random.fold_in(side_key, i), 3)
        g = jax.random.randint(kg, (), 0, groups)
        e = centres[g] + (noise / np.sqrt(rank)) * jax.random.normal(
            kn, (rank,), jnp.float32)
        b = bias_sd * jax.random.normal(kb, (), jnp.float32)
        return jnp.concatenate([e, b[None]])

    return jax.vmap(one)(idx)


@functools.lru_cache(maxsize=8)
def _jit_rows(side: int, groups: int, rank: int, noise: float, bias_sd: float):
    import jax

    def fn(key, idx):
        return _rows(key, side, idx, _centres(key, groups, rank), noise,
                     bias_sd)

    return jax.jit(fn)


def tower_rows(seed: int, side: int, idx, shape: dict):
    """Device f32 rows ``idx`` of the user (``USER_SIDE``) or item tower."""
    import jax
    import jax.numpy as jnp

    bias_sd = shape["user_bias_sd"] if side == USER_SIDE else shape["item_bias_sd"]
    fn = _jit_rows(side, int(shape["groups"]), int(shape["rank"]),
                   float(shape["noise"]), float(bias_sd))
    return fn(jax.random.key(fold_seed(seed)), jnp.asarray(idx, jnp.int32))


def towers(seed: int, n_users: int, n_items: int, shape: dict) -> dict:
    """Both whole towers on the device, in the f32 ``[rows, rank+1]`` layout
    the program persists and restores."""
    import jax.numpy as jnp

    return {
        "ue": tower_rows(seed, USER_SIDE, jnp.arange(n_users), shape),
        "ie": tower_rows(seed, ITEM_SIDE, jnp.arange(n_items), shape),
    }


def vocab(prefix: str, n: int) -> np.ndarray:
    """Object array of python strings, as ``assemble_triples`` returns."""
    return np.asarray([f"{prefix}{i}" for i in range(n)], object)


def rating_triples(seed: int, n_users: int, n_items: int, per_user: int,
                   shape: dict):
    """``per_user`` ratings for every user: items by Zipf popularity, rating =
    3.5 + item quality + taste-group affinity + noise, half-star 0.5..5.0.
    Returns int32 users, int32 items, float32 ratings (host numpy)."""
    rng = np.random.default_rng(fold_seed(seed))
    groups = int(shape["groups"])
    n = n_users * per_user
    users = np.repeat(np.arange(n_users, dtype=np.int32), per_user)
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -float(shape["item_zipf_s"])
    cdf = np.cumsum(w)
    pop_rank = np.minimum(
        np.searchsorted(cdf, rng.random(n) * cdf[-1]), n_items - 1)
    items = rng.permutation(n_items).astype(np.int32)[pop_rank]
    quality = rng.normal(0.0, 0.5, n_items).astype(np.float32)
    user_group = rng.integers(0, groups, n_users)
    item_group = rng.integers(0, groups, n_items)
    affinity = rng.normal(0.0, 0.7, (groups, groups)).astype(np.float32)
    r = (3.5 + quality[items] + affinity[user_group[users], item_group[items]]
         + rng.normal(0.0, 0.5, n).astype(np.float32))
    ratings = (np.clip(np.round(r * 2.0), 1, 10) / 2.0).astype(np.float32)
    return users, items, ratings
