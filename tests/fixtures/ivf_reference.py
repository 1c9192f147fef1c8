"""The IVF build's clustering in host numpy, as ``serving/ann.py`` ran it
until ISSUE 44: the reference the jitted programs of ``ops/retrieval.py``
(``ivf_sample`` / ``ivf_assign`` / ``ivf_update``) are compared with.
The same key gives the same draws of the same generator in the same order;
the sums are float64 ``bincount`` passes where the programs' are a float32
segment sum, so a near-tie row may land in another partition."""

import numpy as np

#: Rows per chunk of an assignment pass: bounds the [chunk, C] score buffer.
ASSIGN_CHUNK = 131_072


def assign(x: np.ndarray, cent: np.ndarray,
           chunk: int = ASSIGN_CHUNK) -> np.ndarray:
    """Nearest-centroid (euclidean) assignment, chunked over rows."""
    half = 0.5 * np.einsum("cd,cd->c", cent, cent)
    out = np.empty(len(x), np.int32)
    for lo in range(0, len(x), chunk):
        d = x[lo:lo + chunk] @ cent.T
        d -= half[None, :]
        out[lo:lo + chunk] = np.argmax(d, axis=1)
    return out


def kmeans(x: np.ndarray, c: int, iters: int, rng: np.random.Generator,
           reseeded: list) -> np.ndarray:
    """Lloyd's k-means on (a sample of) the augmented rows; empty clusters
    reseed from random rows so every centroid stays live. ``reseeded``
    collects how many were re-drawn, an iteration."""
    cent = x[rng.choice(len(x), size=c, replace=False)].copy()
    d = x.shape[1]
    for _ in range(iters):
        a = assign(x, cent)
        counts = np.bincount(a, minlength=c).astype(np.float64)
        for j in range(d):
            cent[:, j] = np.bincount(a, weights=x[:, j], minlength=c)
        live = counts > 0
        cent[live] /= counts[live, None]
        n_dead = int((~live).sum())
        reseeded.append(n_dead)
        if n_dead:
            cent[~live] = x[rng.choice(len(x), size=n_dead, replace=False)]
    return cent


def cluster(item_emb: np.ndarray, item_bias: np.ndarray, key: dict):
    """``(centroids [C, D+1], assignment [n], reseeded per iteration)`` of
    a catalog under a build key."""
    n = len(item_emb)
    rng = np.random.default_rng(key["seed"])
    aug = np.concatenate([np.asarray(item_emb, np.float32),
                          np.asarray(item_bias, np.float32)[:, None]], axis=1)
    sample = min(int(key["train_sample"]), n)
    train = aug if sample >= n else \
        aug[rng.choice(n, size=sample, replace=False)]
    c = min(key["n_partitions"], max(1, n), len(train))
    reseeded: list = []
    cent = kmeans(train, c, int(key["kmeans_iters"]), rng, reseeded)
    return cent, assign(aug, cent), reseeded
