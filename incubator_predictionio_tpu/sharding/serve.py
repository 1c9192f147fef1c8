"""Sharded serving: per-shard top-k + cross-shard merge.

Exact serving over a sharded catalog used to funnel through one host —
``prepare_for_serving`` gathered the full item table off the mesh and the
single-host scorers took over, which at 10M-item shapes means a multi-GB
deploy transfer and one chip doing all the scoring. Here retrieval runs
WHERE THE ROWS LIVE:

- **Device-exact** (:class:`ShardedServing` with device state): the item
  table stays resident as one ``[rank, N]`` array column-sharded over a
  1-D serve mesh. One jitted dispatch per batch bucket runs, per shard,
  the UNCHANGED exact scoring math (bf16 matmul, fp32 accumulation — the
  same expression as ``_topk_scores``) plus a LOCAL ``lax.top_k``, then
  ``all_gather``s only the ``[b, k]`` ids/scores across the ``shard``
  axis and merges. Only batch-sized index/score traffic crosses ICI; the
  catalog never moves.
- **Host-exact** (per-shard numpy blocks): the CPU-parity twin — same
  per-shard slice math against the single-host numpy oracle, bitwise.
- **Sharded two-stage** (per-shard :class:`~incubator_predictionio_tpu.
  serving.ann.IVFIndex`): each shard clusters ONLY its local rows and
  prunes with its own centroids; the cross-shard merge reranks the
  surviving candidates. Rule filters (``exclude`` / ``row_mask``) translate
  into each shard's local index space; any shard that cannot cover the
  requested top-k with finite-scored candidates falls the whole batch back
  to the sharded-exact path (counted — the pruned path never serves a
  short or masked-padded answer).
- **Streaming deltas** route to the owning shard
  (:meth:`ShardedServing.with_row_updates`): only the owner's block (and
  its IVF staleness overlay) is rebuilt; other shards' arrays are shared
  untouched.

Merge semantics: per-shard candidates arrive best-first per shard,
concatenated in ascending global-row order, and the merge runs the shared
serial-parity selection chain (``serving/topk.py``) — for distinct scores
the merged (ids, scores) are bit-identical to the single-host oracle;
score ties resolve to the earliest candidate position exactly like
``lax.top_k`` does on the full score row.

Env knobs (docs/configuration.md): ``PIO_SHARD_SERVE`` and
``PIO_SHARD_SERVE_SHARDS``, read by :func:`serving_shards_for` alone.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Optional

import numpy as np

from incubator_predictionio_tpu.obs import profile as _profile
from incubator_predictionio_tpu.serving import plan as serve_plan
from incubator_predictionio_tpu.serving.topk import merge_topk
from incubator_predictionio_tpu.sharding import shard_metrics as M
from incubator_predictionio_tpu.sharding.table import (
    ShardSpec,
    array_model_shards,
    hbm_budget,
)

SHARD_AXIS = "shard"


def shard_build_key(n_local: int, shard: int) -> dict:
    """Per-shard IVF build key: the global build key at the shard's local
    catalog size, seed decorrelated per shard (two shards' k-means should
    not mirror each other's clustering noise)."""
    from incubator_predictionio_tpu.serving import ann

    key = ann.build_key(n_local)
    key["n_items"] = n_local
    key["seed"] = int(key["seed"]) * 1000 + shard
    key["shard"] = shard
    return key


def build_or_reuse_shard_ivf(spec: ShardSpec, rows_fn,
                             persisted: Optional[list] = None) -> list:
    """One IVF partition per shard over its LOCAL rows; a persisted shard
    index whose build key still matches is rehydrated (one O(shard) gather)
    instead of re-clustered. ``rows_fn(s) -> (item_emb, item_bias)`` pulls
    one shard's real rows — callers bound peak host memory to a shard."""
    from incubator_predictionio_tpu.serving import ann

    out = []
    for s in range(spec.n_shards):
        lo, hi = spec.shard_bounds(s)
        n_local = hi - lo
        if n_local <= 0:
            out.append(None)
            continue
        key = shard_build_key(n_local, s)
        idx = None
        if persisted is not None and s < len(persisted) \
                and persisted[s] is not None and persisted[s].matches(key):
            idx = persisted[s]
            if not idx.hydrated:
                idx.rehydrate(*rows_fn(s))
        if idx is None:
            idx = ann.build_ivf(*rows_fn(s), key=key)
        out.append(idx)
    return out


def _pull_device_shard_rows(spec: ShardSpec, shard: int, tables,
                            ) -> tuple[np.ndarray, np.ndarray]:
    """ONE shard's real ``(item_emb, item_bias)`` pulled from the device
    tables — the bounded-peak alternative to a full-table gather (the
    single implementation behind both the train-time and deploy-time
    per-shard pulls)."""
    import jax

    k = spec.width - 1
    lo, hi = spec.shard_bounds(shard)
    tp = np.asarray(jax.device_get(tables["ie"][lo:hi]))
    return (np.ascontiguousarray(tp[:, :k], dtype=np.float32),
            np.ascontiguousarray(tp[:, k], dtype=np.float32))


def model_shard_rows(model, spec: ShardSpec):
    """``rows_fn(s)`` over a model's item side — host slices when the
    towers are host numpy, per-shard device pulls (never the full table)
    when they are device-resident."""

    def rows(s: int):
        if model.item_emb is not None:
            lo, hi = spec.shard_bounds(s)
            return (np.asarray(model.item_emb[lo:hi], np.float32),
                    np.asarray(model.item_bias[lo:hi], np.float32))
        return _pull_device_shard_rows(spec, s, model._tables)

    return rows


def layout_shards_of(model) -> int:
    """How many ways a model's restored item table is split on the model
    axis (1 for host towers)."""
    if not model.device_resident or "ie" not in model._tables:
        return 1
    return array_model_shards(model._tables["ie"])


def serving_shards_for(
    n_items: int, rank: int, layout_shards: int = 1,
    host_max_elements: int = serve_plan.HOST_SERVE_MAX_ELEMENTS,
) -> int:
    """How many shards SERVING will use for a model of these facts under
    the current env (0 = the single-host paths). The ONE engage decision —
    shared by the serve plan (serving/plan.resolve), the train-time hook
    (``ALSAlgorithm.train`` building the persisted per-shard IVF), and the
    deploy-time restore path — so the layouts they pick cannot disagree.

    ``PIO_SHARD_SERVE`` = ``auto`` engages only for a catalog over the host
    threshold whose layout already says sharded (the restored device tables
    span ``layout_shards`` > 1 shards on the model axis) or whose
    single-chip serving residency the simulated HBM budget refuses; ``1``
    engages whenever more than one shard is realizable (forced count, or
    one per local device); ``0`` never."""
    raw = os.environ.get("PIO_SHARD_SERVE", "auto").strip().lower()
    mode = {"auto": "auto", "1": "on", "on": "on", "force": "on",
            "0": "off", "off": "off"}.get(raw)
    if mode is None:
        raise ValueError(f"PIO_SHARD_SERVE={raw!r} (want auto|1|0)")
    if mode == "off":
        return 0
    import jax

    ndev = len(jax.devices())
    forced = int(os.environ.get("PIO_SHARD_SERVE_SHARDS", "").strip() or 0)
    forced = forced if forced > 1 else None
    if mode == "on":
        # at least 2: virtual host shards don't need devices, and "always"
        # must mean always — a single-device box still gets the sharded
        # host twin (device tables clamp to the device count at build)
        return forced or max(ndev, 2)
    if n_items * (rank + 1) <= host_max_elements:
        return 0
    budget = hbm_budget()
    if layout_shards > 1 or (budget is not None and ShardSpec(
            "ie", n_items, rank + 1, 1).shard_table_bytes() > budget):
        s = forced or ndev
        return s if s > 1 else 0
    return 0


def restore_shards(n_items: int, rank: int, trained_shards: int = 1) -> int:
    """Shard count a deploy RESTORE should target (0 = replicated restore):
    the checkpoint loader asks this before building its ``like`` template so
    the tables land straight in the serving layout — no host staging, no
    post-restore reshard. ``trained_shards`` comes from the persisted
    :class:`~incubator_predictionio_tpu.sharding.table.ShardSpec` record."""
    s = serving_shards_for(n_items, rank, trained_shards)
    if s > 1:
        import jax

        # clamp forced counts like prepare_for_serving does: the restore
        # template places DEVICE arrays, and a persisted model must
        # redeploy under the same env that served it in-process
        s = min(s, len(jax.devices()))
    return s if s > 1 else 0


def train_time_shard_ivf(model, persisted: Optional[list] = None,
                         ) -> Optional[list]:
    """Per-shard IVF build at TRAIN time for a model that will serve
    sharded — persistence runs right after training, so the clustering
    ships with the model and redeploys skip the per-shard re-cluster.
    Returns None when sharded serving would not engage."""
    s = serving_shards_for(model.n_items, model.config.rank,
                           layout_shards_of(model))
    if s <= 1:
        return None
    spec = ShardSpec("ie", model.n_items, model.config.rank + 1, s)
    return build_or_reuse_shard_ivf(
        spec, model_shard_rows(model, spec), persisted)


# -- device state ------------------------------------------------------------

@dataclasses.dataclass
class _DeviceShards:
    """Resident device-side serving state, column/row sharded over a 1-D
    serve mesh (axis :data:`SHARD_AXIS`)."""

    mesh: Any
    item_t: Any        # [rank, N_p] bf16, P(None, shard)
    bias: Any          # [N_p] f32, P(shard)
    base_mask: Any     # [N_p] f32, P(shard): 0 real rows, -inf padding
    ue_bf: Any         # [U_p, rank] bf16, P(shard, None)
    ub: Any            # [U_p] f32, P(shard)
    ue_full: Any       # [U_p, rank+1] f32, P(shard, None) — host q-row pulls
    n_p: int           # padded catalog columns
    u_p: int


@functools.lru_cache(maxsize=8)
def _serve_mesh(n_shards: int):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_shards > len(devs):
        raise ValueError(
            f"{n_shards} device shards requested but only {len(devs)} "
            f"local devices exist (PIO_SHARD_SERVE_SHARDS)")
    return Mesh(np.array(devs[:n_shards]), (SHARD_AXIS,))


def _build_device_shards(tables, spec_items: ShardSpec,
                         spec_users: ShardSpec, rank: int) -> _DeviceShards:
    """Derive the sharded serving arrays from the (possibly differently
    sharded) training tables — device-to-device placement only, the tables
    never visit the host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _serve_mesh(spec_items.n_shards)
    n_items, n_users = spec_items.n_rows, spec_users.n_rows
    n_p, u_p = spec_items.padded_rows, spec_users.padded_rows
    cols = NamedSharding(mesh, P(None, SHARD_AXIS))
    rows = NamedSharding(mesh, P(SHARD_AXIS))
    rows2d = NamedSharding(mesh, P(SHARD_AXIS, None))

    # the training layout's padding multiple can EXCEED the serve one
    # (trained over more shards than serving uses): slice to the serve
    # padding first — rows past the real count are padding either way
    def repad(t, rows):
        t = t[:rows] if t.shape[0] > rows else t
        return jnp.pad(t, ((0, rows - t.shape[0]), (0, 0)))

    def prep_items(t):
        tp = repad(t, n_p)
        item_t = tp[:, :rank].T.astype(jnp.bfloat16)
        bias = tp[:, rank].astype(jnp.float32)
        base = jnp.where(jnp.arange(n_p) < n_items,
                         jnp.float32(0), -jnp.inf)
        return item_t, bias, base

    def prep_users(t):
        tp = repad(t, u_p)
        return (tp[:, :rank].astype(jnp.bfloat16),
                tp[:, rank].astype(jnp.float32),
                tp.astype(jnp.float32))

    item_t, bias, base = jax.jit(
        prep_items, out_shardings=(cols, rows, rows))(tables["ie"])
    ue_bf, ub, ue_full = jax.jit(
        prep_users, out_shardings=(rows2d, rows, rows2d))(tables["ue"])
    return _DeviceShards(mesh=mesh, item_t=item_t, bias=bias, base_mask=base,
                         ue_bf=ue_bf, ub=ub, ue_full=ue_full,
                         n_p=n_p, u_p=u_p)


@functools.lru_cache(maxsize=64)
def _sharded_exact_fn(mesh, num: int, kl: int, with_rmask: bool):
    """One jitted per-shard-top-k + merge program per (mesh, k, fan-in,
    masked?) — batch-bucket shapes key jit's own cache on top."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def per_shard(uq, ubq, mean, it, ib, m, rm):
        s = jax.lax.axis_index(SHARD_AXIS)
        # EXACTLY the single-host _topk_scores expression (same op order,
        # same dtypes) on this shard's column slice — what makes the merged
        # result bitwise the oracle's
        scores = (
            jax.lax.dot_general(
                uq, it, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + ib[None, :]
            + ubq[:, None]
            + mean
            + m[None, :]
        )
        if rm is not None:
            scores = scores + rm
        v, i = jax.lax.top_k(scores, kl)
        gi = i.astype(jnp.int32) + s.astype(jnp.int32) * jnp.int32(it.shape[1])
        # the ONLY cross-shard traffic: [b, kl] scores + ids per shard
        return (jax.lax.all_gather(v, SHARD_AXIS),
                jax.lax.all_gather(gi, SHARD_AXIS))

    in_specs = [P(), P(), P(), P(None, SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS)]
    if with_rmask:
        in_specs.append(P(None, SHARD_AXIS))
        body = per_shard
    else:
        def body(uq, ubq, mean, it, ib, m):
            return per_shard(uq, ubq, mean, it, ib, m, None)

    smapped = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                            out_specs=(P(), P()), check_vma=False)

    def fn(uidx, ue_bf, ub, mean, item_t, bias, mask, rmask=None):
        # device gather of the query rows from the row-sharded user table
        # (SPMD turns it into local gathers + a batch-sized psum)
        uq = ue_bf[uidx]
        ubq = ub[uidx]
        args = (uq, ubq, mean, item_t, bias, mask)
        if with_rmask:
            args = args + (rmask,)
        vg, ig = smapped(*args)
        b = uidx.shape[0]
        # [S, b, kl] → [b, S·kl] with shard-major candidate order ==
        # ascending global-id blocks (ties resolve like full-row top_k)
        cand_v = jnp.transpose(vg, (1, 0, 2)).reshape(b, -1)
        cand_i = jnp.transpose(ig, (1, 0, 2)).reshape(b, -1)
        v, pos = jax.lax.top_k(cand_v, num)
        return jnp.take_along_axis(cand_i, pos, axis=1), v

    return jax.jit(fn)


@functools.lru_cache(maxsize=1)
def _gather_rows_fn():
    """Jitted batch-row gather from the row-sharded fused user table —
    the host pull is [b, rank+1], never the table."""
    import jax

    return jax.jit(lambda t, idx: t[idx])


@functools.lru_cache(maxsize=1)
def _set_rows_fn():
    """Jitted build-beside row scatter (``.at[].set`` returns a NEW array
    with the operand's sharding) — streaming delta rows land on the owning
    shard without host round trips. Module-cached: a fresh lambda per call
    would recompile per delta."""
    import jax

    return jax.jit(lambda t, i, r: t.at[i].set(r))


@functools.lru_cache(maxsize=1)
def _set_cols_fn():
    import jax

    return jax.jit(lambda t, i, r: t.at[:, i].set(r))


# -- host state --------------------------------------------------------------

@dataclasses.dataclass
class _HostBlock:
    lo: int
    hi: int
    item_t: np.ndarray   # [rank, hi-lo] f32
    bias: np.ndarray     # [hi-lo] f32


def _host_blocks_from(item_emb: np.ndarray, item_bias: np.ndarray,
                      spec: ShardSpec) -> list[_HostBlock]:
    item_t = np.asarray(item_emb, np.float32).T
    bias = np.asarray(item_bias, np.float32)
    out = []
    for s in range(spec.n_shards):
        lo, hi = spec.shard_bounds(s)
        out.append(_HostBlock(lo, hi, item_t[:, lo:hi], bias[lo:hi]))
    return out


# -- the facade --------------------------------------------------------------

class ShardedServing:
    """Per-shard retrieval state for one model: exact engine (device or
    host blocks) + optional per-shard IVF. Read-only after build (streaming
    updates return a NEW instance via :meth:`with_row_updates`)."""

    def __init__(self, spec_items: ShardSpec, spec_users: ShardSpec,
                 mean: float, serve_k: int,
                 device: Optional[_DeviceShards] = None,
                 blocks: Optional[list[_HostBlock]] = None,
                 ivf: Optional[list] = None):
        self.spec = spec_items
        self.spec_users = spec_users
        self.mean = float(mean)
        self.serve_k = int(serve_k)
        self.device = device
        self.blocks = blocks
        self.ivf = ivf

    # -- construction ------------------------------------------------------
    @staticmethod
    def build_device(tables, n_users: int, n_items: int, rank: int,
                     mean: float, serve_k: int, n_shards: int,
                     ) -> "ShardedServing":
        spec_i = ShardSpec("ie", n_items, rank + 1, n_shards)
        spec_u = ShardSpec("ue", n_users, rank + 1, n_shards)
        dev = _build_device_shards(tables, spec_i, spec_u, rank)
        return ShardedServing(spec_i, spec_u, mean, serve_k, device=dev)

    @staticmethod
    def build_host(item_emb: np.ndarray, item_bias: np.ndarray,
                   n_users: int, mean: float, serve_k: int, n_shards: int,
                   ) -> "ShardedServing":
        rank = int(np.asarray(item_emb).shape[1])
        spec_i = ShardSpec("ie", int(np.asarray(item_emb).shape[0]),
                           rank + 1, n_shards)
        spec_u = ShardSpec("ue", n_users, rank + 1, n_shards)
        blocks = _host_blocks_from(item_emb, item_bias, spec_i)
        return ShardedServing(spec_i, spec_u, mean, serve_k, blocks=blocks)

    @property
    def n_shards(self) -> int:
        return self.spec.n_shards

    @property
    def rank(self) -> int:
        return self.spec.width - 1

    # -- shard row access --------------------------------------------------
    def shard_rows(self, shard: int, tables=None,
                   ) -> tuple[np.ndarray, np.ndarray]:
        """ONE shard's real ``(item_emb, item_bias)`` on host — the
        bounded-peak alternative to a full-table gather (per-shard IVF
        builds pull shard-at-a-time; peak host bytes = one shard)."""
        if self.blocks is not None:
            b = self.blocks[shard]
            return np.ascontiguousarray(b.item_t.T), np.asarray(b.bias)
        return _pull_device_shard_rows(self.spec, shard, tables)

    def user_rows(self, model, user_idx) -> tuple[np.ndarray, np.ndarray]:
        """Host ``(q [b, rank], user_bias [b])`` for the given users —
        batch-sized device pull when the towers are device-resident."""
        uidx = np.asarray(user_idx, np.int64)
        if model.user_emb is not None:
            return (np.asarray(model.user_emb, np.float32)[uidx],
                    np.asarray(model.user_bias, np.float32)[uidx])
        dev = self.device
        import jax

        rows = np.asarray(jax.device_get(
            _gather_rows_fn()(dev.ue_full, np.asarray(user_idx, np.int32))))
        return rows[:, : self.rank], rows[:, self.rank]

    # -- per-shard IVF -----------------------------------------------------
    def ensure_ivf(self, model=None, persisted: Optional[list] = None,
                   ) -> list:
        """Build — or rehydrate a persisted — per-shard IVF partition set.
        Each shard clusters only ITS rows (shard-at-a-time host pulls on
        device models: peak host memory is one shard, never the table)."""
        if self.ivf is not None:
            return self.ivf
        tables = getattr(model, "_tables", None) if model is not None else None
        self.ivf = build_or_reuse_shard_ivf(
            self.spec, lambda s: self.shard_rows(s, tables), persisted)
        return self.ivf

    # -- search ------------------------------------------------------------
    def search_exact(self, model, user_idx, num: int,
                     exclude=None, row_mask=None,
                     ) -> tuple[np.ndarray, np.ndarray]:
        t0 = time.perf_counter()
        if self.device is not None:
            res = self._search_device(model, user_idx, num, exclude, row_mask)
        else:
            q, ub = self.user_rows(model, user_idx)
            res = self._search_host(q, ub, num, exclude, row_mask)
        M.TOPK_SEC.observe(time.perf_counter() - t0)
        M.SHARD_BATCHES.inc()
        return res

    def _search_device(self, model, user_idx, num, exclude, row_mask):
        import jax
        import jax.numpy as jnp

        from incubator_predictionio_tpu.models.two_tower import (
            _row_mask_pad_buffer,
        )

        dev = self.device
        t_phase = time.perf_counter()
        b = len(user_idx)
        bucket = serve_plan.serve_bucket(max(b, 1))
        k = self.serve_k if 0 < num <= self.serve_k else num
        k = min(k, self.spec.n_rows)
        kl = min(k, self.spec.rows_per_shard)
        uidx = np.zeros(bucket, np.int32)
        uidx[:b] = np.asarray(user_idx, np.int32)
        mask = dev.base_mask
        if exclude is not None and len(exclude):
            m = np.zeros(dev.n_p, np.float32)
            m[np.asarray(exclude, np.int64)] = -np.inf
            mask = mask + jax.device_put(
                jnp.asarray(m), dev.base_mask.sharding)
        rmask = None
        if row_mask is not None:
            rm = _row_mask_pad_buffer(bucket, dev.n_p)
            rm[:b, : row_mask.shape[1]] = row_mask
            rmask = jax.device_put(
                jnp.asarray(rm),
                jax.sharding.NamedSharding(
                    dev.mesh, jax.sharding.PartitionSpec(None, SHARD_AXIS)))
        M.MERGE_FANIN.observe(self.n_shards * kl)
        from incubator_predictionio_tpu.utils import jitstats

        # phase edge: exclusion-mask / row-mask staging transfers are h2d
        _profile.fence(mask, rmask)
        t_h2d, t_phase = time.perf_counter() - t_phase, time.perf_counter()
        with jitstats.dispatch_timer((
            "two_tower_topk_sharded", self.n_shards, bucket, k,
            self.spec.n_rows, rmask is not None,
        )):
            fn = _sharded_exact_fn(dev.mesh, k, kl, rmask is not None)
            if rmask is not None:
                idx, scores = fn(jnp.asarray(uidx), dev.ue_bf, dev.ub,
                                 jnp.float32(self.mean), dev.item_t,
                                 dev.bias, mask, rmask)
            else:
                idx, scores = fn(jnp.asarray(uidx), dev.ue_bf, dev.ub,
                                 jnp.float32(self.mean), dev.item_t,
                                 dev.bias, mask)
            # phase edge: the fused per-shard score+local-topk+all-gather
            # executable is compute; the host pull after it is gather
            _profile.fence(idx, scores)
            t_compute, t_phase = (time.perf_counter() - t_phase,
                                  time.perf_counter())
            idx_h, scores_h = jax.device_get((idx, scores))
        _profile.record_phases("shard.search", {
            "h2d": t_h2d, "compute": t_compute,
            "gather": time.perf_counter() - t_phase,
        })
        return idx_h[:b, :num], scores_h[:b, :num]

    def _search_host(self, q, ub, num, exclude, row_mask):
        """Per-shard numpy blocks + serial-parity merge — bitwise the
        single-host oracle for distinct scores."""
        b = q.shape[0]
        num = min(num, self.spec.n_rows)
        if num <= 0 or b == 0:
            return (np.zeros((b, 0), np.int64), np.zeros((b, 0), np.float32))
        excl_sorted = None
        if exclude is not None and len(exclude):
            excl_sorted = np.sort(np.asarray(exclude, np.int64))
        ids_parts, sc_parts = [], []
        t_phase = time.perf_counter()
        row = np.arange(b)[:, None]
        for blk in self.blocks:
            n_s = blk.hi - blk.lo
            if n_s <= 0:
                continue
            # the _recommend_batch_host expression on this column slice
            scores = q @ blk.item_t + blk.bias[None, :] + ub[:, None] \
                + self.mean
            if excl_sorted is not None:
                a, z = np.searchsorted(excl_sorted, (blk.lo, blk.hi))
                local = excl_sorted[a:z] - blk.lo
                if len(local):
                    scores[:, local] = -np.inf
            if row_mask is not None:
                scores += row_mask[:, blk.lo:blk.hi]
            kl = min(num, n_s)
            part = np.argpartition(-scores, kl - 1, axis=1)[:, :kl]
            order = np.argsort(-scores[row, part], axis=1)
            top = np.take_along_axis(part, order, 1)
            ids_parts.append(top + blk.lo)
            sc_parts.append(scores[row, top])
        cand_ids = np.concatenate(ids_parts, axis=1)
        cand_sc = np.concatenate(sc_parts, axis=1)
        M.MERGE_FANIN.observe(cand_ids.shape[1])
        t0 = time.perf_counter()
        idx, scores = merge_topk(cand_ids, cand_sc, num)
        M.MERGE_SEC.observe(time.perf_counter() - t0)
        _profile.record_phases("shard.search", {
            "compute": t0 - t_phase, "merge": time.perf_counter() - t0,
        })
        return idx, scores

    def search_ivf(self, q, ub, num: int, exclude=None, row_mask=None,
                   nprobe: Optional[int] = None,
                   backend: Optional[str] = None,
                   ) -> Optional[tuple[np.ndarray, np.ndarray]]:
        """Composed two-stage over shards: each shard prunes its LOCAL
        partitions and reranks its candidates with the exact math; the
        cross-shard merge reranks the union. Returns None (fall back to
        sharded-exact) when any shard under-covers — same conservative
        contract as the single-host two-stage path."""
        b = q.shape[0]
        num = min(num, self.spec.n_rows)
        if num <= 0 or b == 0:
            return (np.zeros((b, 0), np.int64), np.zeros((b, 0), np.float32))
        excl_sorted = None
        if exclude is not None and len(exclude):
            excl_sorted = np.sort(np.asarray(exclude, np.int64))
        ids_parts, sc_parts = [], []
        t_phase = time.perf_counter()
        for s, idx_s in enumerate(self.ivf):
            lo, hi = self.spec.shard_bounds(s)
            n_s = hi - lo
            if n_s <= 0 or idx_s is None:
                continue
            k_s = min(num, n_s)
            local_excl = None
            if excl_sorted is not None:
                a, z = np.searchsorted(excl_sorted, (lo, hi))
                seg = excl_sorted[a:z] - lo
                local_excl = seg if len(seg) else None
            local_rm = row_mask[:, lo:hi] if row_mask is not None else None
            # observe=False: the batch is accounted ONCE in pio_shard_*,
            # not once per shard in pio_retrieval_*
            res = idx_s.search(q, ub, self.mean, k_s, nprobe=nprobe,
                               exclude=local_excl, row_mask=local_rm,
                               observe=False, backend=backend)
            if res is None:
                M.SHARD_FALLBACKS.inc()
                return None
            ids_parts.append(res[0] + lo)
            sc_parts.append(res[1])
        if not ids_parts:
            M.SHARD_FALLBACKS.inc()
            return None
        cand_ids = np.concatenate(ids_parts, axis=1)
        cand_sc = np.concatenate(sc_parts, axis=1)
        if cand_ids.shape[1] < num:
            # even the union can't fill the answer — exact sees more
            M.SHARD_FALLBACKS.inc()
            return None
        M.MERGE_FANIN.observe(cand_ids.shape[1])
        t0 = time.perf_counter()
        idx, scores = merge_topk(cand_ids, cand_sc, num)
        M.MERGE_SEC.observe(time.perf_counter() - t0)
        _profile.record_phases("shard.search", {
            "compute": t0 - t_phase, "merge": time.perf_counter() - t0,
        })
        M.SHARD_BATCHES.inc()
        return idx, scores

    # -- streaming deltas --------------------------------------------------
    def with_row_updates(self, user_rows: Optional[dict],
                         item_rows: Optional[dict]) -> "ShardedServing":
        """A NEW ShardedServing with delta rows applied on their OWNING
        shard; untouched shards share arrays with the receiver (which may
        be live — never mutated)."""
        new = ShardedServing(self.spec, self.spec_users, self.mean,
                             self.serve_k, device=self.device,
                             blocks=self.blocks, ivf=self.ivf)
        k = self.rank

        def stacked(rows_dict, spec):
            ids = np.asarray(sorted(int(i) for i in rows_dict), np.int64)
            rows = np.stack([np.asarray(rows_dict[int(i)], np.float32)
                             for i in ids])
            if rows.shape[1] != k + 1:
                raise ValueError(
                    f"delta row width {rows.shape[1]} != {k + 1}")
            for i in ids:
                spec.owner_of(int(i))  # raises on out-of-range
            return ids, rows

        if item_rows:
            ids, rows = stacked(item_rows, self.spec)
            M.DELTA_ROUTED.inc(len(ids))
            if new.blocks is not None:
                new.blocks = self._updated_blocks(ids, rows)
            if new.device is not None:
                new.device = self._updated_device_items(ids, rows)
            if new.ivf is not None:
                new.ivf = self._updated_ivf(ids, rows)
        if user_rows and self.device is not None:
            ids, rows = stacked(user_rows, self.spec_users)
            M.DELTA_ROUTED.inc(len(ids))
            new.device = self._updated_device_users(new.device, ids, rows)
        if item_rows and new.ivf is not None and new.blocks is not None:
            # host-block mode can re-cluster past the stale threshold
            # immediately (the blocks already hold the current f32 rows);
            # device mode rebuilds via rebuild_stale_ivf(model) once the
            # caller has the updated tables in hand
            new.rebuild_stale_ivf()
        return new

    def rebuild_stale_ivf(self, model=None) -> None:
        """Re-cluster any shard whose IVF staleness overlay exceeds
        ``PIO_STREAM_STALE_REBUILD_FRAC`` — the per-shard twin of the
        single-host rebuild (docs/streaming.md); without it a long stream
        of deltas grows the overlay to O(shard) and every pruned query
        rescans it. Only call on a freshly-updated instance (mutates
        ``self.ivf`` in place)."""
        from incubator_predictionio_tpu.serving import ann

        if not self.ivf:
            # prepare builds the per-shard indexes only under a plan that
            # prunes this catalog: none here, nothing to keep fresh
            return
        frac = float(os.environ.get("PIO_STREAM_STALE_REBUILD_FRAC", "0.25"))
        tables = getattr(model, "_tables", None) if model is not None else None
        for s, idx in enumerate(self.ivf):
            if idx is not None and idx.stale_fraction > frac:
                lo, hi = self.spec.shard_bounds(s)
                self.ivf[s] = ann.build_ivf(
                    *self.shard_rows(s, tables),
                    key=shard_build_key(hi - lo, s))

    def _updated_blocks(self, ids, rows) -> list[_HostBlock]:
        owners = ids // self.spec.rows_per_shard
        out = list(self.blocks)
        k = self.rank
        for s in np.unique(owners):
            blk = self.blocks[int(s)]
            sel = owners == s
            local = ids[sel] - blk.lo
            item_t = np.array(blk.item_t, copy=True)
            bias = np.array(blk.bias, copy=True)
            item_t[:, local] = rows[sel, :k].T
            bias[local] = rows[sel, k]
            out[int(s)] = _HostBlock(blk.lo, blk.hi, item_t, bias)
        return out

    def _updated_device_items(self, ids, rows) -> _DeviceShards:
        import jax.numpy as jnp

        dev = self.device
        k = self.rank
        ids_d = jnp.asarray(ids, jnp.int32)
        new_item_t = _set_cols_fn()(
            dev.item_t, ids_d,
            jnp.asarray(rows[:, :k].T).astype(jnp.bfloat16))
        new_bias = _set_rows_fn()(
            dev.bias, ids_d, jnp.asarray(rows[:, k], jnp.float32))
        return dataclasses.replace(dev, item_t=new_item_t, bias=new_bias)

    def _updated_device_users(self, dev, ids, rows) -> _DeviceShards:
        import jax.numpy as jnp

        k = self.rank
        ids_d = jnp.asarray(ids, jnp.int32)
        rows_d = jnp.asarray(rows, jnp.float32)
        upd = _set_rows_fn()
        return dataclasses.replace(
            dev,
            ue_full=upd(dev.ue_full, ids_d, rows_d),
            ue_bf=upd(dev.ue_bf, ids_d,
                      rows_d[:, :k].astype(jnp.bfloat16)),
            ub=upd(dev.ub, ids_d, rows_d[:, k]),
        )

    def _updated_ivf(self, ids, rows) -> list:
        owners = ids // self.spec.rows_per_shard
        out = list(self.ivf)
        k = self.rank
        for s in np.unique(owners):
            s = int(s)
            if out[s] is None:
                continue
            lo, _hi = self.spec.shard_bounds(s)
            sel = owners == s
            out[s] = out[s].with_updated_rows(
                ids[sel] - lo, rows[sel, :k], rows[sel, k])
        return out

    # -- reporting ---------------------------------------------------------
    def info(self) -> dict:
        kl = min(max(self.serve_k, 1), self.spec.rows_per_shard)
        ivf_stats = None
        if self.ivf is not None:
            ivf_stats = [i.stats() if i is not None else None
                         for i in self.ivf]
        live = [s for s in (ivf_stats or []) if s]
        return {
            "n_shards": self.n_shards,
            "mode": "device" if self.device is not None else "host",
            "items": self.spec.to_dict(),
            "users": self.spec_users.to_dict(),
            "merge_fanin": int(self.n_shards * kl),
            "serve_k": self.serve_k,
            "hbm_budget": hbm_budget(),
            "ivf": ivf_stats,
            # per-shard rerank storage: int8 vs fp32 and the HBM saved by
            # the quantized layout, summed over live shard indexes
            "quantized": bool(live and all(s["quantized"] for s in live)),
            "rerank_bytes": sum(s["rerank_bytes"] for s in live),
            "rerank_bytes_saved": sum(s["bytes_saved"] for s in live),
        }
