"""Mid-training checkpoint/resume — the capability the reference lacks.

The reference has model-level persistence only: a training run either finishes
and Kryo-serializes its models into MODELDATA (workflow/CoreWorkflow.scala:79-84)
or leaves nothing; non-persistable ``P`` models are even *retrained from
scratch at deploy* (controller/Engine.scala:210-232). SURVEY §5 marks this the
explicit tradeoff to beat: orbax checkpoints make it obsolete.

:class:`TrainCheckpointer` wraps ``orbax.checkpoint.CheckpointManager`` with
the narrow contract the trainers need:

- ``save(step, state)`` — state is any pytree of jax/numpy arrays (params +
  optimizer state + epoch counter); sharded ``jax.Array`` leaves are written
  natively, no host gather required;
- ``latest_step()`` / ``restore(step, like=...)`` — restoring against a
  ``like`` template of freshly-initialized device arrays brings leaves back
  *with the template's shardings*, so a resumed run continues on the same mesh
  layout without extra device_puts;
- retention via ``max_to_keep`` (old steps garbage-collected).

Trainers opt in through their config (``checkpoint_dir`` + ``checkpoint_every``
on :class:`~incubator_predictionio_tpu.models.two_tower.TwoTowerConfig` and
:class:`~incubator_predictionio_tpu.models.transformer.TransformerConfig`);
a fit() pointed at a directory holding earlier steps resumes from the latest
one instead of starting over.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Optional

import numpy as np

logger = logging.getLogger(__name__)


class TrainCheckpointer:
    """Step-indexed pytree checkpoints in ``directory`` (created on demand)."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                # synchronous writes: save() returning means the step is
                # durable — the property resume correctness rests on
                enable_async_checkpointing=False,
            ),
        )

    def save(self, step: int, state: Any) -> None:
        """Durable by the time it returns: orbax writes the step into a tmp
        directory and renames it into place (synchronous mode, so the data
        files are flushed), and the directory fsync below makes the rename
        itself survive a power cut — the resume contract is 'a step save()
        returned for is restorable after kill -9 at any point'."""
        from incubator_predictionio_tpu.utils.fs import fsync_dir

        self._mgr.save(step, args=self._ocp.args.StandardSave(state))
        fsync_dir(self.directory)

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        return sorted(self._mgr.all_steps())

    def delete_all(self) -> None:
        """Drop every saved step (stale state from a prior completed run)."""
        for step in self.all_steps():
            self._mgr.delete(step)

    def restore(self, step: Optional[int] = None, like: Any = None) -> Any:
        """Restore ``step`` (default: latest). With ``like``, leaves come back
        matching the template's dtypes/shardings (device arrays stay device
        arrays); without it, plain host numpy in generic containers."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        if like is not None:
            args = self._ocp.args.StandardRestore(like)
            return self._mgr.restore(step, args=args)
        return self._mgr.restore(step)

    def close(self) -> None:
        self._mgr.close()

    def __enter__(self) -> "TrainCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def scalar(x: int) -> np.ndarray:
    """Wrap a python int as an array leaf (checkpoint trees hold arrays)."""
    return np.asarray(x, np.int32)


def maybe_resume(
    directory: Optional[str],
    every: int,
    keep: int,
    params: Any,
    opt_state: Any,
    epochs: int,
    mesh,
    factory=None,
) -> tuple[Optional[TrainCheckpointer], Any, Any, int]:
    """Open a checkpointer and resume an interrupted run if one is recoverable.

    Returns ``(ckpt, params, opt_state, start_epoch)`` — the single entry
    point both trainers share. Three non-resume outcomes all mean "train from
    scratch" (``start_epoch == 0``):

    - checkpointing disabled (no directory / ``every <= 0``): ``ckpt is None``;
    - restore fails (e.g. the vocabulary grew between redeploy passes, so the
      stored tables no longer match the new run's shapes): stale state is
      deleted, fresh start;
    - latest step >= ``epochs``: leftover state from a prior *completed* run —
      this is a new run on possibly-new data, so it must not short-circuit.

    The caller owns ``ckpt.close()`` (wrap the epoch loop in try/finally).

    ``factory`` (default :class:`TrainCheckpointer`) swaps the checkpointer
    implementation — the distributed tier passes
    :class:`~incubator_predictionio_tpu.distributed.checkpoint.DistSliceCheckpointer`
    so every member saves/restores its own slice under the same contract.
    """
    if not directory or every <= 0:
        return None, params, opt_state, 0
    ck = (factory or TrainCheckpointer)(directory, max_to_keep=keep)
    if ck.latest_step() is None:
        return ck, params, opt_state, 0
    try:
        state = restore_placed(
            ck, {"params": params, "opt": opt_state, "epoch": scalar(0)}, mesh
        )
        resumed = int(state["epoch"])
    except Exception as e:  # noqa: BLE001 — any restore failure ⇒ fresh start
        logger.warning(
            "checkpoint restore from %s failed (%s): restarting fresh",
            directory, e,
        )
        ck.delete_all()
        return ck, params, opt_state, 0
    if resumed >= epochs:
        logger.warning(
            "checkpoint at epoch %d >= epochs %d in %s: stale completed-run "
            "state, restarting fresh", resumed, epochs, directory,
        )
        ck.delete_all()  # step numbers will be re-saved
        return ck, params, opt_state, 0
    # operator-visible (and chaos-test-pinned) proof the interrupted run
    # continued instead of restarting: kill -9 costs epochs-since-save only
    logger.info("checkpoint: resuming from epoch %d (of %d) in %s",
                resumed, epochs, directory)
    return ck, state["params"], state["opt"], resumed


def checkpointed_epochs(
    directory: Optional[str],
    every: int,
    keep: int,
    epochs: int,
    params: Any,
    opt_state: Any,
    mesh,
    train_epochs,
    factory=None,
    on_chunk=None,
) -> tuple[Any, Any, Any]:
    """The shared epoch driver both trainers run.

    Resumes via :func:`maybe_resume`, then drives
    ``train_epochs(params, opt_state, n_epochs) -> (params, opt_state, loss)``
    in the largest chunks the checkpoint cadence allows: all remaining epochs
    in ONE dispatch when checkpointing is off, else ``every`` epochs per
    dispatch. Chunking is the TPU-side throughput lever — per-dispatch host
    round-trip latency amortizes over the whole chunk, and the epoch loop
    runs as a ``lax.scan`` entirely on device. The host sync at each chunk boundary doubles as the durability point for the
    checkpoint save (and serializes executions, which the CPU backend's
    subgroup-collective rendezvous requires). Returns
    ``(params, opt_state, loss)``; ``loss`` is ``None`` when no epoch ran.
    """
    from incubator_predictionio_tpu.obs.trace import span

    ckpt, params, opt_state, start_epoch = maybe_resume(
        directory, every, keep, params, opt_state, epochs, mesh,
        factory=factory,
    )
    loss = None
    try:
        e = start_epoch
        while e < epochs:
            if on_chunk is not None:
                # distributed seam: heartbeat + peer/fence check at every
                # chunk boundary (the host-sync point), so a lost member or
                # a stale generation aborts the step instead of hanging the
                # next cross-process collective
                on_chunk(e)
            chunk = min(every, epochs - e) if ckpt is not None else epochs - e
            # one dispatch of the jitted schedule, fenced: the chunk's whole
            # device time lies under this span on the profiler's timeline
            with span("train.epochs.chunk", epoch=e, epochs=chunk):
                params, opt_state, loss = train_epochs(params, opt_state, chunk)
                loss.block_until_ready()
            e += chunk
            if ckpt is not None:
                ckpt.save(e, {"params": params, "opt": opt_state,
                              "epoch": scalar(e)})
    finally:
        if ckpt is not None:
            ckpt.close()
    return params, opt_state, loss


# -- slice-aware coordinated checkpoints ----------------------------------
#
# The distributed training tier checkpoints by SLICE: each mesh member
# writes only the rows it owns, and a step becomes restorable only once a
# commit marker exists — written after every member's slice is durable.
# These helpers are the filesystem protocol (layout, atomicity, retention);
# the member-side driver is distributed/checkpoint.py DistSliceCheckpointer.
#
#   <dir>/slices/step-<s>/member-<m>.npz    one member's owned row blocks
#   <dir>/slices/step-<s>/member-<m>.json   manifest — atomic, written LAST,
#                                           so its presence == slice durable
#   <dir>/slices/commit-<s>.json            commit marker (atomic)
#
# A kill between two members' slice writes leaves step-<s> without a commit
# marker; restore then uses the previous committed step — two histories can
# never compose (tests/test_checkpoint.py pins this).

SLICES_DIR = "slices"


def slice_step_dir(directory: str, step: int) -> str:
    return os.path.join(os.path.abspath(directory), SLICES_DIR, f"step-{int(step)}")


def _commit_path(directory: str, step: int) -> str:
    return os.path.join(os.path.abspath(directory), SLICES_DIR,
                        f"commit-{int(step)}.json")


def save_member_slice(
    directory: str,
    step: int,
    member: int,
    generation: int,
    entries: list[dict],
    arrays: dict[str, np.ndarray],
) -> None:
    """Durably write one member's slice for ``step``.

    ``entries`` describe the payload (one per saved block):
    ``{"key": <npz key>, "leaf": <flat leaf index>, "globalShape": [...],
    "index": [[lo, hi] | None per dim]}`` — ``index`` row-bounds the block
    inside the full leaf; all-``None`` means the member holds the whole
    (replicated) leaf. Data lands first (atomic npz), the manifest last —
    manifest presence is the per-member durability marker the committer
    polls for.
    """
    import io
    import json

    from incubator_predictionio_tpu.utils.fs import atomic_write_bytes

    d = slice_step_dir(directory, step)
    os.makedirs(d, exist_ok=True)
    buf = io.BytesIO()
    np.savez(buf, **{k: np.asarray(v) for k, v in arrays.items()})
    atomic_write_bytes(os.path.join(d, f"member-{int(member)}.npz"),
                       buf.getvalue())
    manifest = {"step": int(step), "member": int(member),
                "generation": int(generation), "entries": entries}
    atomic_write_bytes(os.path.join(d, f"member-{int(member)}.json"),
                       json.dumps(manifest, sort_keys=True).encode("utf-8"))


def read_member_slice(directory: str, step: int, member: int):
    """``(manifest, arrays)`` for one member's durable slice, or ``None``
    when the manifest is absent (slice not finished)."""
    import json

    d = slice_step_dir(directory, step)
    mpath = os.path.join(d, f"member-{int(member)}.json")
    try:
        with open(mpath, "rb") as f:
            manifest = json.loads(f.read().decode("utf-8"))
    except (OSError, ValueError):
        return None
    with np.load(os.path.join(d, f"member-{int(member)}.npz")) as z:
        arrays = {k: z[k] for k in z.files}
    return manifest, arrays


def members_done(directory: str, step: int, members: int, generation: int) -> list[int]:
    """Ranks whose slice for ``(step, generation)`` is durable — the
    committer's poll predicate. A manifest from another generation does NOT
    count: mixing a dead mesh's slice into a new commit is exactly the
    composed-history corruption the marker exists to prevent."""
    import json

    d = slice_step_dir(directory, step)
    done = []
    for m in range(members):
        try:
            with open(os.path.join(d, f"member-{m}.json"), "rb") as f:
                manifest = json.loads(f.read().decode("utf-8"))
        except (OSError, ValueError):
            continue
        if int(manifest.get("generation", -1)) == int(generation):
            done.append(m)
    return done


def write_commit_marker(directory: str, step: int, generation: int,
                        members: int) -> None:
    """The coordinated-commit point: atomic + durable, so restore-side
    visibility of the marker implies every slice it covers is on disk."""
    import json
    import time

    from incubator_predictionio_tpu.utils.fs import atomic_write_bytes

    os.makedirs(os.path.join(os.path.abspath(directory), SLICES_DIR),
                exist_ok=True)
    atomic_write_bytes(_commit_path(directory, step), json.dumps({
        "step": int(step), "generation": int(generation),
        "members": int(members), "committedAt": time.time(),
    }, sort_keys=True).encode("utf-8"))


def read_commit_marker(directory: str, step: int) -> Optional[dict]:
    import json

    try:
        with open(_commit_path(directory, step), "rb") as f:
            return json.loads(f.read().decode("utf-8"))
    except (OSError, ValueError):
        return None


def committed_steps(directory: str) -> list[int]:
    """Steps with a commit marker, ascending — the only restorable steps."""
    d = os.path.join(os.path.abspath(directory), SLICES_DIR)
    try:
        names = os.listdir(d)
    except OSError:
        return []
    out = []
    for name in names:
        if name.startswith("commit-") and name.endswith(".json"):
            try:
                out.append(int(name[len("commit-"):-len(".json")]))
            except ValueError:
                continue
    return sorted(out)


def gc_slice_steps(directory: str, keep: int) -> None:
    """Retention: drop all but the newest ``keep`` committed steps (marker
    first, then the slice dir — a crash between the two leaves an orphan
    dir, which is garbage but never restorable). Uncommitted step dirs
    older than the newest commit (leftovers of a dead generation) go too."""
    import contextlib
    import shutil

    steps = committed_steps(directory)
    if not steps:
        return
    latest = steps[-1]
    for s in steps[:-max(1, keep)] if keep > 0 else []:
        with contextlib.suppress(OSError):
            os.unlink(_commit_path(directory, s))
        shutil.rmtree(slice_step_dir(directory, s), ignore_errors=True)
    base = os.path.join(os.path.abspath(directory), SLICES_DIR)
    kept = set(committed_steps(directory))
    for name in os.listdir(base):
        if not name.startswith("step-"):
            continue
        try:
            s = int(name[len("step-"):])
        except ValueError:
            continue
        if s < latest and s not in kept:
            shutil.rmtree(os.path.join(base, name), ignore_errors=True)


def assemble_committed_step(directory: str, step: int) -> list[np.ndarray]:
    """Reassemble the full flat leaf list of a COMMITTED step from its
    member slices. Every leaf must be fully covered by exactly the slices
    of the commit's generation — partial coverage (a history torn across
    generations could produce it) raises instead of returning frankendata.
    """
    commit = read_commit_marker(directory, step)
    if commit is None:
        raise FileNotFoundError(
            f"step {step} has no commit marker under {directory}")
    generation, members = int(commit["generation"]), int(commit["members"])
    leaves: dict[int, np.ndarray] = {}
    covered: dict[int, list[tuple[int, int]]] = {}
    for m in range(members):
        got = read_member_slice(directory, step, m)
        if got is None:
            raise FileNotFoundError(
                f"committed step {step} is missing member {m}'s slice")
        manifest, arrays = got
        if int(manifest.get("generation", -1)) != generation:
            raise ValueError(
                f"member {m} slice at step {step} is generation "
                f"{manifest.get('generation')} but the commit is {generation}")
        for e in manifest["entries"]:
            leaf = int(e["leaf"])
            block = arrays[e["key"]]
            shape = tuple(e["globalShape"])
            if leaf not in leaves:
                leaves[leaf] = np.zeros(shape, dtype=block.dtype)
                covered[leaf] = []
            index = e.get("index")
            if not index or all(i is None for i in index):
                leaves[leaf][...] = block
                covered[leaf].append((0, shape[0] if shape else 1))
            else:
                lo, hi = int(index[0][0]), int(index[0][1])
                leaves[leaf][lo:hi, ...] = block
                covered[leaf].append((lo, hi))
    out = []
    for leaf in sorted(leaves):
        shape = leaves[leaf].shape
        rows = shape[0] if shape else 1
        spans = sorted(covered[leaf])
        pos = 0
        for lo, hi in spans:
            if lo > pos:
                break
            pos = max(pos, hi)
        if pos < rows:
            raise ValueError(
                f"leaf {leaf} of step {step} only covered to row {pos} of "
                f"{rows} — refusing a partially-assembled restore")
        out.append(leaves[leaf])
    return out


def row_sharding_for(ctx, rows: int, serve_shards: int = 0):
    """The sharding a restored ``[rows, width]`` embedding table should
    land in — deploy restores STRAIGHT into the sharded layout, never
    through a host gather (docs/sharding.md).

    Preference order: the context's ``model`` axis when present and the
    rows divide it; else, when sharded SERVING is engaged
    (``serve_shards > 1``, from ``sharding.serve.serving_shards_for``-style
    decisions) a 1-D serve mesh over the local devices; else replicated.
    """
    from jax.sharding import PartitionSpec

    if "model" in ctx.mesh.shape and rows % ctx.axis_size("model") == 0:
        return ctx.sharding("model", None)
    if serve_shards > 1 and rows % serve_shards == 0:
        from incubator_predictionio_tpu.sharding.serve import (
            SHARD_AXIS,
            _serve_mesh,
        )
        from jax.sharding import NamedSharding

        return NamedSharding(_serve_mesh(serve_shards),
                             PartitionSpec(SHARD_AXIS, None))
    return ctx.replicated()


def restore_placed(ck: TrainCheckpointer, like: Any, mesh) -> Any:
    """Restore the latest step and re-place every leaf for ``mesh``.

    Orbax restores leaves committed to specific devices. Leaves whose template
    carries a ``NamedSharding`` keep it; everything else (optimizer scalar
    counts, host arrays) is replicated over the mesh — a committed
    single-device scalar next to mesh-sharded params is a jit device-mismatch
    error otherwise.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    state = ck.restore(like=like)
    replicated = NamedSharding(mesh, PartitionSpec())

    def put(template, value):
        sh = getattr(template, "sharding", None)
        if isinstance(sh, NamedSharding):
            return jax.device_put(value, sh)
        return jax.device_put(value, replicated)

    return jax.tree.map(put, like, state)
