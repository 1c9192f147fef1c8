"""MeshContext — the execution context handed to every DASE stage.

This is the TPU-native replacement for the reference's ``SparkContext``
(created in workflow/WorkflowContext.scala:29-47 and threaded through every
stage signature, core/BaseDataSource.scala:43, BaseAlgorithm.scala:69):
instead of an RDD factory it owns a ``jax.sharding.Mesh`` over the local (or
multi-host) device topology plus the sharding helpers stages use to lay data
and parameters out across it.

Axis convention (the "How to Scale Your Model" recipe):

- ``data``  — batch-dimension data parallelism (DP); gradients psum over it.
- ``model`` — tensor/model parallelism (TP); embedding tables and wide matmuls
  shard over it.

Extra axes (``seq`` for context parallelism, ``expert`` for MoE) can be added
per engine via ``axes=...``. All collectives ride XLA (psum/all_gather/
ppermute) over ICI — there is no NCCL/MPI analogue to manage.

Multi-host: call :meth:`MeshContext.create` with ``distributed=True`` after
`jax.distributed.initialize`; the mesh then spans all processes' devices and
per-host input feeding goes through :meth:`make_global_array`.
"""

from __future__ import annotations

import contextlib
import glob
import logging
import math
import os
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


#: Off-TPU rehearsal switch: ``1`` runs the Pallas kernels under the Pallas
#: interpreter where a TPU would compile them (docs/configuration.md).
PALLAS_INTERPRET_ENV = "PIO_PALLAS_INTERPRET"


def backend_initialized() -> bool:
    """True once this process has created a JAX backend — from then on it
    holds its devices (a TPU chip belongs to ONE process at a time). jax has
    no public peek that doesn't itself initialize the backends."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def default_compilation_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the parent of the package directory. The
    path is part of the cache key, so it is the same from every working
    directory and holds no pid, time or tempdir."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def configure_compilation_cache() -> Optional[str]:
    """Place JAX's persistent compile cache; returns the directory set in
    code, or None when ``JAX_COMPILATION_CACHE_DIR`` already placed it from
    outside (then nothing is set here). Called at the seam every device verb
    passes (:meth:`MeshContext.create`), so train, deploy and redeploy share
    executables across processes."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None  # JAX reads it itself
    path = default_compilation_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def kernel_backend() -> Optional[str]:
    """How this process runs the package's Pallas kernels — the ONE device
    test behind every kernel-or-reference choice (ops/, models/, serving/).

    - ``"mosaic"``: the default backend is a TPU; kernels compile for it.
    - ``"interpret"``: no TPU, ``PIO_PALLAS_INTERPRET=1``: the same kernels
      under the Pallas interpreter (CPU rehearsal of the device path).
    - ``None``: no TPU; callers run the kernel's jnp reference.

    Initializes the backend, so only code that is about to dispatch device
    work may call it."""
    if jax.default_backend() == "tpu":
        return "mosaic"
    if os.environ.get(PALLAS_INTERPRET_ENV) == "1":
        return "interpret"
    return None


def claim_devices() -> list:
    """``jax.devices()`` with the one-process-per-chip rule spelled out: when
    the platform cannot be initialized (no chip, or another process — a live
    ``pio-tpu deploy``, a trainer — holds it) the error says who holds it and
    what the operator's options are, instead of a bare backend traceback."""
    try:
        return jax.devices()
    except RuntimeError as e:
        raise RuntimeError(
            f"cannot claim the accelerator: {e}\n"
            f"{_chip_holders()}"
            "A TPU chip belongs to one process at a time: stop the holder, "
            "give this verb its own chip, or run it on the host with an "
            "explicit JAX_PLATFORMS=cpu.") from e


def local_tpu_chips() -> list[str]:
    """Device files of the TPU chips attached to this host, found WITHOUT
    initializing a backend (a launcher that did would itself hold the chips
    its children need). ``/dev/accel*`` on older TPU VMs,
    ``/dev/vfio/<group>`` from v5e on."""
    return sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))


def _chip_holders() -> str:
    """Other processes holding a local chip's device file open (``/proc``
    scan; best effort — empty when nothing is visible)."""
    chips = set(local_tpu_chips())
    holders = []
    me = os.getpid()
    for pid in os.listdir("/proc") if chips else ():
        if not pid.isdigit() or int(pid) == me:
            continue
        try:
            held = any(
                os.readlink(f"/proc/{pid}/fd/{fd}") in chips
                for fd in os.listdir(f"/proc/{pid}/fd"))
            if held:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(
                        "utf-8", "replace").strip()
                holders.append(f"  pid {pid}: {cmd[:160]}")
        except OSError:
            continue
    if not holders:
        return ""
    return "held by:\n" + "\n".join(holders) + "\n"


def init_distributed_from_env() -> None:
    """Join (or form) a multi-process job — the spark-submit replacement.

    Coordinator/topology comes from ``PIO_DIST_COORDINATOR`` /
    ``PIO_DIST_NUM_PROCESSES`` / ``PIO_DIST_PROCESS_ID`` (set per process by
    :mod:`incubator_predictionio_tpu.parallel.launcher` or by the operator's
    per-host launch script); absent those, ``jax.distributed.initialize()``
    auto-detects the topology on TPU pods. CPU meshes get gloo cross-process
    collectives — the CI/test stand-in for ICI/DCN.
    """
    if jax.distributed.is_initialized():
        return
    if (os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
            and not backend_initialized()):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    coordinator = os.environ.get("PIO_DIST_COORDINATOR")
    if coordinator:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(os.environ["PIO_DIST_NUM_PROCESSES"]),
            process_id=int(os.environ["PIO_DIST_PROCESS_ID"]),
        )
    else:  # pragma: no cover - needs a real pod environment
        jax.distributed.initialize()


@dataclass(frozen=True)
class MeshConf:
    """Serializable mesh request — stored on EngineInstance rows the way the
    reference stores ``sparkConf`` (EngineInstances.scala:44)."""

    axes: dict[str, int] | None = None  # e.g. {"data": 4, "model": 2}; None = all data
    distributed: bool = False

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "MeshConf":
        return MeshConf(axes=d.get("axes"), distributed=bool(d.get("distributed", False)))

    def to_dict(self) -> dict[str, Any]:
        return {"axes": self.axes, "distributed": self.distributed}


class MeshContext:
    """Device mesh + sharding helpers; one per workflow run.

    Stages receive this as ``ctx`` (where the reference passes ``sc``).
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    # -- construction -----------------------------------------------------
    @staticmethod
    def create(
        axes: Optional[dict[str, int]] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        distributed: bool = False,
    ) -> "MeshContext":
        """Build a mesh over the available devices.

        ``axes`` maps axis name → size; one axis may be -1 (inferred). Default
        is a single ``data`` axis over every device. Axis sizes must multiply
        to the device count — mismatches raise rather than silently dropping
        devices.
        """
        configure_compilation_cache()
        if distributed:
            init_distributed_from_env()
        devs = list(devices if devices is not None else claim_devices())
        if not axes:
            axes = {"data": len(devs)}
        names = list(axes.keys())
        sizes = list(axes.values())
        if sizes.count(-1) > 1:
            raise ValueError("at most one mesh axis may be -1")
        if -1 in sizes:
            known = math.prod(s for s in sizes if s != -1)
            if len(devs) % known:
                raise ValueError(
                    f"cannot infer -1 axis: {len(devs)} devices not divisible by {known}"
                )
            sizes[sizes.index(-1)] = len(devs) // known
        if math.prod(sizes) != len(devs):
            raise ValueError(
                f"mesh axes {dict(zip(names, sizes))} need {math.prod(sizes)} devices, "
                f"have {len(devs)}"
            )
        dev_array = np.array(devs).reshape(sizes)
        mesh = Mesh(dev_array, axis_names=names)
        logger.info("mesh: %s over %d %s devices (%s)",
                    dict(zip(names, sizes)), len(devs), devs[0].platform,
                    devs[0].device_kind)
        return MeshContext(mesh)

    @staticmethod
    def from_conf(conf: MeshConf | dict[str, Any] | None) -> "MeshContext":
        if conf is None:
            return MeshContext.create()
        if isinstance(conf, dict):
            conf = MeshConf.from_dict(conf)
        return MeshContext.create(axes=conf.axes, distributed=conf.distributed)

    # -- topology ---------------------------------------------------------
    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def n_devices(self) -> int:
        return self.mesh.size

    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    def axis_size_or(self, name: str, default: int = 1) -> int:
        """Axis size, or ``default`` when the mesh lacks the axis — how
        optional-axis consumers (the sharded-table layout's ``model``
        axis) ask without a membership check at every call site."""
        return dict(self.mesh.shape).get(name, default)

    @property
    def data_axis(self) -> str:
        """The batch-parallel axis (first axis by convention)."""
        return "data" if "data" in self.mesh.shape else self.mesh.axis_names[0]

    @property
    def is_primary(self) -> bool:
        """True on the process that owns storage writes (process 0; always
        True single-process) — the 'Spark driver' role in a multi-host job."""
        return jax.process_index() == 0

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def process_index(self) -> int:
        return jax.process_index()

    # -- sharding helpers -------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def put(self, a, *spec):
        """Place a host array onto the mesh with PartitionSpec ``spec``.

        Single-process this is ``device_put``; multi-process it builds a
        global ``jax.Array`` from each process's copy of the full host array
        (``make_array_from_callback`` hands every addressable shard its
        global slice), so the same staging code runs on a laptop mesh and a
        pod."""
        a = np.asarray(a)
        sh = self.sharding(*spec)
        if jax.process_count() == 1:
            return jax.device_put(a, sh)
        return jax.make_array_from_callback(  # pragma: no cover - multiproc
            a.shape, sh, lambda idx: a[idx]
        )

    def replicate(self, tree):
        """Place a pytree replicated on every device."""
        if jax.process_count() == 1:
            return jax.device_put(tree, self.replicated())
        return jax.tree.map(  # pragma: no cover - multiproc
            lambda x: self.put(x), tree
        )

    def host_gather(self, tree):
        """Global device arrays → host numpy on every process (collective
        when the tree spans processes; one batched device_get otherwise —
        per-leaf np.asarray costs one device round trip PER LEAF)."""
        if jax.process_count() == 1:
            return jax.device_get(tree)
        from jax.experimental import multihost_utils  # pragma: no cover

        return multihost_utils.process_allgather(  # pragma: no cover
            tree, tiled=True
        )

    def shard_batch(self, tree, axis_name: Optional[str] = None):
        """Shard leading (batch) dim over the data axis; pads are the caller's
        job — batch size must divide the axis size."""
        axis = axis_name or self.data_axis
        sh = self.sharding(axis)

        def put(x):
            x = np.asarray(x)
            if x.shape[0] % self.axis_size(axis):
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by mesh axis "
                    f"{axis}={self.axis_size(axis)}"
                )
            return jax.device_put(x, sh)

        return jax.tree.map(put, tree)

    def pad_to_batch_multiple(self, n: int) -> int:
        """Smallest multiple of the data-axis size ≥ n (static-shape friend)."""
        k = self.axis_size(self.data_axis)
        return ((n + k - 1) // k) * k

    def make_global_array(self, local_data: np.ndarray, spec: P):
        """Multi-host input feeding (jax.make_array_from_process_local_data)."""
        return jax.make_array_from_process_local_data(
            self.sharding(*spec), local_data
        )

    def put_local_batches(self, tree, axis: Optional[str] = None):
        """Per-process staged batches → one global array per leaf.

        Each leaf is ``[n_batches, B_local, ...]`` holding ONLY this
        process's rows; the result is the global ``[n_batches, B, ...]``
        array sharded over the data axis on dim 1 (B = B_local × processes).
        This is the bounded-memory alternative to :meth:`put`'s
        full-copy-per-process staging: host RSS per process is data/P.
        """
        axis = axis or self.data_axis

        def put(x):
            x = np.asarray(x)
            sh = self.sharding(None, axis)
            if jax.process_count() == 1:
                return jax.device_put(x, sh)
            return self.make_global_array(x, P(None, axis))

        return jax.tree.map(put, tree)

    def allgather_obj(self, obj: Any) -> list[Any]:
        """All-gather a small picklable host object across processes —
        the metadata exchange primitive (vocab union, row counts) of the
        sharded input path. Single-process returns ``[obj]``. Two rounds of
        ``process_allgather`` (lengths, then padded payloads) because
        payloads differ per process."""
        import pickle

        if jax.process_count() == 1:
            return [obj]
        from jax.experimental import multihost_utils

        payload = np.frombuffer(pickle.dumps(obj), np.uint8)
        lens = np.asarray(multihost_utils.process_allgather(
            np.asarray([len(payload)], np.int64))).reshape(-1)
        padded = np.zeros(int(lens.max()), np.uint8)
        padded[: len(payload)] = payload
        gathered = np.asarray(multihost_utils.process_allgather(padded))
        gathered = gathered.reshape(jax.process_count(), -1)
        return [
            pickle.loads(gathered[i, : int(lens[i])].tobytes())
            for i in range(jax.process_count())
        ]

    @contextlib.contextmanager
    def activate(self):
        """``with ctx.activate():`` — make the mesh current for shard_map /
        implicit-sharding code regions."""
        with self.mesh:
            yield self

    def stop(self) -> None:
        """Release the context (parity with sc.stop(); devices are
        process-owned in JAX so this is a no-op hook for plugins)."""

    def __repr__(self) -> str:  # pragma: no cover
        return f"MeshContext({dict(self.mesh.shape)})"
