"""Multi-process launcher — the ``Runner.runOnSpark`` counterpart.

The reference scales out by forking ``spark-submit`` with a serialized env
(tools/Runner.scala:185-335); here scale-out is N identical processes running
the SAME CLI verb under ``jax.distributed``, with XLA collectives over
ICI/DCN doing what Spark's shuffle/RPC did. This module is the process
spawner for the single-host/multi-process form — the integration-test
stand-in for a pod, using CPU devices + gloo (on a TPU host one process
drives all local chips through the mesh instead); on a real multi-host pod the
operator runs one ``pio-tpu <verb> --distributed`` per host and
``jax.distributed.initialize`` auto-detects the topology, so no launcher
process is needed at all.

Each spawned process gets:

- ``PIO_DIST_COORDINATOR``  — host:port of process 0's coordinator service;
- ``PIO_DIST_NUM_PROCESSES`` / ``PIO_DIST_PROCESS_ID`` — the job topology;

consumed by :func:`incubator_predictionio_tpu.parallel.mesh.
init_distributed_from_env` when the verb builds its MeshContext with
``distributed=True``. Storage writes happen only on process 0
(``MeshContext.is_primary``), mirroring the reference's single Spark driver.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from dataclasses import dataclass
from typing import Optional, Sequence

from incubator_predictionio_tpu.parallel.mesh import local_tpu_chips

CLI_MODULE = "incubator_predictionio_tpu.tools.cli"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def refuse_local_tpu_fanout(
    num_processes: int,
    cpu_devices_per_process: Optional[int],
    env: Optional[dict[str, str]] = None,
) -> None:
    """Raise when N local processes would contend for this host's chips.

    A TPU chip belongs to one process at a time and the children get no
    per-process chip assignment, so each would try to claim every local
    chip: all but one die on the libtpu lock and the survivor waits for
    peers that never join. On a TPU host ONE process drives all local chips
    through the mesh; N local processes are the CPU rehearsal topology."""
    if num_processes <= 1 or cpu_devices_per_process:
        return
    if {**os.environ, **(env or {})}.get(
            "JAX_PLATFORMS", "").startswith("cpu"):
        return
    chips = local_tpu_chips()
    if chips:
        raise RuntimeError(
            f"refusing to start {num_processes} local processes on a TPU "
            f"host ({len(chips)} chip(s): {', '.join(chips)}): a chip "
            "belongs to one process at a time and every child would try to "
            "claim all of them. Drive the local chips from ONE process "
            "through the mesh (pio-tpu train --mesh-axes "
            "'{\"data\": 2, \"model\": 2}'), or pass "
            "--cpu-devices-per-process for a CPU rehearsal; a multi-host "
            "pod runs one `pio-tpu <verb> --distributed` per host.")


@dataclass
class LaunchResult:
    returncodes: list[int]
    outputs: list[str]  # combined stdout+stderr per process
    timed_out: bool = False  # deadline hit; unfinished processes got rc=124

    @property
    def ok(self) -> bool:
        return not self.timed_out and all(rc == 0 for rc in self.returncodes)


def launch_local(
    cli_args: Sequence[str],
    num_processes: int,
    coordinator_port: Optional[int] = None,
    cpu_devices_per_process: Optional[int] = None,
    env: Optional[dict[str, str]] = None,
    timeout: Optional[float] = None,
    command: Optional[Sequence[str]] = None,
) -> LaunchResult:
    """Run ``pio-tpu <cli_args>`` as ``num_processes`` coordinated processes.

    ``cpu_devices_per_process`` forces a CPU mesh with that many virtual
    devices per process (the no-hardware test topology). Without it, on a
    host with TPU chips, more than one process is refused
    (:func:`refuse_local_tpu_fanout`).
    Processes run concurrently and are all waited on; output is captured
    per process. ``command`` replaces the default ``python -m <cli>`` argv
    entirely (same coordination env) — used by harness dry runs that execute
    an inline script instead of a CLI verb.
    """
    import tempfile
    import time

    if num_processes < 1:
        raise ValueError("num_processes must be >= 1")
    refuse_local_tpu_fanout(num_processes, cpu_devices_per_process, env)
    port = coordinator_port or free_port()
    procs: list[subprocess.Popen] = []
    # capture into temp files, not pipes: a child blocked on a full 64KB
    # pipe blocks its collectives, which stalls every coordinated peer —
    # a deadlock no sequential drain order can avoid
    logs = [tempfile.TemporaryFile(mode="w+") for _ in range(num_processes)]
    for pid in range(num_processes):
        penv = dict(os.environ)
        if env:
            penv.update(env)
        penv["PIO_DIST_COORDINATOR"] = f"127.0.0.1:{port}"
        penv["PIO_DIST_NUM_PROCESSES"] = str(num_processes)
        penv["PIO_DIST_PROCESS_ID"] = str(pid)
        if cpu_devices_per_process:
            penv["JAX_PLATFORMS"] = "cpu"
            flags = penv.get("XLA_FLAGS", "")
            flags = " ".join(
                f for f in flags.split()
                if "xla_force_host_platform_device_count" not in f
            )
            penv["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cpu_devices_per_process}"
            ).strip()
        procs.append(subprocess.Popen(
            list(command) if command is not None
            else [sys.executable, "-m", CLI_MODULE, *cli_args],
            env=penv,
            stdout=logs[pid],
            stderr=subprocess.STDOUT,
            text=True,
        ))
    deadline = None if timeout is None else time.monotonic() + timeout
    returncodes: list[int] = []
    timed_out = False
    try:
        for p in procs:
            remaining = None if deadline is None else deadline - time.monotonic()
            try:
                if remaining is not None and remaining <= 0:
                    raise subprocess.TimeoutExpired(p.args, timeout or 0)
                returncodes.append(p.wait(timeout=remaining))
            except subprocess.TimeoutExpired:
                # Kill the whole job but return normally: the captured logs
                # are the evidence of WHICH peer wedged — raising would
                # discard them.
                timed_out = True
                killed = set()
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                        q.wait()
                        killed.add(id(q))
                returncodes = [
                    124 if id(q) in killed else q.returncode for q in procs
                ]
                break
    finally:
        outputs = []
        for f in logs:
            f.seek(0)
            outputs.append(f.read())
            f.close()
    return LaunchResult(returncodes, outputs, timed_out=timed_out)
