"""What every runner shares: cell resolution from data files, the chip claim,
the compile counter, the profiler window and the contract's result line.

Nothing here knows a configuration, a traffic mix or a metric by name: a cell
is ``BENCHMARK.json`` + ``configs/<config>.json`` + ``traffic/<mix>.json`` +
(optionally) ``cells/<cell>.json``; the traffic file's ``kind`` names the
module under ``runners/`` and each per-layer metric names its reader under
``layer_metrics/``. Adding any of them is adding files.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import json
import math
import os
import shutil
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


class HarnessError(Exception):
    """A cell that cannot be resolved or run as written; never a result."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<mix>.json merged with cells/<cell>.json
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list
    root: str

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, root: str = ROOT) -> Cell:
    """``<config>.<mix>`` → its files. A cell file may override only the keys
    the traffic file lists under ``per_cell`` (rates and limits found once, on
    the chip, for that pair)."""
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise HarnessError(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise HarnessError(f"workload {name!r} names no listed config")
    bdir = os.path.join(root, "benchmarks")
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    tpath = os.path.join(bdir, "traffic", entry["traffic"] + ".json")
    if not os.path.exists(tpath):
        raise HarnessError(f"no traffic file {tpath}")
    traffic = _load_json(tpath)
    cpath = os.path.join(bdir, "cells", name + ".json")
    if os.path.exists(cpath):
        allowed = set(traffic.get("per_cell", ()))
        for k, v in _load_json(cpath).items():
            if k == "notes":
                continue
            if k not in allowed:
                raise HarnessError(
                    f"{cpath}: key {k!r} is not one the traffic file lets a "
                    f"cell set ({sorted(allowed)})")
            traffic[k] = v
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        traffic_name=entry["traffic"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def check_keys(what: str, got: dict, known: set) -> None:
    """A runner that meets a key it does not know fails, never ignores it."""
    unknown = sorted(set(got) - set(known))
    if unknown:
        raise HarnessError(
            f"{what}: unknown key(s) {unknown}; known: {sorted(known)}")


def load_runner(kind: str):
    try:
        return importlib.import_module(f"benchmarks.runners.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmarks.runners.{kind}":
            raise
        raise HarnessError(f"traffic kind {kind!r} has no runner module") from e


def load_reader(metric_name: str):
    """``layer_metrics/<name>.py`` with dots and dashes as underscores."""
    mod = metric_name.replace(".", "_").replace("-", "_")
    try:
        return importlib.import_module(f"benchmarks.layer_metrics.{mod}").read
    except ModuleNotFoundError as e:
        if e.name != f"benchmarks.layer_metrics.{mod}":
            raise
        raise HarnessError(
            f"per-layer metric {metric_name!r} has no reader "
            f"benchmarks/layer_metrics/{mod}.py") from e


def read_layer_metrics(cell: Cell, evidence: dict) -> dict:
    """Each reader takes what the run gathered (spans, counters, the reduced
    trace) and returns a number, or None where it found nothing to read."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(evidence)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the chip -----------------------------------------------------------------

def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    peaks = _load_json(os.path.join(root, "benchmarks", "peaks.json"))
    if device_kind not in peaks:
        raise HarnessError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"({sorted(peaks)}); add its published peaks with their source")
    return peaks[device_kind]


def claim_chip(chips: int) -> list:
    """The devices of this machine, or an error: never a CPU run. Sets
    ``JAX_PLATFORMS=tpu`` before jax starts, so a machine without a chip fails
    in jax's own start-up."""
    os.environ["JAX_PLATFORMS"] = "tpu"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise HarnessError(f"no accelerator: jax reports {devices[0].platform}")
    if len(devices) < chips:
        raise HarnessError(
            f"the cell asks for {chips} chip(s), jax finds {len(devices)}")
    load_peaks(devices[0].device_kind)
    return devices


def configure_jax_cache() -> None:
    """Every program of a run goes to the persistent cache the program itself
    places (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), the
    sub-second ones too: each run is a new process, and set-up is most of what
    a check costs."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_report(devices, memory: "MemoryWatch") -> dict:
    """The contract's ``device`` object. ``memory_peak_bytes`` is the most the
    allocator held on the fullest chip at ONE instant of the run, set-up
    included. Beside it (the driver ignores them): the same inside the
    measured window alone, and the live buffers' share of that, so that a
    cell which meets the size floor only by a transient of its set-up, or by
    what the runtime reserves for its programs' temporaries, says so."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory.peak()),
            "memory_window_bytes": int(memory.win["held"]),
            "memory_window_live_bytes": int(memory.win["in_use"])}


def held_bytes(stats: dict) -> int:
    """What one chip's allocator holds now: live buffers plus what the
    runtime has reserved for the temporaries of the programs it is running.
    The TPU runtime counts the two apart (``bytes_in_use`` leaves out a
    program's scratch; PERF.md section 6, PR 23). Both are read in one call,
    so the sum is of one instant, never of two separate peaks."""
    return int(stats.get("bytes_in_use", 0)) + int(stats.get("bytes_reserved", 0))


class MemoryWatch:
    """Samples every chip's ``memory_stats()`` from a thread, a few times a
    second, from the chip claim to the end of the run."""

    def __init__(self, devices, period_s: float = 0.2):
        self.devices, self.period_s = list(devices), period_s
        zero = {"held": 0, "in_use": 0, "reserved": 0}
        self.run, self.win = dict(zero), dict(zero)
        self._in_window = False
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def sample(self) -> None:
        for d in self.devices:
            st = d.memory_stats() or {}
            now = {"held": held_bytes(st),
                   "in_use": int(st.get("bytes_in_use", 0)),
                   "reserved": int(st.get("bytes_reserved", 0))}
            for acc in (self.run, self.win) if self._in_window else (self.run,):
                for k, v in now.items():
                    acc[k] = max(acc[k], v)

    def _loop(self) -> None:
        while not self._stop.wait(self.period_s):
            self.sample()

    def window(self, inside: bool) -> None:
        """The measured window opens or closes: both edges are sampled."""
        if inside:
            self._in_window = True
        self.sample()
        self._in_window = inside

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        self.sample()

    def peak(self) -> int:
        """Never under the allocator's own peak of live buffers, which no
        sample can miss."""
        own = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in self.devices)
        return max(self.run["held"], own)


class CompileCounter:
    """Counts XLA backend compilations (jax.monitoring), so a run can show
    that none happened inside its measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1


# -- work directory, profiler window -------------------------------------------

def work_dir(cell: Cell) -> str:
    """``benchmarks/_work/<cell>`` inside the checkout, emptied at the start
    of every run (fixed path: nothing here is part of a cache key)."""
    d = os.path.join(cell.root, "benchmarks", "_work", cell.name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


class ProfilerWindow:
    """A jax.profiler trace of (part of) the measured window. Host python
    frames are off: only TraceAnnotation spans and the device planes are
    wanted, and the tracer shares the host's cores with the system."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.started = None
        self.window_s = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.started = time.perf_counter()

    def stop(self) -> str:
        import jax

        self.window_s = time.perf_counter() - self.started
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise HarnessError(f"the profiler wrote no trace under {self.log_dir}")
        return found[-1]


@contextlib.contextmanager
def span(name: str):
    """A benchmark-side host span on the profiler's own clock."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


# -- the result line ------------------------------------------------------------

def result_line(cell: Cell, trace: bool, correct: bool, attempted: int,
                failed: int, e2e: dict, layer: dict, device: dict,
                breakdown: dict | None = None) -> str:
    """The contract's last line: end-to-end metrics without a trace,
    per-layer metrics with one."""
    if trace:
        metrics = layer
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise HarnessError(
                    f"runner reported no {m['name']} for {cell.name}")
            metrics[m["name"]] = {
                "value": float(e2e[m["name"]]), "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace and breakdown:
        line["breakdown"] = breakdown
    return json.dumps(line)
