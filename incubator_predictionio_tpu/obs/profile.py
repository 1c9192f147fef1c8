"""Always-on, low-overhead profiler: the per-phase aggregate every span
feeds, a sampling wall-stack profiler, MFU, and device-memory watermarks
(docs/observability.md "Profiling").

`/metrics` says *how much* and *how slow*; nothing in the repo said *where
the time goes*. ALX (arxiv 2112.02194) attributes TPU matrix-factorization
step time to per-phase buckets (gather/compute/collective) to find its
wins — this module makes that attribution continuous and cheap enough to
leave on in production:

- **Phase aggregate.** The program opens spans through ONE primitive,
  :func:`incubator_predictionio_tpu.obs.trace.span`. On exit a span named
  ``<scope>.<phase>`` (``train.fit.compute``, ``serve.batch.dispatch``)
  adds its duration to that scope and phase here (:func:`record_span`
  keeps a pending sum per name, folded in when the aggregate is read):
  ``pio_profile_phase_seconds_total`` / ``pio_profile_phases_total``,
  ``GET /profile.json``, ``pio-tpu profile``. A span whose own name is a
  scope that already holds phases (``train.verb`` over
  ``train.verb.read`` …) also books the scope's enclosing wall time, so
  the unattributed remainder shows. Callers drop a :func:`fence`
  (``jax.block_until_ready``) at a span's edge so async device work is
  billed to the span that launched it, not whichever blocks next.
  :func:`record_phases` folds durations a caller measured itself
  (``shard.search``, ``stream.fold``) into the same aggregate.
- **Wall-stack sampler.** A daemon thread samples every Python thread's
  stack at ``PIO_PROFILE_HZ`` (default 0 = off; a few Hz is the intended
  always-on rate) and aggregates self-symbolized collapsed stacks — the
  top-N lands in ``GET /profile.json`` and ``pio-tpu profile <url>``. No
  external profiler, no dump files: the aggregation IS the artifact.
- **MFU per training step** (:func:`record_training_step`): the step's
  analytic flops over the chip's peak, folded into a live
  ``pio_training_mfu`` gauge so sustained efficiency is observable on a
  running trainer.
- **Device-memory watermark**: the high-water mark of
  :func:`device_memory_report`'s point read, sampled at exposition time
  and from the sampler thread, on ``pio_device_bytes_peak``.
- :func:`profile_trace` captures an XLA/TPU profiler trace of a block
  (``pio-tpu train --profile-dir DIR``); every thread-scoped span opened
  inside it lies on that timeline as ``pio.<name>``.

Everything here degrades to near-zero cost when idle: no jax import and no
backend creation is ever triggered (device reads happen only in a process
that already holds a backend), the sampler is off by default, and the
aggregate is plain arithmetic.
"""

from __future__ import annotations

import contextlib
import logging
import os
import re
import sys
import threading
from typing import Any, Iterator, Optional

from incubator_predictionio_tpu.obs.metrics import REGISTRY

logger = logging.getLogger(__name__)

#: env knobs (docs/configuration.md "Continuous profiler")
ENV_HZ = "PIO_PROFILE_HZ"
ENV_TOPN = "PIO_PROFILE_TOPN"
DEFAULT_TOPN = 30
#: stack frames kept per sample (leaf-first) — enough to tell call sites
#: apart without unbounded key cardinality
STACK_DEPTH = 8

#: chip peak dense compute (bf16 FLOPs/s per chip): the denominator of the
#: live ``pio_training_mfu`` gauge that ``pio-tpu`` shows an operator, and
#: its only reader. The benchmark's judged numbers are divided by
#: ``benchmarks/peaks.json``, not by this.
TPU_PEAK_FLOPS = [
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]

PHASE_SECONDS = REGISTRY.counter(
    "pio_profile_phase_seconds_total",
    "Wall seconds attributed to each profiler phase bucket within a scope "
    "(gather/compute/collective/h2d/…; docs/observability.md Profiling)",
    labels=("scope", "phase"))
PHASES_TOTAL = REGISTRY.counter(
    "pio_profile_phases_total",
    "Completed profiler phase intervals per scope and phase",
    labels=("scope", "phase"))
SCOPE_SECONDS = REGISTRY.counter(
    "pio_profile_scope_seconds_total",
    "Wall seconds of enclosing profiler scopes (the denominator the phase "
    "buckets must conserve against)", labels=("scope",))
SCOPES_TOTAL = REGISTRY.counter(
    "pio_profile_scopes_total",
    "Completed enclosing profiler scopes (steps/requests/folds)",
    labels=("scope",))
SAMPLES_TOTAL = REGISTRY.counter(
    "pio_profile_samples_total",
    "Stack samples taken by the wall-stack profiler thread "
    "(PIO_PROFILE_HZ)")
MFU_GAUGE = REGISTRY.gauge(
    "pio_training_mfu",
    "Model FLOPs utilization of the most recent training step/run "
    "(analytic flops / wall / chip peak; 0 when no TPU peak is known)")
STEP_SECONDS = REGISTRY.histogram(
    "pio_training_step_seconds",
    "Wall time of training steps/runs reported to the profiler",
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0, 30.0, 60.0, 120.0))
DEVICE_PEAK = REGISTRY.gauge(
    "pio_device_bytes_peak",
    "High-water mark of accelerator memory in use per device (watermark "
    "over device_memory_report point reads)", labels=("device",))


# ---------------------------------------------------------------------------
# phase timers
# ---------------------------------------------------------------------------

_AGG_LOCK = threading.Lock()
#: scope -> {"wall_seconds", "count", "phases": {phase: {"seconds","count"}}}
_AGG: dict[str, dict[str, Any]] = {}


def _scope_entry(scope: str) -> dict[str, Any]:
    entry = _AGG.get(scope)
    if entry is None:
        entry = _AGG[scope] = {"wall_seconds": 0.0, "count": 0, "phases": {}}
    return entry


def _add_phase(bucket: dict[str, Any], phase: str, seconds: float,
               count: int = 1) -> None:
    ph = bucket.get(phase)
    if ph is None:
        ph = bucket[phase] = {"seconds": 0.0, "count": 0}
    ph["seconds"] += seconds
    ph["count"] += count


#: a span feeds the aggregate when its name is dotted lower-case identifiers
#: (``train.fit.compute``); route spans (``POST /queries.json``) and bare
#: names (``forward``) are spans like any other and no phase of anything
_PHASE_NAME = re.compile(r"[a-z0-9_]+(\.[a-z0-9_]+)+\Z")

#: span name -> [seconds, count] since the last fold: all a finished span
#: costs here is one short lock and two additions; :func:`_fold_spans` moves
#: the sums into the aggregate and the counter families when either is read
_PENDING_LOCK = threading.Lock()
_PENDING: dict[str, list] = {}


def record_span(name: str, seconds: float) -> None:
    """One finished span (called by ``obs/trace`` at every span's exit)."""
    with _PENDING_LOCK:
        acc = _PENDING.get(name)
        if acc is None:
            acc = _PENDING[name] = [0.0, 0]
        acc[0] += seconds
        acc[1] += 1


def _fold_spans() -> None:
    """Move what the spans accumulated into the aggregate: ``<scope>.<phase>``
    adds to that scope's phase bucket; a name that IS a scope with phases
    (``train.verb`` over ``train.verb.read`` …) also books the scope's
    enclosing wall. Runs when the aggregate is read: ``phase_snapshot`` and,
    as a registry collector, every ``/metrics`` exposition."""
    global _PENDING
    with _PENDING_LOCK:
        pending, _PENDING = _PENDING, {}
    named = {n: acc for n, acc in pending.items() if _PHASE_NAME.match(n)}
    with _AGG_LOCK:
        for name, (seconds, count) in named.items():
            scope, _, phase = name.rpartition(".")
            _add_phase(_scope_entry(scope)["phases"], phase,
                       max(0.0, seconds), count)
        walls = {n: acc for n, acc in named.items() if n in _AGG}
        for name, (seconds, count) in walls.items():
            _AGG[name]["wall_seconds"] += max(0.0, seconds)
            _AGG[name]["count"] += count
    for name, (seconds, count) in named.items():
        scope, _, phase = name.rpartition(".")
        PHASE_SECONDS.labels(scope=scope, phase=phase).inc(max(0.0, seconds))
        PHASES_TOTAL.labels(scope=scope, phase=phase).inc(count)
    for name, (seconds, count) in walls.items():
        SCOPE_SECONDS.labels(scope=name).inc(max(0.0, seconds))
        SCOPES_TOTAL.labels(scope=name).inc(count)


REGISTRY.add_collector("profile_spans", _fold_spans)


def record_phases(scope: str, phases: dict[str, float],
                  wall_seconds: Optional[float] = None) -> None:
    """Fold phase durations the caller measured itself into the aggregate
    the spans feed — for pipelines that keep their own per-phase timers
    (``shard.search``'s per-shard threads, ``stream.fold``).
    ``wall_seconds`` defaults to the phase sum (a fully attributed step)."""
    wall = sum(phases.values()) if wall_seconds is None else wall_seconds
    with _AGG_LOCK:
        entry = _scope_entry(scope)
        entry["wall_seconds"] += max(0.0, wall)
        entry["count"] += 1
        for phase, dt in phases.items():
            _add_phase(entry["phases"], phase, max(0.0, dt))
    SCOPE_SECONDS.labels(scope=scope).inc(max(0.0, wall))
    SCOPES_TOTAL.labels(scope=scope).inc()
    for phase, dt in phases.items():
        PHASE_SECONDS.labels(scope=scope, phase=phase).inc(max(0.0, dt))
        PHASES_TOTAL.labels(scope=scope, phase=phase).inc()


def fence(*values: Any) -> None:
    """``jax.block_until_ready`` on each value — the phase-edge fence that
    pins async device work to the launching phase. A no-op when jax was
    never imported (host-only paths share the instrumentation), and
    tolerant of plain host values (block_until_ready passes them through)."""
    if "jax" not in sys.modules:
        return
    import jax

    for v in values:
        if v is not None:
            jax.block_until_ready(v)


def phase_snapshot() -> dict[str, dict[str, Any]]:
    """Deep copy of the per-scope phase aggregates (``/profile.json``).
    A scope no enclosing span or :func:`record_phases` call has timed
    (``count`` 0) reports the sum of its phases as its wall."""
    _fold_spans()
    with _AGG_LOCK:
        return {
            scope: {
                "wall_seconds": e["wall_seconds"] if e["count"] else sum(
                    ph["seconds"] for ph in e["phases"].values()),
                "count": e["count"],
                "phases": {p: dict(ph) for p, ph in e["phases"].items()},
            }
            for scope, e in _AGG.items()
        }


def reset_phases() -> None:
    """Test hook: drop the in-process aggregates (registry families are
    reset separately via ``REGISTRY.reset()``)."""
    global _PENDING
    with _PENDING_LOCK:
        _PENDING = {}
    with _AGG_LOCK:
        _AGG.clear()


# ---------------------------------------------------------------------------
# MFU + device-memory watermark
# ---------------------------------------------------------------------------

_peak_cache: list = []  # [float | None] once detected


def _backend_live() -> bool:
    """Whether THIS process already created a JAX backend. A TPU chip belongs
    to one process, and processes that merely import jax-using modules (the
    stream updater, the jobs worker) must not have a ``/metrics`` scrape be
    what first claims it — so device reads key on this, never on "jax was
    imported"."""
    mesh = sys.modules.get("incubator_predictionio_tpu.parallel.mesh")
    return mesh is not None and mesh.backend_initialized()


def peak_flops_for(platform: str, device_kind: str) -> Optional[float]:
    """Peak bf16 FLOPs/s from :data:`TPU_PEAK_FLOPS`, or ``None`` for a
    device the table does not know — there is no default chip (an MFU
    against a guessed peak is a made-up number)."""
    if platform != "tpu":
        return None
    kind = device_kind.lower()
    return next((f for key, f in TPU_PEAK_FLOPS if key in kind), None)


def detected_peak_flops() -> Optional[float]:
    """Peak bf16 FLOPs/s of local device 0. ``None`` off-TPU (a CPU 'MFU'
    would be a lie), on a TPU kind the table does not list (logged once),
    and while this process holds no backend. Cached after the first read."""
    if _peak_cache:
        return _peak_cache[0]
    if not _backend_live():
        return None
    import jax

    d = jax.local_devices()[0]
    peak = peak_flops_for(d.platform, d.device_kind)
    if peak is None and d.platform == "tpu":
        logger.warning(
            "no peak FLOP/s known for device kind %r: pio_training_mfu "
            "stays unset (add it to obs/profile.TPU_PEAK_FLOPS)",
            d.device_kind)
    _peak_cache.append(peak)
    return peak


def record_training_step(flops: float, seconds: float,
                         peak_flops: Optional[float] = None,
                         ) -> Optional[float]:
    """Report one training step/run: observes the step-time histogram and,
    when a chip peak is known (or injected), sets ``pio_training_mfu``.
    Returns the MFU or None."""
    if seconds <= 0:
        return None
    STEP_SECONDS.observe(seconds)
    peak = peak_flops if peak_flops is not None else detected_peak_flops()
    if not peak:
        return None
    mfu = flops / seconds / peak
    MFU_GAUGE.set(mfu)
    return mfu


def device_memory_report() -> list[dict[str, Any]]:
    """One row per local device: platform + allocator stats when available
    (``pio-tpu status``; platforms without allocator stats — CPU — report
    ``None`` values)."""
    import jax

    rows: list[dict[str, Any]] = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — CPU/older backends have no stats
            stats = {}
        rows.append({
            "device": str(d),
            "platform": d.platform,
            "bytes_in_use": stats.get("bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return rows


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace of the enclosed block into ``log_dir``
    (TensorBoard 'profile' plugin layout): device timelines, HLO cost
    breakdowns, and every thread-scoped ``obs/trace.span`` opened inside the
    block as a ``pio.<name>`` host event."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def update_device_watermark() -> None:
    """Fold each local device's current/peak bytes-in-use into the
    ``pio_device_bytes_peak`` watermark gauges. Never imports jax and never
    creates a backend itself; never raises (runs as a collector and inside
    the sampler thread)."""
    if not _backend_live():
        return
    try:
        for row in device_memory_report():
            seen = row.get("peak_bytes_in_use")
            if seen is None:
                seen = row.get("bytes_in_use")
            if seen is None:
                continue
            g = DEVICE_PEAK.labels(device=row["device"])
            if seen > g.value:
                g.set(seen)
    except Exception:  # noqa: BLE001 - diagnostics must not break /metrics
        logger.debug("device watermark sample failed", exc_info=True)


REGISTRY.add_collector("profile_watermark", update_device_watermark)


# ---------------------------------------------------------------------------
# sampling wall-stack profiler
# ---------------------------------------------------------------------------

def _short_path(path: str) -> str:
    parts = path.replace("\\", "/").split("/")
    return "/".join(parts[-2:]) if len(parts) > 2 else path


def _collapse(frame, depth: int = STACK_DEPTH) -> tuple[str, ...]:
    """Leaf-first collapsed stack for one thread's current frame."""
    out: list[str] = []
    f = frame
    while f is not None and len(out) < depth:
        code = f.f_code
        out.append(f"{code.co_name} ({_short_path(code.co_filename)}:"
                   f"{f.f_lineno})")
        f = f.f_back
    return tuple(out)


class StackSampler:
    """Daemon thread sampling every Python thread's stack at ``hz``.

    Aggregation is in-process (collapsed stack -> count), so the profiler
    has no output files and no post-processing step: :meth:`top` is the
    deliverable. ``sample_once`` is callable directly with a fake
    ``frames`` mapping so tests exercise collapse/aggregation without
    timing."""

    def __init__(self, hz: float, topn: int = DEFAULT_TOPN,
                 depth: int = STACK_DEPTH):
        self.hz = float(hz)
        self.topn = topn
        self.depth = depth
        self.interval = 1.0 / max(0.001, self.hz)
        self.samples = 0
        self._counts: dict[tuple[str, ...], int] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="pio-profile-sampler")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        # watermark ride-along at ~1 Hz regardless of the sampling rate
        wm_every = max(1, round(self.hz))
        tick = 0
        while not self._stop.wait(self.interval):
            self.sample_once()
            tick += 1
            if tick % wm_every == 0:
                update_device_watermark()

    def sample_once(self, frames: Optional[dict] = None) -> None:
        if frames is None:
            frames = sys._current_frames()
        me = threading.get_ident()
        with self._lock:
            for tid, frame in frames.items():
                if tid == me:
                    continue  # never profile the profiler
                key = _collapse(frame, self.depth)
                if key:
                    self._counts[key] = self._counts.get(key, 0) + 1
            self.samples += 1
        SAMPLES_TOTAL.inc()

    def top(self, n: Optional[int] = None) -> list[dict[str, Any]]:
        """Top-N collapsed stacks by sample count, with share of all
        attributed samples."""
        with self._lock:
            items = sorted(self._counts.items(), key=lambda kv: -kv[1])
            total = sum(self._counts.values())
            samples = self.samples
        n = self.topn if n is None else n
        return [{
            "stack": list(stack),
            "samples": count,
            "pct": round(100.0 * count / total, 2) if total else 0.0,
            "of_samples": samples,
        } for stack, count in items[:n]]

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=timeout)


# ---------------------------------------------------------------------------
# process-wide wiring
# ---------------------------------------------------------------------------

_STATE_LOCK = threading.Lock()
_SAMPLER: Optional[StackSampler] = None
_SERVICE = "proc"


def _float_env(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("ignoring non-numeric %s=%r", name, raw)
        return default


def configure_profiler_from_env(service: str) -> Optional[StackSampler]:
    """Apply PIO_PROFILE_* to this process: start (or stop) the wall-stack
    sampler. Phase timers and the watermark collector are always on — only
    the sampler thread is gated. Idempotent; last call wins; returns the
    active sampler (None when off)."""
    global _SAMPLER, _SERVICE
    with _STATE_LOCK:
        _SERVICE = service
        if _SAMPLER is not None:
            _SAMPLER.stop()
            _SAMPLER = None
        hz = _float_env(ENV_HZ, 0.0)
        if hz <= 0:
            return None
        _SAMPLER = StackSampler(
            hz, topn=int(_float_env(ENV_TOPN, DEFAULT_TOPN)))
        _SAMPLER.start()
        logger.info("%s: wall-stack profiler on at %.3g Hz", service, hz)
        return _SAMPLER


def active_sampler() -> Optional[StackSampler]:
    return _SAMPLER


def close_profiler() -> None:
    """Stop the sampler thread (tests, bench lanes, shutdown)."""
    global _SAMPLER
    with _STATE_LOCK:
        if _SAMPLER is not None:
            _SAMPLER.stop()
            _SAMPLER = None


def profile_payload() -> dict[str, Any]:
    """The ``GET /profile.json`` document: phase aggregates, sampler top-N,
    training MFU, and device watermarks."""
    update_device_watermark()
    sampler = _SAMPLER
    return {
        "service": _SERVICE,
        "phases": phase_snapshot(),
        "sampler": None if sampler is None else {
            "hz": sampler.hz,
            "samples": sampler.samples,
            "top": sampler.top(),
        },
        "training": {
            "mfu": MFU_GAUGE.value,
            "peak_flops": _peak_cache[0] if _peak_cache else None,
        },
        "deviceWatermark": {
            "|".join(key): child.value
            for key, child in DEVICE_PEAK.children()
        },
    }


__all__ = [
    "ENV_HZ", "ENV_TOPN", "TPU_PEAK_FLOPS", "StackSampler",
    "record_span", "record_phases", "fence",
    "phase_snapshot", "reset_phases",
    "record_training_step", "detected_peak_flops",
    "device_memory_report", "profile_trace", "update_device_watermark",
    "configure_profiler_from_env", "active_sampler", "close_profiler",
    "profile_payload",
]
