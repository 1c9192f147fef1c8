"""End-to-end request tracing: contextvar-scoped trace/span IDs, a ring
buffer of recent spans served as JSON, and ``X-PIO-Trace`` header propagation.

A *trace* is one logical request; a *span* is one timed operation inside it
(an HTTP route, one storage-RPC attempt, a batch dispatch). The current
span's identity rides a :mod:`contextvars` variable, so it composes with the
resilience layer's ``deadline_scope`` (both are ambient, both survive
``contextvars.copy_context()`` hops into worker threads) and it crosses
process boundaries via the ``X-PIO-Trace: <trace_id>:<span_id>`` header —
the ``remote`` storage transport injects it on every attempt, the storage
server's telemetry middleware adopts it, so a query-server → storage-server
call is ONE trace across both span logs.

Every finished span lands in :data:`TRACES`, a bounded ring the servers
serve at ``GET /traces.json`` — the flight-recorder view an operator reads
after a latency blip, without having deployed a tracing backend first.

The ring is process-local and evicts under load; the durable half of the
trace plane lives in :mod:`.spool` (finished spans appended to a CRC-framed
on-disk spool) and :mod:`.collect` (cross-process assembly). This module
additionally owns the *sampling* identity: the process at the edge of a
request (the fleet router, or the first server a client hits) mints a
head-based keep/drop decision when it roots a trace, and the decision rides
the ``X-PIO-Trace`` header as a ``:s=0|1`` suffix so every downstream hop
agrees. Tail-based keep rules (error spans, slow spans) are applied by the
export hook regardless of the head decision (docs/observability.md).

:func:`span` is the ONE way the program opens a span. Besides the ring and
the exporter, every finished span feeds the per-phase aggregate in
:mod:`.profile` (a span named ``<scope>.<phase>`` is a row of
``pio_profile_phase_seconds_total``) and, when jax is loaded and the body
stays on one thread, lies on the jax profiler's timeline as
``pio.<name>`` — the same clock as the device's ``XLA Ops`` line.
"""

from __future__ import annotations

import contextvars
import random
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from incubator_predictionio_tpu.obs import profile as _profile

#: Propagation header: ``<trace_id>:<span_id>[:s=0|1]`` (ids are 16 hex
#: chars; the optional third field is the head sampling decision — peers
#: that predate it simply ignore extra ``:``-separated fields).
TRACE_HEADER = "X-PIO-Trace"


class SpanContext:
    """The ambient identity: which trace we are in, which span is current,
    and whether the trace's head sampling decision said *keep*."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled


class Span:
    """One timed operation, and the context manager that times it:
    ``with span(name, **attrs) as sp`` opens it as a child of the current
    context (or the root of a fresh trace), makes it current for the block —
    while open it is the ambient identity of its children, with the three
    fields of a :class:`SpanContext` — and records it on exit: into the
    ring, into the aggregate (``<scope>.<phase>`` →
    ``pio_profile_phase_seconds_total``) and to the exporter. Mutable while
    open (attrs, status). An escaping exception marks
    ``status="error:<Type>"`` and re-raises.

    ``thread_scoped`` (default): the body runs on one thread with no
    ``await`` inside, so the span also lies on the jax profiler's timeline
    as ``pio.<name>``, beside the device's operations, whenever a profiler
    session is running. Pass ``False`` for a span that crosses an ``await``
    — the profiler's annotations are per thread, and interleaved coroutines
    would nest wrongly.

    ``start`` (a ``time.perf_counter()`` reading): the interval began
    before the block could be entered — on another task, across an
    ``await`` — so the recorded span starts there; the annotation on the
    timeline still covers the block alone."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "service",
                 "start_unix", "duration", "status", "attrs", "sampled",
                 "_t0", "_buffer", "_thread_scoped", "_token", "_ann")

    def __init__(self, name: str, service: Optional[str] = None,
                 buffer: Optional["TraceBuffer"] = None,
                 thread_scoped: bool = True, start: Optional[float] = None,
                 **attrs: Any):
        self.name = name
        self.service = service
        self.attrs = attrs
        self.duration = 0.0
        self.status = "ok"
        self._buffer = buffer
        self._thread_scoped = thread_scoped
        self._t0 = start
        self._ann = None

    def _begin(self, parent: Optional[Any]) -> None:
        """Identity and clocks, under ``parent`` or as a fresh trace's root
        (which mints the trace's head sampling decision)."""
        if parent is not None:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
            self.sampled = parent.sampled
        else:
            self.trace_id = _new_id()
            self.parent_id = None
            self.sampled = _mint_sampled()
        self.span_id = _new_id()
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now
        self.start_unix = time.time() - (now - self._t0)

    def __enter__(self) -> "Span":
        self._begin(_CURRENT.get())
        self._token = _CURRENT.set(self)
        if self._thread_scoped:
            cls = _TRACE_ANNOTATION or _trace_annotation()
            if cls is not None and cls.is_enabled():
                self._ann = cls("pio." + self.name)
                self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        # the body may have already classified the outcome (the telemetry
        # middleware downgrades raised 4xx HTTPExceptions to a non-error
        # terminal status before they propagate) — respect it
        if exc_type is not None and self.status == "ok":
            self.status = f"error:{exc_type.__name__}"
        _CURRENT.reset(self._token)
        self._token = None
        _finish(self)

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    @property
    def context(self) -> "Span":
        """This span as the ambient identity of its children."""
        return self

    def to_dict(self) -> dict[str, Any]:
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "name": self.name,
            "service": self.service,
            "startUnix": self.start_unix,
            "durationSec": self.duration,
            "status": self.status,
            "sampled": self.sampled,
            "attrs": dict(self.attrs),
        }


#: ``obs/trace.span`` is the ONE way the program opens a span
span = Span


#: the ambient identity: a :class:`SpanContext` adopted from a header, or
#: the open :class:`Span` itself (same three fields)
_CURRENT: contextvars.ContextVar[Optional[Any]] = \
    contextvars.ContextVar("pio_trace_context", default=None)


_ID_RNG = random.Random()  # seeded from os.urandom, per process


def _new_id() -> str:
    # 64 random bits fill the header's 16 hex chars at 0.3 µs an id
    return "%016x" % _ID_RNG.getrandbits(64)


# -- sampling + export configuration ----------------------------------------
# Process-wide, set once at boot (obs/spool.py configure_export_from_env) or
# explicitly by tests. ``None`` rate means "not configured": every root is
# sampled, matching the pre-sampling behaviour bit for bit.

_SAMPLE_RATE: Optional[float] = None
_SLOW_SEC: Optional[float] = None
_EXPORTER: Optional[Callable[[Span], None]] = None
_SAMPLE_RNG = random.Random()


def set_sampling(rate: Optional[float] = None,
                 slow_ms: Optional[float] = None) -> None:
    """Install the head sampling rate (0..1; None = keep everything) and the
    tail slow-span threshold in milliseconds (None = no slow rule)."""
    global _SAMPLE_RATE, _SLOW_SEC
    _SAMPLE_RATE = None if rate is None else min(1.0, max(0.0, float(rate)))
    _SLOW_SEC = None if slow_ms is None else float(slow_ms) / 1e3


def sampling() -> tuple[Optional[float], Optional[float]]:
    """(rate, slow_sec) as currently configured."""
    return _SAMPLE_RATE, _SLOW_SEC


def set_exporter(fn: Optional[Callable[[Span], None]]) -> None:
    """Install (or clear) the finished-span export hook. The hook runs on
    whatever thread finished the span and MUST NOT raise — a broken export
    sink must never fail the request that produced the span."""
    global _EXPORTER
    _EXPORTER = fn


def export_enabled() -> bool:
    return _EXPORTER is not None


def _mint_sampled() -> bool:
    """The head-based decision, minted exactly once per trace — at the
    process that roots it (the edge)."""
    if _SAMPLE_RATE is None or _SAMPLE_RATE >= 1.0:
        return True
    if _SAMPLE_RATE <= 0.0:
        return False
    return _SAMPLE_RNG.random() < _SAMPLE_RATE


def keep_reason(sampled: bool, status: str, duration_sec: float,
                slow_sec: Optional[float]) -> Optional[str]:
    """Why a finished span should reach the durable spool, or None to drop.

    Tail rules outrank the head decision: ``error:*`` spans and spans over
    the slow threshold are ALWAYS kept, so 1% head sampling still captures
    100% of the interesting traces. Non-error terminal statuses (e.g. the
    middleware's ``http401`` for orderly raised 4xx) follow the head
    decision — a client hammering bad credentials must not flood the spool.
    Pure — the FakeClock-style tail-sampling tests drive it with synthetic
    durations, zero wall sleeps."""
    if status.startswith("error"):
        return "error"
    if slow_sec is not None and duration_sec >= slow_sec:
        return "slow"
    return "head" if sampled else None


def current_context() -> Optional[SpanContext]:
    return _CURRENT.get()


def current_trace_id() -> Optional[str]:
    ctx = _CURRENT.get()
    return ctx.trace_id if ctx is not None else None


class TraceBuffer:
    """Bounded ring of finished spans, grouped on demand by trace id."""

    def __init__(self, capacity: int = 2048):
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=capacity)

    def add(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)

    def spans(self, trace_id: Optional[str] = None) -> list[dict]:
        with self._lock:
            snap = list(self._spans)
        return [s.to_dict() for s in snap
                if trace_id is None or s.trace_id == trace_id]

    def traces(self, limit: int = 50) -> list[dict]:
        """Recent traces, newest first: one entry per trace id with its span
        tree flattened (spans in start order).

        Every span carries ``"selfSec"`` (:func:`self_seconds`): where a
        request's time went is the spans with the largest self time.

        Each entry carries ``"complete"``: the root span is present AND no
        span's ``parentId`` dangles. A trace whose older spans were evicted
        by the ring looks exactly like a short trace otherwise — the flag is
        what keeps a partial trace from being read as a whole one."""
        if limit <= 0:  # order[-limit:] would invert the meaning
            return []
        with self._lock:
            snap = list(self._spans)
        by_trace: dict[str, list[Span]] = {}
        order: list[str] = []
        for s in snap:
            if s.trace_id not in by_trace:
                by_trace[s.trace_id] = []
                order.append(s.trace_id)
            by_trace[s.trace_id].append(s)
        out = []
        for tid in reversed(order[-limit:]):
            spans = sorted(by_trace[tid], key=lambda s: s.start_unix)
            ids = {s.span_id for s in spans}
            has_root = any(s.parent_id is None for s in spans)
            dangling = any(s.parent_id is not None and s.parent_id not in ids
                           for s in spans)
            dicts = [s.to_dict() for s in spans]
            own = self_seconds(dicts)
            for d in dicts:
                d["selfSec"] = own[d["spanId"]]
            out.append({
                "traceId": tid,
                "spanCount": len(spans),
                "durationSec": max((s.duration for s in spans), default=0.0),
                "complete": has_root and not dangling,
                "spans": dicts,
            })
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


#: Process-wide flight recorder, served at ``GET /traces.json``.
TRACES = TraceBuffer()


def self_seconds(spans: list[dict]) -> dict[str, float]:
    """``{spanId: seconds}`` of each span's own time: its duration minus
    what its direct children cover of it (the union of their intervals, so
    children that overlap — coalesced requests, concurrent attempts — are
    not subtracted twice). Takes ``Span.to_dict()`` rows of one trace."""
    kids: dict[str, list[tuple[float, float]]] = {}
    for s in spans:
        if s["parentId"] is not None:
            kids.setdefault(s["parentId"], []).append(
                (s["startUnix"], s["startUnix"] + s["durationSec"]))
    out = {}
    for s in spans:
        lo, hi = s["startUnix"], s["startUnix"] + s["durationSec"]
        covered, cur = 0.0, lo
        for a, b in sorted(kids.get(s["spanId"], ())):
            a, b = max(a, cur), min(b, hi)
            if b > a:
                covered += b - a
                cur = b
        out[s["spanId"]] = max(0.0, s["durationSec"] - covered)
    return out


def context_of(ctx: contextvars.Context) -> Optional[SpanContext]:
    """The span identity a captured ``contextvars.Context`` carries (the
    micro-batcher keeps each request's context beside its queue entry)."""
    return ctx.get(_CURRENT)


_TRACE_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation once jax is up


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or None while jax is not imported
    (this module never imports it)."""
    global _TRACE_ANNOTATION
    jax = sys.modules.get("jax")
    cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    if cls is not None:
        _TRACE_ANNOTATION = cls
    return cls


def _finish(sp: Span, export: bool = True) -> None:
    """Every finished span, however it was timed: ring, aggregate, export."""
    (sp._buffer or TRACES).add(sp)
    _profile.record_span(sp.name, sp.duration)
    exporter = _EXPORTER
    if export and exporter is not None:
        exporter(sp)


def record_span(name: str, start: float, duration: float,
                context: Optional[Any] = None,
                service: Optional[str] = None,
                buffer: Optional[TraceBuffer] = None, export: bool = True,
                **attrs: Any) -> Span:
    """Record a span whose interval was timed by the caller: ``start`` is a
    ``time.perf_counter()`` reading, ``duration`` seconds. For intervals no
    ``with`` block can hold — they cross an ``await`` or begin on another
    task (a request's wait in the batcher's queue). A child of ``context``
    (default: the current one); lands in the ring, the aggregate and
    (``export``) the exporter, never on the profiler's timeline."""
    sp = Span(name, service, buffer, False, start, **attrs)
    sp._begin(context if context is not None else _CURRENT.get())
    sp.duration = max(0.0, duration)
    _finish(sp, export)
    return sp


class Track:
    """Back-to-back intervals of one state machine that no ``with`` block
    can hold: both ends of each lie on ONE thread (the event loop's) but in
    different tasks, outside every other span's block. ``switch(phase)``
    closes the open interval, recording it as the span ``<scope>.<phase>``
    (ring and aggregate, through :func:`record_span`), and opens the next;
    ``switch(None)`` only closes. The intervals are sibling roots of one
    trace of the track's own: they are nobody's request, and they never
    reach the exporter (every long interval of a quiet server would pass
    the spool's slow rule).

    A phase listed in ``timeline`` also lies on the jax profiler's timeline
    as ``pio.<scope>.<phase>``: a bare annotation held open between the two
    switches, entered only while a profiler session runs. An interval that
    was already open when the session started has no annotation and is
    simply missing from that capture. The ambient context is never touched
    (a :class:`Span` resets a contextvar token on exit, which another
    task's context would refuse)."""

    __slots__ = ("_scope", "_timeline", "_ctx", "phase", "_t0", "_ann")

    def __init__(self, scope: str, timeline: tuple = ()):
        self._scope = scope
        self._timeline = frozenset(timeline)
        # (no span id: the intervals are roots, not children of one)
        self._ctx = SpanContext(_new_id(), None, False)
        #: the open interval's phase, None before the first switch and
        #: after ``switch(None)`` (read only)
        self.phase: Optional[str] = None
        self._t0 = 0.0
        self._ann = None

    def switch(self, phase: Optional[str]) -> None:
        now = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.phase is not None:
            record_span(f"{self._scope}.{self.phase}", self._t0,
                        now - self._t0, context=self._ctx, export=False)
        self.phase, self._t0 = phase, now
        if phase in self._timeline:
            cls = _TRACE_ANNOTATION or _trace_annotation()
            if cls is not None and cls.is_enabled():
                self._ann = cls(f"pio.{self._scope}.{phase}")
                self._ann.__enter__()


class trace_scope:
    """Force the ambient context for a block — how a server middleware adopts
    a remote parent parsed from ``X-PIO-Trace`` (``ctx=None`` is a no-op, not
    a reset: spans below still start a fresh trace naturally)."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[Any]):
        self._ctx = ctx

    def __enter__(self) -> None:
        self._token = None if self._ctx is None else _CURRENT.set(self._ctx)

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._token is not None:
            _CURRENT.reset(self._token)


# -- header propagation -----------------------------------------------------

def header_value() -> Optional[str]:
    """The outbound ``X-PIO-Trace`` value for the current context, or None
    when no trace is active (callers simply omit the header). Carries the
    head sampling decision as ``:s=0|1`` — peers that predate the flag only
    read the first two ``:`` fields and ignore the rest."""
    ctx = _CURRENT.get()
    if ctx is None:
        return None
    return f"{ctx.trace_id}:{ctx.span_id}:s={1 if ctx.sampled else 0}"


def parse_header(value: Optional[str]) -> Optional[SpanContext]:
    """``<trace_id>:<span_id>[:s=0|1]`` (or bare ``<trace_id>``) →
    SpanContext. Malformed values are ignored — a bad header must never
    fail a request. An absent/unparseable ``s=`` flag means *sampled*: a
    header from an old peer keeps today's keep-everything behaviour."""
    if not value:
        return None

    def ok(s: str) -> bool:
        # ASCII-only: isalnum() alone admits non-ASCII "alphanumerics" that
        # http.client cannot latin-1-encode when the id is re-injected into
        # outbound headers — a crafted header must never fail a request
        return 0 < len(s) <= 64 and s.isascii() and s.isalnum()

    parts = value.strip().split(":")
    tid = parts[0]
    if not ok(tid):
        return None
    sid = parts[1] if len(parts) > 1 and parts[1] else tid
    if not ok(sid):
        return None
    sampled = True
    for extra in parts[2:]:
        if extra == "s=0":
            sampled = False
        elif extra == "s=1":
            sampled = True
        # anything else: a future field this version doesn't know — ignore
    return SpanContext(tid, sid, sampled)


def inject(headers) -> None:
    """Set ``X-PIO-Trace`` on a mutable mapping when a trace is active."""
    v = header_value()
    if v is not None:
        headers[TRACE_HEADER] = v
