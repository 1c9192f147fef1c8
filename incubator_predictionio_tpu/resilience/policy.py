"""Retry/deadline policy engine — ONE failure-handling vocabulary for every
network backend (postgres, elasticsearch, s3, webhdfs, remote) and the
serving layer.

The pieces:

- :class:`Deadline` — a point in (injected-clock) time; propagated from the
  serving layer to storage calls via :func:`deadline_scope` so a query's
  remaining budget caps every per-attempt socket timeout beneath it.
- :class:`RetryPolicy` — exponential backoff with deterministic (seedable)
  jitter, per-attempt cap, total-deadline awareness.
- :class:`ResiliencePolicy` — retry + breaker + clock glued together behind
  one ``call(fn, idempotent=...)``. Transports raise :class:`TransientError`
  for retry-worthy failures; anything else passes straight through without
  touching the breaker (a 404 is not a backend outage).

Idempotency discipline (the heart of the retry classification): only calls
declared idempotent are ever re-sent — a write whose response was lost may
have committed, so re-sending would double-apply. Non-idempotent calls get
exactly one attempt; their transient failures still count against the
breaker (the backend IS failing), they just aren't retried automatically.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import os
import random
from typing import Any, Callable, Optional

from incubator_predictionio_tpu.data.storage.base import StorageError
from incubator_predictionio_tpu.obs import trace as _trace
from incubator_predictionio_tpu.obs.metrics import REGISTRY
from incubator_predictionio_tpu.resilience.breaker import (
    BREAKERS,
    BreakerRegistry,
    CircuitBreaker,
    CircuitOpenError,
)
from incubator_predictionio_tpu.resilience.clock import SYSTEM_CLOCK, Clock

_ATTEMPTS = REGISTRY.counter(
    "pio_resilience_attempts_total",
    "Policy-routed call attempts by operation", labels=("op",))
_RETRIES = REGISTRY.counter(
    "pio_resilience_retries_total",
    "Retries (second and later attempts) by operation", labels=("op",))
_DEADLINE_EXPIRED = REGISTRY.counter(
    "pio_deadline_expired_total",
    "Calls abandoned because their time budget ran out", labels=("op",))


class TransientError(StorageError):
    """A failure worth retrying (connection reset, timeout, 5xx): transports
    wrap their raw socket/HTTP errors in this so the policy engine never has
    to know each library's exception hierarchy.

    ``no_retry = True`` on a subclass marks a condition that is transient
    *cluster-wise* but can never improve by retrying THIS endpoint (an
    epoch-fenced write on a deposed replica): the policy fails it fast so
    a higher layer — the multi-endpoint transport's failover, the event
    server's spill — can act instead of burning the retry budget in
    place."""

    no_retry = False


#: HTTP statuses that signal a transient service condition (throttle or
#: gateway/overload) for EVERY HTTP-speaking backend. Backends whose 500s
#: are usually infrastructure (S3 InternalError, HDFS standby failover) use
#: :data:`TRANSIENT_HTTP_CODES_WITH_500`; Elasticsearch deliberately does
#: not (its 500s are usually real request bugs).
TRANSIENT_HTTP_CODES = frozenset({429, 502, 503, 504})
TRANSIENT_HTTP_CODES_WITH_500 = TRANSIENT_HTTP_CODES | {500}


class DeadlineExceeded(StorageError):
    """The call's time budget ran out (before, between, or instead of
    further attempts)."""


class ServingUnavailable(StorageError):
    """Every algorithm of a deployed engine is unavailable (breaker-open or
    failed) — the serving layer should degrade, not 500."""


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

class Deadline:
    """An absolute expiry on an injected clock. ``expires_at=None`` means
    unbounded (the common no-deadline case costs one comparison)."""

    __slots__ = ("expires_at", "clock")

    def __init__(self, expires_at: Optional[float],
                 clock: Clock = SYSTEM_CLOCK):
        self.expires_at = expires_at
        self.clock = clock

    @classmethod
    def after(cls, seconds: Optional[float],
              clock: Clock = SYSTEM_CLOCK) -> "Deadline":
        if seconds is None:
            return cls(None, clock)
        return cls(clock.monotonic() + seconds, clock)

    def remaining(self) -> Optional[float]:
        if self.expires_at is None:
            return None
        return max(0.0, self.expires_at - self.clock.monotonic())

    def expired(self) -> bool:
        return self.expires_at is not None and \
            self.clock.monotonic() >= self.expires_at

    def attempt_timeout(self, default: float) -> float:
        """Per-attempt socket timeout: the configured default, capped by
        what's left of the budget (never zero — sockets treat 0 as
        non-blocking)."""
        rem = self.remaining()
        if rem is None:
            return default
        return max(0.001, min(default, rem))

    def tightened(self, seconds: Optional[float]) -> "Deadline":
        """The earlier of this deadline and ``now + seconds``."""
        if seconds is None:
            return self
        candidate = self.clock.monotonic() + seconds
        if self.expires_at is None or candidate < self.expires_at:
            return Deadline(candidate, self.clock)
        return self


_AMBIENT: contextvars.ContextVar[Optional[Deadline]] = contextvars.ContextVar(
    "pio_resilience_deadline", default=None)


def current_deadline() -> Optional[Deadline]:
    """The ambient deadline set by an enclosing :func:`deadline_scope`."""
    return _AMBIENT.get()


@contextlib.contextmanager
def deadline_scope(seconds: Optional[float], clock: Clock = SYSTEM_CLOCK):
    """Bound every policy-routed call in this context by ``seconds``. Nested
    scopes tighten (the effective deadline is the earliest)."""
    outer = _AMBIENT.get()
    if outer is not None:
        scoped = outer.tightened(seconds)
    else:
        scoped = Deadline.after(seconds, clock)
    token = _AMBIENT.set(scoped)
    try:
        yield scoped
    finally:
        _AMBIENT.reset(token)


def run_with_deadline(seconds: Optional[float], fn: Callable[..., Any],
                      *args: Any) -> Any:
    """Run ``fn(*args)`` under a deadline scope — the executor-thread form
    (``loop.run_in_executor`` does not copy contextvars, so the serving
    layer wraps its worker calls in this to propagate the budget)."""
    with deadline_scope(seconds):
        return fn(*args)


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RetryPolicy:
    max_attempts: int = 3
    base_delay: float = 0.05      # first backoff
    max_delay: float = 2.0        # per-sleep cap
    multiplier: float = 2.0       # exponential growth
    jitter: float = 0.2           # ± fraction of the delay
    total_deadline: Optional[float] = None  # per-call budget (seconds)
    seed: Optional[int] = None    # deterministic jitter for tests

    def delay(self, attempt: int, rng: random.Random) -> float:
        """Backoff before retry number ``attempt`` (1-based count of
        failures so far)."""
        d = min(self.max_delay,
                self.base_delay * (self.multiplier ** (attempt - 1)))
        if self.jitter:
            d *= 1.0 + self.jitter * (2.0 * rng.random() - 1.0)
        return max(0.0, d)


class ResiliencePolicy:
    """Retry + breaker + deadline, applied to one callable at a time.

    ``fn`` receives the effective :class:`Deadline` so transports can derive
    per-attempt socket timeouts from the remaining budget.
    """

    #: below this remaining budget an attempt is a guaranteed timeout —
    #: raise DeadlineExceeded instead of charging the backend's breaker
    #: with a failure it never had a chance to avoid
    MIN_ATTEMPT_BUDGET = 0.005

    def __init__(self, retry: Optional[RetryPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 clock: Clock = SYSTEM_CLOCK):
        self.retry = retry or RetryPolicy()
        self.breaker = breaker
        self.clock = clock
        self._rng = random.Random(self.retry.seed)

    def call(self, fn: Callable[[Deadline], Any], *,
             idempotent: bool = True, op: str = "") -> Any:
        deadline = Deadline.after(self.retry.total_deadline, self.clock)
        ambient = current_deadline()
        if ambient is not None and (
                deadline.expires_at is None
                or (ambient.expires_at is not None
                    and ambient.expires_at < deadline.expires_at)):
            # the ambient scope carries its own clock — honor it so a test's
            # FakeClock deadline isn't judged by the system clock
            deadline = ambient
        if self.breaker is not None and not self.breaker.allow():
            raise CircuitOpenError(self.breaker.name,
                                   self.breaker.retry_after())
        attempts = 0
        opname = op or "call"
        while True:
            rem = deadline.remaining()
            if rem is not None and rem < self.MIN_ATTEMPT_BUDGET:
                # expired — or so little budget left that an attempt would
                # be a guaranteed socket timeout: failing here must not
                # charge the breaker (the backend was never really tried)
                if attempts == 0 and self.breaker is not None:
                    # hand back the admitted half-open probe instead of
                    # wedging the breaker
                    self.breaker.release_probe()
                _DEADLINE_EXPIRED.labels(op=opname).inc()
                raise DeadlineExceeded(
                    f"{op or 'call'}: deadline exceeded "
                    f"after {attempts} attempt(s)")
            attempts += 1
            _ATTEMPTS.labels(op=opname).inc()
            if attempts > 1:
                _RETRIES.labels(op=opname).inc()
            try:
                # one span per attempt: retries and half-open probes show up
                # individually under the caller's ambient trace, and the
                # transport injects X-PIO-Trace per attempt with THIS span as
                # the parent — the cross-process stitch point
                with _trace.span(opname, kind="attempt", attempt=attempts):
                    result = fn(deadline)
            except TransientError as e:
                if self.breaker is not None:
                    self.breaker.record_failure()
                if e.no_retry or not idempotent \
                        or attempts >= self.retry.max_attempts:
                    raise
                pause = self.retry.delay(attempts, self._rng)
                rem = deadline.remaining()
                if rem is not None and pause >= rem:
                    _DEADLINE_EXPIRED.labels(op=opname).inc()
                    raise DeadlineExceeded(
                        f"{op or 'call'}: retry budget exhausted after "
                        f"{attempts} attempt(s)") from e
                self.clock.sleep(pause)
            except Exception:
                # a non-transient error IS a completed round trip (the
                # backend answered — 404s and validation errors are the
                # caller's problem, not an outage): the breaker must see it
                # as health, or a half-open probe ending in a semantic
                # error would leak its slot and wedge the breaker
                if self.breaker is not None:
                    self.breaker.record_success()
                raise
            else:
                if self.breaker is not None:
                    self.breaker.record_success()
                return result


# ---------------------------------------------------------------------------
# configuration surface
# ---------------------------------------------------------------------------

#: (config key, RetryPolicy field, parser)
_RETRY_KEYS = (
    ("RETRY_MAX_ATTEMPTS", "max_attempts", int),
    ("RETRY_BASE_DELAY", "base_delay", float),
    ("RETRY_MAX_DELAY", "max_delay", float),
    ("RETRY_MULTIPLIER", "multiplier", float),
    ("RETRY_JITTER", "jitter", float),
    ("TOTAL_DEADLINE", "total_deadline", float),
    ("RETRY_SEED", "seed", int),
)


def _lookup(key: str, config: Optional[dict]) -> Optional[str]:
    """Per-source config key first (PIO_STORAGE_SOURCES_<NAME>_<KEY>), then
    the process-wide PIO_RESILIENCE_<KEY> env default."""
    if config is not None and key in config:
        return config[key]
    return os.environ.get(f"PIO_RESILIENCE_{key}")


def policy_from_config(name: str, config: Optional[dict[str, str]] = None, *,
                       clock: Clock = SYSTEM_CLOCK,
                       registry: Optional[BreakerRegistry] = BREAKERS,
                       ) -> ResiliencePolicy:
    """Build the shared policy for one backend instance.

    ``name`` keys the breaker in the registry (so ``/health`` reports it);
    per-source config keys override ``PIO_RESILIENCE_*`` env defaults which
    override the dataclass defaults. ``BREAKER_THRESHOLD=0`` disables the
    breaker for that backend.
    """
    retry = RetryPolicy()
    for key, field, parse in _RETRY_KEYS:
        raw = _lookup(key, config)
        if raw is not None:
            try:
                setattr(retry, field, parse(raw))
            except ValueError:
                raise StorageError(
                    f"invalid resilience setting {key}={raw!r} for {name}")
    retry.max_attempts = max(1, retry.max_attempts)

    def _num(key: str, default: float) -> float:
        raw = _lookup(key, config)
        try:
            return float(raw) if raw is not None else default
        except ValueError:
            raise StorageError(
                f"invalid resilience setting {key}={raw!r} for {name}")

    threshold = int(_num("BREAKER_THRESHOLD", 5))
    breaker = None
    if threshold > 0:
        kwargs = dict(failure_threshold=threshold,
                      reset_timeout=_num("BREAKER_RESET", 30.0),
                      clock=clock)
        if registry is not None:
            breaker = registry.get_or_create(name, **kwargs)
        else:
            breaker = CircuitBreaker(name, **kwargs)
    return ResiliencePolicy(retry=retry, breaker=breaker, clock=clock)
