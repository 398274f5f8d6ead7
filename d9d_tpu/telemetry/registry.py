"""Process-local runtime-metric primitives: counters, gauges, fixed-bin
histograms, and a monotonic span timeline.

The reference d9d design treats metric collection as a first-class loop
component; this package is its always-on runtime half — cheap enough to
stay enabled in production (a span costs two ``perf_counter`` calls, one
bisect, and a deque append; no jax import anywhere in the package). The
profiler traces (``core/tracing.py`` + ``JobProfiler``) remain the
capture-window microscope; this registry is the continuous signal an
operator watches and alerts on between captures.

Everything here is plain host Python. Device work is NEVER synchronized
to take a measurement — instrumented components time their *host*
interactions (dispatch, readback, staging, IO waits) and derive device
signals from values that were already coming back to the host anyway
(loss fetches, serving token readbacks).

Thread safety: one lock guards the instrument maps and the span
timeline (prefetch producers, checkpoint IO threads, and the main loop
share the registry); individual instrument updates ride the GIL —
telemetry tolerates a lost increment under contention, a lock per
``record`` would not be low-overhead.
"""

import bisect
import collections
import dataclasses
import math
import threading
import time
from typing import Any, Callable, Iterable

__all__ = [
    "SCHEMA_VERSION",
    "Counter",
    "Gauge",
    "Histogram",
    "Span",
    "MetricRegistry",
    "PhaseTimeline",
    "exp_edges",
]

# JSONL event-log schema version (docs/design/observability.md) — bump on
# any breaking change to event shapes emitted by sinks.JsonlSink.
# v2: adds the ``executable`` event kind (per-executable compile/HBM/FLOPs
# records from telemetry/introspect.py); v1 files remain readable.
# v3: adds the ``request_trace`` event kind (per-request serving
# milestones keyed by a fleet-stable trace id); v1/v2 files remain
# readable.
# v4: adds the ``numerics`` event kind (per-layer training tensor
# statistics windows from telemetry/numerics.py); v1-v3 files remain
# readable.
# v5: adds the ``host_stacks`` event kind (folded controller-thread
# stack samples from telemetry/host_sampler.py, one event per capture
# window); v1-v4 files remain readable.
SCHEMA_VERSION = 5


def exp_edges(lo: float, hi: float, bins: int) -> tuple[float, ...]:
    """``bins + 1`` log-spaced edges from ``lo`` to ``hi`` — the default
    shape for latency histograms (latencies span decades; linear bins
    waste resolution where it matters)."""
    if lo <= 0 or hi <= lo or bins < 1:
        raise ValueError(f"need 0 < lo < hi and bins >= 1, got {lo}, {hi}, {bins}")
    ratio = (hi / lo) ** (1.0 / bins)
    return tuple(lo * ratio**i for i in range(bins + 1))


# 1 µs .. 1000 s, 36 log bins: covers a fused-decode dispatch on a tiny
# CPU model through a multi-minute first-step compile in one shape
DEFAULT_LATENCY_EDGES = exp_edges(1e-6, 1e3, 36)


class Counter:
    """Monotonic accumulator (events, tokens, bytes)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def add(self, n: float = 1.0) -> None:
        self.value += n


class Gauge:
    """Last-write-wins instantaneous value (tokens/s, MFU, queue depth)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = float("nan")

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Fixed-bin histogram with running count/sum/min/max.

    ``edges`` are the ``len(counts) + 1`` bin boundaries (the shape the
    ``TrackerRun.track_histogram`` API takes). Values below the first
    edge land in bin 0, values at/above the last edge in the final bin —
    nothing is dropped, so ``sum(counts) == count`` always holds.
    """

    __slots__ = ("name", "edges", "counts", "count", "total", "min", "max")

    def __init__(self, name: str, edges: Iterable[float] = DEFAULT_LATENCY_EDGES):
        self.name = name
        self.edges = tuple(float(e) for e in edges)
        if len(self.edges) < 2 or any(
            b <= a for a, b in zip(self.edges, self.edges[1:])
        ):
            raise ValueError("edges must be >= 2 strictly increasing values")
        self.counts = [0] * (len(self.edges) - 1)
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf

    def record(self, v: float) -> None:
        v = float(v)
        # bisect over interior edges: < edges[1] -> bin 0, >= edges[-2] -> last
        i = bisect.bisect_right(self.edges, v, 1, len(self.edges) - 1) - 1
        self.counts[i] += 1
        self.count += 1
        self.total += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else float("nan")

    def percentile(self, p: float) -> float:
        """Approximate percentile (``p`` in [0, 1]) by linear interpolation
        within the containing bin; exact at the recorded min/max ends."""
        if not 0.0 <= p <= 1.0:
            raise ValueError(f"p must be in [0, 1], got {p}")
        if self.count == 0:
            return float("nan")
        target = p * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            if seen + c >= target and c > 0:
                lo = max(self.edges[i], self.min)
                hi = min(self.edges[i + 1], self.max)
                frac = (target - seen) / c
                # clamp into [min, max]: samples land in the edge bins
                # even when they fall outside the edge range entirely,
                # where the bin-bounds interpolation runs backwards
                return min(max(lo + frac * (hi - lo), self.min), self.max)
            seen += c
        return self.max

    def snapshot(self) -> dict[str, Any]:
        return {
            "count": self.count,
            "sum": self.total,
            "min": self.min if self.count else None,
            "max": self.max if self.count else None,
            "mean": self.total / self.count if self.count else None,
            "p50": self.percentile(0.5) if self.count else None,
            "p99": self.percentile(0.99) if self.count else None,
            "counts": list(self.counts),
            "edges": list(self.edges),
        }


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed timed region on the monotonic timeline."""

    name: str
    t0: float  # perf_counter seconds (monotonic, process-local origin)
    dur_s: float
    step: int | None = None
    meta: dict[str, Any] | None = None


class _SpanContext:
    __slots__ = ("_registry", "_name", "_step", "_meta", "_t0")

    def __init__(self, registry, name, step, meta):
        self._registry = registry
        self._name = name
        self._step = step
        self._meta = meta

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._registry.record_span(
            self._name, self._t0, t1 - self._t0, step=self._step,
            meta=self._meta,
        )
        return False


class MetricRegistry:
    """Named instruments + a bounded span timeline.

    ``span_observers`` fire synchronously on every completed span (the
    JSONL sink streams the timeline through one); keep observers cheap.
    """

    def __init__(
        self, *, timeline_capacity: int = 8192, flush_ring_capacity: int = 16
    ):
        self._lock = threading.Lock()
        self.counters: dict[str, Counter] = {}
        self.gauges: dict[str, Gauge] = {}
        self.gauge_fns: dict[str, Callable[[], float]] = {}
        self.histograms: dict[str, Histogram] = {}
        self.spans: collections.deque[Span] = collections.deque(
            maxlen=timeline_capacity
        )
        self.span_observers: list[Callable[[Span], None]] = []
        # raw-value observers, fired by record_value (the SLO layer's
        # streaming digests subscribe here — the fixed-bin histograms
        # are too coarse for tail SLOs, so digests need the raw samples)
        self.value_observers: list[Callable[[str, float], None]] = []
        # flight-recorder ring (docs/design/observability.md): the last N
        # flush snapshots, appended by Telemetry.flush — what the anomaly
        # flight recorder dumps when something goes wrong
        self.flush_ring: collections.deque[dict[str, Any]] = (
            collections.deque(maxlen=flush_ring_capacity)
        )
        # loop-global step tag: the trainer advances it; components that
        # have no step plumbed through (executor, checkpointer) stamp
        # their spans with it
        self.current_step: int | None = None

    # -- instruments ---------------------------------------------------

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self.counters.get(name)
            if c is None:
                c = self.counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self.gauges.get(name)
            if g is None:
                g = self.gauges[name] = Gauge(name)
            return g

    def gauge_fn(self, name: str, fn: Callable[[], float]) -> None:
        """Register a callable evaluated at snapshot time — for live
        rates that must stay honest when the instrumented component goes
        quiet (a last-write-wins gauge would freeze at its last healthy
        value through a stall). NaN return = absent; exceptions skip the
        gauge for that snapshot. Registrations survive
        ``reset_instruments`` (they are wiring, not accumulated state)."""
        with self._lock:
            self.gauge_fns[name] = fn

    def unregister_gauge_fn(self, name: str, fn=None) -> None:
        """Remove a callback gauge registration. With ``fn`` given, the
        removal only happens if the registration still points at that
        exact callable — a component renaming its gauge (replica
        labelling) must not tear down a different component's later
        registration under the same name."""
        with self._lock:
            cur = self.gauge_fns.get(name)
            if cur is not None and (fn is None or cur is fn):
                del self.gauge_fns[name]

    def histogram(
        self, name: str, edges: Iterable[float] | None = None
    ) -> Histogram:
        with self._lock:
            h = self.histograms.get(name)
            if h is None:
                h = self.histograms[name] = Histogram(
                    name, edges if edges is not None else DEFAULT_LATENCY_EDGES
                )
            return h

    def record_value(
        self, name: str, value: float, edges: Iterable[float] | None = None
    ) -> None:
        """Record one raw sample: feeds the fixed-bin histogram AND every
        registered value observer (the SLO layer's streaming quantile
        digests). Components whose latencies may carry tail SLOs record
        through this instead of ``histogram(...).record``."""
        self.histogram(name, edges).record(value)
        for obs in list(self.value_observers):
            obs(name, value)

    # -- timeline ------------------------------------------------------

    def span(
        self, name: str, *, step: int | None = None, **meta: Any
    ) -> _SpanContext:
        """Context manager timing one region; records a Span (and feeds
        the same-named histogram) on exit."""
        return _SpanContext(self, name, step, meta or None)

    def record_span(
        self,
        name: str,
        t0: float,
        dur_s: float,
        *,
        step: int | None = None,
        meta: dict[str, Any] | None = None,
    ) -> None:
        if step is None:
            step = self.current_step
        span = Span(name=name, t0=t0, dur_s=dur_s, step=step, meta=meta)
        self.histogram(name).record(dur_s)
        with self._lock:
            self.spans.append(span)
            observers = list(self.span_observers)
        for obs in observers:
            obs(span)

    def phases(self, prefix: str, *, step: int | None = None) -> "PhaseTimeline":
        return PhaseTimeline(self, prefix, step=step)

    def reset_instruments(self) -> None:
        """Drop every counter/gauge/histogram (the span timeline and
        observers stay). Bench harnesses call this between measurement
        windows so each flush snapshot covers exactly one window —
        instruments are re-looked-up by name on every record, so they
        simply reappear empty on next use."""
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()

    # -- snapshot ------------------------------------------------------

    def snapshot(self) -> dict[str, Any]:
        """Point-in-time copy of every instrument (cumulative values) —
        what sinks flush. Spans are NOT included (they stream through
        observers / stay on the in-memory timeline)."""
        with self._lock:
            counters = {n: c.value for n, c in self.counters.items()}
            gauges = {
                n: g.value
                for n, g in self.gauges.items()
                if not math.isnan(g.value)
            }
            histograms = {n: h.snapshot() for n, h in self.histograms.items()}
            fns = list(self.gauge_fns.items())
        for n, fn in fns:  # outside the lock: fns may touch the registry
            try:
                v = float(fn())
            except Exception:  # noqa: BLE001 — one bad fn must not kill flush
                continue
            if not math.isnan(v):
                gauges[n] = v
        return {
            "counters": counters,
            "gauges": gauges,
            "histograms": histograms,
        }


class PhaseTimeline:
    """Contiguous named phases partitioning one interval — gap-free by
    construction, so the per-step phase breakdown always accounts for
    100% of the wall time between construction and ``close()``.

    ``mark(phase)`` closes the currently open phase at *now* and opens
    the next; ``close()`` ends the last phase and emits the enclosing
    ``{prefix}/step`` span, which carries ``meta`` (fill it any time
    before ``close()``: counts that belong to the interval as a whole).
    """

    def __init__(self, registry: MetricRegistry, prefix: str, *, step=None):
        self._registry = registry
        self._prefix = prefix
        self._step = step
        self._t0 = time.perf_counter()
        self._last = self._t0
        self._closed = False
        self.meta: dict[str, Any] = {}

    def mark(self, phase: str) -> None:
        now = time.perf_counter()
        self._registry.record_span(
            # d9d-lint: disable=D9D006 — caller-prefixed ({train,serve,bench}/phase/*, documented)
            f"{self._prefix}/phase/{phase}", self._last, now - self._last,
            step=self._step,
        )
        self._last = now

    def cancel(self) -> None:
        """Abandon the timeline without emitting anything — for intervals
        that turn out not to be a step at all (e.g. the data iterator
        raised StopIteration before any work ran), so span consumers
        never see a phantom ``{prefix}/step``."""
        self._closed = True

    def close(self, tail_phase: str | None = None) -> float:
        """Finish the timeline; returns the total wall seconds. Any time
        since the last ``mark`` is attributed to ``tail_phase`` (default
        ``other``) so nothing is left unaccounted."""
        if self._closed:
            return 0.0
        self._closed = True
        if tail_phase is None:
            tail_phase = "other"
        now = time.perf_counter()
        if now > self._last:
            self._registry.record_span(
                # d9d-lint: disable=D9D006 — caller-prefixed ({train,serve,bench}/phase/*, documented)
                f"{self._prefix}/phase/{tail_phase}", self._last,
                now - self._last, step=self._step,
            )
        total = now - self._t0
        self._registry.record_span(
            # d9d-lint: disable=D9D006 — caller-prefixed ({train,serve,bench}/step, documented)
            f"{self._prefix}/step", self._t0, total, step=self._step,
            meta=self.meta or None,
        )
        return total
