"""Always-on runtime telemetry: registry + sinks + the process hub.

Usage shape (see docs/design/observability.md):

- Instrumented components (trainer, pipeline executor, serving batcher,
  checkpointer, data loader) call :func:`get_telemetry` and record into
  its registry. The hub always exists; with no sinks attached the cost
  is a few host-clock reads per region and in-memory accumulation.
- A driver (``Trainer`` via its config, bench harnesses via
  ``D9D_TELEMETRY_DIR``) attaches sinks — JSONL event log, tracker
  bridge, console summary — and calls :meth:`Telemetry.flush` on its
  metric cadence.
- Tests and embedders may install a fresh hub with :func:`set_telemetry`
  to isolate their measurements.

Metric namespace (enforced by convention, documented in the design doc):
``train/*`` trainer loop, ``pp/*`` pipeline executor, ``serve/*``
continuous batching, ``io/*`` checkpoint + data IO, ``host/*`` the
process itself (``host/gc``: the cyclic collector's pauses).
"""

import collections
import gc
import logging
import threading
import time as _time
from typing import Any

from d9d_tpu.telemetry.flops import (
    active_param_count,
    device_peak_flops,
    model_flops_per_token,
)
from d9d_tpu.telemetry.registry import (
    SCHEMA_VERSION,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    PhaseTimeline,
    Span,
    exp_edges,
)
from d9d_tpu.telemetry.sinks import (
    ConsoleSink,
    JsonlSink,
    TelemetrySink,
    TrackerBridge,
    iter_events,
    validate_event,
)

__all__ = [
    "SCHEMA_VERSION",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "PhaseTimeline",
    "Span",
    "Telemetry",
    "TelemetrySink",
    "JsonlSink",
    "TrackerBridge",
    "ConsoleSink",
    "exp_edges",
    "get_telemetry",
    "set_telemetry",
    "iter_events",
    "validate_event",
    "model_flops_per_token",
    "active_param_count",
    "device_peak_flops",
    "tracked_jit",
    "recompile_guard",
    # monitoring plane (docs/design/observability.md)
    "MetricsServer",
    "render_prometheus",
    "SloMonitor",
    "SloPolicy",
    "StreamingQuantileDigest",
    "FlightRecorder",
    # host sampling profiler (telemetry/host_sampler.py)
    "HostSampler",
    # training numerics plane (telemetry/numerics.py)
    "DriftPolicy",
    "NumericsMonitor",
    "RollingBaseline",
    "TrainDriftMonitor",
    "default_drift_policies",
]


class Telemetry:
    """One registry + its attached sinks.

    Spans stream to sinks as they complete (via a registry observer);
    counters/gauges/histograms reach sinks only on :meth:`flush` — the
    metric-collector cadence, so the hot loop never serializes a
    snapshot per step.
    """

    def __init__(self, registry: MetricRegistry | None = None):
        self.registry = registry if registry is not None else MetricRegistry()
        self.registry.span_observers.append(self._on_span)
        self._sinks: list[TelemetrySink] = []
        self._lock = threading.Lock()
        # monitoring plane attachments (both optional): the SLO monitor
        # is evaluated on every flush (and by /metrics scrapes); the
        # flight recorder makes dump_flight_record a real dump instead of
        # a no-op (telemetry/flight_recorder.py)
        self.slo_monitor = None
        self.flight_recorder = None
        # last numerics window (telemetry/numerics.py): kept so flight-
        # recorder dumps carry the per-layer stats + first-non-finite
        # verdict of the moment things went wrong
        self.last_numerics = None
        self._slo_eval_warned_t = -float("inf")
        self._gc_t0: float | None = None
        # pauses the collector's hook has seen and no span records yet
        self._gc_pauses: collections.deque = collections.deque()

    # -- instrument passthrough (the API components actually use) ------

    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def gauge_fn(self, name: str, fn) -> None:
        self.registry.gauge_fn(name, fn)

    def histogram(self, name: str, edges=None) -> Histogram:
        return self.registry.histogram(name, edges)

    def observe(self, name: str, value: float, edges=None) -> None:
        """Record one raw latency/value sample: the fixed-bin histogram
        plus every value observer (SLO streaming digests)."""
        self.registry.record_value(name, value, edges)

    def span(self, name: str, *, step: int | None = None, **meta: Any):
        return self.registry.span(name, step=step, **meta)

    def phases(self, prefix: str, *, step: int | None = None) -> PhaseTimeline:
        return self.registry.phases(prefix, step=step)

    def set_step(self, step: int | None) -> None:
        """Tag subsequent spans from step-unaware components (executor,
        checkpointer IO) with the loop's current step."""
        self.registry.current_step = step

    def reset_instruments(self) -> None:
        """Drop all counters/gauges/histograms (sinks stay attached) —
        bench harnesses call this between measurement windows so each
        flush snapshot covers exactly one window."""
        self.registry.reset_instruments()

    # -- sinks ---------------------------------------------------------

    def add_sink(self, sink: TelemetrySink) -> TelemetrySink:
        with self._lock:
            self._sinks.append(sink)
        return sink

    def remove_sink(self, sink: TelemetrySink, *, close: bool = True) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)
        if close:
            sink.close()

    @property
    def sinks(self) -> tuple[TelemetrySink, ...]:
        with self._lock:
            return tuple(self._sinks)

    def _on_span(self, span: Span) -> None:
        if self._gc_pauses and span.name != "host/gc":
            self._record_gc_pauses()
        for sink in self.sinks:
            sink.on_span(span)

    def record_executable(self, record: dict[str, Any]) -> None:
        """Stream one per-executable introspection record (compile cost,
        FLOPs, HBM breakdown — telemetry/introspect.py) to every sink as
        a schema-v2 ``executable`` event."""
        for sink in self.sinks:
            sink.on_executable(record)

    def record_request_trace(self, record: dict[str, Any]) -> None:
        """Stream one per-request milestone (schema v3 ``request_trace``,
        docs/design/observability.md) to every sink. With no sinks
        attached this is a loop over an empty tuple — the serving hot
        path pays nothing for tracing it isn't exporting."""
        for sink in self.sinks:
            sink.on_request_trace(record)

    def record_numerics(self, record: dict[str, Any]) -> None:
        """Stream one per-layer numerics window (schema v4 ``numerics``,
        telemetry/numerics.py) to every sink, and keep it as the hub's
        ``last_numerics`` so flight-recorder dumps carry the window."""
        self.last_numerics = record
        for sink in self.sinks:
            sink.on_numerics(record)

    def record_host_stacks(self, record: dict[str, Any]) -> None:
        """Stream one folded controller-stack window (schema v5
        ``host_stacks``, telemetry/host_sampler.py) to every sink —
        emitted once per profiling capture window, never on the step
        path."""
        for sink in self.sinks:
            sink.on_host_stacks(record)

    def flush(self, step: int | None = None) -> dict[str, Any]:
        """Snapshot every instrument and hand it to each sink; returns
        the snapshot (callers fold headline values into their own logs).
        Each flush also (a) evaluates the attached SLO monitor first, so
        slo/* instruments in the snapshot are current, and (b) appends
        the snapshot to the registry's flight-recorder ring."""
        self._record_gc_pauses()
        if self.slo_monitor is not None:
            try:
                self.slo_monitor.evaluate()
            except Exception:  # noqa: BLE001 — SLO eval must not kill flush
                # rate-limited log: a broken policy silently freezing the
                # slo/* surface would be invisible on scraper-less jobs
                now = _time.monotonic()
                if now - self._slo_eval_warned_t >= 60.0:
                    self._slo_eval_warned_t = now
                    logging.getLogger("d9d_tpu.telemetry").exception(
                        "SLO evaluation failed during flush; slo/* "
                        "instruments are stale until this is fixed"
                    )
        snapshot = self.registry.snapshot()
        self.registry.flush_ring.append({
            "unix_time": _time.time(),
            "step": step,
            "snapshot": snapshot,
        })
        for sink in self.sinks:
            sink.on_flush(snapshot, step)
        return snapshot

    def dump_flight_record(self, event: str, *, extra=None):
        """Dump the flight-recorder ring (recent flush windows + span
        tail + executable inventory) as ``flight_recorder_{event}.json``
        — a no-op returning None until a recorder is configured
        (:meth:`configure_flight_recorder`). Never raises: the recorder
        exists to observe failures, not to cause new ones."""
        if self.flight_recorder is None:
            return None
        try:
            return self.flight_recorder.dump(
                event, self.registry, extra=extra,
                numerics=self.last_numerics,
            )
        except Exception:  # noqa: BLE001 — see docstring
            return None

    def configure_flight_recorder(self, directory, **kwargs):
        """Install a :class:`FlightRecorder` writing into ``directory``;
        returns it. Idempotent per directory: re-configuring the same
        directory keeps the existing recorder (and its per-event
        rate-limit state — a second Trainer over the same telemetry dir
        must not reset the one-dump-per-interval guarantee)."""
        from pathlib import Path

        from d9d_tpu.telemetry.flight_recorder import FlightRecorder

        if (
            self.flight_recorder is not None
            and self.flight_recorder.directory == Path(directory)
        ):
            return self.flight_recorder
        self.flight_recorder = FlightRecorder(directory, **kwargs)
        return self.flight_recorder

    # -- the cyclic collector's pauses ---------------------------------

    # a young-generation collection takes microseconds and comes every
    # few hundred allocations: only a pause worth seeing is recorded
    GC_SPAN_MIN_S = 1e-3

    def watch_gc(self) -> None:
        """Record a ``host/gc`` span (meta: generation, collected) for
        every collection of generation 2 and every collection longer
        than ``GC_SPAN_MIN_S``: a pause of the whole interpreter that no
        loop's own phases can name. One ``gc.callbacks`` hook, installed
        for the process hub (:func:`get_telemetry`, :func:`set_telemetry`)
        and removed by :meth:`close`; nothing runs on a step path.

        A collection starts wherever an allocation happens, also while
        this thread holds the registry's or a sink's lock, so the hook
        itself only reads the clock and appends to a deque; the span is
        recorded with the next span of any kind and at every flush."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def unwatch_gc(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = _time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
            return
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is None:
            return
        generation = info.get("generation")
        if generation == 2 or now - t0 > self.GC_SPAN_MIN_S:
            self._gc_pauses.append(
                (t0, now - t0, generation, info.get("collected"))
            )

    def _record_gc_pauses(self) -> None:
        while self._gc_pauses:
            try:
                t0, dur_s, generation, collected = self._gc_pauses.popleft()
            except IndexError:  # another thread took the last one
                return
            self.registry.record_span(
                "host/gc", t0, dur_s,
                meta={"generation": generation, "collected": collected},
            )

    def close(self) -> None:
        self.unwatch_gc()
        self._record_gc_pauses()
        for sink in self.sinks:
            self.remove_sink(sink)


_default: Telemetry | None = None
_default_lock = threading.Lock()


def get_telemetry() -> Telemetry:
    """The process-local hub every instrumented component records into."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Telemetry()
                _default.watch_gc()
    return _default


def set_telemetry(hub: Telemetry) -> Telemetry:
    """Replace the process hub (tests, embedders); returns the new hub."""
    global _default
    with _default_lock:
        if _default is not None and _default is not hub:
            _default.unwatch_gc()  # one hook per process
        _default = hub
        hub.watch_gc()
    return hub


# imported AFTER get_telemetry exists: introspect records through the hub
# (deferred inside its methods), and re-exporting here keeps the public
# surface one import wide
from d9d_tpu.telemetry.introspect import (  # noqa: E402
    recompile_guard,
    tracked_jit,
)
from d9d_tpu.telemetry.export import (  # noqa: E402
    MetricsServer,
    render_prometheus,
)
from d9d_tpu.telemetry.flight_recorder import FlightRecorder  # noqa: E402
from d9d_tpu.telemetry.host_sampler import HostSampler  # noqa: E402
from d9d_tpu.telemetry.slo import (  # noqa: E402
    SloMonitor,
    SloPolicy,
    StreamingQuantileDigest,
)
from d9d_tpu.telemetry.numerics import (  # noqa: E402
    DriftPolicy,
    NumericsMonitor,
    RollingBaseline,
    TrainDriftMonitor,
    default_drift_policies,
)
