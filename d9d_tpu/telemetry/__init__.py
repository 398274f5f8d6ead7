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
process itself (``host/gc``: the cyclic collector's pauses;
``host/hiccup``, ``host/probe``: the late wake-ups of a thread that only
sleeps, and the witness that it ran).
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
    "HiccupProbe",
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


class HiccupProbe:
    """The loop of the ``d9d-host-hiccup`` thread: sleep ``interval_s``,
    read the clock, and ``late = now - due`` is how long a thread with
    nothing to wait for but the operating system was kept from running.
    No loop's own clock can say that: it runs on the thread that is
    itself frozen or blocked. A wake-up is also late while another
    thread holds the interpreter (a collection, a long C call that keeps
    the GIL); a reader tells those apart by what the hiccup overlaps.

    It appends ``(name, t0, dur_s, meta)`` to ``out`` and takes no lock
    but the deque's own. The clock and the wait are arguments so that
    tests drive :meth:`run` with a fake clock and no sleeping; ``wait``
    returns true when the probe is to stop, as ``Event.wait`` does."""

    WITNESS_S = 1.0
    THREAD_NAME = "d9d-host-hiccup"

    def __init__(self, out: collections.deque, *, interval_s: float,
                 span_min_s: float, clock=_time.perf_counter, wait=None):
        self._out = out
        self.interval_s = interval_s
        self.span_min_s = span_min_s
        self._clock = clock
        self._stop = threading.Event()
        self._wait = wait if wait is not None else self._stop.wait
        self._thread: threading.Thread | None = None

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.run, name=self.THREAD_NAME, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self.alive and self._thread is not threading.current_thread():
            self._thread.join(timeout=1.0)

    def run(self) -> None:
        clock, out = self._clock, self._out
        since, wakes, late_sum, late_max = clock(), 0, 0.0, 0.0
        while True:
            due = clock() + self.interval_s
            stopped = self._wait(self.interval_s)
            now = clock()
            if not stopped:
                late = max(now - due, 0.0)
                wakes += 1
                late_sum += late
                late_max = max(late_max, late)
                if late >= self.span_min_s:
                    out.append(("host/hiccup", due, late, None))
            if wakes and (stopped or now - since >= self.WITNESS_S):
                out.append(("host/probe", since, now - since, {
                    "wakes": wakes, "late_sum_s": late_sum,
                    "late_max_s": late_max,
                }))
                since, wakes, late_sum, late_max = now, 0, 0.0, 0.0
            if stopped:
                return


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
        # (name, t0, dur_s, meta) of the pauses the collector's hook and
        # the hiccup probe have seen and no span records yet
        self._host_pauses: collections.deque = collections.deque()
        self._hiccups: HiccupProbe | None = None

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
        if self._host_pauses and not span.name.startswith("host/"):
            self._record_host_pauses()
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
        self._record_host_pauses()
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
            self._host_pauses.append((
                "host/gc", t0, now - t0,
                {"generation": generation, "collected": info.get("collected")},
            ))

    def _record_host_pauses(self) -> None:
        while self._host_pauses:
            try:
                name, t0, dur_s, meta = self._host_pauses.popleft()
            except IndexError:  # another thread took the last one
                return
            self.registry.record_span(name, t0, dur_s, meta=meta)

    # -- the host's own pauses -----------------------------------------

    HICCUP_INTERVAL_S = 10e-3
    # twice the interpreter's 5 ms switch interval: a main thread that
    # merely runs Python does not read as a pause
    HICCUP_SPAN_MIN_S = 10e-3

    def watch_hiccups(self) -> None:
        """Start the ``d9d-host-hiccup`` thread (:class:`HiccupProbe`): a
        ``host/hiccup`` span for every wake-up of a thread that only
        sleeps ``HICCUP_INTERVAL_S`` that came ``HICCUP_SPAN_MIN_S`` or
        more late (a host that stood still: a descheduled VM, a stopped
        process, a thread that kept the interpreter), and a
        ``host/probe`` span a second (meta: ``wakes``, ``late_sum_s``,
        ``late_max_s``), the witness that the probe ran. Started for the
        process hub beside the collector's hook and stopped by
        :meth:`close`; a second call while the thread lives is a no-op,
        one after a fork (the thread is not the child's) starts it anew.
        Like the hook, the thread only reads the clock and appends to a
        deque: the spans are recorded with the next span of any kind and
        at every flush."""
        if self._hiccups is None or not self._hiccups.alive:
            self._hiccups = HiccupProbe(
                self._host_pauses, interval_s=self.HICCUP_INTERVAL_S,
                span_min_s=self.HICCUP_SPAN_MIN_S)
            self._hiccups.start()

    def unwatch_hiccups(self) -> None:
        probe, self._hiccups = self._hiccups, None
        if probe is not None:
            probe.stop()

    def close(self) -> None:
        self.unwatch_gc()
        self.unwatch_hiccups()
        self._record_host_pauses()
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
                _default.watch_hiccups()
    return _default


def set_telemetry(hub: Telemetry) -> Telemetry:
    """Replace the process hub (tests, embedders); returns the new hub."""
    global _default
    with _default_lock:
        if _default is not None and _default is not hub:
            _default.unwatch_gc()  # one hook per process
            _default.unwatch_hiccups()  # and one probe
        _default = hub
        hub.watch_gc()
        hub.watch_hiccups()
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
