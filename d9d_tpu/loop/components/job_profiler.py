"""Periodic + on-demand jax.profiler tracing.

Reference: d9d/internals/profiling/profile.py:11 + loop/component/
job_profiler.py:13 — torch.profiler with a wait/warmup/active periodic
schedule, per-rank chrome traces. TPU equivalent: ``jax.profiler`` traces
(viewable in XProf/TensorBoard, incl. device HLO timelines); one trace dir
per cycle, named by step and process index.

Two capture modes share one profiler. jax allows exactly one live trace
per process: ``core/tracing.start_trace`` / ``stop_trace`` own that rule
(and the profiler's options, the annotation flag and the clock anchor);
both modes start and stop through them, so they exclude each other and
any other capture in the process (a benchmark's, a tool's):

- the original step-cadence schedule (``every_steps``/``active_steps``);
- :meth:`capture` — a wall-clock one-shot for operator-driven captures
  (``MetricsServer`` ``/debug/profile``) and the ``FlightRecorder``
  capture hook: start a trace now, stop it on a timer thread after
  ``duration_s``. Both modes run the ``HostSampler``
  (telemetry/host_sampler.py) over the controller thread for the window
  and emit its folded stacks as a schema-v5 ``host_stacks`` event, so
  every device trace ships with matching host attribution.
"""

import logging
import threading
import time
from pathlib import Path

import jax

from d9d_tpu.core import tracing
from d9d_tpu.telemetry import get_telemetry
from d9d_tpu.telemetry.host_sampler import HostSampler

logger = logging.getLogger("d9d_tpu.profiler")


class JobProfiler:
    """Trace ``active_steps`` steps every ``every_steps`` (first cycle after
    ``wait_steps``). No-op when ``every_steps`` is None. ``capture()``
    works regardless of the cadence config (it needs no trace_dir — the
    caller supplies the output directory)."""

    def __init__(
        self,
        trace_dir: str | Path | None = None,
        *,
        every_steps: int | None = None,
        active_steps: int = 3,
        wait_steps: int = 10,
    ):
        self.trace_dir = Path(trace_dir) if trace_dir else None
        self.every_steps = every_steps
        self.active_steps = active_steps
        self.wait_steps = wait_steps
        self._tracing_until: int | None = None
        self._lock = threading.Lock()
        self._capture_dir: Path | None = None
        self._capture_timer: threading.Timer | None = None
        self._sampler: HostSampler | None = None

    @property
    def capture_active(self) -> bool:
        """A one-shot :meth:`capture` is currently live (step-cadence
        windows don't count — callers gate new captures on this)."""
        with self._lock:
            return self._capture_dir is not None

    def _should_start(self, step: int) -> bool:
        if self.every_steps is None or self.trace_dir is None:
            return False
        if step < self.wait_steps:
            return False
        return (step - self.wait_steps) % self.every_steps == 0

    def _start_sampler(self) -> None:
        self._sampler = HostSampler()
        self._sampler.start()

    def _stop_sampler(self) -> None:
        if self._sampler is None:
            return
        record = self._sampler.stop()
        self._sampler = None
        try:
            get_telemetry().record_host_stacks(record)
        except Exception:  # noqa: BLE001 — a sink failure must not
            # take down the trace stop path
            logger.warning("host-stacks emission failed", exc_info=True)

    def _start(self, out: Path) -> None:
        """Profiler, then sampler: the profiler's first-use initialization
        can take seconds and must not pollute the host-stacks window."""
        tracing.start_trace(out)
        self._start_sampler()

    def _stop(self) -> None:
        """Sampler, then profiler: stopping serializes the xplane (can
        take seconds) and must not pollute the host-stacks window."""
        self._stop_sampler()
        tracing.stop_trace()

    def step_begin(self, step: int) -> None:
        if self._tracing_until is None and self._should_start(step):
            with self._lock:
                if self._capture_dir is not None:
                    return  # a one-shot capture owns the profiler
                out = (
                    self.trace_dir
                    / f"step_{step}_proc_{jax.process_index()}"
                )
                logger.info("profiler: tracing steps %d..%d -> %s",
                            step, step + self.active_steps - 1, out)
                try:
                    self._start(out)
                except tracing.TraceBusyError:
                    return  # someone else's capture: skip this cycle
                self._tracing_until = step + self.active_steps

    def step_end(self, step: int) -> None:
        if self._tracing_until is not None and step + 1 >= self._tracing_until:
            with self._lock:
                self._stop()
                self._tracing_until = None

    # -- on-demand one-shot capture ------------------------------------

    def capture(
        self, duration_s: float, out_dir: str | Path
    ) -> Path | None:
        """Start a wall-clock one-shot capture into ``out_dir`` and
        return the capture directory immediately (the trace stops on a
        timer thread after ``duration_s``). Returns ``None`` — never
        raises to its caller's caller — when the profiler is already
        busy (a cadence window, another capture, or any other trace
        started through ``core/tracing`` is live)."""
        with self._lock:
            if self._capture_dir is not None or self._tracing_until is not None:
                return None
            stamp = time.strftime("%Y%m%d_%H%M%S")
            out = (
                Path(out_dir)
                / f"ondemand_{stamp}_proc{jax.process_index()}"
            )
            logger.info(
                "profiler: on-demand capture (%.1fs) -> %s",
                duration_s, out,
            )
            try:
                self._start(out)
            except tracing.TraceBusyError:
                return None
            self._capture_dir = out
            tele = get_telemetry()
            tele.counter("profile/captures").add(1)
            tele.gauge("profile/last_duration_s").set(duration_s)
            timer = threading.Timer(
                max(duration_s, 0.05), self._finish_capture
            )
            timer.daemon = True
            self._capture_timer = timer
            timer.start()
            return out

    def _finish_capture(self) -> None:
        with self._lock:
            if self._capture_dir is None:
                return
            try:
                self._stop()
            except Exception:  # noqa: BLE001 — a failing stop must not
                # kill the timer thread
                logger.warning("capture stop failed", exc_info=True)
            logger.info(
                "profiler: on-demand capture done -> %s", self._capture_dir
            )
            self._capture_dir = None
            self._capture_timer = None

    def close(self) -> None:
        timer = self._capture_timer
        if timer is not None:
            timer.cancel()
        self._finish_capture()  # no-op when no capture is live
        with self._lock:
            if self._tracing_until is not None:
                self._stop()
                self._tracing_until = None
