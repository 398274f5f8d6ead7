"""Hot-path trace attribution and the one control for the profiler.

The reference wraps every pipeline action and grad region in
``torch.profiler.record_function`` (d9d/pipelining/runtime/executor.py:96,
internals/grad_sync/bucket.py:194, internals/grad_norm/norm.py:125) so a
captured trace attributes time to schedule slots. The TPU equivalents:

- **Host side** — :func:`annotate` emits a ``jax.profiler.TraceAnnotation``
  (TraceMe) around dispatch regions (pipeline actions, optimizer phases,
  batch staging). Annotations are gated behind a process-wide flag so the
  steady-state step path pays one attribute read per region when profiling
  is off; :func:`start_trace` flips the flag for the duration of a capture.
- **Device side** — jitted stage/step functions wrap their bodies in
  ``jax.named_scope`` (zero runtime cost: names attach to HLO ops at trace
  time), so XLA ops in the captured trace carry ``pp_stage*/fwd`` -style
  prefixes that ``benchmarks/harness/trace.py`` groups by.
- **The control** — :func:`start_trace` / :func:`stop_trace` (and
  :func:`trace` over the two) are the only callers of
  ``jax.profiler.start_trace`` / ``stop_trace`` in the repo. jax allows
  one live trace per process, so they own the lock that says so, the
  profiler's options, the annotation flag and the **clock anchor**: right
  after the profiler has started and right before it stops they emit one
  ``TraceAnnotation`` called ``d9d.clock/<perf_counter_ns>``. The trace
  then holds, on the profiler's clock, two events that state the program
  clock's reading, so a registry ``Span`` (``t0`` on ``perf_counter``)
  can be placed on the trace's timeline to within the annotation's own
  duration (:func:`clock_shift`).
"""

import contextlib
import os
import threading
import time

import jax

__all__ = [
    "CLOCK_ANCHOR",
    "TraceBusyError",
    "annotate",
    "annotations_enabled",
    "clock_shift",
    "set_trace_annotations",
    "start_trace",
    "stop_trace",
    "trace",
]

CLOCK_ANCHOR = "d9d.clock/"

_enabled = False

_NULL = contextlib.nullcontext()

_trace_lock = threading.Lock()
_live_logdir: str | None = None


class TraceBusyError(RuntimeError):
    """``start_trace`` while a trace is live: jax allows one per process."""


def set_trace_annotations(on: bool) -> None:
    """Globally enable/disable host-side trace annotations (cheap toggle;
    :func:`start_trace` / :func:`stop_trace` call it around a capture)."""
    global _enabled
    _enabled = bool(on)


def annotations_enabled() -> bool:
    return _enabled


def annotate(label: str):
    """Context manager: a named host-trace region when annotations are on,
    a shared null context otherwise."""
    if _enabled:
        return jax.profiler.TraceAnnotation(label)
    return _NULL


def _anchor() -> None:
    with jax.profiler.TraceAnnotation(
        f"{CLOCK_ANCHOR}{time.perf_counter_ns()}"
    ):
        pass


def start_trace(logdir, *, python_tracer: bool = False) -> None:
    """Start the profiler into ``logdir``; raises :class:`TraceBusyError`
    when a trace is already live. The Python tracer is off unless asked
    for: it records every Python call and slows the host it measures.
    Host tracer level 2 keeps the TraceMe annotations and the runtime's
    enqueue events."""
    global _live_logdir
    with _trace_lock:
        if _live_logdir is not None:
            raise TraceBusyError(
                f"a profiler trace is already live -> {_live_logdir}"
            )
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        options.host_tracer_level = 2
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(str(logdir), profiler_options=options)
        _live_logdir = str(logdir)
        set_trace_annotations(True)
        _anchor()


def stop_trace() -> None:
    """Stop the live trace and write it (this can take seconds); a no-op
    when none is live. The profiler is stopped even when the anchor
    raises, and the annotation flag and the lock are released even when
    the profiler's own stop does."""
    global _live_logdir
    with _trace_lock:
        if _live_logdir is None:
            return
        try:
            try:
                _anchor()
            finally:
                jax.profiler.stop_trace()
        finally:
            set_trace_annotations(False)
            _live_logdir = None


@contextlib.contextmanager
def trace(logdir, *, python_tracer: bool = False):
    """Profile the body into ``logdir``."""
    start_trace(logdir, python_tracer=python_tracer)
    try:
        yield
    finally:
        stop_trace()


def clock_shift(host_events) -> float | None:
    """Seconds to ADD to a ``perf_counter`` reading to place it on the
    trace's clock, from the anchors among ``(name, start_s)`` host events
    of a trace; ``None`` when the trace holds none. An anchor's name is
    the program clock read just before the annotation opened, so each
    gives ``start - reading`` with an error of at most the annotation's
    own set-up; the smallest difference is the closest."""
    shifts = [
        start - int(name[len(CLOCK_ANCHOR):]) / 1e9
        for name, start in host_events
        if name.startswith(CLOCK_ANCHOR)
    ]
    return min(shifts) if shifts else None
