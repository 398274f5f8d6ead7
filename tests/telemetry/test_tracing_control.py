"""The one control for the profiler (``core/tracing``): start, busy on a
second start, stop, the annotation flag and the clock anchor; and
``JobProfiler``'s cadence window and one-shot capture both through it."""

import glob
import time

import pytest

from d9d_tpu.core import tracing
from d9d_tpu.loop.components.job_profiler import JobProfiler


def host_events(logdir):
    """(name, start seconds) of the host plane's events of the capture."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    return [
        (e.name, e.start_ns / 1e9)
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
    ]


def test_start_busy_stop_flag_and_anchor(tmp_path):
    assert not tracing.annotations_enabled()
    before = time.perf_counter()
    tracing.start_trace(tmp_path / "a")
    try:
        assert tracing.annotations_enabled()
        with pytest.raises(tracing.TraceBusyError, match="already live"):
            tracing.start_trace(tmp_path / "b")
        with tracing.annotate("test.region"):
            pass
    finally:
        tracing.stop_trace()
    after = time.perf_counter()
    assert not tracing.annotations_enabled()
    tracing.stop_trace()  # a no-op when none is live
    assert not (tmp_path / "b").exists()

    events = host_events(tmp_path / "a")
    names = [n for n, _ in events]
    assert "test.region" in names
    anchors = [n for n in names if n.startswith(tracing.CLOCK_ANCHOR)]
    assert len(anchors) == 2  # one after the start, one before the stop
    readings = sorted(int(n[len(tracing.CLOCK_ANCHOR):]) / 1e9 for n in anchors)
    assert before <= readings[0] <= readings[1] <= after
    # both anchors give the same shift between the two clocks, to well
    # under a millisecond, and it places the region between them
    shifts = [
        start - int(n[len(tracing.CLOCK_ANCHOR):]) / 1e9
        for n, start in events if n.startswith(tracing.CLOCK_ANCHOR)
    ]
    assert abs(shifts[0] - shifts[1]) < 1e-3
    shift = tracing.clock_shift(events)
    assert shift == min(shifts)
    region = dict(events)["test.region"]
    assert readings[0] + shift <= region <= readings[1] + shift + 1e-3


def test_context_manager_releases_on_error(tmp_path):
    with pytest.raises(ZeroDivisionError):
        with tracing.trace(tmp_path):
            1 / 0
    assert not tracing.annotations_enabled()
    assert tracing.clock_shift([("other", 1.0)]) is None


def test_stop_stops_the_profiler_even_when_the_anchor_raises(
        tmp_path, monkeypatch):
    tracing.start_trace(tmp_path / "a")
    monkeypatch.setattr(tracing, "_anchor", lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        tracing.stop_trace()
    assert not tracing.annotations_enabled()
    monkeypatch.undo()
    # the profiler itself was stopped: jax takes a new start
    with tracing.trace(tmp_path / "b"):
        pass
    assert glob.glob(f"{tmp_path}/b/plugins/profile/*/*.xplane.pb")


@pytest.fixture
def calls(monkeypatch):
    """Every start and stop that reaches the control, in order."""
    seen = []
    start, stop = tracing.start_trace, tracing.stop_trace

    def counted_start(logdir, **kw):
        start(logdir, **kw)
        seen.append(("start", kw))

    def counted_stop():
        live = tracing.annotations_enabled()
        stop()
        if live:
            seen.append(("stop", {}))

    monkeypatch.setattr(tracing, "start_trace", counted_start)
    monkeypatch.setattr(tracing, "stop_trace", counted_stop)
    return seen


def test_job_profiler_cadence_goes_through_the_control(tmp_path, calls):
    prof = JobProfiler(tmp_path, every_steps=10, active_steps=2, wait_steps=1)
    for step in range(4):
        prof.step_begin(step)
        assert tracing.annotations_enabled() == (step in (1, 2))
        prof.step_end(step)
    # the Python tracer is off unless asked for
    assert calls == [("start", {}), ("stop", {})]
    prof.close()
    assert len(calls) == 2


def test_job_profiler_one_shot_goes_through_the_control(tmp_path, calls):
    prof = JobProfiler()
    out = prof.capture(30.0, tmp_path)
    assert out is not None and prof.capture_active
    assert calls == [("start", {})]
    # busy: a second one-shot, a cadence window, any other start
    assert prof.capture(1.0, tmp_path) is None
    other = JobProfiler(tmp_path / "c", every_steps=1, wait_steps=0)
    other.step_begin(0)
    assert other._tracing_until is None
    with pytest.raises(tracing.TraceBusyError):
        tracing.start_trace(tmp_path / "d")
    prof.close()  # stops it before its timer fires
    assert not prof.capture_active and not tracing.annotations_enabled()
    assert [c[0] for c in calls] == ["start", "stop"]


def test_gc_span_for_a_forced_collection_and_hook_removed_on_close():
    import gc

    from d9d_tpu.telemetry import Telemetry

    hub = Telemetry()
    hooks = len(gc.callbacks)
    hub.watch_gc()
    hub.watch_gc()  # one hook, however often it is asked for
    assert len(gc.callbacks) == hooks + 1
    gc.collect(0)  # a young collection of microseconds leaves no span
    gc.collect(2)
    # the hook only notes the pause (a collection can start while this
    # thread holds the registry's lock); the next span records it
    assert not [s for s in hub.registry.spans if s.name == "host/gc"]
    with hub.span("anything"):
        pass
    spans = [s for s in hub.registry.spans if s.name == "host/gc"]
    assert spans and spans[-1].meta["generation"] == 2
    assert spans[-1].dur_s > 0 and "collected" in spans[-1].meta
    assert all(
        s.meta["generation"] == 2 or s.dur_s > hub.GC_SPAN_MIN_S
        for s in spans
    )
    # a collection while the registry's lock is held must not deadlock
    with hub.registry._lock:
        gc.collect(2)
    hub.flush()  # a flush records what is pending too
    assert len([s for s in hub.registry.spans if s.name == "host/gc"]) \
        == len(spans) + 1
    hub.close()
    assert len(gc.callbacks) == hooks
    seen = len(hub.registry.spans)
    gc.collect(2)
    assert len(hub.registry.spans) == seen


def test_the_process_hub_watches_the_collector_once():
    import gc

    from d9d_tpu import telemetry

    before = telemetry.get_telemetry()
    try:
        fresh = telemetry.set_telemetry(telemetry.Telemetry())
        ours = [
            cb for cb in gc.callbacks
            if getattr(cb, "__self__", None) in (before, fresh)
        ]
        assert [cb.__self__ for cb in ours] == [fresh]
    finally:
        telemetry.set_telemetry(before)
        fresh.close()
