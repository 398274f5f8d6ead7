"""The process hub's hiccup probe (``Telemetry.watch_hiccups``): the
loop on a fake clock and a fake wait, with no sleeping, and the real
thread's lifetime under what the suite does to hubs."""

import threading

import pytest

from d9d_tpu import telemetry
from d9d_tpu.telemetry import HiccupProbe, Telemetry

MS = 1e-3
NAME = HiccupProbe.THREAD_NAME


def probe_threads():
    return [t for t in threading.enumerate() if t.name == NAME and t.is_alive()]


class FakeHost:
    """A clock, and a wait that comes back ``late`` seconds after its
    timeout for each entry of ``lates`` and then says stop."""

    def __init__(self, lates, start=100.0):
        self.now = start
        self.lates = list(lates)

    def clock(self):
        return self.now

    def wait(self, timeout):
        if not self.lates:
            return True
        self.now += timeout + self.lates.pop(0)
        return False


def run_probe(lates, hub=None, start=100.0):
    hub = hub or Telemetry()
    host = FakeHost(lates, start)
    HiccupProbe(
        hub._host_pauses, interval_s=hub.HICCUP_INTERVAL_S,
        span_min_s=hub.HICCUP_SPAN_MIN_S, clock=host.clock, wait=host.wait,
    ).run()
    return hub


def spans(hub, name):
    return [s for s in hub.registry.spans if s.name == name]


def test_a_wake_4_ms_late_is_no_hiccup_and_counts_in_the_witness():
    hub = run_probe([4 * MS])
    hub.flush()
    assert not spans(hub, "host/hiccup")
    (probe,) = spans(hub, "host/probe")
    assert probe.meta["wakes"] == 1
    assert probe.meta["late_sum_s"] == pytest.approx(4 * MS)
    assert probe.meta["late_max_s"] == pytest.approx(4 * MS)


def test_a_wake_30_ms_late_is_a_hiccup_from_when_it_was_due():
    hub = run_probe([0.0, 30 * MS, 0.0])
    hub.flush()
    (hiccup,) = spans(hub, "host/hiccup")
    # the second wake-up: due an interval after the first came back
    assert hiccup.t0 == pytest.approx(100.0 + 2 * hub.HICCUP_INTERVAL_S)
    assert hiccup.dur_s == pytest.approx(30 * MS)
    assert hiccup.meta is None


def test_a_wake_at_the_threshold_is_a_hiccup():
    # from 0 the fake clock's sums are exact
    hub = run_probe([Telemetry.HICCUP_SPAN_MIN_S], start=0.0)
    hub.flush()
    assert len(spans(hub, "host/hiccup")) == 1


def test_the_witness_of_a_second_carries_its_wakes_and_their_lateness():
    # 49 wake-ups 1 ms late, one 25 ms late and 43 on time pass the
    # second: one witness, and the stop's own is left out when it holds
    # no wake-up
    hub = run_probe([1 * MS] * 49 + [25 * MS] + [0.0] * 43)
    hub.flush()
    (probe,) = spans(hub, "host/probe")
    assert probe.t0 == pytest.approx(100.0)
    assert probe.dur_s == pytest.approx(93 * 10 * MS + 25 * MS + 49 * MS)
    assert probe.meta == {
        "wakes": 93, "late_sum_s": pytest.approx(74 * MS),
        "late_max_s": pytest.approx(25 * MS),
    }
    assert len(spans(hub, "host/hiccup")) == 1


def test_a_stop_inside_a_second_leaves_a_witness_of_the_part():
    hub = run_probe([0.0] * 130)
    hub.flush()
    first, rest = spans(hub, "host/probe")
    assert first.meta["wakes"] == 100 and rest.meta["wakes"] == 30
    assert rest.t0 == pytest.approx(first.t0 + first.dur_s)
    assert rest.dur_s == pytest.approx(0.3)


def test_spans_come_with_the_next_span_of_another_kind_and_at_a_flush():
    hub = run_probe([40 * MS])
    # the probe only appends to the hub's deque: nothing is recorded yet
    assert not hub.registry.spans
    with hub.span("anything"):
        pass
    assert [s.name for s in hub.registry.spans] == [
        "anything", "host/hiccup", "host/probe"]
    run_probe([50 * MS], hub)
    hub.flush()
    assert len(spans(hub, "host/hiccup")) == 2
    assert len(spans(hub, "host/probe")) == 2


def test_the_probe_takes_no_lock_of_the_registry_or_a_sink():
    hub = Telemetry()
    with hub.registry._lock, hub._lock:
        run_probe([40 * MS], hub)  # would deadlock if it recorded a span
    assert len(hub._host_pauses) == 2


def test_a_hub_of_its_own_has_no_probe_and_a_second_watch_is_a_no_op():
    before = probe_threads()
    hub = Telemetry()
    assert probe_threads() == before
    hub.watch_hiccups()
    (mine,) = set(probe_threads()) - set(before)
    assert mine.daemon
    hub.watch_hiccups()
    assert set(probe_threads()) - set(before) == {mine}
    hub.unwatch_hiccups()
    assert probe_threads() == before
    hub.watch_hiccups()  # restartable: a fork's child finds none alive
    assert len(probe_threads()) == len(before) + 1
    hub.close()
    assert probe_threads() == before


def test_one_probe_a_process_through_get_set_and_close():
    first = telemetry.get_telemetry()
    first.watch_hiccups()  # in case an earlier test closed the hub
    assert len(probe_threads()) == 1
    try:
        other = telemetry.set_telemetry(Telemetry())
        assert len(probe_threads()) == 1 and other._hiccups.alive
        assert first._hiccups is None
        telemetry.set_telemetry(other)  # the hub it already is
        assert len(probe_threads()) == 1
        other.close()
        assert not probe_threads()
    finally:
        telemetry.set_telemetry(first)
    assert len(probe_threads()) == 1


def test_the_real_thread_bears_witness_within_a_flush():
    hub = Telemetry()
    hub.watch_hiccups()
    try:
        threading.Event().wait(0.05)
    finally:
        hub.close()  # stops, joins, records what is pending
    probes = spans(hub, "host/probe")
    assert probes and sum(p.meta["wakes"] for p in probes) >= 1
    assert all(
        h.dur_s >= hub.HICCUP_SPAN_MIN_S for h in spans(hub, "host/hiccup"))
