"""The three readers of the process hub's hiccup probe (PR 54), each over
a hand-made span timeline: ``serve.host_hiccup_ms``,
``serve.longest_chunk_hiccup_ms`` and ``train.host_hiccup_ms``. No
device, no thread: the probe is stopped and the spans are written by
hand."""

import types

import pytest

from benchmarks.harness import manifest, readers

MS = 1e-3
SERVING = "serve-"
NEW = {
    "serve.host_hiccup_ms": ("serve_tpot_p95_ms", "serving loop"),
    "serve.longest_chunk_hiccup_ms": ("serve_ttft_p95_ms", "serving loop"),
    "train.host_hiccup_ms": ("train_tokens_per_s_per_chip", "training loop"),
}


@pytest.fixture
def hub():
    from d9d_tpu import telemetry

    before = telemetry.get_telemetry()
    fresh = telemetry.set_telemetry(telemetry.Telemetry())
    fresh.unwatch_hiccups()  # the spans below are the only ones
    yield fresh
    telemetry.set_telemetry(before)
    fresh.close()


def a_run(opened_at=100.0, closed_at=110.0, trace=None, traced=None):
    observed = types.SimpleNamespace(
        opened_at=opened_at, closed_at=closed_at, traced=traced)
    return readers.Run(cell=None, observed=observed, setup_s=0.0,
                       inventory=(), device_kind="TPU v5 lite", trace=trace)


def witnesses(hub, start=99.5, seconds=12, late_us=80.0):
    """A ``host/probe`` a second from ``start``: 100 wake-ups each."""
    for i in range(seconds):
        hub.registry.record_span("host/probe", start + i, 1.0, meta={
            "wakes": 100, "late_sum_s": 100 * late_us * 1e-6,
            "late_max_s": 2 * late_us * 1e-6})


def a_chunk(hub, step, t0, phases=(1, 2, 3, 90, 4)):
    """One chunk's phases (milliseconds) and its ``serve/step``; returns
    where each phase starts and the chunk's end."""
    t, starts = t0, {}
    for phase, dur in zip(
            ("admit", "plan", "dispatch", "readback", "commit"), phases):
        starts[phase] = t
        hub.registry.record_span(
            f"serve/phase/{phase}", t, dur * MS, step=step)
        t += dur * MS
    hub.registry.record_span("serve/step", t0, t - t0, step=step)
    return starts, t


def hiccup(hub, t0, ms):
    hub.registry.record_span("host/hiccup", t0, ms * MS)


def read(run, name):
    value = readers.read(run, name)
    return value, {
        k[len(name) + 1:]: v for k, v in run.notes.items()
        if k.startswith(name + ".")
    }


# -- serve.host_hiccup_ms ------------------------------------------------------


def test_the_windows_hiccups_are_summed_and_split_by_what_they_overlap(hub):
    witnesses(hub)
    hiccup(hub, 99.0, 500)  # before the window
    starts, t = a_chunk(hub, 1, 100.0)
    hiccup(hub, starts["readback"] + 0.010, 40)  # inside the readback
    starts, t = a_chunk(hub, 2, t, phases=(1, 2, 3, 90, 64))
    hub.registry.record_span(
        "host/gc", starts["commit"] + 0.002, 0.060, meta={"generation": 2})
    hiccup(hub, starts["commit"] + 0.004, 55)  # inside the collection
    starts, t = a_chunk(hub, 3, t, phases=(1, 30, 3, 90, 4))
    hiccup(hub, starts["plan"] + 0.001, 25)  # the interpreter, or the host
    hiccup(hub, 109.990, 30)  # straddles the window's end: 10 ms of it
    hiccup(hub, 111.0, 700)  # the drain
    value, notes = read(a_run(), "serve.host_hiccup_ms")
    assert value == pytest.approx(40 + 55 + 25 + 10)
    assert notes == {
        "count": 4, "longest_ms": pytest.approx(55.0),
        "gc_ms": pytest.approx(55.0), "readback_ms": pytest.approx(40.0),
        "other_ms": pytest.approx(35.0),
        "probe_wakes": pytest.approx(1000.0),
        "mean_wake_late_us": pytest.approx(80.0),
    }


def test_a_quiet_window_with_a_witness_reads_zero_not_nothing(hub):
    witnesses(hub, late_us=120.0)
    a_chunk(hub, 1, 100.0)
    value, notes = read(a_run(), "serve.host_hiccup_ms")
    assert value == 0.0
    assert notes["count"] == 0 and notes["longest_ms"] == 0.0
    assert notes["mean_wake_late_us"] == pytest.approx(120.0)


def test_the_witness_counts_by_the_share_of_a_second_inside_the_window(hub):
    # one witness of 1.25 s with 120 wake-ups, four fifths of it inside
    hub.registry.record_span("host/probe", 99.75, 1.25, meta={
        "wakes": 120, "late_sum_s": 0.024, "late_max_s": 0.004})
    _, notes = read(a_run(), "serve.host_hiccup_ms")
    assert notes["probe_wakes"] == pytest.approx(96.0)
    assert notes["mean_wake_late_us"] == pytest.approx(200.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_window_without_a_witness_gives_nothing_to_read(hub, name):
    witnesses(hub, start=50.0, seconds=3)  # long before the window
    t = 100.0
    for step in range(1, 4):
        _, t = a_chunk(hub, step, t)
        hub.registry.record_span("train/step", t - 0.1, 0.1, step=step)
    hiccup(hub, 100.5, 200)  # a span, but of a probe that bore no witness
    run = a_run()
    assert readers.read(run, name) is None
    assert not run.notes


def test_a_traced_run_reads_the_idle_under_the_traced_hiccups(hub):
    witnesses(hub, seconds=16)
    a_chunk(hub, 1, 100.0)
    hiccup(hub, 112.10, 60)  # in the traced seconds: 40 ms of it idle
    # the anchor: perf_counter 112.0 is the trace's second 5.0
    trace = {
        "host": [["main", f"d9d.clock/{int(112.0 * 1e9)}", 5.0, 1e-6, None]],
        "devices": {"0": {"modules": [], "async": [], "ops": [
            ["%fusion.1 = f32[] fusion()", 5.0, 0.12],
            ["%fusion.2 = f32[] fusion()", 5.16, 0.34],
        ]}},
    }
    value, notes = read(
        a_run(trace=trace, traced=(112.0, 112.5)), "serve.host_hiccup_ms")
    assert value == 0.0
    assert notes["traced_idle_ms"] == pytest.approx(40.0)
    # traced seconds the probe bore no witness of say nothing of idle
    _, notes = read(
        a_run(trace=trace, traced=(120.0, 124.0)), "serve.host_hiccup_ms")
    assert "traced_idle_ms" not in notes


# -- serve.longest_chunk_hiccup_ms -----------------------------------------------


def chunks_with_one_long(hub, long_phases):
    witnesses(hub)
    t = 100.0
    for step in range(1, 6):
        starts, t = a_chunk(
            hub, step, t, phases=long_phases if step == 3 else (1, 2, 3, 90, 4))
        if step == 3:
            long_starts = starts
    return long_starts


def test_a_late_readback_under_a_hiccup_is_the_hosts(hub):
    starts = chunks_with_one_long(hub, (1, 2, 3, 170, 4))  # 80 ms late
    hiccup(hub, starts["readback"] + 0.085, 70)
    hiccup(hub, 100.01, 15)  # in another chunk: not this one's
    value, notes = read(a_run(), "serve.longest_chunk_hiccup_ms")
    assert value == pytest.approx(70.0)
    note = notes["chunk"]
    assert note["chunk"] == 3 and note["verdict"] == "host"
    assert note["seconds"] == pytest.approx(0.180)
    assert note["median_seconds"] == pytest.approx(0.100)
    assert note["by_phase_ms"] == {
        "admit": 0.0, "plan": 0.0, "dispatch": 0.0,
        "readback": pytest.approx(70.0), "commit": 0.0}
    assert note["gc_ms"] == 0.0
    assert note["outside_gc_ms"] == pytest.approx(70.0)


def test_a_long_chunk_under_a_collection_is_the_collectors(hub):
    starts = chunks_with_one_long(hub, (1, 2, 3, 90, 150))
    hub.registry.record_span(
        "host/gc", starts["commit"] + 0.001, 0.142, meta={"generation": 2})
    hiccup(hub, starts["commit"] + 0.003, 138)  # the probe waited too
    value, notes = read(a_run(), "serve.longest_chunk_hiccup_ms")
    assert value == pytest.approx(138.0)
    note = notes["chunk"]
    assert note["verdict"] == "gc"
    assert note["gc_ms"] == pytest.approx(142.0)
    assert note["outside_gc_ms"] == pytest.approx(0.0, abs=1e-9)
    assert note["by_phase_ms"]["commit"] == pytest.approx(138.0)


def test_a_late_readback_with_the_host_awake_is_the_devices_or_runtimes(hub):
    starts = chunks_with_one_long(hub, (1, 2, 3, 170, 4))
    hiccup(hub, starts["readback"] + 0.020, 12)  # a sixth of the excess
    value, notes = read(a_run(), "serve.longest_chunk_hiccup_ms")
    assert value == pytest.approx(12.0)
    assert notes["chunk"]["verdict"] == "device_or_runtime"


def test_a_window_of_equal_chunks_blames_no_one(hub):
    witnesses(hub)
    t = 100.0
    for step in range(1, 4):
        _, t = a_chunk(hub, step, t)
    value, notes = read(a_run(), "serve.longest_chunk_hiccup_ms")
    assert value == 0.0
    assert notes["chunk"]["verdict"] == "device_or_runtime"


# -- train.host_hiccup_ms ----------------------------------------------------------


def test_a_training_windows_hiccups_and_its_longest_period(hub):
    witnesses(hub)
    t = 100.0
    for step, seconds in enumerate((0.5, 0.5, 1.1, 0.5, 0.5), start=20):
        hub.registry.record_span("train/step", t, seconds, step=step)
        if step == 22:  # the long fetch period
            hiccup(hub, t + 0.45, 580)
            hub.registry.record_span(
                "host/gc", t + 0.46, 0.030, meta={"generation": 2})
        t += seconds
    hiccup(hub, 100.2, 20)
    value, notes = read(a_run(), "train.host_hiccup_ms")
    assert value == pytest.approx(600.0)
    assert notes["count"] == 2
    assert notes["longest_ms"] == pytest.approx(580.0)
    assert notes["gc_ms"] == pytest.approx(30.0)
    assert notes["probe_wakes"] == pytest.approx(1000.0)
    assert notes["longest_period"] == {
        "step": 22, "seconds": pytest.approx(1.1),
        "hiccup_ms": pytest.approx(580.0)}
    assert "readback_ms" not in notes


# -- the manifest ------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_metric_is_listed_for_its_cells_by_name(name):
    bench = manifest.manifest()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    moves, layer = NEW[name]
    assert (entry["moves"], entry["layer"]) == (moves, layer)
    assert (entry["source"], entry["better"], entry["unit"]) == (
        "program_span", "lower", "ms")
    cells = {w["name"]: w for w in bench["workloads"]}
    serving = name.startswith("serve.")
    assert set(entry["workloads"]) == {
        c for c, w in cells.items() if (SERVING in w["traffic"]) == serving}
    reports = next(
        m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(entry["workloads"]) <= set(reports.get("workloads", cells))
    for cell in entry["workloads"]:
        assert name in [m["name"] for m in manifest.cell(cell).per_layer]


def test_the_three_stand_in_the_issues_order():
    names = [m["name"] for m in manifest.manifest()["per_layer"]]
    at = [names.index(n) for n in (
        "serve.host_hiccup_ms", "serve.longest_chunk_hiccup_ms",
        "train.host_hiccup_ms")]
    assert at == sorted(at)
