"""The readers PR 37 adds, each over a hand-made ``Run``: the serving
chunk's dispatch split (``dispatch_key_s``, ``dispatch_enqueue_s``,
``dispatch_arg_leaves``, ``stage_s`` on the ``serve/step`` spans), the
device's idle time by the program's own phase spans, and the dropless
exchange's two counters on the ``train/step`` spans. No device."""

import types

import pytest

from benchmarks.harness import manifest, readers

MS = 1e-3
SERVING_CELLS = ["qwen3-30b-a3b-decode.serve-rollout-closed",
                 "glm-4.7-flash-decode.serve-reason-closed",
                 "jamba2-3b-decode.serve-reason-closed",
                 "mimo-v2-flash-share16-decode.serve-reason-closed"]
SPLIT = ["serve.dispatch_key_ms_per_chunk",
         "serve.dispatch_enqueue_ms_per_chunk", "serve.stage_ms_per_chunk",
         "serve.dispatch_arg_leaves", "serve.phase_gap_ms_per_chunk"]
EP = ["moe.ep_buffer_fill_pct", "moe.ep_fallback_pct"]


@pytest.fixture
def hub():
    from d9d_tpu import telemetry

    before = telemetry.get_telemetry()
    fresh = telemetry.set_telemetry(telemetry.Telemetry())
    yield fresh
    telemetry.set_telemetry(before)
    fresh.close()


def a_run(opened_at=10.0, closed_at=20.0, trace=None, traced=None):
    observed = types.SimpleNamespace(
        opened_at=opened_at, closed_at=closed_at, traced=traced)
    return readers.Run(cell=None, observed=observed, setup_s=0.0,
                       inventory=(), device_kind="TPU v5 lite", trace=trace)


def a_chunk(hub, step, t0, key_ms, enqueue_ms, stage_ms, leaves,
            phases=(1, 2, 3, 90, 4)):
    """One chunk's phase spans (milliseconds, in the clock's order) and
    its closing ``serve/step`` with the dispatch's split on its meta."""
    t = t0
    for phase, dur in zip(
        ("admit", "plan", "dispatch", "readback", "commit"), phases
    ):
        hub.registry.record_span(
            f"serve/phase/{phase}", t, dur * MS, step=step)
        t += dur * MS
    meta = {"rows_reset": 0}
    if leaves is not None:
        meta.update(
            dispatch_key_s=key_ms * MS, dispatch_enqueue_s=enqueue_ms * MS,
            stage_s=stage_ms * MS, dispatch_arg_leaves=leaves,
            stage_transfers=4)
    hub.registry.record_span("serve/step", t0, t - t0, step=step, meta=meta)
    return t


@pytest.mark.parametrize("name,expected", [
    ("serve.dispatch_key_ms_per_chunk", (0.6 + 1.0 + 0.8) / 3),
    ("serve.dispatch_enqueue_ms_per_chunk", (0.3 + 0.5 + 0.4) / 3),
    ("serve.stage_ms_per_chunk", (0.5 + 1.5 + 0.7) / 3),
    ("serve.dispatch_arg_leaves", 133.0),
])
def test_the_dispatch_split_is_read_from_the_windows_chunks(
        hub, name, expected):
    a_chunk(hub, 0, 9.0, 50.0, 50.0, 50.0, 999)  # before the window
    t = a_chunk(hub, 1, 10.0, 0.6, 0.3, 0.5, 130)
    t = a_chunk(hub, 2, t, 1.0, 0.5, 1.5, 133)  # the chunk with admission
    t = a_chunk(hub, 3, t, 0.8, 0.4, 0.7, 130)
    a_chunk(hub, 4, 20.5, 70.0, 70.0, 70.0, 999)  # the drain: after it
    assert readers.read(a_run(), name) == pytest.approx(expected)


@pytest.mark.parametrize("name", SPLIT[:4])
def test_spans_without_the_keys_give_nothing_to_read(hub, name):
    """The parent's ``serve/step`` spans carry none of the keys, and a
    run with no chunk in its window has no span at all."""
    assert readers.read(a_run(), name) is None
    a_chunk(hub, 1, 10.0, 0, 0, 0, None)
    assert readers.read(a_run(), name) is None


def a_trace(anchor_at=8.0, reading=100.0):
    """Three executions of the fused program on the device, one every
    100 ms from 8.05 s of the trace's clock, each busy for 90 ms: the
    device idles 10 ms before each and the profile ends with the last.
    The program's clock reads ``reading`` where the trace's reads
    ``anchor_at``."""
    ops, modules = [], []
    for i in range(3):
        start = anchor_at + 0.05 + 0.1 * i
        ops.append(["%fusion.1 = f32[] fusion()", start, 0.09])
        modules.append(["jit_fused_fn(7)", start, 0.09, i])
    name = f"d9d.clock/{int(reading * 1e9)}"
    return {"devices": {"0": {"ops": ops, "async": [], "modules": modules}},
            "host": [["main", name, anchor_at, 1e-6, None]]}


def test_idle_time_is_owned_by_the_programs_phase_spans(hub):
    """Idle before an execution falls in the chunk's commit (4 ms of the
    last chunk), then the next one's admit, plan and dispatch (1 + 2 + 3
    ms): the four host phases own the 10 ms and the readback none, so
    the two gaps between three executions give 20 ms over 3 chunks."""
    # chunk n's dispatch phase ends where execution n starts
    for i in range(3):
        a_chunk(hub, i, 100.05 + 0.1 * i - 0.006, 0, 0, 0, 130)
    hub.registry.record_span("host/gc", 100.141, 0.002)  # inside a commit
    run = a_run(trace=a_trace(), traced=(100.0, 100.4))
    assert readers.read(run, "serve.phase_gap_ms_per_chunk") == \
        pytest.approx(20.0 / 3, abs=1e-3)
    # the parent has the phase spans too: the reader needs no new key
    for span in hub.registry.spans:
        if span.name == "serve/step":
            span.meta.clear()
    assert readers.read(run, "serve.phase_gap_ms_per_chunk") == \
        pytest.approx(20.0 / 3, abs=1e-3)


def test_no_trace_no_anchor_or_no_chunk_reads_no_phase_gap(hub):
    name = "serve.phase_gap_ms_per_chunk"
    a_chunk(hub, 0, 100.044, 0, 0, 0, 130)
    assert readers.read(a_run(traced=(100.0, 100.4)), name) is None
    # a --trace 1 line of a training cell: no traced seconds of chunks
    assert readers.read(a_run(trace=a_trace()), name) is None
    unanchored = dict(a_trace(), host=[])
    assert readers.read(
        a_run(trace=unanchored, traced=(100.0, 100.4)), name) is None
    empty = {"devices": {}, "host": []}
    assert readers.read(
        a_run(trace=empty, traced=(100.0, 100.4)), name) is None
    none_ran = a_trace()
    none_ran["devices"]["0"]["modules"] = []
    assert readers.read(
        a_run(trace=none_ran, traced=(100.0, 100.4)), name) is None


@pytest.mark.parametrize("name,expected", [
    ("moe.ep_buffer_fill_pct", 100.0 * (0.8 + 0.7) / 2),
    ("moe.ep_fallback_pct", 100.0 * (0.0 + 0.25) / 2),
])
def test_the_exchanges_counters_are_the_windows_fetched_steps(
        hub, name, expected):
    record = hub.registry.record_span
    record("train/step", 5.0, 0.4, step=2, meta={  # warm-up
        "moe/ep_buffer_fill": 0.1, "moe/ep_fallback_share": 1.0})
    record("train/step", 11.0, 0.4, step=10, meta={
        "moe/ep_buffer_fill": 0.8, "moe/ep_fallback_share": 0.0})
    record("train/step", 12.0, 0.4, step=11)  # a step that fetched nothing
    record("train/step", 15.0, 0.4, step=20, meta={
        "moe/ep_buffer_fill": 0.7, "moe/ep_fallback_share": 0.25})
    assert readers.read(a_run(), name) == pytest.approx(expected)


@pytest.mark.parametrize("name", EP)
def test_a_program_with_no_exchange_reads_no_exchange_counter(hub, name):
    hub.registry.record_span("train/step", 11.0, 0.4, step=10, meta={
        "moe/rows_held": 1.0, "moe/rows_routed": 8.0})
    assert readers.read(a_run(), name) is None


def check_a_dispatch_metric_is_listed_for_the_serving_cells(
        name, root=manifest.ROOT):
    """Listed since PR 43, entries alone: each file holds what its entry
    repeats, and a reader; every serving cell emits every key."""
    by_name = {m["name"]: m for m in manifest.manifest(root)["per_layer"]}
    entry = by_name[name]
    # every serving cell of PR 43; a later serving cell adds its name
    assert set(SERVING_CELLS) <= set(entry["workloads"])
    own = manifest.metric_file(name, root=root)
    assert own["name"] == name and own["reader"] == {"file": True}
    assert (root / "benchmarks/metrics" / f"{name}.py").is_file()
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key], key
    assert own["layer"] == "serving loop"
    assert own["moves"] == "serve_tokens_per_s"
    assert own["better"] == "lower"
    assert own["unit"] == ("leaves" if name.endswith("arg_leaves") else "ms")
    # the one counter of the five is what a CPU run prints
    assert (own["source"] == "program_counter") == \
        (name == "serve.dispatch_arg_leaves")
    assert not {"kinds", "min_chips", "workloads"} & set(own)


@pytest.mark.parametrize("name", SPLIT)
def test_a_dispatch_metric_is_listed_for_the_serving_cells(name):
    check_a_dispatch_metric_is_listed_for_the_serving_cells(name)


def check_the_manifest_lists_the_exchanges_counters_fill_before_fallback(
        root=manifest.ROOT):
    per_layer = manifest.manifest(root)["per_layer"]
    by_name = {m["name"]: m for m in per_layer}
    for name in EP:
        assert by_name[name]["workloads"] == ["qwen3-30b-a3b-ep4.train-16k"]
        assert by_name[name]["source"] == "program_counter"
    names = [m["name"] for m in per_layer]
    assert names.index(EP[0]) < names.index(EP[1])


def test_the_manifest_lists_the_exchanges_counters_fill_before_fallback():
    check_the_manifest_lists_the_exchanges_counters_fill_before_fallback()
