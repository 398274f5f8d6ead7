"""The per-layer readers PR 25 adds and what they share
(``benchmarks/harness/layers.py``): the ``model.*`` shares on the trace
recorded on the v5e, the clock-anchor arithmetic and the expert matmuls'
scopes on hand-made data, and the serving loop's phase readers on a
hand-made span timeline. No device."""

import gzip
import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import layers, readers
from benchmarks.harness import trace as tr

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "train_v5e_small.json.gz", "rt") as f:
        return json.load(f)


def a_run(trace=None, scopes=None, observed=None, inventory=()):
    return readers.Run(
        cell=None, observed=observed, setup_s=0.0, inventory=inventory,
        device_kind="TPU v5 lite", trace=trace, scopes=scopes,
    )


# -- model.* on the recorded trace --------------------------------------------


def test_model_shares_on_the_recorded_trace(recorded):
    run = a_run(recorded["trace"], recorded["scopes"])
    share = {
        layer: readers.read(run, f"model.train_{layer}_device_pct")
        for layer in ("experts", "attention", "head_loss", "optimizer")
    }
    # PERF.md section 5, same cell: ragged-dot 8.1 %, head + CE 52.8 %,
    # optimizer 16.4 %, flash 5.4 % (the attention module holds its
    # projections too). The trace predates the moe/experts scopes, so the
    # custom calls are all of the expert time it can show.
    assert share["experts"] == pytest.approx(8.03, abs=0.05)
    assert share["head_loss"] == pytest.approx(52.8, abs=1.5)
    assert 16.0 < share["optimizer"] < 22.0
    assert 5.4 < share["attention"] < 10.0
    assert 60.0 < sum(share.values()) < 100.0
    # a reader looks at an op's own name and scope, never at its operands:
    # the accepted kernel metric's pattern also takes the fusions that
    # read a ragged-dot's result (0.1549 s), this takes the calls alone
    calls = layers.scope_seconds(
        recorded["trace"], {}, r"never", layers.RAGGED_CALL
    )
    busy = recorded["expected"]["busy_s"]
    assert 100 * calls / busy == pytest.approx(share["experts"])
    assert calls < recorded["expected"]["ragged_dot_s"]


def test_model_shares_need_a_trace():
    for name in ("model.train_experts_device_pct",
                 "model.decode_attention_device_pct"):
        assert readers.read(a_run(), name) is None


# -- the clock anchor ----------------------------------------------------------


def span(name, t0, dur, step=None):
    return types.SimpleNamespace(name=name, t0=t0, dur_s=dur, step=step)


def test_clock_anchor_places_registry_spans_on_the_trace():
    # program clock 100.0 s is 7.0 s on the trace's; the first anchor's
    # annotation opened 40 us after the clock was read, the second 10 us
    trace = {"devices": {"0": {"ops": [["%f = f32[] fusion()", 7.0, 1.0],
                                       ["%g = f32[] fusion()", 9.0, 1.0]],
                               "async": [], "modules": []}},
             "host": [["main", "d9d.clock/100000000000", 7.00004, 1e-6, None],
                      ["main", "serve.dispatch", 8.2, 0.3, None],
                      ["main", "d9d.clock/103000000000", 10.00001, 1e-6, None]]}
    assert layers.clock_shift(trace) == pytest.approx(-93.0 + 1e-5, abs=1e-9)
    assert layers.clock_shift({"host": [], "devices": {}}) is None
    placed = layers.spans_on_trace(
        trace, [span("serve/phase/commit", 101.0, 0.5),
                span("serve/phase/admit", 101.5, 0.5)]
    )
    assert [p[0] for p in placed] == ["serve/phase/commit",
                                      "serve/phase/admit"]
    assert placed[0][1:] == pytest.approx((8.00001, 8.50001))
    # the device idles from 8.0 to 9.0: half under each phase
    gaps = dict(tr.idle_gaps(trace, placed))
    assert gaps["serve/phase/commit"] == pytest.approx(0.5, abs=1e-4)
    assert gaps["serve/phase/admit"] == pytest.approx(0.5, abs=1e-4)
    assert layers.spans_on_trace({"host": [], "devices": {}}, placed) == []


# -- a scope for the expert matmuls' custom calls ------------------------------

HLO = """
  %concat.1 = bf16[8,64,256]{2,1,0} fusion(%gate, %up), kind=kLoop, metadata={op_name="jit(step)/jvp(M)/mlp/moe/experts/gate_up/concatenate"}
  %ragged-dot-metadata = (s32[9]{0}) custom-call(%sizes), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.3 = bf16[512,256]{1,0} custom-call(%meta.0, %meta.1, %rows.1, %concat.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %act.1 = bf16[512,128]{1,0} fusion(%ragged-dot-none.3), kind=kLoop, metadata={op_name="jit(step)/jvp(M)/mlp/moe/experts/act/mul"}
  ROOT %ragged-dot-none = bf16[8,64,256]{2,1,0} custom-call(%meta.0, %meta.1, %rows.1, %dgu.1), custom_call_target="tpu_custom_call"
"""


def test_every_ragged_dot_call_gets_the_experts_scope():
    plain = tr.scopes_from_hlo([HLO])
    assert plain["ragged-dot-none.3"] == "ragged-dot-none"  # what the compiler left
    scopes = layers.with_expert_matmuls(plain, [HLO])
    assert {k: v for k, v in scopes.items() if k.startswith("ragged-dot")} == {
        "ragged-dot-metadata": layers.RAGGED_SCOPE,
        "ragged-dot-none.3": layers.RAGGED_SCOPE,
        "ragged-dot-none": layers.RAGGED_SCOPE,  # no metadata at all
    }
    # every other instruction keeps the scope it had
    assert {k: v for k, v in scopes.items()
            if not k.startswith("ragged-dot")} == {
        k: v for k, v in plain.items() if not k.startswith("ragged-dot")}
    text = ('%ragged-dot-none.3 = bf16[512,256]{1,0} custom-call(s32[9]{0} '
            '%meta.0), custom_call_target="tpu_custom_call"')
    assert tr.label(text, plain) == "custom-call:ragged-dot-none"
    assert tr.label(text, scopes) == "custom-call:moe/experts/ragged_dot"
    # the accepted roofline readers find these calls by name, not by scope
    assert not layers.RAGGED_CALL.search(layers.RAGGED_SCOPE)


# -- the serving loop's phase readers ------------------------------------------


@pytest.fixture
def hub():
    from d9d_tpu import telemetry

    before = telemetry.get_telemetry()
    fresh = telemetry.set_telemetry(telemetry.Telemetry())
    yield fresh
    telemetry.set_telemetry(before)
    fresh.close()


def a_chunk(hub, step, t0, admit, plan, dispatch, readback, commit):
    t = t0
    for phase, dur in (("admit", admit), ("plan", plan),
                       ("dispatch", dispatch), ("readback", readback),
                       ("commit", commit)):
        hub.registry.record_span(f"serve/phase/{phase}", t, dur, step=step)
        t += dur
    hub.registry.record_span("serve/step", t0, t - t0, step=step)
    return t


def test_serving_phase_readers_on_a_hand_made_timeline(hub):
    ms = 1e-3
    t = a_chunk(hub, 0, 9.0, ms, ms, ms, 250 * ms, ms)  # before the window
    t = a_chunk(hub, 1, 10.0, 1 * ms, 2 * ms, 1 * ms, 250 * ms, 2 * ms)
    t = a_chunk(hub, 2, t, 2 * ms, 2 * ms, 1 * ms, 7000 * ms, 3 * ms)
    hub.registry.record_span("host/gc", t - 7.0, 0.4,
                             meta={"generation": 2, "collected": 5})
    hub.registry.record_span("host/gc", 9.5, 0.2)  # before the window
    end = a_chunk(hub, 3, t, 1 * ms, 2 * ms, 1 * ms, 250 * ms, 2 * ms)
    a_chunk(hub, 4, end, ms, ms, ms, 250 * ms, ms)  # the drain: after it
    observed = types.SimpleNamespace(opened_at=10.0, closed_at=end)
    run = a_run(observed=observed)

    # (6 + 8 + 6) ms of host work over three chunks
    assert readers.read(run, "serve.phase_host_ms_per_chunk") == \
        pytest.approx(20 / 3)
    assert readers.read(run, "serve.longest_chunk_ms") == pytest.approx(7008)
    assert run.notes["serve.longest_chunk"] == {
        "chunk": 2, "seconds": pytest.approx(7.008),
        "held_by": "serve/phase/readback", "held_seconds": pytest.approx(7.0),
    }
    assert "serve.longest_traced_chunk" not in run.notes
    assert readers.read(run, "serve.gc_pause_ms") == pytest.approx(400.0)

    # a traced run says the same of its traced seconds (here: the drain)
    observed.traced = (end, end + 1.0)
    readers.read(run, "serve.longest_chunk_ms")
    assert run.notes["serve.longest_traced_chunk"] == {
        "chunk": 4, "seconds": pytest.approx(0.254),
        "held_by": "serve/phase/readback", "held_seconds": pytest.approx(0.25),
    }


def test_serving_phase_readers_find_nothing_without_the_clock(hub):
    run = a_run(observed=types.SimpleNamespace(opened_at=0.0, closed_at=1.0))
    assert readers.read(run, "serve.phase_host_ms_per_chunk") is None
    assert readers.read(run, "serve.longest_chunk_ms") is None
    assert readers.read(run, "serve.gc_pause_ms") == 0.0


def test_lower_seconds_and_prompt_slot_steps():
    records = [types.SimpleNamespace(lower_s=1.5, compile_s=20.0),
               types.SimpleNamespace(lower_s=4.0, compile_s=0.1)]
    run = a_run(inventory=records)
    assert readers.read(run, "entry.lower_s") == 5.5
    assert readers.read(run, "entry.compile_s") == 25.6
    stats = {"slot_steps_prompt": 239, "slot_steps_busy": 1000}
    run = a_run(observed=types.SimpleNamespace(stats_window=stats))
    assert readers.read(run, "serve.prompt_slot_steps_pct") == \
        pytest.approx(23.9)
