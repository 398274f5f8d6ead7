"""``serve.reset_rows_ms_per_admitting_chunk`` on hand-made observations:
the device time under ``serve/reset_rows`` (the loop of row writes and
the writes inside it) over the traced chunks whose ``serve/step`` span
says it admitted rows; traced seconds in which none did, a program
without the scope or without the count, give nothing to read."""

import types

import pytest

from benchmarks.harness import layers, manifest, readers
from tests.benchmarks.hand_made import program, ran_by

NAME = "serve.reset_rows_ms_per_admitting_chunk"
CELLS = ["jamba2-3b-decode.serve-reason-closed",
         "granite-4.0-h-small-share4-decode.serve-reason-closed",
         "solar-open2-250b-share8-decode.serve-reason-closed"]
RESET = "jit(fused_fn)/serve/reset_rows/"
OPS = [
    # the loop holds its row writes: its own time is what they leave over
    ("%while.1 = (s32[], f32[256,64,128,128]) while(%t)", 30.000, 0.00030),
    ("%fusion.2 = f32[256,64,128,128] fusion(%a)", 30.00002, 0.00010),
    ("%fusion.3 = bf16[256,3,24576] fusion(%b)", 30.00014, 0.00012),
    ("%fusion.4 = s32[256] fusion(%c)", 30.001, 0.00002),
    ("%fusion.5 = f32[256,64,128,128] fusion(%d)", 30.010, 0.150),
    ("%while.1 = (s32[], f32[256,64,128,128]) while(%t)", 30.300, 0.00020),
]
SCOPES = {
    "while.1": RESET + "while",
    "fusion.2": RESET + "while/body/dynamic_update_slice",
    "fusion.3": RESET + "while/body/dynamic_update_slice",
    "fusion.4": RESET + "select_n",
    "fusion.5": "jit(fused_fn)/while/body/closed_call/M.logits_last/model/"
                "layers_1/kda/kda/state_update/kda_step/pallas_call",
}


def span(t0, step, rows_reset=None):
    meta = None if rows_reset is None else {
        "rows_reset": rows_reset, "recurrent_state_bytes": 1}
    return types.SimpleNamespace(
        name="serve/step", t0=t0, dur_s=0.2, step=step, meta=meta)


def a_run(monkeypatch, spans, traced=(29.9, 31.0)):
    monkeypatch.setattr(layers, "program_spans", lambda: list(spans))
    run = readers.Run(
        cell=None, observed=types.SimpleNamespace(traced=traced),
        setup_s=0.0, inventory=(), device_kind="TPU v5 lite")
    return ran_by(run, OPS, SCOPES)


def test_it_reads_the_resets_device_time_a_chunk_that_admitted(monkeypatch):
    run = a_run(monkeypatch, [
        span(15.0, 3, rows_reset=9),   # the window's: left out
        span(30.0, 9, rows_reset=3),   # inside the capture, admitting
        span(30.3, 10, rows_reset=1),
        span(30.6, 11, rows_reset=0),  # a chunk that admitted nothing
    ])
    # the two loops and the mask, never the mixer's step: 0.52 ms over two
    assert readers.read(run, NAME) == pytest.approx(0.26)
    assert run.notes[NAME + ".admitting_chunks"] == 2
    assert run.notes[NAME + ".device_s"] == pytest.approx(0.00052)


def test_nothing_to_read_gives_nothing(monkeypatch):
    admitting = [span(30.0, 9, rows_reset=3)]
    # traced seconds in which no chunk admitted, or spans without the count
    assert readers.read(
        a_run(monkeypatch, [span(30.0, 9, rows_reset=0)]), NAME) is None
    assert readers.read(a_run(monkeypatch, [span(30.0, 9)]), NAME) is None
    # a program with no op under the scope (no per-row state to clear)
    run = a_run(monkeypatch, admitting)
    run.programs = (program({"fusion.5": SCOPES["fusion.5"]}),)
    assert readers.read(run, NAME) is None
    # no capture
    assert readers.read(a_run(monkeypatch, admitting, traced=None), NAME) is None
    run = a_run(monkeypatch, admitting)
    run.trace = None
    assert readers.read(run, NAME) is None


def test_the_entry_lists_the_cells_that_keep_recurrent_state():
    entry, = (m for m in manifest.manifest()["per_layer"]
              if m["name"] == NAME)
    # a later cell with such state appends itself to both lists
    assert set(CELLS) <= set(entry["workloads"])
    state, = (m for m in manifest.manifest()["per_layer"]
              if m["name"] == "serve.recurrent_state_gb")
    assert set(entry["workloads"]) <= set(state["workloads"])
    own = manifest.metric_file(NAME)
    assert own["reader"] == {"file": True}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key], key
    assert (own["unit"], own["better"], own["source"], own["layer"],
            own["moves"]) == (
        "ms", "lower", "device_trace", "serving loop", "serve_tokens_per_s")
