"""The Mamba-1 decode step's cost function against hand arithmetic at
AI21-Jamba2-3B's published sizes, and this PR's three readers on
hand-made observations: what they read, and that a program without the
spans, counters or scopes gives them nothing to read (the parent commit
under these files)."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, peaks, readers
from benchmarks.metrics import ssm_decode_cost
from tests.benchmarks.hand_made import program, ran_by

ROOT = Path(__file__).resolve().parents[2]
JAMBA = json.loads(
    (ROOT / "benchmarks/configs/jamba2-3b-decode.json").read_text()
)
SLOTS = JAMBA["serving"]["slots"]


def test_the_state_is_84_mb_a_layer_and_moves_twice_a_step():
    # 256 rows x 5,120 channels x 16 state numbers in float32. (ISSUE 32
    # writes "2 x 256 x 5120 x 16 x 4 B = 42 MB": that product is 41.9 M
    # numbers; in bytes it is 167.8 MB, read and written.)
    assert SLOTS == 256
    assert ssm_decode_cost.state_bytes(JAMBA, SLOTS) == 83_886_080
    assert ssm_decode_cost.mamba_layers(JAMBA) == 26
    resident = 26 * ssm_decode_cost.state_bytes(JAMBA, SLOTS)
    assert round(resident / 1e9, 2) == 2.18
    assert round(2 * resident / 1e9, 2) == 4.36  # moved a step


def test_one_layer_one_step_by_hand():
    one = ssm_decode_cost.layer_step(JAMBA, SLOTS)
    d_inner, n, k = 5120, 16, 4
    state = 2 * SLOTS * d_inner * n * 4
    tail = 2 * SLOTS * (k - 1) * d_inner * 2
    operands = SLOTS * (d_inner * (2 + 2 + 4) + 2 * n * 4)  # xs, z, dt; B, C
    assert state == 167_772_160
    assert one["bytes"] == state + tail + operands
    assert one["flops"] == SLOTS * d_inner * (7 * n + 2 * k + 4)
    # memory-bound by three orders: 0.24 ms a layer a step at 819 GB/s
    least, bound = costs.roofline_seconds(one, peaks.peak_for("TPU v5 lite"))
    assert bound == "memory"
    assert least == pytest.approx(0.238e-3, rel=0.02)


def test_the_projections_ride_along_by_hand():
    """The mixer's 41.2 M parameters in bf16 once a step and two
    operations a matmul weight a row: 85 MB against the state's 194, and
    still memory-bound at 256 rows."""
    got = ssm_decode_cost.projections(JAMBA, SLOTS)
    e, d_inner, n, rank, k = 2560, 5120, 16, 160, 4
    matmuls = e * 2 * d_inner + d_inner * (rank + 2 * n) + rank * d_inner \
        + d_inner * e
    small = d_inner * (1 + k + 1 + n + 1) + rank + 2 * n
    assert matmuls + small == 41_241_792  # test_jamba's 412 x 1e5
    assert got["bytes"] == (matmuls + small) * 2 + 2 * SLOTS * e * 2
    assert got["flops"] == 2 * SLOTS * matmuls
    peak = peaks.peak_for("TPU v5 lite")
    both = {
        key: got[key] + ssm_decode_cost.layer_step(JAMBA, SLOTS)[key]
        for key in got
    }
    least, bound = costs.roofline_seconds(both, peak)
    assert bound == "memory"
    assert least == pytest.approx(0.341e-3, rel=0.02)


def test_work_scales_with_the_mamba_layers_and_the_steps():
    work = ssm_decode_cost.ssm_decode_work(JAMBA, SLOTS, steps=15 * 8)
    one = ssm_decode_cost.layer_step(JAMBA, SLOTS)
    around = ssm_decode_cost.projections(JAMBA, SLOTS)
    assert work["bytes"] == (one["bytes"] + around["bytes"]) * 26 * 120
    assert work["flops"] == (one["flops"] + around["flops"]) * 26 * 120
    # 8.9 ms of every decode step, whatever the contexts
    least, _ = costs.roofline_seconds(work, peaks.peak_for("TPU v5 lite"))
    assert least / 120 == pytest.approx(8.86e-3, rel=0.02)
    # 14 layers keep one period: 13 mixers
    assert ssm_decode_cost.mamba_layers(dict(JAMBA, num_hidden_layers=14)) == 13


# -- the readers ---------------------------------------------------------------


def span(name, t0, dur_s, step, meta=None):
    return types.SimpleNamespace(
        name=name, t0=t0, dur_s=dur_s, step=step, meta=meta
    )


def run_of(config=JAMBA, **observed):
    cell = types.SimpleNamespace(config=config)
    o = types.SimpleNamespace(
        stats_window={}, opened_at=10.0, closed_at=20.0, chunk_k=8,
        slots=SLOTS, **observed,
    )
    return readers.Run(cell=cell, observed=o, setup_s=0.0, inventory=(),
                       device_kind="TPU v5 lite")


def with_timeline(monkeypatch, spans):
    from benchmarks.harness import layers

    monkeypatch.setattr(layers, "program_spans", lambda: list(spans))


STATE = {"recurrent_state_bytes": 2_589_982_720, "rows_reset": 3}
OPS = [
    ("%fusion.1 = f32[256,16,5120] fusion(%a)", 30.00, 0.004),
    ("%fusion.2 = f32[256,3,5120] fusion(%b)", 30.01, 0.002),
    ("%fusion.3 = f32[512,10240] fusion(%c)", 30.02, 0.010),
    ("%fusion.4 = f32[256,2560] fusion(%d)", 30.04, 0.003),
]
SCOPES = {
    "fusion.1": "jit(f)/while/body/model/layers_1/mamba/mamba/state_update/mul",
    "fusion.2": "jit(f)/while/body/model/layers_1/mamba/mamba/conv/conv1d/add",
    "fusion.3": "jit(f)/while/body/model/layers_1/mamba/mamba/in_proj/in_proj/dot",
    "fusion.4": "jit(f)/while/body/model/layers_7/self_attn/o_proj/dot",
}


def test_recurrent_state_gb_is_the_windows_count(monkeypatch):
    with_timeline(monkeypatch, [
        span("serve/step", 5.0, 0.2, 0, {"recurrent_state_bytes": 1}),  # pre-roll
        span("serve/step", 11.0, 0.2, 1, STATE),
        span("serve/step", 12.0, 0.2, 2, STATE),
        span("serve/phase/admit", 12.0, 0.01, 2),
    ])
    assert readers.read(run_of(), "serve.recurrent_state_gb") == \
        pytest.approx(2.58998272)
    # the parent's serve/step spans carry no such count
    with_timeline(monkeypatch, [
        span("serve/step", 11.0, 0.2, 1, {"pool_pages": 4}),
        span("serve/step", 12.0, 0.2, 2),
    ])
    assert readers.read(run_of(), "serve.recurrent_state_gb") is None


def test_ssm_share_is_the_mixers_scope_over_busy_time():
    run = ran_by(run_of(), OPS, SCOPES)
    assert readers.read(run, "model.decode_ssm_device_pct") == \
        pytest.approx(100.0 * 0.016 / 0.019)
    # a program with no op under a mixer's scope, or no trace: nothing
    run.programs = (program({"fusion.4": SCOPES["fusion.4"]}),)
    assert readers.read(run, "model.decode_ssm_device_pct") is None
    assert readers.read(run_of(), "model.decode_ssm_device_pct") is None


def test_roofline_share_from_the_traced_steps(monkeypatch):
    with_timeline(monkeypatch, [
        span("serve/step", 15.0, 0.2, 3, STATE),   # the window's: left out
        span("serve/step", 30.0, 0.2, 9, STATE),   # inside the capture
        span("serve/step", 30.3, 0.2, 10, STATE),
    ])
    run = ran_by(run_of(traced=(29.9, 31.0)), OPS, SCOPES)
    want = ssm_decode_cost.ssm_decode_work(JAMBA, SLOTS, steps=16)
    least, _ = costs.roofline_seconds(want, run.peak)
    got = readers.read(run, "kernel.ssm_decode_roofline")
    # every op under a mixer's scope, its projections too (the state's
    # traffic hides under them), and not the attention layer's
    assert got == pytest.approx(100.0 * least / 0.016)
    assert run.notes["kernel.ssm_decode_roofline.bound"] == "memory"
    assert run.notes["kernel.ssm_decode_roofline.traced_chunks"] == 2
    # no capture, no op under the scopes, spans without the count, or a
    # configuration without the family's keys: nothing
    assert readers.read(run_of(traced=None), "kernel.ssm_decode_roofline") is None
    run.programs = (program({}),)
    assert readers.read(run, "kernel.ssm_decode_roofline") is None
    run.programs = (program(SCOPES),)
    with_timeline(monkeypatch, [span("serve/step", 30.0, 0.2, 9)])
    assert readers.read(run, "kernel.ssm_decode_roofline") is None
    other = ran_by(
        run_of(config={"hidden_size": 2048}, traced=(29.9, 31.0)), OPS, SCOPES)
    assert readers.read(other, "kernel.ssm_decode_roofline") is None
