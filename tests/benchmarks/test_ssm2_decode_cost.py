"""The Mamba-2 decode step's cost function against hand arithmetic at
granite-4.0-h-small's published sizes, and its reader on hand-made
observations: what it reads, and that a program without the spans, or a
configuration of another recurrence, gives it nothing to read."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, peaks, readers
from benchmarks.metrics import ssm2_decode_cost
from tests.benchmarks.hand_made import program, ran_by
from tests.conftest import load_repo_module

# the hand-made spans and traces of the Mamba-1 reader's tests
_ssm = load_repo_module(
    "bench_ssm_decode_cost", "tests/benchmarks/test_ssm_decode_cost.py")
span, with_timeline = _ssm.span, _ssm.with_timeline

ROOT = Path(__file__).resolve().parents[2]
GRANITE = json.loads((
    ROOT / "benchmarks/configs/granite-4.0-h-small-share4-decode.json"
).read_text())
JAMBA = json.loads(
    (ROOT / "benchmarks/configs/jamba2-3b-decode.json").read_text())
SLOTS = GRANITE["serving"]["slots"]
PEAK = peaks.peak_for("TPU v5 lite")


def test_the_state_is_537_mb_a_layer_and_moves_twice_a_step():
    # 128 rows x 128 heads x 64 x 128 state numbers in float32: 4.19 MB a
    # layer a caller, where Jamba's is 0.33
    assert SLOTS == 128
    assert ssm2_decode_cost.widths(GRANITE) == (8192, 8448, 128)
    assert ssm2_decode_cost.state_bytes(GRANITE, 1) == 4_194_304
    assert ssm2_decode_cost.state_bytes(GRANITE, SLOTS) == 536_870_912
    # one period of the 40-long pattern: attention at 5
    assert ssm2_decode_cost.mamba_layers(GRANITE) == 9
    assert ssm2_decode_cost.mamba_layers(
        dict(GRANITE, num_hidden_layers=40)) == 36
    resident = 9 * ssm2_decode_cost.state_bytes(GRANITE, SLOTS)
    assert round(resident / 1e9, 2) == 4.83
    assert round(2 * resident / 1e9, 2) == 9.66  # moved a step
    tails = 9 * SLOTS * 3 * 8448 * 2
    # what the serve/step spans' recurrent_state_bytes should read
    assert round((resident + tails) / 1e9, 2) == 4.89


def test_one_layer_one_step_by_hand():
    one = ssm2_decode_cost.layer_step(GRANITE, SLOTS)
    d_inner, conv, heads, n, k = 8192, 8448, 128, 128, 4
    state = 2 * SLOTS * d_inner * n * 4
    tail = 2 * SLOTS * (k - 1) * conv * 2
    operands = SLOTS * (d_inner + conv + heads) * 2  # z; x, B, C; dt
    assert state == 1_073_741_824
    assert one["bytes"] == state + tail + operands
    assert one["flops"] == SLOTS * (d_inner * (5 * n + 6) + conv * 2 * k)
    # memory-bound by two orders: 1.32 ms a layer a step at 819 GB/s
    least, bound = costs.roofline_seconds(one, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(1.324e-3, rel=0.01)


def test_the_projections_ride_along_by_hand():
    """The mixer's 102.3 M parameters in bf16 once a step and two
    operations a matmul weight a row: 207 MB against the state's 1,085,
    and still memory-bound at 128 rows."""
    got = ssm2_decode_cost.projections(GRANITE, SLOTS)
    e, d_inner, conv, heads, k = 4096, 8192, 8448, 128, 4
    matmuls = e * (d_inner + conv + heads) + d_inner * e
    small = conv * (k + 1) + 3 * heads + d_inner
    assert matmuls == 68_681_728 + 33_554_432
    assert matmuls + small == 102_286_976  # test_granite's 102.3 x 1e6
    assert got["bytes"] == (matmuls + small) * 2 + 2 * SLOTS * e * 2
    assert got["flops"] == 2 * SLOTS * matmuls
    step = ssm2_decode_cost.layer_step(GRANITE, SLOTS)
    both = {key: got[key] + step[key] for key in got}
    least, bound = costs.roofline_seconds(both, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(1.577e-3, rel=0.01)


def test_work_scales_with_the_mamba_layers_and_the_steps():
    work = ssm2_decode_cost.ssm2_decode_work(GRANITE, SLOTS, steps=15 * 8)
    one = ssm2_decode_cost.layer_step(GRANITE, SLOTS)
    around = ssm2_decode_cost.projections(GRANITE, SLOTS)
    assert work["bytes"] == (one["bytes"] + around["bytes"]) * 9 * 120
    assert work["flops"] == (one["flops"] + around["flops"]) * 9 * 120
    # 14.3 ms of every decode step, whatever the contexts: 11.7 GB (state
    # and weights 11.5, tails, operands and rows the rest), the 72 % of a
    # step's 15.9 GB the cell's ``why`` states
    least, _ = costs.roofline_seconds(work, PEAK)
    assert least / 120 == pytest.approx(14.26e-3, rel=0.005)
    assert round(work["bytes"] / 120 / 1e9, 2) == 11.68


def test_mamba_1s_count_is_not_borrowed():
    """The two recurrences read different keys: neither file gives the
    other's cost function what it asks for."""
    from benchmarks.metrics import ssm_decode_cost

    with pytest.raises(KeyError):
        ssm_decode_cost.ssm_decode_work(GRANITE, SLOTS, steps=8)
    with pytest.raises(KeyError):
        ssm2_decode_cost.ssm2_decode_work(JAMBA, SLOTS, steps=8)


# -- the reader ----------------------------------------------------------------


def run_of(config=GRANITE, **observed):
    cell = types.SimpleNamespace(config=config)
    o = types.SimpleNamespace(
        stats_window={}, opened_at=10.0, closed_at=20.0, chunk_k=8,
        slots=SLOTS, **observed)
    return readers.Run(cell=cell, observed=o, setup_s=0.0, inventory=(),
                       device_kind="TPU v5 lite")


STATE = {"recurrent_state_bytes": 4_890_230_784, "rows_reset": 3}
OPS = [
    ("%fusion.1 = f32[128,128,64,128] fusion(%a)", 30.00, 0.150),
    ("%fusion.2 = f32[128,3,8448] fusion(%b)", 30.20, 0.010),
    ("%fusion.3 = bf16[128,16768] fusion(%c)", 30.30, 0.060),
    ("%fusion.4 = f32[128,4096] fusion(%d)", 30.40, 0.020),
]
SCOPES = {
    "fusion.1": "jit(f)/while/body/model/layers_1/mamba/mamba/state_update/mul",
    "fusion.2": "jit(f)/while/body/model/layers_1/mamba/mamba/conv/conv1d/add",
    "fusion.3": "jit(f)/while/body/model/layers_1/mamba/mamba/in_proj/in_proj/dot",
    "fusion.4": "jit(f)/while/body/model/layers_5/self_attn/o_proj/dot",
}


def test_roofline_share_from_the_traced_steps(monkeypatch):
    with_timeline(monkeypatch, [
        span("serve/step", 15.0, 0.2, 3, STATE),   # the window's: left out
        span("serve/step", 30.0, 0.2, 9, STATE),   # inside the capture
        span("serve/step", 30.3, 0.2, 10, STATE),
    ])
    run = ran_by(run_of(traced=(29.9, 31.0)), OPS, SCOPES)
    want = ssm2_decode_cost.ssm2_decode_work(GRANITE, SLOTS, steps=16)
    least, _ = costs.roofline_seconds(want, run.peak)
    got = readers.read(run, "kernel.ssm2_decode_roofline")
    # every op under a mixer's scope, its projections too (the state's
    # traffic hides under them), and not the attention layer's
    assert got == pytest.approx(100.0 * least / 0.220)
    assert run.notes["kernel.ssm2_decode_roofline.bound"] == "memory"
    assert run.notes["kernel.ssm2_decode_roofline.traced_chunks"] == 2
    assert run.notes["kernel.ssm2_decode_roofline.device_s"] == pytest.approx(0.220)
    # no capture, no op under the scopes, spans without the count, or a
    # configuration of another recurrence (Mamba-1's keys): nothing
    assert readers.read(
        run_of(traced=None), "kernel.ssm2_decode_roofline") is None
    run.programs = (program({}),)
    assert readers.read(run, "kernel.ssm2_decode_roofline") is None
    run.programs = (program(SCOPES),)
    with_timeline(monkeypatch, [span("serve/step", 30.0, 0.2, 9)])
    assert readers.read(run, "kernel.ssm2_decode_roofline") is None
    other = ran_by(run_of(config=JAMBA, traced=(29.9, 31.0)), OPS, SCOPES)
    assert readers.read(other, "kernel.ssm2_decode_roofline") is None


def test_the_metric_is_listed_for_its_cell_alone():
    from benchmarks.harness import manifest

    name = "kernel.ssm2_decode_roofline"
    entry, = (m for m in manifest.manifest()["per_layer"]
              if m["name"] == name)
    assert entry["workloads"] == [
        "granite-4.0-h-small-share4-decode.serve-reason-closed"]
    own = manifest.metric_file(name)
    assert own["reader"] == {"file": True}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key], key
    assert (own["unit"], own["source"], own["layer"], own["moves"]) == (
        "%", "device_trace", "kernels", "serve_tokens_per_s")
